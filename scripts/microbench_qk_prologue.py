#!/usr/bin/env python
"""QK-norm + rotary between the projections and the flash kernels, alone on
the chip: ``ops/rotary.py``'s one pass against the ``jnp`` chain it stands in
for (``models/mixers/attention.py``: ``_rms_norm`` -> ``_rope``), the forward and the
backward apart, at a cell's operand.

    chiprun -- python scripts/microbench_qk_prologue.py [--rows 16384] [--batch 1]
        [--heads 32] [--kv-heads 4] [--norm 1] [--rotary-dim 64] [--iters 20] [--copies 8]
        [--out chiprun_out/x.jsonl]

A line a variant: ms a layer's call, the bytes the pass needs from its shapes
(``ops.rotary.pass_bytes``) and their rate against the chip's published HBM
rate (``perfbench/harness/peaks.py``). A
dispatch costs the host about a millisecond, which is more than the pass
takes, so a timed program runs ``--copies`` layers' operands in a row and
the host stays two programs ahead of the one it waits for: the time is the
whole over iters x copies. ``--describe`` compiles both for a described v5e instead (no
chip) and prints XLA's ``bytes accessed`` of each, which is how the chain's
cost was first read. Results and cotangents live on the flash kernels' side,
a (rows, 128) slab a head, for both variants, as in the layer. The backward
is the gradient of a loss that is linear in the results, so that only the
backward's own work is timed (the chain's norm is computed again inside it,
as under the cells' remat).
"""

import argparse
import functools
import json
import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rows", type=int, default=16384)
    ap.add_argument("--batch", type=int, default=1)
    ap.add_argument("--heads", type=int, default=32)
    ap.add_argument("--kv-heads", type=int, default=4)
    ap.add_argument("--norm", type=int, default=1)
    ap.add_argument("--rotary-dim", type=int, default=None,
                    help="the leading lanes of a head that rotate (default: all 128)")
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--copies", type=int, default=8)
    ap.add_argument("--describe", action="store_true")
    ap.add_argument("--out")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    from distributed_llm_training_benchmark_framework_tpu.models import common
    from distributed_llm_training_benchmark_framework_tpu.models.mixers import attention
    from distributed_llm_training_benchmark_framework_tpu.ops import rotary

    B, S, H, KV, D, norm = args.batch, args.rows, args.heads, args.kv_heads, 128, bool(args.norm)
    eps, theta, part = 1e-6, 1e6, args.rotary_dim

    def the_pass(q, k, qs, ks):
        table = rotary.table(jnp.arange(S, dtype=jnp.int32), D, theta, rotary_dim=part)
        return rotary.qk_prologue(q, k, qs if norm else None, ks if norm else None, table, eps,
                                  rotary_dim=part)

    def the_chain(q, k, qs, ks):
        pos = jnp.arange(S, dtype=jnp.int32)
        q, k = q.reshape(B, S, H, D), k.reshape(B, S, KV, D)
        if norm:
            q, k = common._rms_norm(q, qs, eps), common._rms_norm(k, ks, eps)
        return (attention._rope(q, pos, theta, rotary_dim=part),
                attention._rope(k, pos, theta, rotary_dim=part))

    def forward(fn, q, k, qs, ks, wq, wk):
        a, b = fn(q, k, qs, ks)  # (B, S, heads, D) -> head-major, as flash reads them
        return a.transpose(0, 2, 1, 3), b.transpose(0, 2, 1, 3)

    def loss(fn, *operands):
        a, b = forward(fn, *operands)
        wq, wk = operands[-2:]
        return jnp.sum(a.astype(jnp.float32) * wq) + jnp.sum(b.astype(jnp.float32) * wk)

    shapes = [((B, S, H * D), jnp.bfloat16), ((B, S, KV * D), jnp.bfloat16),
              ((D,), jnp.float32), ((D,), jnp.float32),
              ((B, H, S, D), jnp.bfloat16), ((B, KV, S, D), jnp.bfloat16)]

    def in_a_row(one):  # a program over several layers' operands, each its own call
        return jax.jit(lambda layers: [one(*operands) for operands in layers])

    variants = {
        f"{name}.{what}": in_a_row(
            functools.partial(forward, fn) if what == "forward"
            else jax.grad(functools.partial(loss, fn), argnums=(0, 1, 2, 3)))
        for name, fn in (("pass", the_pass), ("chain", the_chain))
        for what in ("forward", "backward")
    }
    needs = rotary.pass_bytes(B * S, H * D, KV * D, 2, norm)
    rows = []
    if args.describe:
        from jax.experimental import topologies
        from jax.sharding import SingleDeviceSharding

        jax.config.update("jax_enable_compilation_cache", False)
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
        one = SingleDeviceSharding(topo.devices[0])
        avals = [[jax.ShapeDtypeStruct(s, d, sharding=one) for s, d in shapes]]
        for name, fn in variants.items():
            compiled = fn.lower(avals).compile()
            rows.append(dict(
                variant=name, described="v5e:2x2",
                xla_bytes_accessed_gb=compiled.cost_analysis()["bytes accessed"] / 1e9,
                mosaic_calls=compiled.as_text().count('custom_call_target="tpu_custom_call"')))
    else:
        from distributed_llm_training_benchmark_framework_tpu.utils.platform import require_tpu
        from perfbench.harness.peaks import peaks

        require_tpu()
        kind = jax.devices()[0].device_kind
        hbm_bytes_per_s = peaks(kind)["hbm_bytes_per_s"]

        def layer(seed):
            keys = jax.random.split(jax.random.key(seed), len(shapes))
            return [jax.random.normal(k, s, jnp.float32).astype(d) + (1.0 if s == (D,) else 0.0)
                    for k, (s, d) in zip(keys, shapes)]

        layers = [layer(seed) for seed in range(args.copies)]
        for name, fn in variants.items():
            jax.block_until_ready(fn(layers))
            t = time.perf_counter()
            ahead = []  # two programs enqueued ahead of the one waited for; no more
            for _ in range(args.iters):  # results than that are alive at a time
                ahead.append(fn(layers))
                if len(ahead) > 2:
                    jax.block_until_ready(ahead.pop(0))
            jax.block_until_ready(ahead)
            ms = (time.perf_counter() - t) * 1e3 / (args.iters * args.copies)
            need = needs[name.split(".")[1]]
            rows.append(dict(
                variant=name, ms=ms, pass_needs_gb=need / 1e9,
                of_hbm_rate_pct=100 * need / hbm_bytes_per_s / (ms / 1e3),
                device=kind))
    shape = dict(batch=B, rows=S, heads=H, kv_heads=KV, norm=norm, rotary_dim=part or D)
    for row in rows:
        print(json.dumps({**shape, **row}), flush=True)
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "a") as f:
            for row in rows:
                f.write(json.dumps({**shape, **row}) + "\n")


if __name__ == "__main__":
    main()
