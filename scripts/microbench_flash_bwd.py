#!/usr/bin/env python
"""Flash-attention backward: the fused kernel against the dq / dk+dv pair,
over tile sizes, at the two shapes the benchmark's cells run.

  tinygpt-a.seq8192   BH 16, S 8192, D 64, not causal, dropout 0.1
  mistral-7b.d2       BH 64, S 4096, D 128, causal, no dropout

Each row times one call of ``ops.flash_attention._fused_backward`` or
``_pair_backward`` on residuals the real forward kernel produced, and checks
each fused row's gradients against the pair's (run at bq 1024, bk 512; equal
tiles give equal bits, other tiles differ by an ulp). A call is
5-20 ms, so the host clock around ``--iters`` queued calls and one fetch is
the device time.

  chiprun -- python scripts/microbench_flash_bwd.py            # the sweep
  JAX_PLATFORMS=cpu python scripts/microbench_flash_bwd.py --describe
      # no chip: compile every row for a described v5e (what Mosaic refuses,
      # e.g. for VMEM, it refuses here)
"""

import argparse
import json
import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax
import jax.numpy as jnp

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from distributed_llm_training_benchmark_framework_tpu.ops import (  # noqa: E402
    flash_attention as fa,
)

SHAPES = {
    "tinygpt-a.seq8192": dict(BH=16, S=8192, D=64, causal=False, rate=0.1),
    "mistral-7b.d2": dict(BH=64, S=4096, D=128, causal=True, rate=0.0),
}
TILES = [(bq, bk) for bq in (512, 1024, 2048) for bk in (256, 512, 1024)]


def backward_fn(impl, causal, rate, bq, bk):
    kernel = fa._fused_backward if impl == "fused" else fa._pair_backward

    def run(q, k, v, do, lse3, delta3, seed, bhv):
        return kernel(
            q, k, v, do, lse3, delta3, seed, bhv, causal, rate, bq, bk, False
        )

    return jax.jit(run)


def avals(shape, sharding):
    BH, S, D = shape["BH"], shape["S"], shape["D"]
    x = jax.ShapeDtypeStruct((BH, S, D), jnp.bfloat16, sharding=sharding)
    stat = jax.ShapeDtypeStruct((BH, 8, S), jnp.float32, sharding=sharding)
    seed = jax.ShapeDtypeStruct((1,), jnp.uint32, sharding=sharding)
    bhv = jax.ShapeDtypeStruct((BH,), jnp.int32, sharding=sharding)
    return x, x, x, x, stat, stat, seed, bhv


def residuals(shape):
    """q, k, v, do and the forward kernel's lse / delta, on the device."""
    BH, S, D = shape["BH"], shape["S"], shape["D"]
    keys = jax.random.split(jax.random.key(0), 4)
    q, k, v, do = (
        jax.random.normal(key, (BH, S, D), jnp.bfloat16) for key in keys
    )
    seed = jnp.asarray([1234], jnp.uint32)
    bhv = jnp.arange(BH, dtype=jnp.int32)
    out, lse = jax.jit(
        lambda q, k, v: fa._flash_forward(
            q, k, v, shape["causal"], False, 1024, 1024, shape["rate"],
            seed, bhv,
        )
    )(q, k, v)
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32), -1)
    lse3 = jnp.broadcast_to(lse[:, None, :], (BH, 8, S))
    delta3 = jnp.broadcast_to(delta[:, None, :], (BH, 8, S))
    return q, k, v, do, lse3, delta3, seed, bhv


def time_ms(fn, args, iters):
    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    float(out[0][0, 0, 0])
    return (time.perf_counter() - t0) / iters * 1e3, out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--describe", action="store_true")
    ap.add_argument("--iters", type=int, default=10)
    ap.add_argument("--shapes", nargs="*", default=sorted(SHAPES))
    ap.add_argument("--out", default="chiprun_out/flash_bwd_sweep.jsonl")
    args = ap.parse_args()

    if args.describe:
        from jax.experimental import topologies
        from jax.sharding import SingleDeviceSharding

        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
        sharding = SingleDeviceSharding(topo.devices[0])
    elif jax.default_backend() != "tpu":
        sys.exit("no TPU: --describe compiles without one, timing needs one")

    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    rows = []
    for name in args.shapes:
        shape = SHAPES[name]
        least_ms = (
            10 * shape["BH"] * shape["S"] ** 2 * shape["D"]
            / (2 if shape["causal"] else 1) / 197e12 * 1e3
        )
        print(f"{name}: {shape}; least time for one fused pass "
              f"{least_ms:.2f} ms", flush=True)
        data = None if args.describe else residuals(shape)
        want = None
        for impl, bq, bk in [("pair", 1024, 512)] + [
            ("fused", bq, bk) for bq, bk in TILES
        ]:
            row = dict(shape=name, impl=impl, bq=bq, bk=bk)
            fn = backward_fn(impl, shape["causal"], shape["rate"], bq, bk)
            try:
                if args.describe:
                    fn.lower(*avals(shape, sharding)).compile()
                    row["compiles"] = True
                else:
                    row["ms"], got = time_ms(fn, data, args.iters)
                    row["roofline_pct"] = 100 * least_ms / row["ms"]
                    if impl == "pair":
                        want = got
                    else:
                        row["max_abs_diff_vs_pair"] = max(
                            float(jnp.max(jnp.abs(
                                g.astype(jnp.float32) - w.astype(jnp.float32)
                            )))
                            for g, w in zip(got, want)
                        )
            except Exception as e:  # Mosaic's refusal is the finding
                row["error"] = str(e).splitlines()[0][:200]
            rows.append(row)
            print(json.dumps(row), flush=True)
    with open(args.out, "w") as f:
        f.writelines(json.dumps(r) + "\n" for r in rows)


if __name__ == "__main__":
    main()
