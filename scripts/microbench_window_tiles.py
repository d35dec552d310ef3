#!/usr/bin/env python
"""The flash kernels under a sliding window, alone on the chip, by tile: what
``ops/flash_attention.py::pick_tiles`` chooses among under a window narrower
than the default tile (``_window_tile``).

    chiprun -- python scripts/microbench_window_tiles.py [--window 512] [--rows 16384]
        [--heads 64] [--tiles 1024 512 256 128] [--iters 10] [--out chiprun_out/x.jsonl]

A line a tile: ms of one layer's forward and of its forward + backward (the
fused backward from 4096 rows), the true pairs a head, the pairs the two
kernels multiply at that tile (``mixers.attention.attn_mask_stats``'s arithmetic) and
the fill. K and V enter repeated to the query heads, as in the layer.
"""

import argparse
import json
import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--window", type=int, default=512)
    ap.add_argument("--rows", type=int, default=16384)
    ap.add_argument("--heads", type=int, default=64)
    ap.add_argument("--tiles", type=int, nargs="*", default=[1024, 512, 256, 128])
    ap.add_argument("--iters", type=int, default=10)
    ap.add_argument("--out")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    from distributed_llm_training_benchmark_framework_tpu.ops import flash_attention as fa
    from distributed_llm_training_benchmark_framework_tpu.utils.platform import require_tpu

    require_tpu()
    S, H, D = args.rows, args.heads, 128
    rule = fa.SlidingWindow(args.window)
    keys = jax.random.split(jax.random.key(0), 4)
    q, k, v, do = (jax.random.normal(key, (1, S, H, D), jnp.float32).astype(jnp.bfloat16)
                   for key in keys)

    def timed(fn, *operands):
        jax.block_until_ready(fn(*operands))
        t = time.perf_counter()
        ahead = []
        for _ in range(args.iters):
            ahead.append(fn(*operands))
            if len(ahead) > 2:
                jax.block_until_ready(ahead.pop(0))
        jax.block_until_ready(ahead)
        return (time.perf_counter() - t) * 1e3 / args.iters

    rows = []
    for tile in args.tiles:
        attend = lambda q, k, v: fa.flash_attention(
            q, k, v, causal=rule, block_q=tile, block_k=tile, block_k_bwd=tile)
        forward = jax.jit(attend)
        both = jax.jit(lambda q, k, v, do: jax.vjp(attend, q, k, v)[1](do))
        multiplied = {}
        for name, piece in (("fwd", fa._fwd_sub_k(tile)), ("bwd", fa._bwd_sub_q(tile, 0.0))):
            units, _, unit_pairs = fa.visited_units(rule, S, tile, tile, piece)
            multiplied[name] = units * unit_pairs
        true = rule.true_pairs(S)
        rows.append(dict(
            window=args.window, rows=S, heads=H, tile=tile,
            forward_ms=timed(forward, q, k, v), forward_backward_ms=timed(both, q, k, v, do),
            true_pairs=true, fwd_pairs_multiplied=multiplied["fwd"],
            bwd_pairs_multiplied=multiplied["bwd"],
            live_fill_pct=100 * 2 * true / (multiplied["fwd"] + multiplied["bwd"]),
            device=jax.devices()[0].device_kind))
        print(json.dumps(rows[-1]), flush=True)
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "a") as f:
            for row in rows:
                f.write(json.dumps(row) + "\n")


if __name__ == "__main__":
    main()
