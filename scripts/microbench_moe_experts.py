#!/usr/bin/env python3
"""Grouped matmuls for dropless SwiGLU experts on the chip: ``jax.lax.ragged_dot``
(XLA's own Mosaic grouped matmul on a TPU) against the Pallas grouped matmul
that ships with jax (``jax.experimental.pallas.ops.tpu.megablox``: ``gmm`` and,
for the weight gradient, ``tgmm``) over a few fixed tilings and the tiling
``models/moe.py::gmm_tiling`` makes of each call's own widths.

    chiprun -- python3 scripts/microbench_moe_experts.py [rows hidden width experts] [--calls]

The benchmark's routed cells: 65536 2048 1024 64 (``olmoe-1b-7b.d1``, the
default), 12288 2048 1408 8 (the DeepSeek cell's expected rows), 16384 2048 768
16 (SDAR's), 32768 2304 896 16 (Mellum's).

Times the expert stack of ``models/moe.py::_experts_dropless`` alone (rows
already in expert order: gate+up grouped matmul, silu * up, down grouped
matmul), forward and forward + backward (gradients of the rows and of both
weights), on uneven group sizes drawn from the seed. Prints one JSON line with
each of the six calls' tile fill (``moe.gmm_tile_fill``: the share of what its
tiles cover that is operand) under the rule and under the constant (512, 1024,
1024) clipped by the forward's widths that the program had until PR 42, then one
a variant: ms a call (median of 10 after 3 warm-ups), the share of the bf16 peak
the forward's 6 * rows * hidden * width FLOPs (18 * ... with the backward) reach,
and the largest difference from the ragged_dot result. ``--calls`` times each of
the six kernels alone instead, over every pair of dividing tiles from 384 up, the
old constant, and both at half the row tile: the sweep the rule was read from
(PERF.md, PR 42). Standalone on purpose: the program keeps one of them.
"""

import importlib
import itertools
import json
import math
import os
import statistics
import sys
import time

import jax
import jax.numpy as jnp
from jax.experimental.pallas.ops.tpu.megablox import ops as megablox

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from distributed_llm_training_benchmark_framework_tpu.models import moe  # noqa: E402

backend = importlib.import_module("jax.experimental.pallas.ops.tpu.megablox.gmm")

PEAK = 197e12  # TPU v5e, bf16
INTERPRET = jax.default_backend() != "tpu"  # a CPU rehearsal
OLD = (512, 1024, 1024)  # the program's one tile until PR 42
TILINGS = [(512, 512, 512), OLD, (256, 1024, 1024), (512, 1024, 512),
           (1024, 512, 1024), (256, 2048, 512), (128, 1024, 1024)]


def experts(grouped, rows, wgu, wd, sizes):
    width = wd.shape[1]
    gu = grouped(rows, wgu, sizes)
    return grouped(jax.nn.silu(gu[:, :width]) * gu[:, width:], wd, sizes)


def ragged(rows, weights, sizes):
    return jax.lax.ragged_dot(rows, weights, sizes,
                              preferred_element_type=jnp.float32).astype(jnp.bfloat16)


def pallas(tiling):
    def grouped(rows, weights, sizes):
        return megablox.gmm(rows, weights, sizes, jnp.bfloat16, tiling, interpret=INTERPRET)
    return grouped


def timed(f, *args, repeats=1):
    """ms a call; with ``repeats`` that many calls enqueued back to back before
    the host waits: the device's time, not the host's round trip (0.5 ms)."""
    for _ in range(3):
        jax.block_until_ready(f(*args))
    times = []
    for _ in range(10):
        t = time.perf_counter()
        for _ in range(repeats):
            out = f(*args)
        jax.block_until_ready(out)
        times.append((time.perf_counter() - t) / repeats)
    return 1e3 * statistics.median(times)


def six_calls(rows_n, hidden, width):
    """The expert stack's grouped matmuls -> (name, kernel, (m, k, n), the old
    tiling as the forward's widths clipped it)."""
    clip = lambda k, n: (math.gcd(rows_n, OLD[0]), min(OLD[1], k), min(OLD[2], n))
    up, down = clip(hidden, 2 * width), clip(width, hidden)
    return [
        ("gate+up", "gmm", (rows_n, hidden, 2 * width), up),
        ("gate+up rows' gradient", "gmm transposed", (rows_n, 2 * width, hidden), up),
        ("gate+up weights' gradient", "tgmm", (rows_n, hidden, 2 * width), up),
        ("down", "gmm", (rows_n, width, hidden), down),
        ("down rows' gradient", "gmm transposed", (rows_n, hidden, width), down),
        ("down weights' gradient", "tgmm", (rows_n, width, hidden), down),
    ]


def fills(calls):
    """Each call's tile fill under the rule and under the old constant, and the
    six together (operand area over tile area: all six share their rows, so
    area weighs as FLOPs do)."""
    by_name = {name: {"rule": list(moe.gmm_tiling(m, k, n)),
                      "rule_fill": moe.gmm_tile_fill(m, k, n),
                      "old": list(old), "old_fill": moe.gmm_tile_fill(m, k, n, old)}
               for name, _, (m, k, n), old in calls}
    operand = sum(k * n for _, _, (_, k, n), _ in calls)
    together = {key: operand / sum(k * n / by_name[name][f"{key}_fill"]
                                   for name, _, (_, k, n), _ in calls) for key in ("rule", "old")}
    return by_name, together


def one_call(kernel, m, k, n, n_experts, sizes, tiling, key):
    """One of megablox's three kernels on operands of its own -> ms a call."""
    a, b = jax.random.split(key)
    bf = jnp.bfloat16
    if kernel == "tgmm":
        lhs = jax.random.normal(a, (k, m), bf)
        rhs = jax.random.normal(b, (m, n), bf)
        f = jax.jit(lambda x, y, s: backend.tgmm(x, y, s, bf, tiling, interpret=INTERPRET))
    else:
        transposed = kernel != "gmm"
        lhs = jax.random.normal(a, (m, k), bf)
        rhs = jax.random.normal(b, (n_experts, n, k) if transposed else (n_experts, k, n), bf)
        f = jax.jit(lambda x, y, s: backend.gmm(x, y, s, bf, tiling, transpose_rhs=transposed,
                                                interpret=INTERPRET))
    return timed(f, lhs, rhs, sizes, repeats=10)


def sweep_calls(calls, n_experts, sizes, key):
    for name, kernel, (m, k, n), old in calls:
        rule = moe.gmm_tiling(m, k, n)
        pairs = [(tk, tn) for tk, tn in itertools.product(
            moe._dividing_tiles(k), moe._dividing_tiles(n)) if min(tk, tn) >= 384]
        tilings = [old, rule] + [(rule[0], tk, tn) for tk, tn in pairs]
        tilings += [(t[0] // 2,) + t[1:] for t in (old, rule)]  # fewer rows a tile
        for tiling in dict.fromkeys(tilings):
            line = {"call": name, "kernel": kernel, "mkn": [m, k, n], "tiling": list(tiling),
                    "fill": moe.gmm_tile_fill(m, k, n, tiling),
                    "is": [w for w, t in (("old", old), ("rule", rule)) if t == tiling]}
            try:
                ms = one_call(kernel, m, k, n, n_experts, sizes, tiling, key)
                line.update(ms=ms, peak_pct=100 * 2.0 * m * k * n / (ms * 1e-3) / PEAK)
            except Exception as e:  # a tiling the compiler refuses is a result too
                line["error"] = str(e).splitlines()[0][:200]
            print(json.dumps(line), flush=True)


def main(argv):
    by_call = "--calls" in argv
    argv = [a for a in argv if a != "--calls"]
    rows_n, hidden, width, n_experts = (int(x) for x in argv) if argv else (65536, 2048, 1024, 64)
    keys = jax.random.split(jax.random.key(0), 6)
    # Uneven groups, as a fresh router gives them: shares from a softmax of noise.
    share = jax.nn.softmax(0.5 * jax.random.normal(keys[4], (n_experts,)))
    sizes = jnp.floor(share * rows_n).astype(jnp.int32)
    sizes = sizes.at[0].add(rows_n - sizes.sum())
    calls = six_calls(rows_n, hidden, width)
    by_name, together = fills(calls)
    print(json.dumps({"device": jax.devices()[0].device_kind, "rows": rows_n, "hidden": hidden,
                      "width": width, "experts": n_experts,
                      "max_over_mean": float(sizes.max() * n_experts / rows_n),
                      "tile_fill": together, "calls": by_name}), flush=True)
    if by_call:
        return sweep_calls(calls, n_experts, sizes, keys[5])
    rows = jax.random.normal(keys[0], (rows_n, hidden), jnp.bfloat16)
    wgu = (0.02 * jax.random.normal(keys[1], (n_experts, hidden, 2 * width))).astype(jnp.bfloat16)
    wd = (0.02 * jax.random.normal(keys[2], (n_experts, width, hidden))).astype(jnp.bfloat16)
    cotangent = jax.random.normal(keys[3], (rows_n, hidden), jnp.bfloat16)
    flops = 6.0 * rows_n * hidden * width
    want = None
    variants = [("ragged_dot", ragged), ("gmm_tiling", pallas(moe.gmm_tiling))]
    for name, grouped in variants + [(f"gmm{t}", pallas(t)) for t in TILINGS]:
        forward = jax.jit(lambda r, a, b, s, g=grouped: experts(g, r, a, b, s))
        both = jax.jit(jax.grad(  # the cotangent is an argument: closed over, 268 MB of constants
            lambda r, a, b, s, ct, g=grouped: jnp.sum(
                experts(g, r, a, b, s).astype(jnp.float32) * ct.astype(jnp.float32)),
            argnums=(0, 1, 2)))
        try:
            out = forward(rows, wgu, wd, sizes)
            want = out if want is None else want
            error = float(jnp.max(jnp.abs(out.astype(jnp.float32) - want.astype(jnp.float32))))
            fwd_ms = timed(forward, rows, wgu, wd, sizes)
            both_ms = timed(both, rows, wgu, wd, sizes, cotangent)
        except Exception as e:  # a tiling the compiler refuses is a result too
            print(json.dumps({"variant": name, "error": str(e).splitlines()[0][:300]}), flush=True)
            continue
        print(json.dumps({
            "variant": name, "forward_ms": fwd_ms, "forward_backward_ms": both_ms,
            "forward_peak_pct": 100 * flops / (fwd_ms * 1e-3) / PEAK,
            "forward_backward_peak_pct": 100 * 3 * flops / (both_ms * 1e-3) / PEAK,
            "max_abs_diff_from_ragged_dot": error,
            "largest_output": float(jnp.max(jnp.abs(want.astype(jnp.float32)))),
        }), flush=True)


if __name__ == "__main__":
    main(sys.argv[1:])
