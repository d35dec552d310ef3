#!/usr/bin/env python3
"""Grouped matmuls for dropless SwiGLU experts on the chip: ``jax.lax.ragged_dot``
(XLA's own Mosaic grouped matmul on a TPU) against the Pallas grouped matmul
that ships with jax (``jax.experimental.pallas.ops.tpu.megablox``: ``gmm`` and,
for the weight gradient, ``tgmm``) over a few tilings.

    chiprun -- python3 scripts/microbench_moe_experts.py [rows hidden width experts]

Times the expert stack of ``models/moe.py::_experts_dropless`` alone (rows
already in expert order: gate+up grouped matmul, silu * up, down grouped
matmul), forward and forward + backward (gradients of the rows and of both
weights), on uneven group sizes drawn from the seed. Prints one JSON line a
variant: ms a call (median of 10 after 3 warm-ups), the share of the bf16 peak
the forward's 6 * rows * hidden * width FLOPs (18 * ... with the backward) reach,
and the largest difference from the ragged_dot result. Standalone on purpose:
the program keeps one of the two.
"""

import json
import statistics
import sys
import time

import jax
import jax.numpy as jnp
from jax.experimental.pallas.ops.tpu.megablox import ops as megablox

PEAK = 197e12  # TPU v5e, bf16
TILINGS = [(512, 512, 512), (512, 1024, 1024), (256, 1024, 1024), (512, 1024, 512),
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
        return megablox.gmm(rows, weights, sizes, jnp.bfloat16, tiling,
                            interpret=jax.default_backend() != "tpu")  # a CPU rehearsal
    return grouped


def timed(f, *args):
    for _ in range(3):
        jax.block_until_ready(f(*args))
    times = []
    for _ in range(10):
        t = time.perf_counter()
        jax.block_until_ready(f(*args))
        times.append(time.perf_counter() - t)
    return 1e3 * statistics.median(times)


def main(argv):
    rows_n, hidden, width, n_experts = (int(x) for x in argv) if argv else (65536, 2048, 1024, 64)
    keys = jax.random.split(jax.random.key(0), 5)
    rows = jax.random.normal(keys[0], (rows_n, hidden), jnp.bfloat16)
    wgu = (0.02 * jax.random.normal(keys[1], (n_experts, hidden, 2 * width))).astype(jnp.bfloat16)
    wd = (0.02 * jax.random.normal(keys[2], (n_experts, width, hidden))).astype(jnp.bfloat16)
    cotangent = jax.random.normal(keys[3], (rows_n, hidden), jnp.bfloat16)
    # Uneven groups, as a fresh router gives them: shares from a softmax of noise.
    share = jax.nn.softmax(0.5 * jax.random.normal(keys[4], (n_experts,)))
    sizes = jnp.floor(share * rows_n).astype(jnp.int32)
    sizes = sizes.at[0].add(rows_n - sizes.sum())
    print(json.dumps({"device": jax.devices()[0].device_kind, "rows": rows_n, "hidden": hidden,
                      "width": width, "experts": n_experts,
                      "max_over_mean": float(sizes.max() * n_experts / rows_n)}), flush=True)
    flops = 6.0 * rows_n * hidden * width
    want = None
    for name, grouped in [("ragged_dot", ragged)] + [(f"gmm{t}", pallas(t)) for t in TILINGS]:
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
