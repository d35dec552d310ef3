#!/usr/bin/env python
"""Head-dim-64 MXU wall prototypes — the measured battery behind
docs/PERFORMANCE.md §15.

Context (§9): at the parity config the flash forward kernel's in-kernel
efficiency is ~23% of bf16 peak, and the score matmuls contract over
head_dim = 64 — half the MXU's 128-wide contraction. The round-4 verdict
asked for kernel-layout prototypes rather than concession. This script
times, at tier-A attention shapes (BH=16, S=2048, D=64, bf16):

  xla_sdpa        — plain XLA dot_general chain (materialized scores), the
                    no-kernel ceiling check
  matmul_floor    — the two dots alone (q@k^T then s@v), no softmax, no
                    masking: the in-kernel MXU floor the other variants
                    chase
  flash_current   — the production kernel (ops/flash_attention.py)
  flash_headpair  — grid halved over batch*heads; each program computes a
                    2-head batched dot (batch dims on the MXU call) so
                    Mosaic may pack two 64-contractions per pass
  flash_kt        — k fed pre-transposed (D, bk): the q@k^T contraction
                    becomes a plain (bq,64)x(64,bk) matmul with no
                    transposed operand, minor-dim-contiguous on both sides
  flash_qscaled   — softmax scale folded into the narrow (bq, D) q tile
                    instead of the wide (bq, bk) score tile; bit-exact
                    when the scale is a power of two (D=64 -> 2^-3)
  flash_production— the repo's real ops/flash_attention.py forward
                    (dropout off), so prototype wins/losses are judged
                    against what the model actually runs

Timing discipline: a kernel call here is a fraction of a millisecond, less
than one host dispatch plus fetch, so every variant is timed by chaining N
calls inside ONE jit (output feeding input) and fetching a single scalar
(docs/TROUBLESHOOTING.md §17).

Run on the chip:  python scripts/microbench_flash_fwd.py [--iters 50]
"""

import argparse
import functools
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

NEG_INF = -1e30


def timeit_chained(fn, args, chain=500, n=5):
    """Median ms per call, measured as `chain` sequential calls inside ONE
    jitted computation (each output feeds the next input, forcing the device
    to actually execute them in series) with a single scalar fetched at the
    end, so per-call dispatch and fetch latency stay out of a
    sub-millisecond kernel's time (docs/TROUBLESHOOTING.md §17)."""

    @jax.jit
    def many(*a):
        x = a[0]
        for _ in range(chain):
            x = fn(x, *a[1:])
        return jnp.float32(x).sum()

    float(many(*args))  # compile + warm
    ts = []
    for _ in range(n):
        t0 = time.perf_counter()
        float(many(*args))
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts) / chain * 1e3)


# --- variant kernels (softmax, no dropout — isolate the matmul layout) ---

def _fwd_kernel_current(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr,
                        *, bq, bk, scale):
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    q = q_ref[0]
    k = k_ref[0]
    v = v_ref[0]
    s = lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale
    m_prev = m_scr[:, :1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)
    l_scr[:, :1] = l_scr[:, :1] * alpha + jnp.sum(p, axis=-1, keepdims=True)
    m_scr[:, :1] = m_new
    acc_scr[:] = acc_scr[:] * alpha + lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    @pl.when(ki == nk - 1)
    def _done():
        o_ref[0] = (acc_scr[:] / l_scr[:, :1]).astype(o_ref.dtype)


def flash_current(q, k, v, bq=1024, bk=1024):
    BH, S, D = q.shape
    scale = 1.0 / (D ** 0.5)
    return pl.pallas_call(
        functools.partial(_fwd_kernel_current, bq=bq, bk=bk, scale=scale),
        out_shape=jax.ShapeDtypeStruct((BH, S, D), q.dtype),
        grid=(BH, S // bq, S // bk),
        in_specs=[
            pl.BlockSpec((1, bq, D), lambda b, qi, ki: (b, qi, 0)),
            pl.BlockSpec((1, bk, D), lambda b, qi, ki: (b, ki, 0)),
            pl.BlockSpec((1, bk, D), lambda b, qi, ki: (b, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, D), lambda b, qi, ki: (b, qi, 0)),
        scratch_shapes=[
            pltpu.VMEM((bq, 8), jnp.float32),
            pltpu.VMEM((bq, 8), jnp.float32),
            pltpu.VMEM((bq, D), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
    )(q, k, v)


def _fwd_kernel_headpair(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr,
                         *, bq, bk, scale):
    """2 heads per program; the dots carry a batch dim so the compiler can
    interleave two 64-deep contractions per MXU pass (if it can)."""
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    q = q_ref[:]  # (2, bq, D)
    k = k_ref[:]
    v = v_ref[:]
    s = lax.dot_general(
        q, k, (((2,), (2,)), ((0,), (0,))), preferred_element_type=jnp.float32
    ) * scale  # (2, bq, bk)
    m_prev = m_scr[:, :, :1]  # (2, bq, 1)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)
    l_scr[:, :, :1] = l_scr[:, :, :1] * alpha + jnp.sum(p, -1, keepdims=True)
    m_scr[:, :, :1] = m_new
    acc_scr[:] = acc_scr[:] * alpha + lax.dot_general(
        p.astype(v.dtype), v, (((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32,
    )

    @pl.when(ki == nk - 1)
    def _done():
        o_ref[:] = (acc_scr[:] / l_scr[:, :, :1]).astype(o_ref.dtype)


def flash_headpair(q, k, v, bq=1024, bk=1024):
    BH, S, D = q.shape
    scale = 1.0 / (D ** 0.5)
    return pl.pallas_call(
        functools.partial(_fwd_kernel_headpair, bq=bq, bk=bk, scale=scale),
        out_shape=jax.ShapeDtypeStruct((BH, S, D), q.dtype),
        grid=(BH // 2, S // bq, S // bk),
        in_specs=[
            pl.BlockSpec((2, bq, D), lambda b, qi, ki: (b, qi, 0)),
            pl.BlockSpec((2, bk, D), lambda b, qi, ki: (b, ki, 0)),
            pl.BlockSpec((2, bk, D), lambda b, qi, ki: (b, ki, 0)),
        ],
        out_specs=pl.BlockSpec((2, bq, D), lambda b, qi, ki: (b, qi, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, bq, 8), jnp.float32),
            pltpu.VMEM((2, bq, 8), jnp.float32),
            pltpu.VMEM((2, bq, D), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
    )(q, k, v)


def _fwd_kernel_kt(q_ref, kt_ref, v_ref, o_ref, m_scr, l_scr, acc_scr,
                   *, bq, bk, scale):
    """k arrives pre-transposed (D, bk): contraction is minor-dim of q
    against major-dim of kt — a plain untransposed matmul."""
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    q = q_ref[0]   # (bq, D)
    kt = kt_ref[0]  # (D, bk)
    v = v_ref[0]
    s = lax.dot_general(
        q, kt, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale
    m_prev = m_scr[:, :1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)
    l_scr[:, :1] = l_scr[:, :1] * alpha + jnp.sum(p, axis=-1, keepdims=True)
    m_scr[:, :1] = m_new
    acc_scr[:] = acc_scr[:] * alpha + lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    @pl.when(ki == nk - 1)
    def _done():
        o_ref[0] = (acc_scr[:] / l_scr[:, :1]).astype(o_ref.dtype)


def flash_kt(q, kt, v, bq=1024, bk=1024):
    BH, S, D = q.shape
    scale = 1.0 / (D ** 0.5)
    return pl.pallas_call(
        functools.partial(_fwd_kernel_kt, bq=bq, bk=bk, scale=scale),
        out_shape=jax.ShapeDtypeStruct((BH, S, D), q.dtype),
        grid=(BH, S // bq, S // bk),
        in_specs=[
            pl.BlockSpec((1, bq, D), lambda b, qi, ki: (b, qi, 0)),
            pl.BlockSpec((1, D, bk), lambda b, qi, ki: (b, 0, ki)),
            pl.BlockSpec((1, bk, D), lambda b, qi, ki: (b, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, D), lambda b, qi, ki: (b, qi, 0)),
        scratch_shapes=[
            pltpu.VMEM((bq, 8), jnp.float32),
            pltpu.VMEM((bq, 8), jnp.float32),
            pltpu.VMEM((bq, D), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
    )(q, kt, v)


def _fwd_kernel_matmul_only(q_ref, k_ref, v_ref, o_ref, acc_scr, *, bq, bk, scale):
    """The two dots with a trivial elementwise between — the MXU floor."""
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        acc_scr[:] = jnp.zeros_like(acc_scr)

    q = q_ref[0]
    k = k_ref[0]
    v = v_ref[0]
    s = lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale
    acc_scr[:] = acc_scr[:] + lax.dot_general(
        s.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    @pl.when(ki == nk - 1)
    def _done():
        o_ref[0] = acc_scr[:].astype(o_ref.dtype)


def matmul_floor(q, k, v, bq=1024, bk=1024):
    BH, S, D = q.shape
    scale = 1.0 / (D ** 0.5)
    return pl.pallas_call(
        functools.partial(_fwd_kernel_matmul_only, bq=bq, bk=bk, scale=scale),
        out_shape=jax.ShapeDtypeStruct((BH, S, D), q.dtype),
        grid=(BH, S // bq, S // bk),
        in_specs=[
            pl.BlockSpec((1, bq, D), lambda b, qi, ki: (b, qi, 0)),
            pl.BlockSpec((1, bk, D), lambda b, qi, ki: (b, ki, 0)),
            pl.BlockSpec((1, bk, D), lambda b, qi, ki: (b, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, D), lambda b, qi, ki: (b, qi, 0)),
        scratch_shapes=[pltpu.VMEM((bq, D), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
    )(q, k, v)


def _fwd_kernel_qscaled(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr,
                        *, bq, bk, scale):
    """The softmax scale folded into the narrow (bq, D) q tile instead of
    the wide (bq, bk) score tile. Bit-exact when scale is a power of two
    (D=64 -> 2^-3: exponent shift, no mantissa change) — verified max|Δ|=0
    vs flash_current on-chip."""
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    q = q_ref[0] * jnp.asarray(scale, q_ref.dtype)  # narrow mul
    k = k_ref[0]
    v = v_ref[0]
    s = lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )
    m_prev = m_scr[:, :1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)
    l_scr[:, :1] = l_scr[:, :1] * alpha + jnp.sum(p, axis=-1, keepdims=True)
    m_scr[:, :1] = m_new
    acc_scr[:] = acc_scr[:] * alpha + lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    @pl.when(ki == nk - 1)
    def _done():
        o_ref[0] = (acc_scr[:] / l_scr[:, :1]).astype(o_ref.dtype)


def flash_qscaled(q, k, v, bq=1024, bk=1024):
    BH, S, D = q.shape
    scale = 1.0 / (D ** 0.5)
    return pl.pallas_call(
        functools.partial(_fwd_kernel_qscaled, bq=bq, bk=bk, scale=scale),
        out_shape=jax.ShapeDtypeStruct((BH, S, D), q.dtype),
        grid=(BH, S // bq, S // bk),
        in_specs=[
            pl.BlockSpec((1, bq, D), lambda b, qi, ki: (b, qi, 0)),
            pl.BlockSpec((1, bk, D), lambda b, qi, ki: (b, ki, 0)),
            pl.BlockSpec((1, bk, D), lambda b, qi, ki: (b, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, D), lambda b, qi, ki: (b, qi, 0)),
        scratch_shapes=[
            pltpu.VMEM((bq, 8), jnp.float32),
            pltpu.VMEM((bq, 8), jnp.float32),
            pltpu.VMEM((bq, D), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
    )(q, k, v)


def flash_production(q, k, v):
    """The repo's real forward (ops/flash_attention.py), dropout off.
    Takes/returns (B, S, H, D); the caller reshapes."""
    from distributed_llm_training_benchmark_framework_tpu.ops.flash_attention import (
        flash_attention,
    )

    return flash_attention(q, k, v)


def xla_sdpa(q, k, v):
    scale = 1.0 / (q.shape[-1] ** 0.5)
    s = jnp.einsum("bqd,bkd->bqk", q, k, preferred_element_type=jnp.float32) * scale
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bqk,bkd->bqd", p.astype(v.dtype), v)


def device_bf16_peak_flops() -> float:
    """bf16 peak for the local device from the repo's own table (utils/
    flops.py); 197 TFLOP/s (v5e) when the kind is unknown."""
    try:
        from distributed_llm_training_benchmark_framework_tpu.utils.flops import (
            device_peak_tflops,
        )

        peak = device_peak_tflops(jax.devices()[0].device_kind)
        if peak:
            return peak * 1e12
    except Exception:
        pass
    return 197e12


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--chain", type=int, default=500,
                    help="kernel calls chained per timed jit execution")
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--bh", type=int, default=16)
    ap.add_argument("--seq", type=int, default=2048)
    ap.add_argument("--dim", type=int, default=64)
    args = ap.parse_args()

    BH, S, D = args.bh, args.seq, args.dim
    # The prototype kernels hard-code 1024-wide tiles and the headpair
    # variant pairs heads; refuse geometries that would silently produce a
    # zero-size grid (a kernel that never runs times as "very fast").
    if S % 1024 != 0:
        ap.error(f"--seq must be a multiple of 1024 (got {S})")
    if BH % 2 != 0:
        ap.error(f"--bh must be even for the headpair variant (got {BH})")
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal((BH, S, D)), jnp.bfloat16)
    k = jnp.asarray(rng.standard_normal((BH, S, D)), jnp.bfloat16)
    v = jnp.asarray(rng.standard_normal((BH, S, D)), jnp.bfloat16)
    kt = jnp.swapaxes(k, 1, 2)
    # Production API takes (B, S, H, D).
    q4 = jnp.swapaxes(q, 0, 1)[None]
    k4 = jnp.swapaxes(k, 0, 1)[None]
    v4 = jnp.swapaxes(v, 0, 1)[None]

    flops = 2 * 2 * BH * S * S * D
    peak = device_bf16_peak_flops()
    print(f"shapes BH={BH} S={S} D={D}; bf16 peak {peak/1e12:.0f} TFLOP/s; "
          f"analytic MXU floor {flops / peak * 1e3:.3f} ms")

    variants = {
        "xla_sdpa": (xla_sdpa, (q, k, v)),
        "matmul_floor": (matmul_floor, (q, k, v)),
        "flash_current": (flash_current, (q, k, v)),
        "flash_headpair": (flash_headpair, (q, k, v)),
        "flash_kt": (flash_kt, (q, kt, v)),
        "flash_qscaled": (flash_qscaled, (q, k, v)),
        "flash_production": (flash_production, (q4, k4, v4)),
    }
    ref = None
    for name, (fn, a) in variants.items():
        try:
            chain = args.chain if name != "xla_sdpa" else max(args.chain // 5, 20)
            ms = timeit_chained(fn, a, chain=chain, n=args.reps)
        except Exception as e:
            print(f"{name:16s} FAILED: {type(e).__name__}: {str(e)[:160]}")
            continue
        out = np.asarray(jax.jit(fn)(*a), np.float32)
        if name == "flash_production":
            out = np.swapaxes(out[0], 0, 1)
        if name == "xla_sdpa":
            ref = out
        tag = ""
        if ref is not None and name not in ("xla_sdpa", "matmul_floor"):
            err = np.max(np.abs(out - ref))
            tag = f"  max|Δ| vs sdpa {err:.3e}"
        eff = flops / (ms / 1e3) / peak * 100
        print(f"{name:16s} {ms:8.3f} ms   {eff:5.1f}% of bf16 peak{tag}")


if __name__ == "__main__":
    main()
