#!/usr/bin/env python
"""Flash-attention forward: the (bq, bk) DMA tile walked in compute pieces,
against the whole-tile body, at the shapes the benchmark's cells run.

  tinygpt-a.seq8192   BH 16, S 8192, D 64, not causal, dropout 0.1
  tinygpt-a.seq2048   BH 16, S 2048, D 64, not causal, dropout 0.1
  mistral-7b.d2       BH 64, S 4096, D 128, causal, no dropout
  sdar-30b-a3b.share8-bd8192   BH 32, a stream of 16,384 (a document of 8192
                      twice), D 128, ``BlockDiffusion(8192, 4)``, no dropout:
                      the production kernel only (the prototype below and the
                      floors know no rule)
  mellum2.global / laguna.global   BH 32 / 48, S 16,384, D 128, causal

A shape's ``KV`` is the rows of k and v (batch x kv heads; ``--kv-heads``
overrides it): where it is less than BH the kernel reads row b // rep of k and
v for query row b, ``flash_production`` is handed them as they are, and a
``flash_repeated`` row times what ran until PR 48, k and v repeated a query
head in front of the same kernel; the floors and the prototype take whole
heads, repeated outside what is timed.

docs/PERFORMANCE.md section 15 measured that the two products of a score tile
alone took twice the MXU's time, and refuted three operand layouts that keep
the whole-tile chain (one product, every elementwise op over the whole
(bq, bk) f32 tile, one product). ``flash_subtiled`` below changes the chain:
one grid step still brings a (bq, bk) tile into VMEM, and the body walks it
in (sub_q, sub_k) pieces, unrolled, each with its own online-softmax update.
Its knobs are what PR 27 swept (``PERF.md`` section 6 has the table):

  layout     qmajor: a piece is (sub_q, sub_k), statistics (sub_q, 1) columns,
             the production layout before PR 27; at sub_q = bq and sub_k = bk
             it is that body. kmajor: a piece is (sub_k, sub_q), statistics
             (1, sub_q) rows, the accumulator out^T: what production runs now
             (at sub_q = bq, lookahead and trim on)
  lookahead  the next piece's QK^T issued before this piece's softmax
  trim       one multiply a score: scale, log2(e) and 1 / keep_prob folded

The rows:

  matmul_floor      the two products with a cast between them, no softmax
  xla_sdpa          XLA's materialised-score chain (skipped over 2 GiB of scores)
  flash_production  ops/flash_attention.py::_flash_forward as the model runs it
                    (``--rows prod:bq:bk:0:sub_k:0:0`` forces its tile and piece).
                    Under a mask rule a live tile has a shape (``fa._tile_shape``:
                    *full* or *lower*) and the kernel a body a shape: the row
                    is timed with ``--bodies`` all (what the model runs) and
                    none (every live tile the *full* body, the kernel until
                    PR 37), and a ``by_shape`` line gives us a tile by shape
                    beside the mean over live tiles and the area a call visits
  flash_subtiled    every (layout, DMA tile, sub_q, sub_k, lookahead, trim) asked for

A call is a fraction of a millisecond to a few, so each row chains enough
calls for ``--target-ms`` inside one jit (a ``fori_loop``, each output the next q)
and fetches one scalar (docs/TROUBLESHOOTING.md section 17).

  chiprun -- python scripts/microbench_flash_fwd.py              # the q-major and k-major grids
  JAX_PLATFORMS=cpu python scripts/microbench_flash_fwd.py --describe
      # no chip: compile every row for a described v5e, and print how to take
      # the compiler's own schedule of a row (``--bundles <dir>`` reads it, a
      # body a line)
  ... --shapes tinygpt-a.seq2048 --dropout 0 --causal 1          # one shape, overridden
  ... --rows kmajor:2048:2048:1024:128:1:1 prod:1024:1024:0:256:0:0   # named rows only
"""

import argparse
import functools
import itertools
import json
import math
import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from distributed_llm_training_benchmark_framework_tpu.ops import (  # noqa: E402
    flash_attention as fa,
)
from microbench_flash_bwd import (  # noqa: E402  (beside this file)
    BODIES, CLOCK_HZ, LLO_DUMP_HELP, bodies, read_bundles, shape_counts, us_by_shape,
)

NEG_INF = fa.NEG_INF
LOG2_E = math.log2(math.e)
PEAK_FLOPS = 197e12  # v5e bf16

SHAPES = {
    "tinygpt-a.seq8192": dict(BH=16, S=8192, D=64, causal=False, rate=0.1),
    "tinygpt-a.seq2048": dict(BH=16, S=2048, D=64, causal=False, rate=0.1),
    "mistral-7b.d2": dict(BH=64, KV=16, S=4096, D=128, causal=True, rate=0.0),
    "sdar-30b-a3b.share8-bd8192": dict(
        BH=32, KV=4, S=16384, D=128, causal=fa.BlockDiffusion(8192, 4), rate=0.0),
    "mellum2.global": dict(BH=32, KV=4, S=16384, D=128, causal=True, rate=0.0),
    "laguna.global": dict(BH=48, KV=8, S=16384, D=128, causal=True, rate=0.0),
}
DMA_TILES = [(1024, 1024), (2048, 1024), (1024, 2048), (2048, 2048)]
SUB_K = [128, 256, 512, 1024]
ROW_CHUNKS = [0, 128, 256, 512]  # 0: the whole tile's q positions


def _fwd_kernel_subtiled(
    seed_ref, bhv_ref, q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr,
    acc_scr,
    *, kmajor, bq, bk, sub_q, sub_k, lookahead, scale, causal, rate, trim,
):
    """The forward with its (bq, bk) tile cut both ways: q positions in chunks
    of ``sub_q`` (independent of each other), k positions in sub-tiles of
    ``sub_k`` (one online-softmax update each). ``lookahead`` issues the next
    piece's QK^T before this piece's softmax in program order.

    ``kmajor`` holds a score piece as (sub_k, sub_q), as the fused backward
    holds its tile: the softmax's max and sum run down the rows (plain vector
    ops, no cross-lane reduction), m, l and alpha are (1, sub_q) lane-dense
    vectors, and the accumulator is out^T, (D, bq), rescaled by a sublane
    broadcast and transposed once a q tile. There the cut along q is the one
    that costs the MXU nothing: Q^T chunks are QK^T's stationary operand, P
    chunks V^T P's. Otherwise (q-major, the production layout before PR 27)
    a piece is (sub_q, sub_k) and the statistics are (sub_q, 1) columns."""
    bh = pl.program_id(0)
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)
    axis = 0 if kmajor else 1  # of a score piece, the k positions

    def along(n, q_axis):  # (1, n) or (n, 1) iota: positions along one axis
        lanes = q_axis == kmajor
        return lax.broadcasted_iota(
            jnp.int32, (1, n) if lanes else (n, 1), 1 if lanes else 0
        )

    def at(q_part, rest=slice(None)):  # index into m / l / acc: q x the rest
        return (rest, q_part) if kmajor else (q_part, rest)

    @pl.when(ki == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    live = (not causal) or (ki * bk < (qi + 1) * bq)

    @pl.when(live)
    def _accumulate():
        def scores(r0, c0):
            q = q_ref[0, r0:r0 + sub_q, :]
            k = k_ref[0, c0:c0 + sub_k, :]
            return lax.dot_general(
                *((k, q) if kmajor else (q, k)), (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )

        pieces = [(r0, c0) for r0 in range(0, bq, sub_q)
                  for c0 in range(0, bk, sub_k)]
        s_next = scores(*pieces[0]) if lookahead else None
        for i, (r0, c0) in enumerate(pieces):
            if lookahead:
                s = s_next
                if i + 1 < len(pieces):
                    s_next = scores(*pieces[i + 1])
            else:
                s = scores(r0, c0)
            if c0 == 0:
                rows = qi * bq + r0 + along(sub_q, True)
                if rate > 0.0:
                    rowbase = fa._dropout_rowbase(seed_ref[0], bhv_ref[bh], rows)
                chunk = slice(r0, r0 + sub_q)
                m = m_scr[at(chunk, slice(0, 1))]
                l = l_scr[at(chunk, slice(0, 1))]
                acc = acc_scr[at(chunk)]
            cols = ki * bk + c0 + along(sub_k, False)
            if causal:
                mask = rows >= cols
                s = jnp.where(mask, s, NEG_INF)
            if trim:
                # One multiply a score where the plain body has three: the
                # softmax scale and exp's log2(e) are one constant, and the
                # dropout's 1 / keep_prob rides in the subtracted maximum
                # (l then sums p / keep_prob; _finalize takes it out).
                c = scale * LOG2_E
                m_new = jnp.maximum(m, jnp.max(s, axis=axis, keepdims=True) * c)
                alpha = jnp.exp2(m - m_new)
                shift = m_new + math.log2(1.0 - rate) if rate > 0.0 else m_new
                p = jnp.exp2(s * c - shift)
            else:
                s = s * scale
                m_new = jnp.maximum(m, jnp.max(s, axis=axis, keepdims=True))
                alpha = jnp.exp(m - m_new)
                p = jnp.exp(s - m_new)
                if causal:
                    p = jnp.where(mask, p, 0.0)
            if rate > 0.0:
                keep = fa._mix32(
                    rowbase + cols.astype(jnp.uint32)
                ) < fa._dropout_threshold(rate)
                scaled = p if trim else p * (1.0 / (1.0 - rate))
                p_acc = jnp.where(keep, scaled, 0.0)
            else:
                p_acc = p
            l = alpha * l + jnp.sum(p, axis=axis, keepdims=True)
            p_acc = p_acc.astype(q_ref.dtype)
            v = v_ref[0, c0:c0 + sub_k, :]
            acc = acc * alpha + (
                lax.dot_general(  # out^T: V^T P
                    v, p_acc, (((0,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32,
                ) if kmajor else lax.dot_general(
                    p_acc, v, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )
            )
            m = m_new
            if c0 + sub_k == bk:
                copies = (1, sub_q) if kmajor else (sub_q, 128)
                m_scr[at(chunk)] = jnp.broadcast_to(m, copies)
                l_scr[at(chunk)] = jnp.broadcast_to(l, copies)
                acc_scr[at(chunk)] = acc

    @pl.when(ki == nk - 1)
    def _finalize():
        l = l_scr[at(slice(None), slice(0, 1))]
        l_safe = jnp.where(l == 0.0, 1.0, l)  # fully-masked rows -> zero output
        m = m_scr[at(slice(None), slice(0, 1))]
        if trim:  # m in log2 units of the scaled scores, l over p / keep_prob
            l_safe = l_safe * (1.0 - rate)
            m = m * (1.0 / LOG2_E)
        out = acc_scr[:] / l_safe
        lse = m + jnp.log(l_safe)
        o_ref[0] = (out.T if kmajor else out).astype(o_ref.dtype)
        lse_ref[0] = jnp.broadcast_to(lse if kmajor else lse[:, 0][None, :], (8, bq))


def flash_subtiled(
    q, k, v, seed, bhv, *, causal, rate, layout, bq, bk, sub_q, sub_k,
    lookahead, trim=False,
):
    BH, S, D = q.shape
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    kmajor = layout == "kmajor"
    out, lse = pl.pallas_call(
        functools.partial(
            _fwd_kernel_subtiled, kmajor=kmajor, bq=bq, bk=bk, sub_q=sub_q,
            sub_k=sub_k, trim=trim,
            lookahead=lookahead, scale=1.0 / (D ** 0.5), causal=causal,
            rate=rate,
        ),
        out_shape=[
            jax.ShapeDtypeStruct((BH, S, D), q.dtype),
            jax.ShapeDtypeStruct((BH, 8, S), jnp.float32),
        ],
        grid=(BH, S // bq, S // bk),
        in_specs=[
            smem, smem,
            pl.BlockSpec((1, bq, D), lambda b, qi, ki: (b, qi, 0)),
            pl.BlockSpec((1, bk, D), lambda b, qi, ki: (b, ki, 0)),
            pl.BlockSpec((1, bk, D), lambda b, qi, ki: (b, ki, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, bq, D), lambda b, qi, ki: (b, qi, 0)),
            pl.BlockSpec((1, 8, bq), lambda b, qi, ki: (b, 0, qi)),
        ],
        scratch_shapes=[
            pltpu.VMEM((1, bq) if kmajor else (bq, 128), jnp.float32),
            pltpu.VMEM((1, bq) if kmajor else (bq, 128), jnp.float32),
            pltpu.VMEM((D, bq) if kmajor else (bq, D), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            # Room for a whole (2048, 2048) f32 tile and its temporaries, so
            # that a refusal in the sweep is never this limit's.
            vmem_limit_bytes=100 * 2**20,
        ),
        name=f"flash_fwd_{layout}",
    )(seed, bhv, q, k, v)
    return out, lse[:, 0, :]


def _matmul_only_kernel(q_ref, k_ref, v_ref, o_ref, acc_scr, *, scale):
    """The two dots with a scale and a cast between them: the MXU floor."""
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_scr[:] = jnp.zeros_like(acc_scr)

    q = q_ref[0]
    s = lax.dot_general(
        q, k_ref[0], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) * scale
    acc_scr[:] = acc_scr[:] + lax.dot_general(
        s.astype(q.dtype), v_ref[0], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    @pl.when(ki == pl.num_programs(2) - 1)
    def _done():
        o_ref[0] = acc_scr[:].astype(o_ref.dtype)


def matmul_floor(q, k, v, bq=1024, bk=1024):
    BH, S, D = q.shape
    return pl.pallas_call(
        functools.partial(_matmul_only_kernel, scale=1.0 / (D ** 0.5)),
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


def xla_sdpa(q, k, v):
    scale = 1.0 / (q.shape[-1] ** 0.5)
    s = jnp.einsum("bqd,bkd->bqk", q, k, preferred_element_type=jnp.float32) * scale
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bqk,bkd->bqd", p.astype(v.dtype), v)


def chained(fn):
    """``n`` calls of ``fn(q, *rest) -> (out, lse)`` in one jit, each output
    the next call's q; ``n`` is an argument, so the one compilation serves
    the timed chain and the single call whose results are compared."""

    def many(n, q, *rest):
        def body(_, carry):
            out, lse = fn(carry[0], *rest)
            return out, carry[1] if lse is None else lse

        stat = jnp.zeros(q.shape[:2], jnp.float32)
        out, lse = lax.fori_loop(0, n, body, (q, stat))
        return jnp.sum(out.astype(jnp.float32)), out, lse

    return jax.jit(many)


def time_ms(many, args, chain, reps):
    float(many(chain, *args)[0])  # compile + warm
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        float(many(chain, *args)[0])
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts) / chain * 1e3)


def sweep_rows(S, layouts):
    """(layout, bq, bk, sub_q, sub_k, lookahead) of the sweep. The whole grid
    at the (1024, 1024) DMA tile; at the larger tiles only pieces of at most
    512 q positions and at least 512 k positions (a whole (2048, 2048) f32
    tile is what the pieces are there to avoid)."""
    rows = []
    for layout, (bq, bk) in itertools.product(layouts, DMA_TILES):
        if S % bq or S % bk:
            continue
        for sub_k, chunk, lookahead in itertools.product(
            SUB_K + [bk], ROW_CHUNKS, (False, True)
        ):
            sub_q, sub_k = chunk or bq, min(sub_k, bk)
            if lookahead and sub_k == bk and sub_q == bq:
                continue  # one piece: nothing to look ahead to
            if (bq, bk) != (1024, 1024) and (sub_q > 512 or sub_k < 512):
                continue
            rows.append((layout, bq, bk, sub_q, sub_k, lookahead))
    return sorted(set(rows))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--describe", action="store_true",
                    help="compile every row for a described v5e; no timing")
    ap.add_argument("--bundles", metavar="DIR",
                    help="summarise the LLO dump in DIR and exit")
    ap.add_argument("--bodies", nargs="*", default=list(BODIES), choices=BODIES,
                    help="under a mask rule, the bodies flash_production may run")
    ap.add_argument("--shapes", nargs="*", default=list(SHAPES))
    ap.add_argument("--kv-heads", type=int, default=None,
                    help="override the shapes' rows of k and v (batch x kv heads); "
                         "a shape's BH is a multiple")
    ap.add_argument("--dropout", type=float, default=None,
                    help="override the shapes' dropout rate")
    ap.add_argument("--causal", type=int, default=None, choices=(0, 1),
                    help="override the shapes' masking")
    ap.add_argument("--layouts", nargs="*", default=["qmajor", "kmajor"],
                    help="score tile (rows q, lanes k) or (rows k, lanes q)")
    ap.add_argument("--trim", type=int, nargs="*", default=[0],
                    help="1 = one multiply a score (scale, log2 e and "
                         "1 / keep_prob folded), 0 = the plain arithmetic")
    ap.add_argument("--rows", nargs="*", default=None,
                    help="explicit rows layout:bq:bk:sub_q:sub_k:lookahead:trim "
                         "instead of the grid; layout prod = the real kernel "
                         "at that tile with that sub_k (sub_q is ignored)")
    ap.add_argument("--target-ms", type=float, default=150.0,
                    help="device time one timed execution should take")
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--out", default="chiprun_out/flash_fwd_sweep.jsonl")
    args = ap.parse_args()

    if args.bundles:
        read_bundles(args.bundles)
        return
    sharding = None
    if args.describe:
        from jax.experimental import topologies
        from jax.sharding import SingleDeviceSharding

        print(LLO_DUMP_HELP.format(clock=CLOCK_HZ / 1e9).replace("_bwd", "_fwd"), flush=True)
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
        sharding = SingleDeviceSharding(topo.devices[0])
    elif jax.default_backend() != "tpu":
        sys.exit("no TPU: --describe compiles without one, timing needs one")

    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    for name in args.shapes:
        shape = dict(SHAPES[name])
        if args.dropout is not None:
            shape["rate"] = args.dropout
        if args.causal is not None:
            shape["causal"] = bool(args.causal)
        BH, S, D = shape["BH"], shape["S"], shape["D"]
        KV = shape["KV"] = args.kv_heads or shape.get("KV", BH)
        if BH % KV:
            sys.exit(f"{name}: {BH} query rows over {KV} of k and v")
        rep = BH // KV
        causal, rate = shape["causal"], shape["rate"]
        ruled = isinstance(causal, fa.BlockDiffusion)
        pairs = (causal.tile_counts(1024, 1024)[2] if ruled
                 else S * S / (2 if causal else 1))
        flops = 4 * BH * pairs * D
        least_ms = flops / PEAK_FLOPS * 1e3
        # Score tiles a call visits, in units of (1024, 1024). (What the MXU
        # allows one: 2 products x 8192 row pushes over 4 MXUs at 1.5 GHz =
        # 2.73 us, at D 64 as at D 128.)
        counts = shape_counts(causal, S, 1024, fa._fwd_sub_k(1024))
        tiles = BH * (counts["full"] + counts["lower"])
        print(f"{name}: {shape}; least time {least_ms:.3f} ms a call at "
              f"full-width peak; {tiles} live (1024, 1024) tiles, a head "
              f"{counts}", flush=True)

        x = jax.ShapeDtypeStruct((BH, S, D), jnp.bfloat16, sharding=sharding)
        kv_a = jax.ShapeDtypeStruct((KV, S, D), jnp.bfloat16, sharding=sharding)
        seed_a = jax.ShapeDtypeStruct((1,), jnp.uint32, sharding=sharding)
        bhv_a = jax.ShapeDtypeStruct((BH,), jnp.int32, sharding=sharding)
        n_a = jax.ShapeDtypeStruct((), jnp.int32, sharding=sharding)
        if not args.describe:
            keys = jax.random.split(jax.random.key(0), 3)
            q, k, v = (jax.random.normal(key, (rows, S, D), jnp.bfloat16)
                       for key, rows in zip(keys, (BH, KV, KV)))
            seed = jnp.asarray([1234], jnp.uint32)
            bhv = jnp.arange(BH, dtype=jnp.int32)
        chain = max(4, int(args.target_ms / max(2.0 * least_ms, 0.05)))

        def production(q, k, v, seed, bhv, bq=None, bk=None, sub_k=None):
            return fa._flash_forward(
                q, k, v, causal, False,
                bq or fa._pick_block(S, fa._FWD_BLOCK_Q),
                bk or fa._pick_block(S, fa._FWD_BLOCK_K),
                rate, seed, bhv, sub_k=sub_k,
            )

        def with_bodies(which, **cfg):
            def run(*a):
                with bodies(which):  # entered when the call is traced
                    return production(*a, **cfg)
            return run

        variants = [("flash_production", {}, production)]
        if counts["lower"]:
            variants = [("flash_production", dict(bodies=which), with_bodies(which))
                        for which in args.bodies]
        if rep > 1:  # the repeat in front of the kernel, timed with it
            variants.append(("flash_repeated", {}, lambda q, k, v, *ids: production(
                q, fa._repeat_groups(k, rep), fa._repeat_groups(v, rep), *ids)))
        if not ruled:
            variants.insert(0, ("matmul_floor", {}, lambda q, k, v, seed, bhv: (matmul_floor(q, k, v), None)))
        if not ruled and BH * S * S * 4 <= 2 * 2**30:
            variants.insert(1, ("xla_sdpa", {}, lambda q, k, v, seed, bhv: (xla_sdpa(q, k, v), None)))
        fields = ("layout", "bq", "bk", "sub_q", "sub_k", "lookahead", "trim")
        if ruled and not args.rows:
            rows = []
        elif args.rows:
            rows = [tuple(f if i == 0 else int(f) for i, f in enumerate(r.split(":")))
                    for r in args.rows]
            rows = [r for r in rows if S % r[1] == 0 and S % r[2] == 0
                    and (r[0] == "prod" or not ruled)]
        else:
            rows = [r + (t,) for t in args.trim
                    for r in sweep_rows(S, args.layouts)]
        for row in rows:
            cfg = dict(zip(fields, row))
            if cfg["layout"] == "prod":  # the real kernel, its piece forced
                cfg = {k: cfg[k] for k in ("bq", "bk", "sub_k")}
                variants.append((
                    "flash_production", cfg, functools.partial(production, **cfg)
                ))
                continue
            cfg.update(lookahead=bool(cfg["lookahead"]), trim=bool(cfg["trim"]))
            variants.append((
                "flash_subtiled", cfg,
                functools.partial(flash_subtiled, causal=causal, rate=rate, **cfg),
            ))

        want, by_bodies = None, {}
        if rep > 1 and not args.describe:
            whole_k, whole_v = fa._repeat_groups(k, rep), fa._repeat_groups(v, rep)
        for variant, cfg, fn in variants:
            row = dict(shape=name, variant=variant, **cfg)
            n = chain if variant != "xla_sdpa" else max(chain // 8, 2)
            many = chained(fn)
            # the kernel as the model runs it finds a query head's kv head
            # itself; every other row takes a k and a v a query head
            grouped = rep > 1 and variant in ("flash_production", "flash_repeated")
            try:
                if args.describe:
                    many.lower(n_a, x, *((kv_a, kv_a) if grouped else (x, x)), seed_a, bhv_a).compile()
                    row["compiles"] = True
                else:
                    operands = (q, k, v, seed, bhv) if grouped or rep == 1 else (q, whole_k, whole_v, seed, bhv)
                    row["ms"] = time_ms(many, operands, n, args.reps)
                    row["pct_of_peak"] = 100 * least_ms / row["ms"]
                    row["us_a_tile"] = row["ms"] * 1e3 / tiles
                    if list(cfg) == ["bodies"]:
                        by_bodies[cfg["bodies"]] = row["ms"]
                    _, out, lse = many(1, *operands)
                    if want is None and variant == "flash_production":
                        want = (out, lse)
                    elif variant.startswith("flash_"):
                        row["max_abs_diff_out"] = float(jnp.max(jnp.abs(
                            out.astype(jnp.float32) - want[0].astype(jnp.float32)
                        )))
                        row["max_abs_diff_lse"] = float(
                            jnp.max(jnp.abs(lse - want[1]))
                        )
            except Exception as e:  # Mosaic's refusal is the finding
                row["error"] = str(e).splitlines()[0][:200]
            print(json.dumps(row), flush=True)
            with open(args.out, "a") as f:
                f.write(json.dumps(row) + "\n")
        if set(by_bodies) == set(BODIES):
            print(json.dumps(dict(
                shape=name, by_shape=True, tiles_a_head=counts,
                us_a_tile=us_by_shape(by_bodies, counts, BH),
            )), flush=True)


if __name__ == "__main__":
    main()
