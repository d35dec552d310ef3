"""Ring attention — sequence/context parallelism over a mesh axis.

Nothing like this exists in the reference (SURVEY §5.7: longest tested
sequence is 2048, no sequence parallelism anywhere); it is a first-class
capability here because long-context is where TPU ICI topology shines.

Mechanism: with the sequence dimension sharded over the mesh axis ``seq``,
each device keeps its local Q block resident and the K/V blocks *rotate*
around the ring via ``ppermute`` — after N-1 hops every device has attended
its queries to every key. Online-softmax statistics (running max / running
sum) merge each incoming block, so the full (S, S) score matrix never exists
anywhere and per-device attention memory is O(S_local * S_local). Communication
rides neighbor-to-neighbor ICI links — exactly the topology ppermute maps to.

Per-hop block compute is the SAME Pallas flash kernel machinery as
``flash_attention`` — a variant that takes global (row, col) offsets from
SMEM and emits the *unnormalized* online-softmax triple (m, l, o) instead of
a normalized output, so the ring merge happens outside the kernel while the
(S_local, S_local) score tile still never leaves VMEM. Measured single-chip
at the parity config, the kernel is ~2x the einsum path the ring used
before (flash 42.0k vs materialized-path 19.5k tok/s/chip —
docs/PERFORMANCE.md), and that per-block gap is what multi-chip sequence
parallelism inherits.

The backward is a second ring pass (Liu et al. 2023, "Ring Attention with
Blockwise Transformers"): each device recomputes its block's attention
probabilities from the saved GLOBAL logsumexp (standard flash backward
identity), accumulates dq locally, and rotates (k, v, dk, dv) around the
ring so after N hops every block's dk/dv arrive back at their home device
fully accumulated. Per-block compute follows the measured S-dependent
backward crossover (docs/PERFORMANCE.md §11-12): XLA-fused blockwise
einsum tiles for S_local < 4096, offset-aware Pallas dq / dk+dv kernels
(1.6-2.1x per block) from 4096 up — the regime multi-chip sequence
parallelism actually runs in.

Attention-probability dropout uses the flash kernel's absolute-coordinate
hash (``flash_attention._dropout_keep``) keyed by global (batch*head, row,
col): with equal seeds, ring and flash produce bitwise-identical keep masks
regardless of how the ring shards the sequence, and the ring backward
regenerates the same mask from coordinates alone.

Usable two ways:
- ``ring_attention(q, k, v)`` inside a jitted function running under a mesh
  that has a ``seq`` axis (it shard_maps itself over that axis);
- ``ring_attention_sharded`` directly inside an existing ``shard_map``.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

from .flash_attention import (
    _dropout_keep,
    _dropout_threshold,
    _pair_backward,
    _pick_block,
    _resolve_interpret,
    _vma_struct,
    _warn_seedless_dropout,
    _FWD_BLOCK_Q,
    _FWD_BLOCK_K,
    _BWD_BLOCK_K,
    _PALLAS_BWD_MIN_SEQ,
)

NEG_INF = -1e30


def _ring_fwd_block_kernel(
    seed_ref, qoff_ref, koff_ref, bhv_ref, q_ref, k_ref, v_ref,
    m_ref, l_ref, o_ref, m_scr, l_scr, acc_scr,
    *, bq: int, bk: int, scale: float, causal: bool, dropout_rate: float,
):
    """Flash forward tile pass emitting UNNORMALIZED (m, l, o) for one ring
    block: identical math to ``flash_attention._flash_fwd_kernel`` except
    (a) row/col coordinates come from per-TILE global base vectors in SMEM
    (``qoff_ref`` (nq,) / ``koff_ref`` (nk,) — shard offset + arange for
    contiguous ring blocks, per-half-chunk bases for the zigzag layout) so
    causal masking and the dropout hash see absolute coordinates, (b) the
    per-grid-row global batch*head index comes from the SMEM vector
    ``bhv_ref`` (data/tensor-parallel shards feed their global offsets in),
    and (c) no normalization — the ring merge outside combines blocks,
    exactly like the kernel's own k-block accumulation combines tiles."""
    bh = pl.program_id(0)
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)
    q_off = qoff_ref[qi]
    k_off = koff_ref[ki]

    @pl.when(ki == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    # Causal skip by GLOBAL position: a k tile strictly above the diagonal
    # contributes nothing. With ring offsets this also skips every tile of a
    # block that sits entirely in this Q shard's future.
    live = True if not causal else (q_off + bq - 1 >= k_off)

    @pl.when(live)
    def _accumulate():
        q = q_ref[0]  # (bq, d) input dtype
        k = k_ref[0]  # (bk, d)
        v = v_ref[0]
        s = lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # (bq, bk) fp32

        rows = q_off + lax.broadcasted_iota(jnp.int32, (bq, 1), 0)
        cols = k_off + lax.broadcasted_iota(jnp.int32, (1, bk), 1)
        if causal:
            mask = rows >= cols
            s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[:, :1]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        if causal:
            p = jnp.where(mask, p, 0.0)

        # Normalizer accumulates UN-dropped p (dropout acts after
        # normalization; normalization is linear); the output accumulator
        # sees the dropped+rescaled p — same convention as the flash kernel.
        if dropout_rate > 0.0:
            keep = _dropout_keep(
                seed_ref[0], bhv_ref[bh], rows, cols,
                _dropout_threshold(dropout_rate),
            )
            p_acc = jnp.where(keep, p * (1.0 / (1.0 - dropout_rate)), 0.0)
        else:
            p_acc = p

        l_prev = l_scr[:, :1]
        l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
        acc_scr[:] = acc_scr[:] * alpha + lax.dot_general(
            p_acc.astype(q.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(ki == nk - 1)
    def _finalize():
        # Stats are logically (bq,); stored sublane-broadcast as (8, bq)
        # because TPU output blocks must tile to (8, 128). o stays fp32 and
        # unnormalized — the ring merge divides once at the very end.
        m_ref[0] = jnp.broadcast_to(m_scr[:, :1].T, (8, bq))
        l_ref[0] = jnp.broadcast_to(l_scr[:, :1].T, (8, bq))
        o_ref[0] = acc_scr[:]


def _block_stats_kernel(
    q3, k3, v3, seed, qoffs, koffs, bh_vec,
    causal: bool, dropout_rate: float, bq: int, bk: int,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Pallas path: (BH, Sq, D) x (BH, Sk, D) -> m, l (BH, Sq) f32 and
    unnormalized o (BH, Sq, D) f32. ``qoffs``/``koffs`` are per-tile global
    base vectors ((Sq//bq,) / (Sk//bk,) int32)."""
    BH, Sq, D = q3.shape
    Sk = k3.shape[1]
    scale = 1.0 / (D ** 0.5)
    m, l, o = pl.pallas_call(
        functools.partial(
            _ring_fwd_block_kernel, bq=bq, bk=bk, scale=scale,
            causal=causal, dropout_rate=dropout_rate,
        ),
        out_shape=[
            _vma_struct((BH, 8, Sq), jnp.float32, q3, k3, v3),
            _vma_struct((BH, 8, Sq), jnp.float32, q3, k3, v3),
            _vma_struct((BH, Sq, D), jnp.float32, q3, k3, v3),
        ],
        grid=(BH, Sq // bq, Sk // bk),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),  # seed (1,) uint32
            pl.BlockSpec(memory_space=pltpu.SMEM),  # q tile bases (nq,)
            pl.BlockSpec(memory_space=pltpu.SMEM),  # k tile bases (nk,)
            pl.BlockSpec(memory_space=pltpu.SMEM),  # global bh ids (BH,)
            pl.BlockSpec((1, bq, D), lambda b, qi, ki: (b, qi, 0)),
            pl.BlockSpec((1, bk, D), lambda b, qi, ki: (b, ki, 0)),
            pl.BlockSpec((1, bk, D), lambda b, qi, ki: (b, ki, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 8, bq), lambda b, qi, ki: (b, 0, qi)),
            pl.BlockSpec((1, 8, bq), lambda b, qi, ki: (b, 0, qi)),
            pl.BlockSpec((1, bq, D), lambda b, qi, ki: (b, qi, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, 128), jnp.float32),  # running max
            pltpu.VMEM((bq, 128), jnp.float32),  # running sum
            pltpu.VMEM((bq, D), jnp.float32),    # output accumulator
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
    )(seed, qoffs, koffs, bh_vec, q3, k3, v3)
    return m[:, 0, :], l[:, 0, :], o


def _block_stats_jnp(
    q3, k3, v3, seed, row_idx, col_idx, bh_vec,
    causal: bool, dropout_rate: float,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Einsum path with the kernel's exact semantics, for backends where the
    Pallas interpreter cannot run inside vma-carrying manual regions (the
    CPU test meshes — same limitation flash_attention._jnp_reference_forward
    covers). ``row_idx``/``col_idx`` are per-row GLOBAL index vectors
    ((Sq,) / (Sk,) int32) — contiguous or zigzag."""
    BH, Sq, D = q3.shape
    Sk = k3.shape[1]
    scale = 1.0 / (D ** 0.5)
    s = jnp.einsum(
        "bqd,bkd->bqk", q3, k3, preferred_element_type=jnp.float32
    ) * scale
    rows = row_idx.astype(jnp.int32)[:, None]
    cols = col_idx.astype(jnp.int32)[None, :]
    if causal:
        mask = (rows >= cols)[None]
        s = jnp.where(mask, s, NEG_INF)
    m = jnp.max(s, axis=-1)
    p = jnp.exp(s - m[..., None])
    if causal:
        p = jnp.where(mask, p, 0.0)
    l = jnp.sum(p, axis=-1)
    if dropout_rate > 0.0:
        keep = _dropout_keep(
            seed[0], bh_vec[:, None, None], rows[None], cols[None],
            _dropout_threshold(dropout_rate),
        )
        p_acc = jnp.where(keep, p * (1.0 / (1.0 - dropout_rate)), 0.0)
    else:
        p_acc = p
    o = jnp.einsum(
        "bqk,bkd->bqd", p_acc.astype(q3.dtype), v3,
        preferred_element_type=jnp.float32,
    )
    return m, l, o


def _block_bwd_jnp(
    q3, k_b, v_b, do3, lse, delta, seed, row_idx, col_idx, bh_vec,
    causal: bool, dropout_rate: float, tile: int,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Einsum path for one resident ring block's backward ->
    (dq_partial, dk_b, dv_b), all fp32 (BH, Sl, D), tiled over K so only
    (Sl, tile) score tiles materialize — flash_attention._jnp_blockwise_bwd
    restricted to this block, with per-row GLOBAL index vectors
    (``row_idx``/``col_idx``, contiguous or zigzag) as _block_stats_jnp."""
    from ..utils.vma import pcast_like

    BH, Sl, D = q3.shape
    f32 = jnp.float32
    cd = q3.dtype
    scale = 1.0 / (D ** 0.5)
    dof = do3.astype(cd)
    threshold = _dropout_threshold(dropout_rate)
    nt = Sl // tile
    ks = k_b.reshape(BH, nt, tile, D).transpose(1, 0, 2, 3)
    vs = v_b.reshape(BH, nt, tile, D).transpose(1, 0, 2, 3)
    col_tiles = col_idx.reshape(nt, tile)

    def one_tile(dq_acc, blk):
        ti, k_t, v_t = blk
        cols = jnp.take(col_tiles, ti, axis=0)
        s = jnp.einsum(
            "bqd,bkd->bqk", q3, k_t, preferred_element_type=f32
        ) * scale
        if causal:
            mask = row_idx[:, None] >= cols[None, :]
            s = jnp.where(mask[None], s, NEG_INF)
        p = jnp.exp(s - lse[:, :, None])  # (BH, Sl, tile) fp32
        if causal:
            p = jnp.where(mask[None], p, 0.0)
        if dropout_rate > 0.0:
            keep = _dropout_keep(
                seed[0], bh_vec[:, None, None], row_idx[None, :, None],
                cols[None, None, :], threshold,
            )
            inv = 1.0 / (1.0 - dropout_rate)
            pd = jnp.where(keep, p * inv, 0.0)
            dp_scale = jnp.where(keep, inv, 0.0)
        else:
            pd = p
            dp_scale = None
        dv_t = jnp.einsum(
            "bqk,bqd->bkd", pd.astype(cd), dof, preferred_element_type=f32
        )
        dp = jnp.einsum(
            "bqd,bkd->bqk", dof, v_t, preferred_element_type=f32
        )
        if dp_scale is not None:
            dp = dp * dp_scale
        ds = (p * (dp - delta[:, :, None]) * scale).astype(cd)
        dq_acc = dq_acc + jnp.einsum(
            "bqk,bkd->bqd", ds, k_t, preferred_element_type=f32
        )
        dk_t = jnp.einsum(
            "bqk,bqd->bkd", ds, q3, preferred_element_type=f32
        )
        return dq_acc, (dk_t, dv_t)

    dq0 = pcast_like(jnp.zeros((BH, Sl, D), f32), q3, k_b, v_b, do3)
    dq_p, (dk_tiles, dv_tiles) = lax.scan(
        one_tile, dq0, (jnp.arange(nt), ks, vs)
    )
    dk_b = dk_tiles.transpose(1, 0, 2, 3).reshape(BH, Sl, D)
    dv_b = dv_tiles.transpose(1, 0, 2, 3).reshape(BH, Sl, D)
    return dq_p, dk_b, dv_b


def _zig_chunk_bases(c, n, h):
    """Global start rows of device ``c``'s two zigzag half-chunks: chunk c
    and chunk 2n-1-c (h tokens each). ``c`` may be traced."""
    return (c * h, (2 * n - 1 - c) * h)


def _bases_to_tiles(bases, h: int, b: int):
    """Per-tile global base vector from per-chunk bases (each chunk h rows,
    tile size b, b | h): concat over chunks of base + arange(h//b)*b."""
    per = h // b
    return jnp.concatenate([
        jnp.asarray(base, jnp.int32) + jnp.arange(per, dtype=jnp.int32) * b
        for base in bases
    ])


def _bases_to_rows(bases, h: int):
    """Per-row global index vector from per-chunk bases."""
    return jnp.concatenate([
        jnp.asarray(base, jnp.int32) + jnp.arange(h, dtype=jnp.int32)
        for base in bases
    ])


def _zig_exchange(x3, axis_name: str, n: int, my, inverse: bool = False):
    """Redistribute (BH, Sl, D) half-chunks between the contiguous layout
    (device c holds chunks 2c, 2c+1) and the zigzag layout (device c holds
    chunks c, 2n-1-c — Brandon et al. 2023 "striped"/zigzag causal load
    balancing): each device's triangular work becomes ~equal, so no ring
    hop waits on the last device's full diagonal. Two ppermutes each way
    (one per half), ~one extra hop-equivalent of traffic per exchange.
    """
    zig = lambda g: g if g < n else 2 * n - 1 - g
    h = x3.shape[1] // 2
    lo, hi = x3[:, :h], x3[:, h:]  # axis 1 = rows; trailing dims pass through
    even = (my % 2) == 0
    if not inverse:
        # contiguous -> zigzag: device c sends chunk 2c on ring A, chunk
        # 2c+1 on ring B; zigzag device d's low chunk (d) arrives on A iff
        # d is even, and its high chunk (2n-1-d) on the other.
        perm_a = [(c, zig(2 * c)) for c in range(n)]
        perm_b = [(c, zig(2 * c + 1)) for c in range(n)]
        recv_a = lax.ppermute(lo, axis_name, perm_a)
        recv_b = lax.ppermute(hi, axis_name, perm_b)
        new_lo = jnp.where(even, recv_a, recv_b)
        new_hi = jnp.where(even, recv_b, recv_a)
    else:
        # zigzag -> contiguous: ring A carries the EVEN global chunk each
        # device holds (its low chunk if the device index is even, else its
        # high chunk), ring B the odd one; contiguous device c receives
        # chunk 2c on A (its low half) and 2c+1 on B.
        perm_a = [(zig(2 * c), c) for c in range(n)]
        perm_b = [(zig(2 * c + 1), c) for c in range(n)]
        send_a = jnp.where(even, lo, hi)
        send_b = jnp.where(even, hi, lo)
        new_lo = lax.ppermute(send_a, axis_name, perm_a)
        new_hi = lax.ppermute(send_b, axis_name, perm_b)
    return jnp.concatenate([new_lo, new_hi], axis=1)


def _global_bh_vec(B: int, H: int, b_off, h_off, n_heads: int) -> jax.Array:
    """(B*H,) int32 of GLOBAL batch*heads indices — matches flash's b*H + h
    keying when batch/heads are themselves sharded over mesh axes."""
    return (
        (b_off + jnp.arange(B, dtype=jnp.int32))[:, None] * n_heads
        + h_off + jnp.arange(H, dtype=jnp.int32)[None, :]
    ).reshape(B * H)


def _ring_offsets(axis_name, batch_axis, heads_axis, B, H):
    n = lax.axis_size(axis_name)
    my = lax.axis_index(axis_name)
    b_off = lax.axis_index(batch_axis) * B if batch_axis else 0
    h_off = lax.axis_index(heads_axis) * H if heads_axis else 0
    n_heads = H * (lax.axis_size(heads_axis) if heads_axis else 1)
    return n, my, b_off, h_off, n_heads


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _ring(opts: Tuple, q, k, v, seed):
    out, _ = _ring_fwd(opts, q, k, v, seed)
    return out


def _ring_fwd(opts, q, k, v, seed):
    """Forward ring pass over (B, Sl, H, D) local shards -> normalized out
    plus the (BH, Sl) global logsumexp residual the backward needs."""
    (axis_name, causal, rate, batch_axis, heads_axis,
     interpret, bq, bk, bk_bwd, zig) = opts
    B, Sl, H, D = q.shape
    n, my, b_off, h_off, n_heads = _ring_offsets(
        axis_name, batch_axis, heads_axis, B, H
    )
    bh_vec = _global_bh_vec(B, H, b_off, h_off, n_heads)
    perm = [(j, (j + 1) % n) for j in range(n)]

    def to3(t):  # (B, Sl, H, D) -> (B*H, Sl, D)
        return t.transpose(0, 2, 1, 3).reshape(B * H, Sl, D)

    q3, k3, v3 = to3(q), to3(k), to3(v)
    if zig:
        # Causal load balancing: redistribute to the zigzag layout so every
        # device's triangle work is ~equal (see _zig_exchange). Global
        # coordinates flow through the per-chunk base vectors, so masking
        # and dropout stay bit-identical to flash.
        q3 = _zig_exchange(q3, axis_name, n, my)
        k3 = _zig_exchange(k3, axis_name, n, my)
        v3 = _zig_exchange(v3, axis_name, n, my)
        h = Sl // 2
        q_bases = _zig_chunk_bases(my, n, h)
    else:
        h = Sl
        q_bases = (my * Sl,)
    m_run = jnp.full((B * H, Sl), NEG_INF, jnp.float32)
    l_run = jnp.zeros((B * H, Sl), jnp.float32)
    o_run = jnp.zeros((B * H, Sl, D), jnp.float32)
    k_cur, v_cur = k3, v3
    # n is a static mesh-axis size, so the ring unrolls as a Python loop: no
    # permute is issued after the final block (the rotated K/V would be
    # discarded), saving one neighbor exchange per call.
    for t in range(n):
        # After t forward hops the resident block originated on (my - t) % n.
        src = (my - t) % n
        k_bases = _zig_chunk_bases(src, n, h) if zig else (src * Sl,)
        if interpret:
            m_b, l_b, o_b = _block_stats_jnp(
                q3, k_cur, v_cur, seed, _bases_to_rows(q_bases, h),
                _bases_to_rows(k_bases, h), bh_vec, causal, rate,
            )
        else:
            m_b, l_b, o_b = _block_stats_kernel(
                q3, k_cur, v_cur, seed, _bases_to_tiles(q_bases, h, bq),
                _bases_to_tiles(k_bases, h, bk), bh_vec, causal,
                rate, bq, bk,
            )
        # Merge online-softmax statistics, exactly as the kernel merges its
        # own k tiles: rescale both accumulators to the joint max.
        m_new = jnp.maximum(m_run, m_b)
        a_run = jnp.exp(m_run - m_new)
        a_b = jnp.exp(m_b - m_new)
        l_run = l_run * a_run + l_b * a_b
        o_run = o_run * a_run[..., None] + o_b * a_b[..., None]
        m_run = m_new
        if t < n - 1:
            k_cur = lax.ppermute(k_cur, axis_name, perm)
            v_cur = lax.ppermute(v_cur, axis_name, perm)
    l_safe = jnp.where(l_run == 0.0, 1.0, l_run)
    out3 = (o_run / l_safe[..., None]).astype(q.dtype)
    lse = m_run + jnp.log(l_safe)  # (BH, Sl) fp32, zigzag-ordered when zig
    if zig:
        out3 = _zig_exchange(out3, axis_name, n, my, inverse=True)
    out = out3.reshape(B, H, Sl, D).transpose(0, 2, 1, 3)
    return out, (q, k, v, out, lse, seed)


def _ring_bwd(opts, res, do):
    """Backward ring pass: recompute per-block probabilities from the saved
    global logsumexp, accumulate dq locally, rotate (k, v, dk, dv) a full
    cycle so every block's dk/dv land home fully summed. Per-block compute
    follows the measured S-dependent crossover: einsum tiles below
    _PALLAS_BWD_MIN_SEQ-sized local shards, the shared offset-aware Pallas
    backward kernels from there up (docs/PERFORMANCE.md §11)."""
    (axis_name, causal, rate, batch_axis, heads_axis,
     interpret, bq, bk, bk_bwd, zig) = opts
    q, k, v, out, lse, seed = res
    B, Sl, H, D = q.shape
    n, my, b_off, h_off, n_heads = _ring_offsets(
        axis_name, batch_axis, heads_axis, B, H
    )
    bh_vec = _global_bh_vec(B, H, b_off, h_off, n_heads)
    perm = [(j, (j + 1) % n) for j in range(n)]
    f32 = jnp.float32
    import numpy as np

    from ..utils.vma import pcast_like

    def to3(t):
        return t.transpose(0, 2, 1, 3).reshape(B * H, Sl, D)

    q3, k3, v3, out3, do3 = to3(q), to3(k), to3(v), to3(out), to3(do)
    # delta is a per-row reduction, invariant to row reordering — compute
    # it in the contiguous layout and exchange the (BH, Sl) result, D times
    # cheaper than exchanging the full out3 activation.
    delta = jnp.sum(do3.astype(f32) * out3.astype(f32), axis=-1)  # (BH, Sl)
    if zig:
        # The forward computed (and saved lse) in the zigzag row order;
        # re-enter it for the backward and leave it again at the end.
        q3 = _zig_exchange(q3, axis_name, n, my)
        k3 = _zig_exchange(k3, axis_name, n, my)
        v3 = _zig_exchange(v3, axis_name, n, my)
        do3 = _zig_exchange(do3, axis_name, n, my)
        delta = _zig_exchange(delta, axis_name, n, my)
        h = Sl // 2
        q_bases = _zig_chunk_bases(my, n, h)
    else:
        h = Sl
        q_bases = (my * Sl,)
    rows = _bases_to_rows(q_bases, h)
    tile = min(bk_bwd, h)
    # Same S-dependent backward crossover as flash_attention (measured,
    # docs/PERFORMANCE.md §12): the einsum tiles win at short blocks, the
    # Pallas kernels from _PALLAS_BWD_MIN_SEQ-sized local shards up — the
    # regime multi-chip sequence parallelism actually runs in.
    use_kernels = (not interpret) and Sl >= _PALLAS_BWD_MIN_SEQ
    if use_kernels:
        # lse/delta enter the kernels sublane-broadcast, as in plain flash.
        lse3 = jnp.broadcast_to(lse[:, None, :], (B * H, 8, Sl))
        delta3 = jnp.broadcast_to(delta[:, None, :], (B * H, 8, Sl))

    dq3 = pcast_like(jnp.zeros((B * H, Sl, D), f32), q3, k3, v3, do3)
    k_cur, v_cur = k3, v3
    dk_cur = pcast_like(jnp.zeros((B * H, Sl, D), f32), q3, k3, v3, do3)
    dv_cur = pcast_like(jnp.zeros((B * H, Sl, D), f32), q3, k3, v3, do3)
    for t in range(n):
        # Same visit order as the forward: at step t the resident K/V block
        # originated on (my - t) % n, and so did the dk/dv accumulators
        # riding along with it.
        src = (my - t) % n
        k_bases = _zig_chunk_bases(src, n, h) if zig else (src * Sl,)
        if use_kernels:
            dq_p, dk_b, dv_b = _pair_backward(
                q3, k_cur, v_cur, do3, lse3, delta3, seed, bh_vec,
                causal, rate, bq, tile, False,
                q_tile_offsets=_bases_to_tiles(q_bases, h, bq),
                k_tile_offsets=_bases_to_tiles(k_bases, h, tile),
                out_dtype=f32,
            )
        else:
            dq_p, dk_b, dv_b = _block_bwd_jnp(
                q3, k_cur, v_cur, do3, lse, delta, seed, rows,
                _bases_to_rows(k_bases, h), bh_vec, causal, rate, tile,
            )
        dq3 = dq3 + dq_p
        dk_cur = dk_cur + dk_b
        dv_cur = dv_cur + dv_b
        # dk/dv must complete the full cycle (n hops) to land home with
        # every device's contribution; k/v are not needed after their last
        # block pass, saving one exchange.
        if t < n - 1:
            k_cur = lax.ppermute(k_cur, axis_name, perm)
            v_cur = lax.ppermute(v_cur, axis_name, perm)
        dk_cur = lax.ppermute(dk_cur, axis_name, perm)
        dv_cur = lax.ppermute(dv_cur, axis_name, perm)

    if zig:
        dq3 = _zig_exchange(dq3, axis_name, n, my, inverse=True)
        dk_cur = _zig_exchange(dk_cur, axis_name, n, my, inverse=True)
        dv_cur = _zig_exchange(dv_cur, axis_name, n, my, inverse=True)

    def back4(t3, dtype):  # (B*H, Sl, D) -> (B, Sl, H, D)
        return t3.reshape(B, H, Sl, D).transpose(0, 2, 1, 3).astype(dtype)

    seed_ct = np.zeros((1,), jax.dtypes.float0)  # integral: no tangent
    return (
        back4(dq3, q.dtype), back4(dk_cur, k.dtype), back4(dv_cur, v.dtype),
        seed_ct,
    )


def _ring_fwd_rule(opts, q, k, v, seed):
    return _ring_fwd(opts, q, k, v, seed)


_ring.defvjp(_ring_fwd_rule, _ring_bwd)


def ring_attention_sharded(
    q: jax.Array,  # (B, S_local, H, D) — this device's sequence shard
    k: jax.Array,
    v: jax.Array,
    axis_name: str = "seq",
    causal: bool = False,
    dropout_rate: float = 0.0,
    dropout_seed: Optional[jax.Array] = None,
    batch_axis: Optional[str] = None,
    heads_axis: Optional[str] = None,
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
    block_k_bwd: Optional[int] = None,
    zigzag: Optional[bool] = None,
) -> jax.Array:
    """Ring attention body; call inside shard_map with seq sharded on axis_name.

    ``batch_axis``/``heads_axis`` name the mesh axes (if any) the batch and
    head dims are sharded over, so dropout-mask coordinates are GLOBAL
    (batch, head) indices — without them, same-local-index examples on
    different data shards would share masks.

    ``zigzag`` (default: auto — on when ``causal``, the ring has >1
    device, the local shard is even, and any explicit block sizes divide
    the half-chunk) redistributes half-chunks so device c owns global chunks
    (c, 2n-1-c): causal triangle work becomes ~equal per device per hop
    instead of the contiguous layout's last-device-does-everything skew
    (~2x wall-clock at large rings). Purely internal — inputs/outputs stay
    in the contiguous layout, and global coordinates keep dropout masks
    bit-identical to flash. Pass ``zigzag=False`` to force contiguous.

    On a TPU backend each ring hop runs the Pallas flash block kernel
    (VMEM-resident score tiles) and nothing else; on other backends (CPU test
    meshes, where the Pallas interpreter cannot run inside vma-carrying
    manual regions) an einsum path with identical semantics — one rule for
    flash and ring, ``flash_attention._resolve_interpret``. Gradients flow
    through a custom VJP that makes a second ring pass (see module
    docstring).
    """
    B, Sl, H, D = q.shape
    if dropout_seed is None:
        _warn_seedless_dropout(dropout_rate, "ring_attention_sharded")
        dropout_rate = 0.0
        seed = jnp.zeros((1,), jnp.uint32)
    else:
        seed = jnp.asarray(dropout_seed, jnp.uint32).reshape((1,))
    interpret = _resolve_interpret(None)
    n = lax.axis_size(axis_name)
    if zigzag is None:
        zig = causal and n > 1 and Sl % 2 == 0
        # Auto mode must never turn a previously-valid config into an
        # error: explicit block sizes that divide the shard but not the
        # half-chunk fall back to the contiguous layout.
        if zig and any(
            b is not None and (Sl // 2) % b != 0
            for b in (block_q, block_k, block_k_bwd)
        ):
            zig = False
    else:
        zig = bool(zigzag) and n > 1
        if zig and Sl % 2 != 0:
            raise ValueError(
                f"zigzag=True needs an even local shard, got S/sp={Sl} "
                f"over '{axis_name}' (the layout splits each shard into "
                "two half-chunks)"
            )
    # Blocks tile one CHUNK: the whole shard normally, a half-chunk under
    # zigzag (tiles must not straddle the half boundary — their rows would
    # not be globally contiguous).
    chunk = Sl // 2 if zig else Sl
    bq = block_q or _pick_block(chunk, _FWD_BLOCK_Q)
    bk = block_k or _pick_block(chunk, _FWD_BLOCK_K)
    bk_bwd = block_k_bwd or _pick_block(chunk, _BWD_BLOCK_K)
    if chunk % bq != 0 or chunk % bk != 0 or chunk % bk_bwd != 0:
        # Same contract as flash_attention, against the LOCAL chunk: a
        # non-dividing (or oversized) block would silently truncate the
        # kernel grid and compute wrong attention.
        raise ValueError(
            f"block sizes (block_q={bq}, block_k={bk}, block_k_bwd="
            f"{bk_bwd}) must divide the local chunk {chunk} "
            f"(S/sp={Sl} over '{axis_name}'"
            + (", halved by the zigzag causal layout)" if zig else ")")
        )
    opts = (
        axis_name, causal, dropout_rate, batch_axis, heads_axis,
        interpret, bq, bk, bk_bwd, zig,
    )
    return _ring(opts, q, k, v, seed)


def ring_attention(
    q: jax.Array,  # (B, S, H, D) — full (mesh-visible) arrays
    k: jax.Array,
    v: jax.Array,
    causal: bool = False,
    axis_name: str = "seq",
    mesh: Optional[jax.sharding.Mesh] = None,
    dropout_rate: float = 0.0,
    dropout_seed: Optional[jax.Array] = None,
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
    block_k_bwd: Optional[int] = None,
    zigzag: Optional[bool] = None,
) -> jax.Array:
    """Shard the sequence over ``axis_name`` and run the ring. Falls back to
    flash attention when no such mesh axis is in scope (so models configured
    with attention_impl='ring' still run on a plain data mesh).

    Attention-probability dropout (``dropout_rate`` + uint32 ``dropout_seed``)
    uses the flash kernel's global-coordinate hash: for equal seeds the mask
    is identical to flash's, independent of the ring's sequence sharding.
    """
    if mesh is None:
        m = jax.sharding.get_abstract_mesh()
        mesh = m if m is not None and axis_name in getattr(m, "axis_names", ()) else None
    if mesh is None or mesh.shape.get(axis_name, 1) == 1:
        from .flash_attention import flash_attention

        return flash_attention(
            q, k, v, causal=causal,
            dropout_rate=dropout_rate, dropout_seed=dropout_seed,
            block_q=block_q, block_k=block_k, block_k_bwd=block_k_bwd,
        )

    # Compose with whatever other parallelism the mesh carries: batch stays
    # sharded on 'data', heads stay sharded on 'model' (tensor parallel) —
    # the ring only ever communicates along the 'seq' axis.
    batch_ax = "data" if mesh.shape.get("data", 1) > 1 else None
    model_ax = "model" if mesh.shape.get("model", 1) > 1 else None
    spec = P(batch_ax, axis_name, model_ax, None)
    if dropout_seed is None:
        _warn_seedless_dropout(dropout_rate, "ring_attention")
        seed = jnp.zeros((), jnp.uint32)
        dropout_rate = 0.0
    else:
        seed = jnp.asarray(dropout_seed, jnp.uint32).reshape(())

    def body(qs, ks, vs, seed_s):
        return ring_attention_sharded(
            qs, ks, vs, axis_name=axis_name, causal=causal,
            dropout_rate=dropout_rate, dropout_seed=seed_s,
            batch_axis=batch_ax, heads_axis=model_ax,
            block_q=block_q, block_k=block_k, block_k_bwd=block_k_bwd,
            zigzag=zigzag,
        )

    fn = jax.shard_map(
        body, mesh=mesh, in_specs=(spec, spec, spec, P()), out_specs=spec
    )
    return fn(q, k, v, seed)
