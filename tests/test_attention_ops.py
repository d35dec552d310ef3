"""Flash + ring attention correctness vs the materialized reference.

Flash runs in Pallas interpret mode on CPU (bit-honest math, slow); ring runs
under shard_map over a 4-way 'seq' axis on the virtual device mesh.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from distributed_llm_training_benchmark_framework_tpu.ops.flash_attention import (
    flash_attention,
    reference_attention,
)
from distributed_llm_training_benchmark_framework_tpu.ops.ring_attention import (
    ring_attention,
)
from distributed_llm_training_benchmark_framework_tpu.parallel import make_mesh


def qkv(B=2, S=128, H=4, D=32, dtype=jnp.float32, seed=0):
    ks = jax.random.split(jax.random.key(seed), 3)
    mk = lambda k: jax.random.normal(k, (B, S, H, D), dtype)
    return mk(ks[0]), mk(ks[1]), mk(ks[2])


@pytest.mark.parametrize("causal", [False, True])
def test_flash_matches_reference(causal):
    q, k, v = qkv()
    out = flash_attention(q, k, v, causal=causal, interpret=True, block_q=32, block_k=32)
    ref = reference_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-3, atol=2e-3)


def test_flash_bf16_inputs():
    q, k, v = qkv(dtype=jnp.bfloat16)
    out = flash_attention(q, k, v, interpret=True, block_q=32, block_k=32)
    ref = reference_attention(q, k, v)
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), rtol=3e-2, atol=3e-2
    )


def test_flash_odd_block_split():
    """Sequence not divisible by the preferred block still works."""
    q, k, v = qkv(S=96)
    out = flash_attention(q, k, v, interpret=True)
    ref = reference_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-3, atol=2e-3)


def test_flash_is_differentiable():
    q, k, v = qkv(B=1, S=32, H=2, D=16)

    def loss_flash(q):
        return flash_attention(q, k, v, interpret=True, block_q=16, block_k=16).sum()

    def loss_ref(q):
        return reference_attention(q, k, v).sum()

    g1 = jax.grad(loss_flash)(q)
    g2 = jax.grad(loss_ref)(q)
    np.testing.assert_allclose(np.asarray(g1), np.asarray(g2), rtol=5e-3, atol=5e-3)


def _hash_keep_mask(seed, B, H, S, rate):
    """Materialize the kernel's keep mask from the same absolute-coordinate
    hash, as a (B, H, S, S) boolean array."""
    from distributed_llm_training_benchmark_framework_tpu.ops import flash_attention as fa

    bh = jnp.arange(B * H)[:, None, None]
    rows = jnp.arange(S)[None, :, None]
    cols = jnp.arange(S)[None, None, :]
    keep = fa._dropout_keep(
        jnp.uint32(seed), bh, rows, cols, fa._dropout_threshold(rate)
    )
    return keep.reshape(B, H, S, S)


def _masked_reference(q, k, v, keep, rate, causal=False):
    """Materialized attention with an explicit post-softmax dropout mask."""
    scale = 1.0 / (q.shape[-1] ** 0.5)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32) * scale
    if causal:
        S = q.shape[1]
        s = jnp.where(jnp.tril(jnp.ones((S, S), bool)), s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    p = jnp.where(keep, p / (1.0 - rate), 0.0)
    return jnp.einsum(
        "bhqk,bkhd->bqhd", p.astype(q.dtype), v, preferred_element_type=jnp.float32
    ).astype(q.dtype)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_dropout_matches_masked_reference(causal):
    """Forward with in-kernel dropout == materialized attention with the same
    hash-derived mask applied post-softmax."""
    rate = 0.25
    B, S, H, D = 2, 128, 4, 32
    q, k, v = qkv(B=B, S=S, H=H, D=D)
    seed = jnp.asarray(1234, jnp.uint32)
    out = flash_attention(
        q, k, v, causal=causal, interpret=True, block_q=32, block_k=32,
        dropout_rate=rate, dropout_seed=seed,
    )
    keep = _hash_keep_mask(1234, B, H, S, rate)
    ref = _masked_reference(q, k, v, keep, rate, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-3, atol=2e-3)


def test_flash_dropout_block_size_invariant():
    """The keep mask is a function of absolute coordinates, so different
    tilings (the fwd/bwd situation) and different compute sub-tiles inside a
    tile produce the same output."""
    from distributed_llm_training_benchmark_framework_tpu.ops import (
        flash_attention as fa,
    )

    rate = 0.1
    q, k, v = qkv(B=1, S=128, H=2, D=32)
    seed = jnp.asarray(7, jnp.uint32)
    kw = dict(interpret=True, dropout_rate=rate, dropout_seed=seed)
    out32 = flash_attention(q, k, v, block_q=32, block_k=32, **kw)
    out64 = flash_attention(q, k, v, block_q=64, block_k=64, **kw)
    # Not bitwise: online-softmax accumulation order differs per tiling. But a
    # single flipped mask element would shift entries by O(p*v/keep) >> 1e-5.
    np.testing.assert_allclose(
        np.asarray(out32), np.asarray(out64), rtol=1e-5, atol=1e-5
    )
    # The same tiles, their keys walked in compute pieces of 16 and 32 (forced
    # below the public call, which has no argument for it).
    bhsd = lambda t: t[0].transpose(1, 0, 2)
    for bq, bk, sub_k in [(64, 64, 16), (32, 128, 32), (128, 64, 16)]:
        out, _ = fa._flash_forward(
            bhsd(q), bhsd(k), bhsd(v), False, True, bq, bk, rate,
            seed.reshape(1), jnp.arange(2, dtype=jnp.int32), sub_k=sub_k,
        )
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(bhsd(out64)), rtol=1e-5, atol=1e-5
        )
    # And both agree with the materialized-mask reference.
    keep = _hash_keep_mask(7, 1, 2, 128, rate)
    ref = _masked_reference(q, k, v, keep, rate)
    np.testing.assert_allclose(np.asarray(out64), np.asarray(ref), rtol=2e-3, atol=2e-3)


def _forward_operands(S, H, D):
    """(BH, S, D) float32 operands of ``_flash_forward`` with its seed and
    (batch, head) ids."""
    q, k, v = (t[0].transpose(1, 0, 2) for t in qkv(B=1, S=S, H=H, D=D))
    return q, k, v, jnp.asarray([21], jnp.uint32), jnp.arange(H, dtype=jnp.int32)


@pytest.mark.parametrize(
    "bq,bk,sub_k", [(64, 64, 16), (32, 128, 32), (128, 32, 32)],
    ids=["64x64/16", "32x128/32", "128x32/32"],
)
@pytest.mark.parametrize("rate", [0.0, 0.1], ids=["nodrop", "dropout"])
@pytest.mark.parametrize("causal", [False, True], ids=["full", "causal"])
def test_flash_forward_pieces_match_whole_tile(causal, rate, bq, bk, sub_k):
    """A (bq, bk) tile whose keys are walked in compute pieces of ``sub_k``
    (one online-softmax update a piece) gives the whole-tile walk's ``out``
    and ``lse`` to f32 rounding, and the materialised reference's with the
    same seed: the keep mask did not move. (128, 32, 32) is the tile no wider
    than the piece: one piece, the same code."""
    from distributed_llm_training_benchmark_framework_tpu.ops import (
        flash_attention as fa,
    )

    q, k, v, seed, bhv = _forward_operands(S=128, H=2, D=32)
    args = (q, k, v, causal, True, bq, bk, rate, seed, bhv)
    out, lse = fa._flash_forward(*args, sub_k=sub_k)
    whole_out, whole_lse = fa._flash_forward(*args, sub_k=bk)
    ref_out, ref_lse = fa._jnp_reference_forward(q, k, v, causal, rate, seed, bhv)
    for got, whole, ref in [(out, whole_out, ref_out), (lse, whole_lse, ref_lse)]:
        np.testing.assert_allclose(np.asarray(got), np.asarray(whole), rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize(
    "bq,bk,sub_k", [(64, 128, 32), (128, 64, 16)], ids=["64x128/32", "128x64/16"]
)
def test_flash_forward_diagonal_cuts_through_pieces(bq, bk, sub_k):
    """Causal, bq != bk, pieces narrower than both: in the live tiles of a q
    tile some pieces lie wholly below the diagonal, some are cut by it at a
    different offset each, and some lie wholly above it (every score masked:
    the running max and sum must pass through unchanged, with no second mask
    on the probabilities)."""
    from distributed_llm_training_benchmark_framework_tpu.ops import (
        flash_attention as fa,
    )

    q, k, v, seed, bhv = _forward_operands(S=256, H=2, D=32)
    out, lse = fa._flash_forward(
        q, k, v, True, True, bq, bk, 0.1, seed, bhv, sub_k=sub_k
    )
    ref_out, ref_lse = fa._jnp_reference_forward(q, k, v, True, 0.1, seed, bhv)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref_out), rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(ref_lse), rtol=1e-5, atol=1e-5)
    assert np.isfinite(np.asarray(lse)).all()


@pytest.mark.parametrize(
    "cell,seq",
    [
        ("tinygpt-a.seq2048", 2048), ("tinygpt-a.seq8192", 8192),
        ("mistral-7b.d2", 4096), ("mistral-7b.fsdp4", 4096),
        ("olmoe-1b-7b.d1", 4096),
    ],
)
def test_forward_piece_chooser(cell, seq):
    """For each benchmark cell's sequence the chooser returns a divisor of
    the DMA tile's keys that the MXU takes whole (a multiple of 128) and that
    is a real cut (the tile is wider); a tile no wider than the piece, or one
    the piece does not divide, is walked whole. Head dim, dropout and mask do
    not enter: the sweep found one width for all three shapes."""
    from distributed_llm_training_benchmark_framework_tpu.ops import (
        flash_attention as fa,
    )

    bk = fa._pick_block(seq, fa._FWD_BLOCK_K)
    sub_k = fa._fwd_sub_k(bk)
    assert bk % sub_k == 0 and sub_k % 128 == 0 and sub_k < bk
    for small in (8, 32, 64, sub_k):
        assert fa._fwd_sub_k(small) == small
    assert fa._fwd_sub_k(sub_k + 8) == sub_k + 8


@pytest.mark.parametrize("pallas_backward", [False, True])
def test_flash_dropout_grad_matches_masked_reference(pallas_backward):
    """Backward (both the jnp blockwise path and the fused Pallas kernel)
    regenerates the identical mask, at a different block size than the
    forward ran with."""
    rate = 0.2
    B, S, H, D = 1, 64, 2, 16
    q, k, v = qkv(B=B, S=S, H=H, D=D)
    seed = jnp.asarray(99, jnp.uint32)
    keep = _hash_keep_mask(99, B, H, S, rate)

    def loss_flash(q, k, v):
        return flash_attention(
            q, k, v, interpret=True, block_q=32, block_k=32, block_k_bwd=16,
            dropout_rate=rate, dropout_seed=seed,
            pallas_backward=pallas_backward,
        ).sum()

    def loss_ref(q, k, v):
        return _masked_reference(q, k, v, keep, rate).sum()

    g1 = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=5e-3, atol=5e-3)


def _backward_operands(S, H, D, causal, rate, dtype=jnp.float32):
    """(BH, S, D) operands of the Pallas backward: q, k, v, do and the lse /
    delta residuals of the real forward kernel (run at its own tiling)."""
    from distributed_llm_training_benchmark_framework_tpu.ops import (
        flash_attention as fa,
    )

    q, k, v = (t[0].transpose(1, 0, 2) for t in qkv(B=1, S=S, H=H, D=D, dtype=dtype))
    do = jax.random.normal(jax.random.key(5), q.shape, dtype)
    seed = jnp.asarray([99], jnp.uint32)
    bhv = jnp.arange(H, dtype=jnp.int32)
    out, lse = fa._flash_forward(q, k, v, causal, True, S // 2, S, rate, seed, bhv)
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)
    stat3 = lambda x: jnp.broadcast_to(x[:, None, :], (H, 8, S))
    return q, k, v, do, stat3(lse), stat3(delta), seed, bhv


@pytest.mark.parametrize("rate", [0.0, 0.2], ids=["nodrop", "dropout"])
@pytest.mark.parametrize("causal", [False, True], ids=["full", "causal"])
def test_flash_fused_backward_matches_kernel_pair(causal, rate):
    """The one-kernel backward against the dq / dk+dv pair (ring's kernels)
    on the same residuals: the same tile products summed in the same order.
    In f32 what moved since PR 33 is rounding alone: scale x log2(e) folded
    into exp2, 1 / keep_prob into the subtracted row, ds's scale taken to
    where dk and dq are written out. block_q != block_k_bwd, neither the
    forward's."""
    from distributed_llm_training_benchmark_framework_tpu.ops import (
        flash_attention as fa,
    )

    args = _backward_operands(64, 2, 16, causal, rate)
    fused = fa._fused_backward(*args, causal, rate, 16, 32, True)
    pair = fa._pair_backward(*args, causal, rate, 16, 32, True)
    for got, want in zip(fused, pair):
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=1e-5, atol=5e-6
        )


@pytest.mark.parametrize(
    "bq,bk,sub", [(64, 64, 16), (32, 128, 8), (128, 32, 32)],
    ids=["64x64/16", "32x128/8", "128x32/32"],
)
@pytest.mark.parametrize("rate", [0.0, 0.1], ids=["nodrop", "dropout"])
@pytest.mark.parametrize("causal", [False, True], ids=["full", "causal"])
def test_flash_fused_backward_pieces_match_whole_tile(causal, rate, bq, bk, sub):
    """A (bk, bq) tile whose queries are walked in compute pieces of ``sub``
    (dq written a slice a piece, dk / dv summed over the pieces) gives the
    whole-tile walk's three gradients to f32 rounding (the pieces' partial
    sums are added in another order), and the kernel pair's, whose mask comes
    from ``_dropout_keep`` whole: the keep mask did not move. bq != bk both
    ways; under ``causal`` the diagonal cuts through pieces at a different
    offset each, and at (32, 128) whole pieces lie above it."""
    from distributed_llm_training_benchmark_framework_tpu.ops import (
        flash_attention as fa,
    )

    args = _backward_operands(128, 2, 32, causal, rate)
    pieced = fa._fused_backward(*args, causal, rate, bq, bk, True, sub=sub)
    whole = fa._fused_backward(*args, causal, rate, bq, bk, True, sub=bq)
    pair = fa._pair_backward(*args, causal, rate, bq, bk, True)
    for got, same, want in zip(pieced, whole, pair):
        np.testing.assert_allclose(np.asarray(got), np.asarray(same), rtol=1e-5, atol=5e-6)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=5e-6)


@pytest.mark.parametrize("widths", [(32, 32), (48, 32)], ids=["D=Dv", "Dqk>Dv"])
@pytest.mark.parametrize("rate", [0.0, 0.1], ids=["nodrop", "dropout"])
@pytest.mark.parametrize("kernel", ["forward", "fused-backward"])
def test_flash_causal_diagonal_tiles_walk_their_live_pieces(kernel, rate, widths, monkeypatch, request):
    """A causal diagonal tile of four pieces a side is *lower*
    (``fa._tile_shape``): its body meets ten of the sixteen and leaves the six
    above the piece diagonal out, operands trimmed to them (the forward's
    lookahead over spans that shrink, the backward's partial rows of dk and
    dv). What it leaves out were exact zeros: the results are the whole-tile
    walk's (every live tile *full*, at the same tiles and pieces) bit for
    bit, keep mask and all. (Tiles of 256: XLA's CPU dot sums in one order
    whatever the operands' extent only from about that size; at tiles of 64
    the two walks differ by an ulp. On the chip the microbenches compare
    them, ``max_abs_diff``.)"""
    from distributed_llm_training_benchmark_framework_tpu.ops import (
        flash_attention as fa,
    )

    S, H, (d_qk, d_v), tile, piece = 512, 2, widths, 256, 64
    keys = jax.random.split(jax.random.key(3), 4)
    q, k = (jax.random.normal(key, (H, S, d_qk), jnp.float32) for key in keys[:2])
    v, do = (jax.random.normal(key, (H, S, d_v), jnp.float32) for key in keys[2:])
    seed, bhv = jnp.asarray([21], jnp.uint32), jnp.arange(H, dtype=jnp.int32)
    out, lse = fa._flash_forward(q, k, v, True, True, tile, tile, rate, seed, bhv, sub_k=piece)
    stat3 = lambda x: jnp.broadcast_to(x[:, None, :], (H, 8, S))

    def run():
        if kernel == "forward":
            return fa._flash_forward(q, k, v, True, True, tile, tile, rate, seed, bhv, sub_k=piece)
        return fa._fused_backward(
            q, k, v, do, stat3(lse), stat3(jnp.sum(do * out, -1)), seed, bhv, True, rate,
            tile, tile, True, sub=piece)

    assert fa._tile_shape(True, 1, tile, 1, tile, piece) and not fa._tile_shape(True, 1, tile, 0, tile, piece)
    by_pieces = run()
    monkeypatch.setattr(fa, "_tile_shape", lambda *a: False)
    fa.forget_kernel_calls()  # a kernel a shape is kept for the process
    request.addfinalizer(fa.forget_kernel_calls)
    for got, whole in zip(by_pieces, run()):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(whole))


@pytest.mark.parametrize("sub", [16, 64], ids=["pieces", "whole"])
def test_flash_fused_backward_pieces_match_masked_reference(sub, monkeypatch):
    """The pieced walk through the public call's custom VJP (the chooser
    patched to the test's piece: no argument reaches it) against ``jax.grad``
    of the materialised f32 reference under the same hash-derived mask,
    causal with dropout, block_q != block_k_bwd."""
    from distributed_llm_training_benchmark_framework_tpu.ops import (
        flash_attention as fa,
    )

    rate, (B, S, H, D) = 0.2, (1, 128, 2, 16)
    q, k, v = qkv(B=B, S=S, H=H, D=D)
    seed = jnp.asarray(99, jnp.uint32)
    keep = _hash_keep_mask(99, B, H, S, rate)
    w = jax.random.normal(jax.random.key(3), q.shape)
    real = fa._fused_backward

    def loss_flash(q, k, v):
        return (w * flash_attention(
            q, k, v, causal=True, interpret=True, block_q=64, block_k=32,
            block_k_bwd=32, dropout_rate=rate, dropout_seed=seed,
            pallas_backward=True,
        )).sum()

    def loss_ref(q, k, v):
        return (w * _masked_reference(q, k, v, keep, rate, causal=True)).sum()

    monkeypatch.setattr(fa, "_fused_backward", lambda *a: real(*a, sub=sub))
    g1 = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=5e-3, atol=5e-3)


@pytest.mark.parametrize(
    "causal,bq,bk,sub", [(False, 64, 32, 16), (True, 32, 32, 8)],
    ids=["full", "causal-live-pieces"],
)
def test_flash_fused_backward_keep_mask_is_the_forwards(causal, bq, bk, sub):
    """The pieces' mask (the hash's row half made once a piece, ``_mix32`` of
    ``rowbase + cols`` over it) is the forward kernel's bit for bit, at
    another tiling than the forward's: with v = 1 and do = 1 every dp is 1
    and delta is the kept share of the row, so ds, and with k = e_0 and
    q = 0 the first column of dq, is p' x (keep - kept share): one flipped
    bit moves it by a whole probability, far over rounding. Under ``causal``
    at square tiles the diagonal tiles are walked by their live pieces, keys
    trimmed to them: the hash takes global coordinates, so the same bits."""
    from distributed_llm_training_benchmark_framework_tpu.ops import (
        flash_attention as fa,
    )

    S, H, D, rate = 128, 2, 8, 0.3
    q = jnp.zeros((H, S, D), jnp.float32)
    k = jnp.zeros((H, S, D), jnp.float32).at[:, :, 0].set(1.0)
    v = do = jnp.ones((H, S, D), jnp.float32)
    seed = jnp.asarray([5], jnp.uint32)
    bhv = jnp.arange(H, dtype=jnp.int32) + 3
    out, lse = fa._flash_forward(q, k, v, causal, True, 32, 128, rate, seed, bhv)
    delta = jnp.sum(do * out, axis=-1)
    stat3 = lambda x: jnp.broadcast_to(x[:, None, :], (H, 8, S))
    dq, _, _ = fa._fused_backward(
        q, k, v, do, stat3(lse), stat3(delta), seed, bhv, causal, rate,
        bq, bk, True, sub=sub,
    )
    rows = jnp.arange(S)[None, :, None]
    cols = jnp.arange(S)[None, None, :]
    keep = fa._dropout_keep(
        seed[0], bhv[:, None, None], rows, cols, fa._dropout_threshold(rate)
    )
    # q = 0: p is 1 / (the keys a query sees), S of them or its own position + 1
    seen = (rows >= cols) if causal else jnp.ones((1, S, S), bool)
    p = seen / jnp.sum(seen, axis=-1, keepdims=True)
    kept = jnp.sum(keep * p, axis=-1) / (1.0 - rate)  # = out = delta / D
    np.testing.assert_allclose(np.asarray(out[:, :, 0]), np.asarray(kept), rtol=1e-5)
    want = jnp.sum(
        (keep / (1.0 - rate) * D - delta[:, :, None]) * p, axis=-1
    ) / (D ** 0.5)
    # One flipped bit is worth D / keep_prob / S / sqrt(D) = 0.03 here.
    np.testing.assert_allclose(
        np.asarray(dq[:, :, 0]), np.asarray(want), rtol=1e-4, atol=1e-5
    )


def test_flash_fused_backward_masked_scores_may_overflow():
    """What the second causal select on p guarded, filled with +inf (a NaN
    there would need an inf operand, which poisons the live scores too): keys
    64.. carry 1e30 in one dim against 1e30 on queries ..63, so every score
    of that block (all of it above the diagonal) overflows; queries 64.. are
    0 there, so the live scores are plain. One select on s before the
    exponent is all the kernel has: p is exactly 0 there (exp2 of +inf would
    be inf, and inf x 0 a NaN in all three products), the gradients are
    finite and the pair's (which still selects twice)."""
    from distributed_llm_training_benchmark_framework_tpu.ops import (
        flash_attention as fa,
    )

    S, H, D, J = 128, 2, 16, 64
    q, k, v, do, _, _, seed, bhv = _backward_operands(S, H, D, True, 0.0)
    q = q.at[:, :J, 0].set(1e30).at[:, J:, 0].set(0.0)
    k = k.at[:, J:, 0].set(1e30).at[:, :J, 0].set(0.0)
    scores = jnp.einsum("hqd,hkd->hqk", q, k)
    assert bool(jnp.isposinf(scores[:, :J, J:]).all())
    assert bool(jnp.isfinite(jnp.tril(scores)).all())
    out, lse = fa._flash_forward(q, k, v, True, True, 64, 32, 0.0, seed, bhv)
    delta = jnp.sum(do * out, axis=-1)
    stat3 = lambda x: jnp.broadcast_to(x[:, None, :], (H, 8, S))
    args = (q, k, v, do, stat3(lse), stat3(delta), seed, bhv, True, 0.0, 32, 64, True)
    pair = fa._pair_backward(*args)
    for sub in (8, 32):
        for got, want in zip(fa._fused_backward(*args, sub=sub), pair):
            assert bool(jnp.isfinite(got).all())
            # The 1e30 column of q and k is in dq and dk too: sums of terms
            # that cancel, so rounding shows at 1e-4 of the result.
            np.testing.assert_allclose(
                np.asarray(got), np.asarray(want), rtol=1e-3, atol=1e-5
            )


@pytest.mark.parametrize(
    "cell,seq,rate,pieces",
    [
        ("tinygpt-a.seq8192", 8192, 0.1, 8),
        ("mistral-7b.d2", 4096, 0.0, 4),
        ("mistral-7b.fsdp4", 4096, 0.0, 4),
        ("olmoe-1b-7b.d1", 4096, 0.0, 4),
        ("deepseek-v2-lite.share8-seq8192", 8192, 0.0, 4),
    ],
)
def test_backward_piece_chooser(cell, seq, rate, pieces):
    """Which walk the fused backward takes in each benchmark cell that runs
    it, fixed by the tile and the dropout rate the call is given: with
    dropout (the hash makes the chain long) the (1024, 1024) tile in eight
    pieces of 128 queries, without it in four of 256: a multiple of the lane
    width and a real cut either way. Head width does not enter: at 64, 128
    and 192 over 128 the same piece measured fastest (PERF.md, PR 33). A tile
    no wider than the piece, or one it does not divide (the CPU tests' small
    tiles), is walked whole."""
    from distributed_llm_training_benchmark_framework_tpu.ops import (
        flash_attention as fa,
    )

    bq = fa._pick_block(seq, fa._FWD_BLOCK_Q)
    sub = fa._bwd_sub_q(bq, rate)
    assert bq % sub == 0 and sub % 128 == 0 and bq // sub == pieces
    for r in (0.0, 0.1):
        piece = fa._bwd_sub_q(bq, r)
        for small in (8, 32, 64, piece, piece + 8):
            assert fa._bwd_sub_q(small, r) == small


@pytest.mark.parametrize("rate", [0.0, 0.2], ids=["nodrop", "dropout"])
@pytest.mark.parametrize("causal", [False, True], ids=["full", "causal"])
def test_flash_fused_backward_matches_masked_reference(causal, rate):
    """jax.grad through flash_attention with the Pallas backward (the fused
    kernel) against the materialized f32 reference under the same mask."""
    B, S, H, D = 1, 64, 2, 16
    q, k, v = qkv(B=B, S=S, H=H, D=D)
    seed = jnp.asarray(99, jnp.uint32)
    keep = (
        _hash_keep_mask(99, B, H, S, rate) if rate
        else jnp.ones((B, H, S, S), bool)
    )
    w = jax.random.normal(jax.random.key(3), q.shape)

    def loss_flash(q, k, v):
        return (w * flash_attention(
            q, k, v, causal=causal, interpret=True, block_q=16, block_k=64,
            block_k_bwd=32, dropout_rate=rate,
            dropout_seed=seed if rate else None, pallas_backward=True,
        )).sum()

    def loss_ref(q, k, v):
        return (w * _masked_reference(q, k, v, keep, rate, causal=causal)).sum()

    g1 = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=5e-3, atol=5e-3)


def test_flash_fused_backward_revisits_the_dq_row():
    """8 k tiles x 4 q tiles, bf16 operands: every slice of the resident dq
    row is zeroed once, added to in each of the 8 passes and written out in
    the last; dk / dv restart at every k tile."""
    from distributed_llm_training_benchmark_framework_tpu.ops import (
        flash_attention as fa,
    )

    args = _backward_operands(128, 2, 32, True, 0.1, dtype=jnp.bfloat16)
    fused = fa._fused_backward(*args, True, 0.1, 32, 16, True)
    pair = fa._pair_backward(*args, True, 0.1, 32, 16, True)
    for got, want in zip(fused, pair):
        assert got.dtype == jnp.bfloat16
        # Not the pair's bits since PR 33: ds goes to bf16 before its scale
        # (one bf16 ulp, 2**-8, a product term, on ds and on p / keep_prob)
        # where the pair rounds after it; a slice zeroed twice or a pass left
        # out would move an entry by a whole term.
        got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
        np.testing.assert_allclose(got, want, rtol=2**-6, atol=2**-7)
        assert np.mean(np.abs(got - want)) < 2**-8 * np.mean(np.abs(want))


def test_pair_backward_identity_offsets_equal_the_default():
    """Plain flash's tile bases (tile i starts at row i*b) are what
    ``_pair_backward`` builds when it is handed none."""
    from distributed_llm_training_benchmark_framework_tpu.ops import (
        flash_attention as fa,
    )

    args = _backward_operands(64, 2, 16, True, 0.2)
    default = fa._pair_backward(*args, True, 0.2, 16, 32, True)
    explicit = fa._pair_backward(
        *args, True, 0.2, 16, 32, True,
        q_tile_offsets=jnp.arange(4, dtype=jnp.int32) * 16,
        k_tile_offsets=jnp.arange(2, dtype=jnp.int32) * 32,
    )
    for got, want in zip(explicit, default):
        assert got.dtype == want.dtype
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("layout", ["past", "diagonal", "zigzag"])
def test_pair_backward_at_ring_offsets_matches_ring_einsum_block(layout):
    """What ring attention runs on a chip from S_local 4096 up (the shared
    pair wrapper at a resident block's global tile bases, float32 out)
    against the einsum block backward it runs below that and on the CPU
    meshes. Non-zero bases reach the causal mask and the dropout hash."""
    from distributed_llm_training_benchmark_framework_tpu.ops import (
        flash_attention as fa,
        ring_attention as ra,
    )

    Sl, H, D, bq, tile, rate = 64, 2, 16, 16, 32, 0.2
    h = Sl // 2 if layout == "zigzag" else Sl
    q_bases, k_bases = {
        "past": ((2 * Sl,), (0,)),       # a block wholly in these queries' past
        "diagonal": ((Sl,), (Sl,)),      # the mask cuts through the block
        "zigzag": (ra._zig_chunk_bases(1, 2, h), ra._zig_chunk_bases(0, 2, h)),
    }[layout]
    q, k, v = (t[0].transpose(1, 0, 2) for t in qkv(B=1, S=Sl, H=H, D=D))
    do = jax.random.normal(jax.random.key(5), q.shape)
    seed = jnp.asarray([99], jnp.uint32)
    bhv = jnp.arange(H, dtype=jnp.int32) + 6  # a batch / head shard's global ids
    rows, cols = ra._bases_to_rows(q_bases, h), ra._bases_to_rows(k_bases, h)
    m, l, o = ra._block_stats_jnp(q, k, v, seed, rows, cols, bhv, True, rate)
    lse = m + jnp.log(l)
    delta = jnp.sum(do * o / l[..., None], axis=-1)

    want = ra._block_bwd_jnp(
        q, k, v, do, lse, delta, seed, rows, cols, bhv, True, rate, tile
    )
    stat3 = lambda x: jnp.broadcast_to(x[:, None, :], (H, 8, Sl))
    args = (q, k, v, do, stat3(lse), stat3(delta), seed, bhv, True, rate, bq, tile, True)
    got = fa._pair_backward(
        *args,
        q_tile_offsets=ra._bases_to_tiles(q_bases, h, bq),
        k_tile_offsets=ra._bases_to_tiles(k_bases, h, tile),
        out_dtype=jnp.float32,
    )
    for a, b in zip(got, want):
        assert a.dtype == jnp.float32
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-5)
    at_zero = fa._pair_backward(*args)
    assert not np.allclose(np.asarray(got[0]), np.asarray(at_zero[0]), atol=1e-3)


def test_flash_backward_takes_the_pair_when_the_dq_row_outgrows_vmem(monkeypatch):
    """The fused kernel keeps a whole (S, D) dq row in VMEM; a shape past the
    cap runs the kernel pair, chosen from the shape alone."""
    from distributed_llm_training_benchmark_framework_tpu.ops import (
        flash_attention as fa,
    )

    for S, D in ((8192, 64), (4096, 128), (65536, 128)):
        assert fa._fused_fits(S, D, jnp.bfloat16)
    assert not fa._fused_fits(131072, 64, jnp.bfloat16)

    calls = []
    for name in ("_fused_backward", "_pair_backward"):
        real = getattr(fa, name)
        monkeypatch.setattr(
            fa, name,
            lambda *a, _n=name, _r=real: calls.append(_n) or _r(*a),
        )
    q, k, v = qkv(B=1, S=48, H=1, D=16)  # a shape no other test traces

    def grad(block):
        return jax.grad(lambda q: fa.flash_attention(
            q, k, v, interpret=True, pallas_backward=True, block_q=block,
            block_k=block, block_k_bwd=block,
        ).sum())(q)

    fused = grad(16)
    monkeypatch.setattr(fa, "_FUSED_MAX_VMEM", 0)
    pair = grad(24)
    assert calls == ["_fused_backward", "_pair_backward"]
    np.testing.assert_allclose(
        np.asarray(fused), np.asarray(pair), rtol=1e-5, atol=1e-5
    )


def test_flash_dropout_keep_statistics():
    """Empirical keep fraction tracks 1 - rate (hash uniformity sanity)."""
    rate = 0.3
    keep = _hash_keep_mask(42, 2, 4, 128, rate)
    frac = float(jnp.mean(keep.astype(jnp.float32)))
    assert abs(frac - 0.7) < 0.01, frac
    # Different seeds decorrelate.
    keep2 = _hash_keep_mask(43, 2, 4, 128, rate)
    assert bool(jnp.any(keep != keep2))


@pytest.mark.parametrize("rate", [0.1, 0.3])
def test_flash_dropout_adjacency_unbiased(rate):
    """Adjacent-element keep decisions are independent: P(keep_i AND
    keep_{i+1}) == (1-rate)^2 along rows, columns, and heads. Guards against
    weakening the hash mixer — a single-multiply variant measured pair rate
    0.446 vs 0.490 expected (striped, biased dropout) and was rejected."""
    keep = np.asarray(_hash_keep_mask(123, 2, 4, 256, rate))
    want = (1.0 - rate) ** 2
    for axis_pairs in (
        (keep[..., :-1] & keep[..., 1:]),       # along columns
        (keep[:, :, :-1, :] & keep[:, :, 1:, :]),  # along rows
        (keep[:, :-1] & keep[:, 1:]),           # across heads
    ):
        got = float(axis_pairs.mean())
        assert abs(got - want) < 0.01, (got, want)


def test_flash_dropout_none_seed_is_deterministic():
    q, k, v = qkv(B=1, S=64, H=2, D=16)
    out = flash_attention(
        q, k, v, interpret=True, dropout_rate=0.5, dropout_seed=None
    )
    ref = reference_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-3, atol=2e-3)


def test_flash_backward_auto_selects_einsum_on_cpu(monkeypatch):
    """pallas_backward=None (auto) must take the blockwise-einsum backward
    in interpret mode regardless of S — the Pallas bwd kernels under the
    HLO interpreter are pure slowdown. Forcing True takes the kernel path."""
    from distributed_llm_training_benchmark_framework_tpu.ops import (
        flash_attention as fa,
    )

    calls = []
    real = fa._jnp_blockwise_bwd
    monkeypatch.setattr(
        fa, "_jnp_blockwise_bwd",
        lambda *a, **k: calls.append("einsum") or real(*a, **k),
    )
    q, k, v = qkv(B=1, S=64, H=2, D=16)

    def loss(q, pallas):
        return fa.flash_attention(
            q, k, v, interpret=True, pallas_backward=pallas,
            block_q=32, block_k=32, block_k_bwd=32,
        ).astype(jnp.float32).sum()

    jax.grad(lambda q: loss(q, None))(q)
    assert calls == ["einsum"]
    calls.clear()
    jax.grad(lambda q: loss(q, True))(q)  # forced: Pallas kernels (interpret)
    assert calls == []


def test_tpu_backend_never_reaches_interpret_or_jnp_fallback(monkeypatch):
    """On a TPU backend the dispatch is the Mosaic kernel or an error: the
    default resolves to interpret=False, and an explicit interpret=True is
    refused instead of silently measuring the interpreter. The jnp
    fallbacks (flash's reference forward, ring's einsum blocks) are gated
    on the same flag, so neither is reachable there."""
    from distributed_llm_training_benchmark_framework_tpu.ops import (
        flash_attention as fa,
    )

    assert fa._resolve_interpret(None) is True  # this CPU backend
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert fa._resolve_interpret(None) is False
    assert fa._resolve_interpret(False) is False
    with pytest.raises(ValueError, match="interpret=True on a TPU backend"):
        fa._resolve_interpret(True)
    q, k, v = qkv(B=1, S=64, H=2, D=16)
    with pytest.raises(ValueError, match="interpret=True on a TPU backend"):
        fa.flash_attention(q, k, v, interpret=True, block_q=32, block_k=16)


def test_flash_dp4_dropout_loss_matches_one_device(eight_devices):
    """flash under a dp=4 mesh runs inside a shard_map, where a kernel's
    grid index is LOCAL to the shard. The dropout hash is keyed by global
    (batch, head) ids instead, so the same seed and global batch give the
    same loss — through two optimizer steps, so the gradients too — as one
    device. With local ids, examples on shards 1-3 would reuse shard 0's
    masks and the losses would part at the first step. (On the CPU the
    manual region takes the jnp reference forward, keyed by the same ids.)
    """
    from distributed_llm_training_benchmark_framework_tpu.data import (
        SyntheticDataset,
    )
    from distributed_llm_training_benchmark_framework_tpu.models import (
        get_model_config,
    )
    from distributed_llm_training_benchmark_framework_tpu.parallel import (
        get_strategy,
    )
    from distributed_llm_training_benchmark_framework_tpu.train import (
        create_train_state,
    )

    seq, gb = 64, 8
    config = get_model_config("S", seq, dropout=0.3, attention_impl="flash")
    ds = SyntheticDataset(vocab_size=config.vocab_size, seq_len=seq, size=64)
    host = ds.batch_for_step(0, gb).reshape(1, gb, seq)

    def two_losses(strategy, n_dev):
        mesh = make_mesh((n_dev,), ("data",), devices=eight_devices[:n_dev])
        st = create_train_state(config, get_strategy(strategy), mesh, seed=0)
        batch = jax.device_put(host, st.batch_sharding)
        params, opt, l0 = st.step_fn(st.params, st.opt_state, batch, 0)
        _, _, l1 = st.step_fn(params, opt, batch, 1)
        return float(l0), float(l1)

    one = two_losses("ddp", 1)
    four = two_losses("fsdp", 4)
    np.testing.assert_allclose(four, one, rtol=5e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_ring_matches_reference(causal, eight_devices):
    mesh = make_mesh((4,), ("seq",), devices=eight_devices[:4])
    q, k, v = qkv(B=2, S=64, H=2, D=16)
    with jax.set_mesh(mesh):
        out = ring_attention(q, k, v, causal=causal, mesh=mesh)
    ref = reference_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-3, atol=2e-3)


def test_ring_falls_back_without_seq_axis():
    q, k, v = qkv(B=1, S=32, H=2, D=16)
    out = ring_attention(q, k, v)  # no mesh in scope -> flash fallback
    ref = reference_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-3, atol=2e-3)


@pytest.mark.slow
def test_ring_dropout_matches_flash_bitmask(eight_devices):
    """Ring and flash share the global-coordinate hash: same seed -> the same
    keep mask regardless of how the ring shards the sequence. Verified
    against the materialized-mask reference (tolerances absorb the online
    merge's fp rounding)."""
    rate = 0.25
    B, S, H, D = 2, 128, 4, 32
    mesh = make_mesh((4,), ("seq",), devices=jax.devices()[:4])
    q, k, v = qkv(B=B, S=S, H=H, D=D)
    seed = jnp.asarray(555, jnp.uint32)
    with jax.set_mesh(mesh):
        out_ring = ring_attention(
            q, k, v, mesh=mesh, dropout_rate=rate, dropout_seed=seed
        )
    keep = _hash_keep_mask(555, B, H, S, rate)
    ref = _masked_reference(q, k, v, keep, rate)
    np.testing.assert_allclose(
        np.asarray(out_ring), np.asarray(ref), rtol=2e-3, atol=2e-3
    )
    # And therefore matches flash with the same seed.
    out_flash = flash_attention(
        q, k, v, interpret=True, block_q=32, block_k=32,
        dropout_rate=rate, dropout_seed=seed,
    )
    np.testing.assert_allclose(
        np.asarray(out_ring), np.asarray(out_flash), rtol=2e-3, atol=2e-3
    )


@pytest.mark.slow
def test_ring_dropout_grads(eight_devices):
    """Autodiff through the ring's unrolled hop loop regenerates the same
    masks (pure function of coordinates) — grads match the masked reference."""
    rate = 0.2
    B, S, H, D = 1, 64, 2, 16
    mesh = make_mesh((4,), ("seq",), devices=jax.devices()[:4])
    q, k, v = qkv(B=B, S=S, H=H, D=D)
    seed = jnp.asarray(9, jnp.uint32)
    keep = _hash_keep_mask(9, B, H, S, rate)

    def loss_ring(q):
        return ring_attention(
            q, k, v, mesh=mesh, dropout_rate=rate, dropout_seed=seed
        ).astype(jnp.float32).sum()

    def loss_ref(q):
        return _masked_reference(q, k, v, keep, rate).astype(jnp.float32).sum()

    g1 = jax.grad(loss_ring)(q)
    g2 = jax.grad(loss_ref)(q)
    np.testing.assert_allclose(np.asarray(g1), np.asarray(g2), rtol=5e-3, atol=5e-3)


@pytest.mark.slow
@pytest.mark.parametrize("causal", [False, True])
def test_ring_full_grads_match_reference(causal, eight_devices):
    """dq AND dk/dv: the ring backward accumulates dk/dv on buffers that
    rotate a full cycle home — every (device, block) contribution must land
    on the right shard. Non-uniform cotangent so dv isn't trivially uniform."""
    B, S, H, D = 2, 64, 2, 16
    mesh = make_mesh((4,), ("seq",), devices=eight_devices[:4])
    q, k, v = qkv(B=B, S=S, H=H, D=D)

    def loss_ring(q, k, v):
        o = ring_attention(q, k, v, causal=causal, mesh=mesh)
        w = jnp.arange(o.size, dtype=jnp.float32).reshape(o.shape) / o.size
        return (o.astype(jnp.float32) * w).sum()

    def loss_ref(q, k, v):
        o = reference_attention(q, k, v, causal=causal)
        w = jnp.arange(o.size, dtype=jnp.float32).reshape(o.shape) / o.size
        return (o.astype(jnp.float32) * w).sum()

    g1 = jax.grad(loss_ring, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(g1, g2, ("dq", "dk", "dv")):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=5e-3, atol=5e-3, err_msg=name
        )


def test_ring_zigzag_balances_causal_work():
    """The point of the zigzag layout, as arithmetic: count live (row >=
    col) kernel tiles per device per hop. Contiguous sharding leaves the
    last device with ~n x the first device's work and a worst-hop critical
    path of a full block; zigzag equalizes per-device totals exactly and
    bounds every hop's max-min spread to <= 2 half-chunk blocks (2*h*h
    single-row tiles)."""
    from distributed_llm_training_benchmark_framework_tpu.ops.ring_attention import (
        _zig_chunk_bases,
    )

    n, h = 8, 4  # 8 devices, half-chunks of 4 rows
    S = 2 * n * h

    def live_tiles(q_rows, k_rows):
        return sum(1 for r in q_rows for c in k_rows if r >= c)

    def totals(layout):
        per_dev = []
        per_hop_spread = []
        for t in range(n):
            hop = []
            for d in range(n):
                src = (d - t) % n
                hop.append(live_tiles(layout(d), layout(src)))
            per_hop_spread.append(max(hop) - min(hop))
            if t == 0:
                per_dev = hop[:]
            else:
                per_dev = [a + x for a, x in zip(per_dev, hop)]
        return per_dev, per_hop_spread

    cont = lambda d: list(range(d * 2 * h, (d + 1) * 2 * h))
    # The REAL layout mapping, so this demonstration cannot drift from the op.
    zig = lambda d: [
        int(base) + i for base in _zig_chunk_bases(d, n, h) for i in range(h)
    ]

    cont_dev, _ = totals(cont)
    zig_dev, zig_spread = totals(zig)
    # Same total triangle either way.
    assert sum(cont_dev) == sum(zig_dev) == S * (S + 1) // 2
    # Contiguous: last device does ~n x the first device's work.
    assert cont_dev[-1] > 5 * cont_dev[0]
    # Zigzag: perfectly equal totals, and every hop's imbalance is tiny
    # (the critical path tracks the mean instead of the max device).
    assert max(zig_dev) == min(zig_dev)
    assert max(zig_spread) <= 2 * h * h


@pytest.mark.slow
def test_ring_zigzag_matches_contiguous_and_flash(eight_devices):
    """The causal zigzag layout (auto-on) is purely internal: same output
    as zigzag=False and as the flash kernel, including DROPOUT — the
    half-chunk exchange must keep every row's global coordinates, or the
    hash mask would shift."""
    from distributed_llm_training_benchmark_framework_tpu.ops.ring_attention import (
        ring_attention_sharded,
    )
    from jax.sharding import PartitionSpec as P

    rate = 0.25
    B, S, H, D = 2, 128, 4, 32
    mesh = make_mesh((4,), ("seq",), devices=jax.devices()[:4])
    q, k, v = qkv(B=B, S=S, H=H, D=D)
    seed = jnp.asarray(321, jnp.uint32)

    def ring_call(zz):
        body = lambda a, b, c: ring_attention_sharded(
            a, b, c, axis_name="seq", causal=True,
            dropout_rate=rate, dropout_seed=seed, zigzag=zz,
        )
        return jax.shard_map(
            body, mesh=mesh,
            in_specs=(P(None, "seq"), P(None, "seq"), P(None, "seq")),
            out_specs=P(None, "seq"),
        )(q, k, v)

    with jax.set_mesh(mesh):
        out_zig = ring_call(None)   # auto -> zigzag (causal, n=4)
        out_cont = ring_call(False)
    np.testing.assert_allclose(
        np.asarray(out_zig), np.asarray(out_cont), rtol=2e-3, atol=2e-3
    )
    out_flash = flash_attention(
        q, k, v, causal=True, interpret=True, block_q=32, block_k=32,
        dropout_rate=rate, dropout_seed=seed,
    )
    np.testing.assert_allclose(
        np.asarray(out_zig), np.asarray(out_flash), rtol=2e-3, atol=2e-3
    )


@pytest.mark.slow
def test_ring_zigzag_full_grads(eight_devices):
    """Causal zigzag grads (dq, dk, dv) — the backward re-enters the zigzag
    layout, rotates dk/dv home, and inverse-exchanges back to contiguous."""
    from distributed_llm_training_benchmark_framework_tpu.ops.ring_attention import (
        ring_attention_sharded,
    )
    from jax.sharding import PartitionSpec as P

    B, S, H, D = 1, 64, 2, 16
    mesh = make_mesh((4,), ("seq",), devices=jax.devices()[:4])
    q, k, v = qkv(B=B, S=S, H=H, D=D)

    def ring_loss(q, k, v):
        body = lambda a, b, c: ring_attention_sharded(
            a, b, c, axis_name="seq", causal=True,
        )
        o = jax.shard_map(
            body, mesh=mesh,
            in_specs=(P(None, "seq"), P(None, "seq"), P(None, "seq")),
            out_specs=P(None, "seq"),
        )(q, k, v)
        w = jnp.arange(o.size, dtype=jnp.float32).reshape(o.shape) / o.size
        return (o.astype(jnp.float32) * w).sum()

    def ref_loss(q, k, v):
        o = reference_attention(q, k, v, causal=True)
        w = jnp.arange(o.size, dtype=jnp.float32).reshape(o.shape) / o.size
        return (o.astype(jnp.float32) * w).sum()

    with jax.set_mesh(mesh):
        g1 = jax.grad(ring_loss, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(ref_loss, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(g1, g2, ("dq", "dk", "dv")):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=5e-3, atol=5e-3, err_msg=name
        )


@pytest.mark.slow
def test_ring_full_grads_with_dropout(eight_devices):
    """Full (dq, dk, dv) parity vs the materialized masked reference with
    dropout: the backward ring regenerates the keep mask from coordinates."""
    rate = 0.2
    B, S, H, D = 1, 64, 2, 16
    mesh = make_mesh((4,), ("seq",), devices=jax.devices()[:4])
    q, k, v = qkv(B=B, S=S, H=H, D=D)
    seed = jnp.asarray(77, jnp.uint32)
    keep = _hash_keep_mask(77, B, H, S, rate)

    def loss_ring(q, k, v):
        return ring_attention(
            q, k, v, mesh=mesh, dropout_rate=rate, dropout_seed=seed
        ).astype(jnp.float32).sum()

    def loss_ref(q, k, v):
        return _masked_reference(q, k, v, keep, rate).astype(jnp.float32).sum()

    g1 = jax.grad(loss_ring, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(g1, g2, ("dq", "dk", "dv")):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=5e-3, atol=5e-3, err_msg=name
        )


@pytest.mark.parametrize("causal", [False, True])
def test_ulysses_matches_reference(causal, eight_devices):
    from distributed_llm_training_benchmark_framework_tpu.ops.ulysses_attention import (
        ulysses_attention,
    )

    mesh = make_mesh((4,), ("seq",), devices=eight_devices[:4])
    q, k, v = qkv(B=2, S=64, H=4, D=16)  # H=4 divides n=4
    out = ulysses_attention(q, k, v, causal=causal, mesh=mesh)
    ref = reference_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-3, atol=2e-3)


def test_ulysses_is_differentiable(eight_devices):
    from distributed_llm_training_benchmark_framework_tpu.ops.ulysses_attention import (
        ulysses_attention,
    )

    mesh = make_mesh((2,), ("seq",), devices=eight_devices[:2])
    q, k, v = qkv(B=1, S=64, H=2, D=16)

    def loss(q):
        return ulysses_attention(q, k, v, mesh=mesh).astype(jnp.float32).sum()

    def loss_ref(q):
        return reference_attention(q, k, v).astype(jnp.float32).sum()

    g1 = jax.grad(loss)(q)
    g2 = jax.grad(loss_ref)(q)
    np.testing.assert_allclose(np.asarray(g1), np.asarray(g2), rtol=5e-3, atol=5e-3)


def test_ulysses_dropout_matches_per_shard_mask(eight_devices):
    """The per-head-group mask is reproducible: shard i's heads use seed
    _shard_seed(seed, i) over GLOBAL (local-bh, row, col) coordinates, which
    we materialize and compare against the masked dense reference."""
    from distributed_llm_training_benchmark_framework_tpu.ops import (
        ulysses_attention as ua,
    )

    rate = 0.25
    B, S, H, D, n = 2, 64, 4, 16, 4
    mesh = make_mesh((n,), ("seq",), devices=jax.devices()[:n])
    q, k, v = qkv(B=B, S=S, H=H, D=D)
    seed = jnp.asarray(77, jnp.uint32)
    out = ua.ulysses_attention(
        q, k, v, mesh=mesh, dropout_rate=rate, dropout_seed=seed
    )
    # Build the global mask: shard i holds head group [i*H/n, (i+1)*H/n) and
    # hashes with bh = b*(H/n) + local_h under its folded seed.
    hp = H // n
    groups = []
    for i in range(n):
        si = int(ua._shard_seed(seed, jnp.asarray(i)))
        groups.append(_hash_keep_mask(si, B, hp, S, rate))
    keep = jnp.concatenate(groups, axis=1)  # (B, H, S, S)
    ref = _masked_reference(q, k, v, keep, rate)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-3, atol=2e-3)


def test_ulysses_rejects_indivisible_heads(eight_devices):
    from distributed_llm_training_benchmark_framework_tpu.ops.ulysses_attention import (
        ulysses_attention,
    )

    mesh = make_mesh((4,), ("seq",), devices=jax.devices()[:4])
    q, k, v = qkv(B=1, S=64, H=2, D=16)  # H=2 < n=4
    with pytest.raises(ValueError, match="heads"):
        ulysses_attention(q, k, v, mesh=mesh)


def test_ulysses_falls_back_without_seq_axis():
    from distributed_llm_training_benchmark_framework_tpu.ops.ulysses_attention import (
        ulysses_attention,
    )

    q, k, v = qkv(B=1, S=32, H=2, D=16)
    out = ulysses_attention(q, k, v)
    ref = reference_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-3, atol=2e-3)


@pytest.mark.slow
def test_ring_is_differentiable(eight_devices):
    mesh = make_mesh((4,), ("seq",), devices=eight_devices[:4])
    q, k, v = qkv(B=1, S=64, H=2, D=16)

    def loss(q):
        return ring_attention(q, k, v, mesh=mesh).astype(jnp.float32).sum()

    def loss_ref(q):
        return reference_attention(q, k, v).astype(jnp.float32).sum()

    g1 = jax.grad(loss)(q)
    g2 = jax.grad(loss_ref)(q)
    np.testing.assert_allclose(np.asarray(g1), np.asarray(g2), rtol=5e-3, atol=5e-3)
