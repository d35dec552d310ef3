"""The flash kernels' mask as a rule (``ops/flash_attention.MaskRule``): none,
causal, and block diffusion over a stream of a noisy and a clean copy of a
document. The rule against a dense mask written from its definition, region
by region; the forward kernel, the fused backward, the kernel pair and the two
``jnp`` paths, interpreted, with tiles wider than a block and several compute
pieces a tile, against materialized attention; the two shortcuts the kernels
take on the running maximum and on the logsumexp, which the causal shape used
to justify; and the calls that existed before tracing what they traced."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_llm_training_benchmark_framework_tpu.ops import flash_attention as fa

# Tiles of 256 over documents of 512 tokens in blocks of 4: a tile holds 64
# blocks, the forward walks it in 2 pieces of 128 keys, the backward in 2 of
# 128 queries, and a noisy query's first live key lies mid-tile.
L, B, TILE, PIECE = 512, 4, 256, 128
BH, D = 2, 16
RULE = fa.BlockDiffusion(L, B)


def dense_mask(seq_len, block):
    """(2L, 2L) bool from the definition: copy 0 noisy, copy 1 clean."""
    pos = np.arange(2 * seq_len)
    copy, blk = pos // seq_len, (pos % seq_len) // block
    q_copy, k_copy, q_blk, k_blk = copy[:, None], copy[None, :], blk[:, None], blk[None, :]
    return (((q_copy == 0) & (k_copy == 0) & (q_blk == k_blk))
            | ((q_copy == 0) & (k_copy == 1) & (k_blk < q_blk))
            | ((q_copy == 1) & (k_copy == 1) & (k_blk <= q_blk)))


def stream_operands(seed=0, seq_len=L):
    keys = jax.random.split(jax.random.key(seed), 4)
    q, k, v, do = (jax.random.normal(key, (BH, 2 * seq_len, D), jnp.float32) for key in keys)
    return q, k, v, do, jnp.asarray([7], jnp.uint32), jnp.arange(BH, dtype=jnp.int32)


def materialized(q, k, v, mask):
    scores = jnp.einsum("bqd,bkd->bqk", q, k) * D ** -0.5
    scores = jnp.where(mask[None], scores, -jnp.inf)
    lse = jax.nn.logsumexp(scores, -1)
    return jnp.einsum("bqk,bkd->bqd", jax.nn.softmax(scores, -1), v), lse


REGIONS = {
    # (query rows, key columns) of the (2L, 2L) matrix
    "noisy->noisy": (slice(0, L), slice(0, L)),
    "noisy->clean": (slice(0, L), slice(L, 2 * L)),
    "clean->clean": (slice(L, 2 * L), slice(L, 2 * L)),
    "clean->noisy": (slice(L, 2 * L), slice(0, L)),
    "block-0-has-no-clean-key": (slice(0, B), slice(L, 2 * L)),
    "blocks-at-a-tile's-edge": (slice(TILE - B, TILE + B), slice(0, 2 * L)),
    "the-last-block": (slice(L - B, L), slice(0, 2 * L)),
}


@pytest.mark.parametrize("region", sorted(REGIONS))
def test_the_rule_is_the_dense_mask_in_every_region(region):
    rows, cols = REGIONS[region]
    want = dense_mask(L, B)[rows, cols]
    pos = jnp.arange(2 * L, dtype=jnp.int32)
    got = np.asarray(RULE.allowed(pos[rows, None], pos[None, cols]))
    np.testing.assert_array_equal(got, want)
    assert want.any() == (region not in ("clean->noisy", "block-0-has-no-clean-key"))


@pytest.mark.parametrize("block", [4, 6, 128], ids=["shift", "divide", "a-block-a-piece"])
def test_every_tile_is_the_dense_mask_and_live_where_it_holds_a_pair(block):
    """``tile_live`` and ``in_tile`` are what the kernels use: every tile of the
    grid, on traced scalars as a kernel has them, against the dense mask."""
    seq_len, tile = (384, 128) if block == 6 else (L, TILE)
    rule, want = fa.BlockDiffusion(seq_len, block), dense_mask(seq_len, block)
    offsets = jnp.arange(0, 2 * seq_len, tile, dtype=jnp.int32)

    def tile_of(q_off, k_off):
        rows = q_off + jnp.arange(tile, dtype=jnp.int32)[:, None]
        cols = k_off + jnp.arange(tile, dtype=jnp.int32)[None, :]
        return rule.tile_live(q_off, tile, k_off, tile), rule.in_tile(q_off, k_off, rows, cols)

    live, inside = jax.jit(jax.vmap(jax.vmap(tile_of, (None, 0)), (0, None)))(offsets, offsets)
    n = len(offsets)
    tiles = want.reshape(n, tile, n, tile).transpose(0, 2, 1, 3)
    np.testing.assert_array_equal(np.asarray(live), tiles.any((2, 3)))
    # inside a tile that is never visited (clean -> noisy) the fast form says nothing
    np.testing.assert_array_equal(np.asarray(inside)[np.asarray(live)], tiles[np.asarray(live)])
    assert rule.tile_counts(tile, tile) == (
        int(tiles.any((2, 3)).sum()), n * n, seq_len * seq_len + seq_len * block)
    assert int(want.sum()) == seq_len * seq_len + seq_len * block


def test_the_cell_shape_visits_80_tiles_of_256():
    """A document of 8192 in blocks of 4 at (1024, 1024) tiles: 8 noisy ->
    noisy diagonal tiles, 36 noisy -> clean, 36 clean -> clean; causal over
    the same 16,384 positions visits 136."""
    live, tiles, pairs = fa.BlockDiffusion(8192, 4).tile_counts(1024, 1024)
    assert (live, tiles, pairs) == (80, 256, 8192 * 8192 + 8192 * 4)
    assert round(100 * pairs / (live * 1024 * 1024), 1) == 80.0


@pytest.mark.parametrize("rate", [0.0, 0.1], ids=["no-dropout", "dropout"])
@pytest.mark.parametrize("sub_k", [PIECE, TILE], ids=["pieces", "whole-tile"])
def test_forward_kernel_matches_materialized_attention(rate, sub_k):
    q, k, v, _, seed, bhv = stream_operands()
    out, lse = fa._flash_forward(q, k, v, RULE, True, TILE, TILE, rate, seed, bhv, sub_k=sub_k)
    ref_out, ref_lse = fa._jnp_reference_forward(q, k, v, RULE, rate, seed, bhv)
    np.testing.assert_allclose(out, ref_out, atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(lse, ref_lse, atol=2e-5, rtol=2e-5)
    assert np.isfinite(np.asarray(lse)).all()  # every query has a live key
    if rate == 0.0:  # and the jnp path is the definition
        want_out, want_lse = materialized(q, k, v, dense_mask(L, B))
        np.testing.assert_allclose(ref_out, want_out, atol=2e-5, rtol=2e-5)
        np.testing.assert_allclose(ref_lse, want_lse, atol=2e-5, rtol=2e-5)


def test_forward_without_the_floor_on_its_maximum_is_wrong(monkeypatch):
    """The shortcut the causal shape allowed (no second select on p, because a
    query's first piece holds a live key) does not hold here: a noisy query
    meets masked scores first. Taking the floor away must show."""
    q, k, v, _, seed, bhv = stream_operands()
    want, _ = materialized(q, k, v, dense_mask(L, B))
    assert not fa.first_piece_live(RULE) and fa.first_piece_live(True) and fa.first_piece_live(False)
    monkeypatch.setattr(fa, "first_piece_live", lambda mask: True)
    out, _ = fa._flash_forward(q, k, v, RULE, True, TILE, TILE, 0.0, seed, bhv, sub_k=PIECE)
    assert not float(jnp.max(jnp.abs(out - want))) < 0.1  # far off, or not a number


@pytest.mark.parametrize("rate", [0.0, 0.1], ids=["no-dropout", "dropout"])
def test_backward_kernels_match_each_other_and_the_einsum_path(rate):
    """One logsumexp (the forward's), three backward passes: the fused kernel
    with its queries in pieces, the dq / dk+dv pair, and the jnp scan."""
    q, k, v, do, seed, bhv = stream_operands(1)
    out, lse = fa._flash_forward(q, k, v, RULE, True, TILE, TILE, rate, seed, bhv)
    delta = jnp.sum(do * out, -1)
    lse3 = jnp.broadcast_to(lse[:, None, :], (BH, 8, 2 * L))
    delta3 = jnp.broadcast_to(delta[:, None, :], (BH, 8, 2 * L))
    args = (q, k, v, do, lse3, delta3, seed, bhv, RULE, rate, TILE, TILE, True)
    fused = fa._fused_backward(*args, sub=PIECE)
    whole = fa._fused_backward(*args, sub=TILE)
    pair = fa._pair_backward(*args)
    einsum = fa._jnp_blockwise_bwd(RULE, TILE, rate, (q, k, v, out, lse, seed, bhv), do)
    for name, f, w, p, e in zip(("dq", "dk", "dv"), fused, whole, pair, einsum):
        np.testing.assert_allclose(f, w, atol=1e-5, rtol=1e-5, err_msg=name)
        np.testing.assert_allclose(f, p, atol=1e-5, rtol=1e-5, err_msg=name)
        np.testing.assert_allclose(f, e, atol=2e-5, rtol=2e-5, err_msg=name)


@pytest.mark.parametrize("pallas_backward", [False, True], ids=["einsum", "fused"])
def test_gradients_match_jax_grad_of_materialized_attention(pallas_backward):
    """Through the public call, heads and batch and all: forward and the
    gradient by q, k and v against ``jax.grad`` of the dense-mask softmax."""
    keys = jax.random.split(jax.random.key(2), 4)
    q, k, v, w = (jax.random.normal(key, (1, 2 * L, BH, D), jnp.float32) for key in keys)
    mask = jnp.asarray(dense_mask(L, B))

    def flash(q, k, v):
        return jnp.sum(w * fa.flash_attention(
            q, k, v, causal=RULE, interpret=True, block_q=TILE, block_k=TILE, block_k_bwd=TILE,
            pallas_backward=pallas_backward))

    def plain(q, k, v):
        to = lambda t: t[0].transpose(1, 0, 2)
        out = materialized(to(q), to(k), to(v), mask)[0]
        return jnp.sum(w * out.transpose(1, 0, 2)[None])

    np.testing.assert_allclose(flash(q, k, v), plain(q, k, v), rtol=1e-5)
    got = jax.grad(flash, argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(plain, argnums=(0, 1, 2))(q, k, v)
    for g, r, name in zip(got, want, "qkv"):
        np.testing.assert_allclose(g, r, atol=5e-5, rtol=5e-4, err_msg=name)
    np.testing.assert_allclose(
        fa.reference_attention(q, k, v, causal=RULE),
        materialized(q[0].transpose(1, 0, 2), k[0].transpose(1, 0, 2), v[0].transpose(1, 0, 2),
                     mask)[0].transpose(1, 0, 2)[None], atol=2e-5, rtol=2e-5)


def test_a_wrong_logsumexp_shows_in_the_fused_backward():
    """The fused backward puts no second select on p either: it leans on a
    finite logsumexp, which the rule promises (every query sees itself) and
    the forward delivers. Handed the logsumexp of rows that had no live key
    (the failure the forward's floor prevents), its gradients are not finite
    numbers of the right size: the comparison above would fail."""
    q, k, v, do, seed, bhv = stream_operands(3)
    out, lse = fa._flash_forward(q, k, v, RULE, True, TILE, TILE, 0.0, seed, bhv)
    broken = lse.at[:, :L].set(fa.NEG_INF)  # what an all-masked row's statistics read
    lse3 = jnp.broadcast_to(broken[:, None, :], (BH, 8, 2 * L))
    delta3 = jnp.broadcast_to(jnp.sum(do * out, -1)[:, None, :], (BH, 8, 2 * L))
    good = fa._jnp_blockwise_bwd(RULE, TILE, 0.0, (q, k, v, out, lse, seed, bhv), do)
    bad = fa._fused_backward(q, k, v, do, lse3, delta3, seed, bhv, RULE, 0.0, TILE, TILE, True)
    assert not np.allclose(bad[1], good[1], atol=1e-2, equal_nan=False)


@pytest.mark.parametrize("causal", [False, True], ids=["none", "causal"])
@pytest.mark.parametrize("pallas_backward", [False, True], ids=["einsum", "fused"])
def test_the_calls_that_existed_trace_what_they_traced(causal, pallas_backward):
    """``causal`` true / false are two of the rule's values, false the
    default: the jaxpr holds none of the integer work block diffusion's rule
    brings (a shift to block numbers, a bit-cast for the unsigned compare).
    The parent commit's jaxprs of these calls, the pair's too, were compared
    with this code's once, equal (CHANGES.md, PR 36)."""
    keys = jax.random.split(jax.random.key(4), 4)
    q, k, v, w = (jax.random.normal(key, (1, 2 * L, BH, D), jnp.float32) for key in keys)

    def program(**rule):
        def f(q, k, v):
            return jnp.sum(w * fa.flash_attention(
                q, k, v, interpret=True, block_q=TILE, block_k=TILE, block_k_bwd=TILE,
                pallas_backward=pallas_backward, **rule))
        return str(jax.make_jaxpr(jax.grad(f, argnums=(0, 1, 2)))(q, k, v))

    default = program(causal=causal)
    if not causal:
        assert default == program()
    assert "shift_right_logical" not in default and "bitcast_convert_type" not in default
    under_the_rule = program(causal=RULE)
    assert "shift_right_logical" in under_the_rule and "bitcast_convert_type" in under_the_rule


def test_what_the_rule_refuses():
    with pytest.raises(ValueError, match="whole blocks"):
        fa.BlockDiffusion(10, 4)
    q = jnp.zeros((1, 2 * L, 1, D))
    with pytest.raises(ValueError, match="tiles that divide"):  # a tile across both copies
        fa.flash_attention(q, q, q, causal=RULE, interpret=True, block_q=2 * L)
    with pytest.raises(ValueError, match="stream of 1024"):  # not a stream of 2L
        fa.flash_attention(q[:, :L], q[:, :L], q[:, :L], causal=RULE, interpret=True)
    # the tiles picked for a stream divide a copy of the document
    assert fa.pick_tiles(2 * L, D, jnp.float32, True, causal=RULE)[:3] == (512, 512, 512)
    assert fa.pick_tiles(2 * 8192, 128, jnp.bfloat16, False, causal=fa.BlockDiffusion(8192, 4)) == (
        1024, 1024, 1024, True)
