"""The flash kernels' mask as a rule (``ops/flash_attention.MaskRule``): none,
causal, and block diffusion over a stream of a noisy and a clean copy of a
document. The rule against a dense mask written from its definition, region
by region; the forward kernel, the fused backward, the kernel pair and the two
``jnp`` paths, interpreted, with tiles wider than a block and several compute
pieces a tile, against materialized attention; the two shortcuts the kernels
take on the running maximum and on the logsumexp, which the causal shape used
to justify; and the calls that existed before tracing what they traced."""

import functools

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


@pytest.fixture(autouse=True)
def kernels_built_by_this_test():
    """The kernels are kept a shape for the life of the process: a test that
    patches what a body reads (``_tile_shape``, ``first_piece_live``) has to
    build its own."""
    fa.forget_kernel_calls()
    yield
    fa.forget_kernel_calls()


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


@pytest.mark.parametrize("walk", ["whole-tiles", "lower-bodies"])
def test_forward_without_the_floor_on_its_maximum_is_wrong(walk, monkeypatch):
    """The shortcut the causal shape allowed (no second select on p, because a
    query's first piece holds a live key) does not hold here: a noisy query
    meets masked scores first, with every live tile walked whole (tiles that
    are not square, or this walk with the shapes turned off) and with the
    diagonal tiles walked by their *lower* body too (a noisy -> noisy one
    still starts a query at the key pieces before its own). Taking the floor
    away must show."""
    q, k, v, _, seed, bhv = stream_operands()
    want, _ = materialized(q, k, v, dense_mask(L, B))
    assert not fa.first_piece_live(RULE) and fa.first_piece_live(True) and fa.first_piece_live(False)
    monkeypatch.setattr(fa, "first_piece_live", lambda mask: True)
    if walk == "whole-tiles":
        monkeypatch.setattr(fa, "_tile_shape", lambda *a: False)
    fa.forget_kernel_calls()
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
def test_the_calls_that_existed_trace_what_they_traced(causal, pallas_backward, monkeypatch):
    """``causal`` true / false are two of the rule's values, false the
    default: the jaxpr holds none of the integer work block diffusion's rule
    brings (a shift to block numbers, a bit-cast for the unsigned compare).
    The parent commit's jaxprs of these calls, the pair's too, were compared
    with this code's once, equal (CHANGES.md, PR 36). Since PR 37 a rule also
    gives its tiles shapes: with no mask there are none and the kernels trace
    what they trace with the shapes turned off, which is PR 36's kernel (the
    Mosaic modules compared once with the parent's, printed without
    locations, forward and fused backward, three widths, with and without
    dropout, equal: CHANGES.md, PR 37; the jaxprs name their ops otherwise
    since the bodies' chains bind ``lax`` primitives); under ``causal`` the
    forward (a tile of two pieces here) gains the diagonal tiles' body, and
    with the shapes off is PR 36's again."""
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
    monkeypatch.setattr(fa, "flash_attention", fa.flash_attention.__wrapped__)  # past its jit's cache
    as_it_is = program(causal=causal)
    shapes_off(monkeypatch)
    assert (as_it_is == program(causal=causal)) == (not causal)


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


# ---- The shape of a live tile, and the bodies that walk only its live pieces ----

def shapes_off(monkeypatch):
    """The whole-tile walk at the same tiles and pieces: every live tile *full*."""
    monkeypatch.setattr(fa, "_tile_shape", lambda *a: False)
    fa.forget_kernel_calls()


def causal_mask(rows, cols):
    return rows[:, None] >= cols[None, :]


def bd_mask(seq_len, block):
    def allowed(rows, cols):  # the definition, on stream positions
        q_copy, k_copy = rows[:, None] // seq_len, cols[None, :] // seq_len
        q_blk, k_blk = (rows[:, None] % seq_len) // block, (cols[None, :] % seq_len) // block
        return (((q_copy == 0) & (k_copy == 0) & (q_blk == k_blk))
                | ((q_copy == 0) & (k_copy == 1) & (k_blk < q_blk))
                | ((q_copy == 1) & (k_copy == 1) & (k_blk <= q_blk)))
    return allowed


CELL_SHAPES = {
    # rule, its dense mask, S, {shape: tiles a head} at (1024, 1024) tiles
    "causal-4096": (True, causal_mask, 4096, {fa.FULL: 6, fa.LOWER: 4}),
    "causal-8192": (True, causal_mask, 8192, {fa.FULL: 28, fa.LOWER: 8}),
    "block-diffusion-8192x4": (
        fa.BlockDiffusion(8192, 4), bd_mask(8192, 4), 16384, {fa.FULL: 56, fa.LOWER: 24}),
}


@pytest.mark.parametrize("piece", [128, 256], ids=["forward-128", "backward-256"])
@pytest.mark.parametrize("cell", sorted(CELL_SHAPES))
def test_no_piece_a_body_skips_holds_an_allowed_pair(cell, piece):
    """The shape the rule gives every tile of the cell shapes, against the
    dense mask written from the definition: a piece outside the *lower*
    body's span (``_piece_span``, the forward's walk and the backward's) holds
    no allowed pair; every piece inside it holds one, but in block diffusion's
    noisy -> noisy diagonal tiles, where only the diagonal pieces do (they
    run the *lower* body all the same); and the count is ``visited_units``'s."""
    rule, dense, S, want = CELL_SHAPES[cell]
    tile, n = 1024, 1024 // piece
    tiles = fa.tiles_by_shape(rule, S, tile, tile, piece)
    assert {shape: int(here.sum()) for shape, here in tiles.items()} == want
    visited = n * n * want[fa.FULL]
    for qi, ki in zip(*np.nonzero(tiles[fa.LOWER])):
        inside = dense(qi * tile + np.arange(tile), ki * tile + np.arange(tile))
        holds = inside.reshape(n, piece, n, piece).any((1, 3))  # [query piece, key piece]
        for keys_walked in (True, False):
            walked = np.zeros((n, n), bool)
            for i in range(n):
                lo, hi = fa._piece_span(fa.LOWER, i, n, keys_walked)
                walked[(slice(lo, hi), i) if keys_walked else (i, slice(lo, hi))] = True
            assert not (holds & ~walked).any(), f"tile ({qi}, {ki})"
            noisy_to_noisy = rule is not True and ki * tile < rule.seq_len
            np.testing.assert_array_equal(holds, np.eye(n, dtype=bool) if noisy_to_noisy else walked)
        visited += int(walked.sum())
    assert fa.visited_units(rule, S, tile, tile, piece) == (visited, (S // tile) ** 2 * n * n, piece * piece)


def test_the_cell_shape_visits_69_and_71_tiles_worth_of_its_80():
    """The sibling of the 80 tiles above: what the kernels multiply inside
    them. 56 whole tiles and 24 *lower* ones (36 of 64 forward pieces of 128,
    10 of 16 backward pieces of 256): 69.5 and 71 tiles' worth."""
    rule = fa.BlockDiffusion(8192, 4)
    pairs = rule.tile_counts(1024, 1024)[2]
    fwd = fa.visited_units(rule, 16384, 1024, 1024, fa._fwd_sub_k(1024))
    bwd = fa.visited_units(rule, 16384, 1024, 1024, fa._bwd_sub_q(1024, 0.0))
    assert fwd == (56 * 64 + 24 * 36, 256 * 64, 128 * 128) and bwd == (71 * 16, 256 * 16, 256 * 256)
    assert round(100 * 2 * pairs / (fwd[0] * fwd[2] + bwd[0] * bwd[2]), 1) == 91.1
    # with dropout the backward's piece is 128: 69.5, as the forward
    assert fa.visited_units(rule, 16384, 1024, 1024, fa._bwd_sub_q(1024, 0.1))[0] == fwd[0]
    # where the rule gives no shapes the unit is the tile: not square, and a piece of 6 blocks and a half
    assert fa.visited_units(rule, 16384, 1024, 512, 128) == (rule.tile_counts(1024, 512)[0], 512, 1024 * 512)
    assert fa.visited_units(fa.BlockDiffusion(6144, 24), 12288, 1024, 1024, 128) == (
        fa.BlockDiffusion(6144, 24).tile_counts(1024, 1024)[0], 144, 1024 * 1024)
    assert fa.visited_units(False, 4096, 1024, 1024, 128) == (16, 16, 1024 * 1024)
    # causal at S 4096: 6 + 4 x 36 / 64 forward, 6 + 4 x 10 / 16 backward
    assert fa.visited_units(True, 4096, 1024, 1024, 128)[0] == 6 * 64 + 4 * 36
    assert fa.visited_units(True, 4096, 1024, 1024, 256)[0] == 6 * 16 + 4 * 10


def widths_operands(d_qk, d_v, seed=5):
    keys = jax.random.split(jax.random.key(seed), 4)
    q, k = (jax.random.normal(key, (BH, 2 * L, d_qk), jnp.float32) for key in keys[:2])
    v, do = (jax.random.normal(key, (BH, 2 * L, d_v), jnp.float32) for key in keys[2:])
    return q, k, v, do, jnp.asarray([7], jnp.uint32), jnp.arange(BH, dtype=jnp.int32)


RULES = {"causal": True, "block-diffusion": RULE}
WIDTHS = {"D=Dv": (16, 16), "Dqk>Dv": (24, 16)}


@pytest.mark.parametrize("widths", sorted(WIDTHS))
@pytest.mark.parametrize("rate", [0.0, 0.1], ids=["no-dropout", "dropout"])
@pytest.mark.parametrize("rule", sorted(RULES))
def test_forward_bodies_give_the_whole_tile_walks_bits(rule, rate, widths, monkeypatch):
    """*lower* tiles walked by the pieces on and below their diagonal against
    every live tile walked whole, at the same tiles and pieces: ``out`` and
    ``lse`` bit for bit (the products left out were exact zeros, alpha 1),
    in every region of the stream, the keep mask with them; and the
    materialized reference to rounding."""
    mask = RULES[rule]
    q, k, v, _, seed, bhv = widths_operands(*WIDTHS[widths])
    args = (q, k, v, mask, True, TILE, TILE, rate, seed, bhv)
    out, lse = fa._flash_forward(*args, sub_k=PIECE)
    ref_out, ref_lse = fa._jnp_reference_forward(q, k, v, mask, rate, seed, bhv)
    shapes_off(monkeypatch)
    whole_out, whole_lse = fa._flash_forward(*args, sub_k=PIECE)
    for name, (rows, _) in REGIONS.items():
        np.testing.assert_array_equal(out[:, rows], whole_out[:, rows], err_msg=name)
        np.testing.assert_array_equal(lse[:, rows], whole_lse[:, rows], err_msg=name)
    np.testing.assert_allclose(out, ref_out, atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(lse, ref_lse, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("widths", sorted(WIDTHS))
@pytest.mark.parametrize("rate", [0.0, 0.1], ids=["no-dropout", "dropout"])
@pytest.mark.parametrize("rule", sorted(RULES))
def test_fused_backward_bodies_give_the_whole_tile_walks_bits(rule, rate, widths, monkeypatch):
    """The same for dq, dk and dv (partial rows of dk and dv, a partial sum
    in dq's contraction: what is left out was p = ds = 0), and the einsum
    path to rounding."""
    mask = RULES[rule]
    q, k, v, do, seed, bhv = widths_operands(*WIDTHS[widths], seed=6)
    out, lse = fa._flash_forward(q, k, v, mask, True, TILE, TILE, rate, seed, bhv)
    lse3 = jnp.broadcast_to(lse[:, None, :], (BH, 8, 2 * L))
    delta3 = jnp.broadcast_to(jnp.sum(do * out, -1)[:, None, :], (BH, 8, 2 * L))
    args = (q, k, v, do, lse3, delta3, seed, bhv, mask, rate, TILE, TILE, True)
    got = fa._fused_backward(*args, sub=PIECE)
    einsum = fa._jnp_blockwise_bwd(mask, TILE, rate, (q, k, v, out, lse, seed, bhv), do)
    shapes_off(monkeypatch)
    whole = fa._fused_backward(*args, sub=PIECE)
    for name, g, w, e in zip(("dq", "dk", "dv"), got, whole, einsum):
        np.testing.assert_array_equal(g, w, err_msg=name)
        np.testing.assert_allclose(g, e, atol=2e-5, rtol=2e-5, err_msg=name)


@pytest.mark.parametrize("rate", [0.0, 0.1], ids=["no-dropout", "dropout"])
@pytest.mark.parametrize("rule", sorted(RULES))
def test_gradients_through_the_bodies_match_jax_grad_of_materialized_attention(rule, rate, monkeypatch):
    """Through the public call with both kernels walking their diagonal tiles
    by pieces (the backward's piece forced: the chooser walks a tile of 256
    whole): the gradient by q, k and v against ``jax.grad`` of the dense-mask
    softmax with the forward's keep mask laid over it."""
    mask = RULES[rule]
    keys = jax.random.split(jax.random.key(8), 4)
    q, k, v, w = (jax.random.normal(key, (1, 2 * L, BH, D), jnp.float32) for key in keys)
    pos = np.arange(2 * L)
    dense = jnp.asarray(causal_mask(pos, pos) if mask is True else dense_mask(L, B))
    seed = jnp.uint32(11)
    keep = fa._dropout_keep(seed, jnp.arange(BH)[:, None, None], pos[None, :, None], pos[None, None, :],
                            fa._dropout_threshold(rate)) if rate else jnp.ones((BH, 2 * L, 2 * L), bool)
    fused = fa._fused_backward
    monkeypatch.setattr(fa, "_fused_backward", lambda *a: fused(*a, sub=PIECE))

    def flash(q, k, v):  # not through the call's jit: its cache would not see the forced piece
        return jnp.sum(w * fa.flash_attention.__wrapped__(
            q, k, v, causal=mask, interpret=True, block_q=TILE, block_k=TILE, block_k_bwd=TILE,
            pallas_backward=True, dropout_rate=rate, dropout_seed=seed if rate else None))

    def plain(q, k, v):
        to = lambda t: t[0].transpose(1, 0, 2)
        scores = jnp.einsum("bqd,bkd->bqk", to(q), to(k)) * D ** -0.5
        p = jax.nn.softmax(jnp.where(dense[None], scores, -jnp.inf), -1)
        out = jnp.einsum("bqk,bkd->bqd", jnp.where(keep, p / (1.0 - rate), 0.0), to(v))
        return jnp.sum(w * out.transpose(1, 0, 2)[None])

    np.testing.assert_allclose(flash(q, k, v), plain(q, k, v), rtol=1e-5)
    got = jax.grad(flash, argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(plain, argnums=(0, 1, 2))(q, k, v)
    for g, r, name in zip(got, want, "qkv"):
        np.testing.assert_allclose(g, r, atol=5e-5, rtol=5e-4, err_msg=name)


def kernel_bodies(fn, *args):
    """{kernel name: [the dot_generals' operand shapes of each body]} of the
    Pallas calls ``fn`` traces: a body is a branch a ``pl.when`` made (a
    ``cond`` of the kernel's jaxpr) that multiplies, or the kernel's top level
    where a body runs unconditionally (no mask)."""
    def dots(jaxpr):
        found = []
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "dot_general":
                found.append(tuple(tuple(x.aval.shape) for x in eqn.invars))
        return found

    def calls(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                yield eqn
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from calls(sub)

    kernels = {}
    for call in calls(jax.make_jaxpr(fn)(*args).jaxpr):
        kernel = call.params["jaxpr"]
        bodies = [dots(kernel)] + [
            dots(branch.jaxpr) for eqn in kernel.eqns if eqn.primitive.name == "cond"
            for branch in eqn.params["branches"]]
        kernels[call.params["name"]] = [b for b in bodies if b]
    return kernels


@pytest.mark.parametrize("rule", ["none"] + sorted(RULES))
def test_the_bodies_issue_the_products_of_the_area_they_visit(rule):
    """What the kernels' jaxprs multiply: a body's score products (k q^T:
    its first operand the keys of a piece, its second the queries it meets)
    add up to the area ``_piece_span`` gives its shape, a body a shape the
    rule has, and over the grid to ``visited_units``; with no mask there is
    one body, the one there was: 2 products a key piece forward, 5 a query
    piece backward."""
    mask = RULES.get(rule, False)
    q, k, v, do, seed, bhv = widths_operands(24, 16)
    stat = jnp.zeros((BH, 8, 2 * L), jnp.float32)
    S, n = 2 * L, TILE // PIECE
    kernels = kernel_bodies(
        lambda q, k, v, do: (
            fa._flash_forward(q, k, v, mask, True, TILE, TILE, 0.0, seed, bhv, sub_k=PIECE),
            fa._fused_backward(q, k, v, do, stat, stat, seed, bhv, mask, 0.0, TILE, TILE, True, sub=PIECE)),
        q, k, v, do)
    assert sorted(kernels) == ["flash_bwd_fused", "flash_fwd"]
    shapes = [fa.LOWER, fa.FULL] if mask else [fa.FULL]  # in the order they are emitted
    tiles = {shape: int(here.sum()) for shape, here in fa.tiles_by_shape(mask, S, TILE, TILE, PIECE).items()}
    for name, products_a_piece, score in (("flash_fwd", 2, lambda a, b: a[0] == PIECE and a[1] == b[1] == 24),
                                          ("flash_bwd_fused", 5, lambda a, b: b[0] == PIECE and a[1] == b[1] == 24)):
        bodies = kernels[name]
        assert len(bodies) == len(shapes), (name, len(bodies))
        total = 0
        for shape, body in zip(shapes, bodies):
            assert len(body) == products_a_piece * n
            area = sum(a[0] * b[0] for a, b in body if score(a, b))
            assert area == PIECE * PIECE * sum(
                hi - lo for lo, hi in (fa._piece_span(shape, i, n, name == "flash_fwd") for i in range(n)))
            total += tiles[shape] * area
        units, _, unit_pairs = fa.visited_units(mask, S, TILE, TILE, PIECE)
        assert total == units * unit_pairs


@pytest.mark.parametrize("tiles,sub_k,sub", [((256, 128), 128, 128), ((128, 256), 128, 64), ((192, 192), None, None),
                                             ((128, 128), 128, 128)],
                         ids=["bq>bk", "bq<bk", "a-piece-that-does-not-divide", "a-tile-no-wider-than-its-piece"])
@pytest.mark.parametrize("rule", sorted(RULES))
def test_tiles_that_are_not_square_or_not_cut_whole_run_the_full_body(rule, tiles, sub_k, sub, monkeypatch):
    """bq != bk, a piece the tile is not a whole number of (the choosers then
    walk it whole) or a tile of one piece: the rule gives no shapes and the
    kernels trace what they traced with none."""
    mask, (bq, bk) = RULES[rule], tiles
    seq_len = 384 if bq == 192 else L
    mask = fa.BlockDiffusion(seq_len, B) if rule == "block-diffusion" else mask
    q = jnp.zeros((BH, 2 * seq_len, D), jnp.float32)
    stat = jnp.zeros((BH, 8, 2 * seq_len), jnp.float32)
    seed, bhv = jnp.asarray([7], jnp.uint32), jnp.arange(BH, dtype=jnp.int32)

    def program():
        return str(jax.make_jaxpr(lambda q: (
            fa._flash_forward(q, q, q, mask, True, bq, bk, 0.0, seed, bhv, sub_k=sub_k),
            fa._fused_backward(q, q, q, q, stat, stat, seed, bhv, mask, 0.0, bq, bk, True, sub=sub)))(q))

    for piece in (sub_k or fa._FWD_SUB_K, sub or fa._BWD_SUB_Q_DROPOUT):
        assert fa._tile_shape(mask, 0, bq, 0, bk, piece) is False
    as_it_is = program()
    shapes_off(monkeypatch)
    assert as_it_is == program()


@pytest.mark.parametrize("rule", sorted(RULES))
def test_a_differentiated_call_traces_each_kernel_once_and_in_few_jits(rule, monkeypatch):
    """What the bodies cost a run's set-up (PERF.md section 6, PR 37). A
    call under ``jax.grad`` with no mesh set traces the forward kernel once:
    the primal and the forward rule share one ``pallas_call`` and one trace
    context. And a body's chain binds ``lax`` primitives: every ``jnp``
    function or operator of a tracer is a jit of its own, traced anew at each
    new shape, and a *lower* body has a new shape a piece. At the Mosaic
    path's tiles (traced, not lowered) a differentiated call of the one-body
    kernels written in ``jnp`` made 238 such traces causal and 482 under
    block diffusion, the two bodies in ``jnp`` 484 and 924; they make 114 and
    305 (the rule's scalars of a tile, made again a piece, are the rest)."""
    from distributed_llm_training_benchmark_framework_tpu.utils import scopes

    S = 4096
    mask = fa.BlockDiffusion(S // 2, B) if rule == "block-diffusion" else RULES[rule]
    traced = {"flash_fwd": 0, "flash_bwd_fused": 0}
    for name, kernel in (("flash_fwd", fa._flash_fwd_kernel), ("flash_bwd_fused", fa._bwd_fused_kernel)):
        def counted(*args, _name=name, _kernel=kernel, **kwargs):
            traced[_name] += 1
            return _kernel(*args, **kwargs)
        monkeypatch.setattr(fa, kernel.__name__, counted)
    q = jax.ShapeDtypeStruct((1, S, 2, 128), jnp.bfloat16)

    def loss(q, k, v):
        out = fa.flash_attention.__wrapped__(  # past its jit's cache
            q, k, v, causal=mask, interpret=False, pallas_backward=True)
        return jnp.sum(out.astype(jnp.float32))

    def jit_traces():
        return sum(count for (event, _), (count, _) in scopes.compile_events()["sums"].items()
                   if event.endswith("jaxpr_trace_duration"))

    before = jit_traces()
    jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(q, q, q)
    assert traced == {"flash_fwd": 1, "flash_bwd_fused": 1}
    assert jit_traces() - before < {"causal": 150, "block-diffusion": 350}[rule]


# ---------------------------------------------------------------------------
# The sliding-window rule (``fa.SlidingWindow``): a causal band, and the grid
# of the forward and the fused backward kernel is the band.
# ---------------------------------------------------------------------------

# (S, window, tile): the window below, equal to and above the tile, not a
# multiple of it, the whole sequence, one key; and tiles that are not square.
WINDOWS = {
    "below-the-tile": (512, 64, (128, 128)),
    "the-tile": (512, 128, (128, 128)),
    "two-tiles": (512, 256, (128, 128)),
    "not-a-multiple": (512, 200, (128, 128)),
    "the-sequence": (512, 512, (128, 128)),
    "one-key": (256, 1, (128, 128)),
    "bq>bk": (512, 300, (256, 128)),
    "bq<bk": (512, 300, (128, 256)),
}


def window_mask(S, window):
    i, j = np.arange(S)[:, None], np.arange(S)[None, :]
    return (j <= i) & (j > i - window)


def window_operands(S, seed=3):
    keys = jax.random.split(jax.random.key(seed), 4)
    q, k, v, do = (jax.random.normal(key, (BH, S, D), jnp.float32) for key in keys)
    return q, k, v, do, jnp.asarray([7], jnp.uint32), jnp.arange(BH, dtype=jnp.int32)


@pytest.mark.parametrize("case", sorted(WINDOWS))
def test_the_window_rule_is_the_dense_mask_tile_by_tile(case):
    """``allowed``, ``tile_live``, ``in_tile`` and ``tile_counts`` against the
    mask written from its definition, and every query has itself."""
    S, window, (bq, bk) = WINDOWS[case]
    rule, want = fa.SlidingWindow(window), window_mask(S, window)
    pos = jnp.arange(S, dtype=jnp.int32)
    np.testing.assert_array_equal(np.asarray(rule.allowed(pos[:, None], pos[None, :])), want)
    assert want.diagonal().all() and int(want.sum()) == rule.true_pairs(S)
    q_offs, k_offs = jnp.arange(0, S, bq, dtype=jnp.int32), jnp.arange(0, S, bk, dtype=jnp.int32)

    def tile_of(q_off, k_off):
        rows = q_off + jnp.arange(bq, dtype=jnp.int32)[:, None]
        cols = k_off + jnp.arange(bk, dtype=jnp.int32)[None, :]
        return rule.tile_live(q_off, bq, k_off, bk), rule.in_tile(q_off, k_off, rows, cols)

    live, inside = jax.jit(jax.vmap(jax.vmap(tile_of, (None, 0)), (0, None)))(q_offs, k_offs)
    tiles = want.reshape(S // bq, bq, S // bk, bk).transpose(0, 2, 1, 3)
    np.testing.assert_array_equal(np.asarray(live), tiles.any((2, 3)))
    np.testing.assert_array_equal(np.asarray(inside), tiles)  # one compare, right everywhere
    assert rule.tile_counts(S, bq, bk) == (int(tiles.any((2, 3)).sum()), tiles.shape[0] * tiles.shape[1],
                                          int(want.sum()))


@pytest.mark.parametrize("case", sorted(WINDOWS))
def test_the_band_grid_visits_every_live_tile_and_little_else(case):
    """``band_steps`` / ``key_tile`` / ``query_tile`` against a brute-force
    walk: every live tile is some step's, no tile twice, and the steps that
    multiply nothing are the band's clipped corner (and, where the tiles are
    not square, the rows whose band is narrower than the widest)."""
    S, window, (bq, bk) = WINDOWS[case]
    rule = fa.SlidingWindow(window)
    live = window_mask(S, window).reshape(S // bq, bq, S // bk, bk).any((1, 3))
    steps = rule.band_steps(S, bq, bk, True)
    seen = np.zeros_like(live)
    for qi in range(S // bq):
        for step in range(steps):
            ki = rule.key_tile(qi, bq, bk, step, steps)
            if 0 <= ki < S // bk:
                assert not seen[qi, ki]
                seen[qi, ki] = True
            else:
                assert ki < 0  # the clipped corner lies before the sequence
    assert (seen | ~live).all() and steps == live.sum(1).max()
    assert rule.grid_counts(S, bq, bk, True) == (int(live.sum()), S // bq * steps)
    steps = rule.band_steps(S, bq, bk, False)
    seen = np.zeros_like(live)
    for ki in range(S // bk):
        for step in range(steps):
            qi = rule.query_tile(ki, bq, bk, step)
            if qi < S // bq:
                assert not seen[qi, ki]
                seen[qi, ki] = True
    assert (seen | ~live).all() and steps == live.sum(0).max()
    assert rule.grid_counts(S, bq, bk, False) == (int(live.sum()), S // bk * steps)


def test_the_cell_shape_walks_31_live_tiles_in_32_steps():
    """S 16,384 under a window of 1024 at (1024, 1024) tiles: 31 live tiles a
    head of the square's 256 (7.3 dead steps a live one on the full grid), a
    band of 2 steps a tile with one clipped; 12.1 % of causal's pairs."""
    rule = fa.SlidingWindow(1024)
    assert rule.tile_counts(16384, 1024, 1024) == (31, 256, 16253440)
    assert rule.grid_counts(16384, 1024, 1024, True) == rule.grid_counts(16384, 1024, 1024, False) == (31, 32)
    assert round((256 - 31) / 31, 1) == 7.3
    assert round(100 * 16253440 / (16384 * 16385 // 2), 1) == 12.1
    units, _, unit = fa.visited_units(rule, 16384, 1024, 1024, 128)
    assert round(100 * 16253440 / (units * unit), 1) == 64.6  # the *lower* body on the diagonal


@pytest.mark.parametrize("rate", [0.0, 0.1], ids=["no-dropout", "dropout"])
@pytest.mark.parametrize("case", sorted(WINDOWS))
def test_window_forward_kernel_matches_materialized_attention(case, rate):
    S, window, (bq, bk) = WINDOWS[case]
    rule = fa.SlidingWindow(window)
    q, k, v, _, seed, bhv = window_operands(S)
    out, lse = fa._flash_forward(q, k, v, rule, True, bq, bk, rate, seed, bhv)
    ref_out, ref_lse = fa._jnp_reference_forward(q, k, v, rule, rate, seed, bhv)
    np.testing.assert_allclose(out, ref_out, atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(lse, ref_lse, atol=2e-5, rtol=2e-5)
    if rate == 0.0:
        want, want_lse = materialized(q, k, v, jnp.asarray(window_mask(S, window)))
        np.testing.assert_allclose(out, want, atol=2e-5, rtol=2e-5)
        np.testing.assert_allclose(lse, want_lse, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("path", ["fused", "pair", "jnp"])
@pytest.mark.parametrize("case", sorted(WINDOWS))
def test_window_backward_paths_match_the_gradient_of_materialized_attention(case, path):
    S, window, (bq, bk) = WINDOWS[case]
    rule, mask = fa.SlidingWindow(window), jnp.asarray(window_mask(S, window))
    q, k, v, do, seed, bhv = window_operands(S)
    out, lse = materialized(q, k, v, mask)
    want = jax.grad(lambda q, k, v: jnp.sum(materialized(q, k, v, mask)[0] * do), (0, 1, 2))(q, k, v)
    if path == "jnp":
        got = fa._jnp_blockwise_bwd(rule, bk, 0.0, (q, k, v, out, lse, seed, bhv), do)
    else:
        lse3 = jnp.broadcast_to(lse[:, None, :], (BH, 8, S))
        delta3 = jnp.broadcast_to(jnp.sum(do * out, -1)[:, None, :], (BH, 8, S))
        backward = fa._fused_backward if path == "fused" else fa._pair_backward
        got = backward(q, k, v, do, lse3, delta3, seed, bhv, rule, 0.0, bq, bk, True)
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, atol=3e-5, rtol=3e-5)


@pytest.mark.parametrize("window", [96, 256], ids=["pieces-cut", "a-tile"])
def test_window_bodies_in_pieces_give_the_whole_tile_walks_results(window, monkeypatch):
    """Square tiles of two pieces: the diagonal tile runs the *lower* body,
    forward and backward, with dropout; against the walk with every tile *full*."""
    S, rule = 1024, fa.SlidingWindow(window)
    q, k, v, do, seed, bhv = window_operands(S)

    def both():
        out, lse = fa._flash_forward(q, k, v, rule, True, TILE, TILE, 0.1, seed, bhv, sub_k=PIECE)
        lse3 = jnp.broadcast_to(lse[:, None, :], (BH, 8, S))
        delta3 = jnp.broadcast_to(jnp.sum(do * out, -1)[:, None, :], (BH, 8, S))
        return (out, lse) + tuple(fa._fused_backward(
            q, k, v, do, lse3, delta3, seed, bhv, rule, 0.1, TILE, TILE, True, sub=PIECE))

    assert fa.tiles_by_shape(rule, S, TILE, TILE, PIECE)[fa.LOWER].sum() == S // TILE
    shaped = both()
    shapes_off(monkeypatch)
    for got, want in zip(shaped, both()):
        np.testing.assert_allclose(got, want, atol=1e-6, rtol=1e-6)


def test_flash_attention_under_a_window_differentiates_to_the_reference():
    """The public call: (B, S, H, D) operands, the rule as ``causal``."""
    S, rule = 256, fa.SlidingWindow(80)
    keys = jax.random.split(jax.random.key(9), 3)
    q, k, v = (jax.random.normal(key, (1, S, 2, D), jnp.float32) for key in keys)

    def loss(attention):
        return lambda q, k, v: jnp.sum(jnp.square(attention(q, k, v, causal=rule)))

    flash = functools.partial(fa.flash_attention, block_q=64, block_k=64, block_k_bwd=64)
    np.testing.assert_allclose(flash(q, k, v, causal=rule), fa.reference_attention(q, k, v, causal=rule),
                               atol=2e-5, rtol=2e-5)
    got = jax.grad(loss(flash), (0, 1, 2))(q, k, v)
    want = jax.grad(loss(fa.reference_attention), (0, 1, 2))(q, k, v)
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, atol=1e-4, rtol=1e-4)


def pallas_calls(fn, *args):
    """[(name, grid)] of the Pallas calls ``fn`` traces, in order."""
    def calls(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                yield eqn.params["name"], tuple(eqn.params["grid_mapping"].grid)
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from calls(sub)

    return list(calls(jax.make_jaxpr(fn)(*args).jaxpr))


# (heads, S, Dqk, Dv, rule, dropout) of the accepted cells' flash calls on the
# Mosaic path, and the window layer's: what each differentiated call hands the chip.
CELL_CALLS = {
    "tinygpt-a.seq8192": (16, 8192, 64, 64, False, 0.1, (8, 8)),
    "mistral-7b.d2": (32, 4096, 128, 128, True, 0.0, (4, 4)),
    "deepseek-v2-lite.share8-seq8192": (16, 8192, 192, 128, True, 0.0, (8, 8)),
    "sdar-30b-a3b.share8-bd8192": (32, 16384, 128, 128, fa.BlockDiffusion(8192, 4), 0.0, ((16, 9), (16, 16))),
    "mellum2-12b-a2.5b.global": (32, 16384, 128, 128, True, 0.0, (16, 16)),
    "mellum2-12b-a2.5b.window": (32, 16384, 128, 128, fa.SlidingWindow(1024), 0.0, (16, 2)),
}


@pytest.mark.parametrize("cell", sorted(CELL_CALLS))
def test_a_cells_call_is_two_kernels_on_the_grid_it_had(cell):
    """Causal and no mask keep the square's grid, (heads, S / 1024, S / 1024),
    forward and backward; a window's grid is its band, and block diffusion's
    forward walks the 9 key tiles a query tile meets at most where its
    backward keeps the square. One forward and one fused backward call a
    differentiated call."""
    heads, S, d_qk, d_v, rule, rate, grid = CELL_CALLS[cell]
    fwd_grid, bwd_grid = grid if isinstance(grid[0], tuple) else (grid, grid)
    q = jax.ShapeDtypeStruct((1, S, heads, d_qk), jnp.bfloat16)
    v = jax.ShapeDtypeStruct((1, S, heads, d_v), jnp.bfloat16)

    def loss(q, k, v):
        out = fa.flash_attention.__wrapped__(
            q, k, v, causal=rule, interpret=False, dropout_rate=rate,
            dropout_seed=jnp.uint32(1) if rate else None)
        return jnp.sum(out.astype(jnp.float32))

    calls = pallas_calls(jax.grad(loss, argnums=(0, 1, 2)), q, q, v)
    assert calls == [("flash_fwd", (heads,) + fwd_grid), ("flash_bwd_fused", (heads,) + bwd_grid)]


# Grouped-query attention: k and v enter ``flash_attention`` at their own head
# count and the kernels' index maps find a query head's kv head. Tiles of 64
# over 256 positions, 8 query heads.
GQA_S, GQA_H, GQA_TILE = 256, 8, 64
GQA_RULES = {"causal": True, "window": fa.SlidingWindow(80), "block-diffusion": fa.BlockDiffusion(GQA_S // 2, 4)}
GQA_CASES = [(rule, rep, (D, D)) for rule in sorted(GQA_RULES) for rep in (1, 4, 8)] + [("causal", 4, (24, D))]


def gqa_operands(rep, widths=(D, D), batch=2, dtype=jnp.float32):
    keys = jax.random.split(jax.random.key(11), 3)
    q = jax.random.normal(keys[0], (batch, GQA_S, GQA_H, widths[0]), dtype)
    k = jax.random.normal(keys[1], (batch, GQA_S, GQA_H // rep, widths[0]), dtype)
    v = jax.random.normal(keys[2], (batch, GQA_S, GQA_H // rep, widths[1]), dtype)
    return q, k, v


def on_repeated_heads(attention, rep):
    """``attention`` on k and v repeated a query head: what the model ran in
    front of the kernels, and what differentiating through it sums back."""
    return lambda q, k, v, **kw: attention(q, jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2), **kw)


@pytest.mark.parametrize("path", ["fused", "pair", "einsum"])
@pytest.mark.parametrize("rule,rep,widths", GQA_CASES,
                         ids=[f"{rule}-rep{rep}-{w[0]}over{w[1]}" for rule, rep, w in GQA_CASES])
def test_kv_heads_enter_as_they_are_and_match_the_reference_on_repeated_heads(rule, rep, widths, path, monkeypatch):
    """Forward, dq, and dk / dv against the group sums of the reference's, at
    every backward there is: the fused kernel (k and v read at row b // rep),
    the kernel pair and the einsum scan (both on heads repeated inside)."""
    mask = GQA_RULES[rule]
    q, k, v = gqa_operands(rep, widths)
    if path == "pair":
        monkeypatch.setattr(fa, "_fused_fits", lambda *a: False)
    flash = functools.partial(
        fa.flash_attention, causal=mask, block_q=GQA_TILE, block_k=GQA_TILE, block_k_bwd=GQA_TILE,
        pallas_backward=path != "einsum")
    reference = on_repeated_heads(functools.partial(fa.reference_attention, causal=mask), rep)
    out = flash(q, k, v)
    assert out.shape == (*q.shape[:3], widths[1])
    np.testing.assert_allclose(out, reference(q, k, v), atol=2e-5, rtol=2e-5)

    def loss(attention):
        return lambda q, k, v: jnp.sum(jnp.square(attention(q, k, v)))

    got = jax.grad(loss(flash), (0, 1, 2))(q, k, v)
    want = jax.grad(loss(reference), (0, 1, 2))(q, k, v)
    assert [g.shape for g in got] == [q.shape, k.shape, v.shape]
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, atol=2e-4, rtol=2e-4)


@pytest.mark.parametrize("rep", [4, 8])
def test_dropout_draws_the_bits_of_the_call_on_repeated_heads(rep):
    """The hash is keyed by the (batch, query head) id: a call on 8 / rep kv
    heads keeps the pairs the call on 8 repeated heads keeps. Each query row
    runs the same products on the same operands, so out and dq are the same
    bits; dk and dv are the group sums."""
    q, k, v = gqa_operands(rep)
    flash = functools.partial(
        fa.flash_attention, causal=True, block_q=GQA_TILE, block_k=GQA_TILE, block_k_bwd=GQA_TILE,
        pallas_backward=True, dropout_rate=0.5, dropout_seed=jnp.uint32(5))
    repeated = on_repeated_heads(flash, rep)
    np.testing.assert_array_equal(flash(q, k, v), repeated(q, k, v))
    assert not np.allclose(flash(q, k, v), flash(q, k, v, dropout_seed=jnp.uint32(6)), atol=1e-3)

    def loss(attention):
        return lambda q, k, v: jnp.sum(jnp.square(attention(q, k, v)))

    got = jax.grad(loss(flash), (0, 1, 2))(q, k, v)
    want = jax.grad(loss(repeated), (0, 1, 2))(q, k, v)
    np.testing.assert_array_equal(got[0], want[0])
    for g, w in zip(got[1:], want[1:]):
        np.testing.assert_allclose(g, w, atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("rule", sorted(GQA_RULES))
def test_a_grouped_call_is_the_two_kernels_with_k_and_v_at_their_own_rows(rule):
    """32 query heads over 4: the forward's grid is the whole-head call's,
    the backward's walks (kv rows, a group's heads, k tiles, q tiles), k and v
    enter the kernels as (4, S, D) and dk / dv leave at 4 heads."""
    mask = GQA_RULES[rule]
    q = jax.ShapeDtypeStruct((1, GQA_S, 32, D), jnp.float32)
    kv = jax.ShapeDtypeStruct((1, GQA_S, 4, D), jnp.float32)

    def loss(q, k, v):
        return jnp.sum(fa.flash_attention.__wrapped__(
            q, k, v, causal=mask, interpret=True, block_q=GQA_TILE, block_k=GQA_TILE,
            block_k_bwd=GQA_TILE, pallas_backward=True))

    (fwd, whole_fwd), (bwd, whole_bwd) = pallas_calls(jax.grad(loss, argnums=(0, 1, 2)), q, q, q)
    assert (fwd, bwd) == ("flash_fwd", "flash_bwd_fused") and whole_fwd[0] == whole_bwd[0] == 32
    assert pallas_calls(jax.grad(loss, argnums=(0, 1, 2)), q, kv, kv) == [
        (fwd, whole_fwd), (bwd, (4, 8) + whole_bwd[1:])]

    def kernel_operands(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                yield eqn.params["name"], [tuple(x.aval.shape) for x in eqn.invars]
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from kernel_operands(sub)

    grads = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(q, kv, kv)
    assert [tuple(x.aval.shape) for x in grads.jaxpr.outvars] == [q.shape, kv.shape, kv.shape]
    for name, shapes in kernel_operands(grads.jaxpr):
        # (seed, ids, q, k, v, ...): k and v a kv head a row
        assert shapes[2:5] == [(32, GQA_S, D), (4, GQA_S, D), (4, GQA_S, D)], name


@pytest.mark.parametrize("rule", sorted(GQA_RULES))
def test_the_two_forms_of_the_grouped_backward_agree(rule):
    """dk and dv summed over a group in the kernel's whole-row accumulators
    (the form taken where the rows fit VMEM) against a query head out of the
    kernel and summed behind it (the form for longer sequences), with pieces
    cut and a *lower* body on the diagonal; dq is the same bits."""
    mask, rep = GQA_RULES[rule], 4
    q, k, v = (t.transpose(0, 2, 1, 3).reshape(-1, GQA_S, D) for t in gqa_operands(rep, batch=1))
    seed, bhv = jnp.asarray([7], jnp.uint32), jnp.arange(GQA_H, dtype=jnp.int32)
    do = jax.random.normal(jax.random.key(12), q.shape, jnp.float32)
    out, lse = fa._flash_forward(q, k, v, mask, True, GQA_TILE, GQA_TILE, 0.1, seed, bhv)
    stats = [jnp.broadcast_to(x[:, None, :], (GQA_H, 8, GQA_S)) for x in (lse, jnp.sum(do * out, -1))]
    args = (q, k, v, do, *stats, seed, bhv, mask, 0.1, GQA_TILE, GQA_TILE, True)
    grouped = fa._fused_backward(*args, sub=32, grouped=True)
    behind = fa._fused_backward(*args, sub=32, grouped=False)
    assert [g.shape for g in grouped] == [q.shape, k.shape, v.shape]
    np.testing.assert_array_equal(grouped[0], behind[0])
    for g, w in zip(grouped[1:], behind[1:]):
        np.testing.assert_allclose(g, w, atol=1e-5, rtol=1e-5)


def test_the_grouped_form_is_taken_where_three_rows_fit_vmem():
    """The cells' S 16,384 at head width 128 fits (80 MiB of the 96 allowed);
    twice that keeps the fused kernel (one row) and sums behind it."""
    assert fa._fused_vmem_bytes(16384, 128, jnp.bfloat16, 128) == 80 * 2**20
    assert fa._grouped_fits(16384, 128, 128, jnp.bfloat16)
    assert fa._fused_fits(32768, 128, jnp.bfloat16) and not fa._grouped_fits(32768, 128, 128, jnp.bfloat16)


def test_head_counts_that_do_not_divide_are_refused():
    q, k, v = gqa_operands(4)
    with pytest.raises(ValueError, match="head count that divides"):
        fa.flash_attention(q, k[:, :, :1], v)  # one head of k under two of v
    with pytest.raises(ValueError, match="head count that divides"):
        fa.flash_attention(q[:, :, :3], k, v)  # 3 query heads over 2


# ---------------------------------------------------------------------------
# Which tile a grid step addresses (``fa._addressed_tile``): a dead step's
# blocks are a live tile's of the same row, and the pipeline copies nothing
# for it. The index maps the two ``pallas_call``s were built with, evaluated
# on the host over the whole grid.
# ---------------------------------------------------------------------------

# (rule, S, (bq, bk)): the cells' shapes (causal at S 4096 and 16,384; the
# SDAR cell's stream; the Mellum and the Laguna window at their tiles) and
# small ones whose tiles are not square or narrower than a block.
WALKS = {
    "causal-4096": (True, 4096, (1024, 1024)),
    "causal-16384": (True, 16384, (1024, 1024)),
    "causal-bq>bk": (True, 512, (128, 32)),
    "causal-bq<bk": (True, 512, (64, 128)),
    "block-diffusion-8192": (fa.BlockDiffusion(8192, 4), 16384, (1024, 1024)),
    "block-diffusion-a-block-four-tiles": (fa.BlockDiffusion(64, 32), 128, (16, 8)),
    "block-diffusion-blocks-across-tiles": (fa.BlockDiffusion(96, 12), 192, (16, 32)),
    "block-diffusion-one-tile-a-copy": (fa.BlockDiffusion(64, 4), 128, (64, 64)),
    "block-diffusion-a-block-a-tile": (fa.BlockDiffusion(64, 16), 128, (16, 16)),
    "window-mellum": (fa.SlidingWindow(1024), 16384, (1024, 1024)),
    "window-laguna": (fa.SlidingWindow(512), 16384, (512, 512)),
    "window-bq>bk": (fa.SlidingWindow(100), 512, (64, 32)),
    "window-bq<bk": (fa.SlidingWindow(33), 512, (32, 128)),
    "no-mask": (False, 512, (128, 64)),
}
WALK_REP = 4  # query heads a kv head


def built_with(monkeypatch, build):
    """What ``build`` handed ``pl.pallas_call``."""
    seen = {}

    def call(kernel, **kwargs):
        seen.update(kwargs)
        return lambda *operands: None

    monkeypatch.setattr(fa.pl, "pallas_call", call)
    build()
    return seen


@pytest.mark.parametrize("kernel", ["forward", "backward", "backward-grouped"])
@pytest.mark.parametrize("walk", sorted(WALKS))
def test_a_dead_step_addresses_a_live_tile_of_its_row(walk, kernel, monkeypatch):
    """At every live step the walked operands' index maps return the step's
    own tile; at every dead step a live tile of the same row, the next the
    walk reaches where there is one (the copy is then under way when the walk
    arrives), else the row's last; k and v keep the row's own tile and their
    kv row. The block index then changes ``tile_fetches`` times a head: once
    a live tile, or less where a row starts on the tile the row before ended
    on."""
    mask, S, (bq, bk) = WALKS[walk]
    forward, grouped = kernel == "forward", kernel == "backward-grouped"
    BH, rep, f32 = 2 * WALK_REP, 1 if kernel == "backward" else WALK_REP, jnp.float32
    if forward:
        built = built_with(monkeypatch, lambda: fa._forward_call(
            BH, S, 128, 128, f32, frozenset(), mask, True, bq, bk, fa._fwd_sub_k(bk), 1.0, 0.0, rep))
        walked, kept = (3, 4), (2,)  # k, v; q
    else:
        built = built_with(monkeypatch, lambda: fa._fused_call(
            BH, S, 128, 128, (f32,) * 3, frozenset(), mask, True, bq, bk, fa._bwd_sub_q(bq, 0.0),
            1.0, 0.0, rep, grouped))
        walked, kept = (2, 5, 6, 7), (3, 4)  # q, dO, lse, delta; k, v
    grid, specs = built["grid"], built["in_specs"]
    outer_n, steps = grid[-2:]
    assert (outer_n, steps) == (S // (bq if forward else bk),
                                fa.grid_steps(mask, S, bq, bk, forward) // (S // (bq if forward else bk)))
    heads = np.arange(BH)
    head_ids = (heads // rep, heads % rep) if grouped else (heads,)
    ids = np.meshgrid(heads, np.arange(outer_n), np.arange(steps), indexing="ij")
    args = (*(h[ids[0]] for h in head_ids), ids[1], ids[2])
    outer, step = ids[1], ids[2]
    # the step's own tile, and whether the rule leaves a pair in it
    if fa._band_steps(mask, S, bq, bk, forward):
        own = mask.key_tile(outer, bq, bk, step, steps) if forward else mask.query_tile(outer, bq, bk, step)
    else:
        own = step
    inner_n = S // (bk if forward else bq)

    def live_at(tile):
        inside = (tile >= 0) & (tile < inner_n)
        qi, ki = (outer, tile) if forward else (tile, outer)
        return inside & np.broadcast_to(fa._tile_rule(mask, qi * bq, bq, ki * bk, bk)[0], inside.shape)

    live = live_at(own)
    tiles_live = np.broadcast_to(fa._tile_rule(
        mask, np.arange(S // bq)[:, None] * bq, bq, np.arange(S // bk)[None, :] * bk, bk)[0],
        (S // bq, S // bk))
    assert int(live[0].sum()) == int(tiles_live.sum())  # the walk reaches every live tile once
    for at in walked:
        index = specs[at].index_map(*args)
        row, tile = index[0], index[2] if at in (6, 7) and not forward else index[1]
        np.testing.assert_array_equal(np.broadcast_to(row, live.shape), ids[0] // rep if forward else ids[0])
        tile = np.broadcast_to(tile, live.shape)
        np.testing.assert_array_equal(tile[live], own[live])
        assert live_at(tile).all()
        # held ahead: the first live tile at or after the step's own, else the row's last
        row_live = tiles_live if forward else tiles_live.T
        for o in range(outer_n):
            live_tiles = np.flatnonzero(row_live[o])
            ahead = np.searchsorted(live_tiles, own[0, o])
            want = live_tiles[np.minimum(ahead, len(live_tiles) - 1)]
            np.testing.assert_array_equal(tile[0, o], want)
        head_walk = tile[0].ravel()
        fetches = 1 + int(np.count_nonzero(head_walk[1:] != head_walk[:-1]))
        assert fetches == fa.tile_fetches(mask, S, bq, bk, forward)
        if mask:
            assert tiles_live.sum() - outer_n < fetches <= tiles_live.sum()
        else:
            assert fetches == outer_n * steps
    for at in kept:  # the outer axis' operands: the row's own tile, and k and v at their kv row
        index = specs[at].index_map(*args)
        np.testing.assert_array_equal(np.broadcast_to(index[1], live.shape), outer)
        want_row = ids[0] if forward else ids[0] // rep
        np.testing.assert_array_equal(np.broadcast_to(index[0], live.shape), want_row)


def test_the_cell_shape_fetches_its_80_live_tiles_and_no_dead_one():
    """The SDAR cell's stream at (1024, 1024) tiles, 80 live tiles a head. The
    forward walks 9 steps a query tile, the most a query tile meets (a noisy
    tile its own and the clean past, a clean one the clean tiles up to its
    own), 144 where the square has 256, and its K walk changes tile 79 times
    (row 8 starts on the tile row 7 ended on); the backward keeps the square
    (a clean key tile meets up to 15 of 16 query tiles) and its q walk
    changes tile 80 times. Causal over the same 16,384 positions: 135 for
    136."""
    rule = fa.BlockDiffusion(8192, 4)
    assert (rule.band_steps(16384, 1024, 1024, True), rule.band_steps(16384, 1024, 1024, False)) == (9, None)
    assert (fa.grid_steps(rule, 16384, 1024, 1024, True), fa.grid_steps(rule, 16384, 1024, 1024, False)) == (144, 256)
    assert (fa.tile_fetches(rule, 16384, 1024, 1024, True), fa.tile_fetches(rule, 16384, 1024, 1024, False)) == (79, 80)
    assert (fa.tile_fetches(True, 16384, 1024, 1024, True), fa.tile_fetches(True, 16384, 1024, 1024, False)) == (135, 135)
    assert fa.tile_fetches(False, 16384, 1024, 1024, True) == 256
    # a query row's live tiles packed against its end: -1 in front of them
    steps = np.arange(9)
    assert list(rule.key_tile(np.int64(3), 1024, 1024, steps, 9)) == [-1, -1, -1, -1, 3, 8, 9, 10, 11]
    assert list(rule.key_tile(np.int64(7), 1024, 1024, steps, 9)) == [7, 8, 9, 10, 11, 12, 13, 14, 15]
    assert list(rule.key_tile(np.int64(11), 1024, 1024, steps, 9)) == [-1] * 5 + [8, 9, 10, 11]
    # and the steps in front address the row's first live tile
    tile = fa._addressed_tile(rule, 16384, 1024, 1024, True)
    assert list(tile(np.int64(3), steps)) == [3, 3, 3, 3, 3, 8, 9, 10, 11]
    assert list(tile(np.int64(11), steps)) == [8] * 6 + [9, 10, 11]
    # a clean key row: the first noisy query tile that sees it, then its own
    tile = fa._addressed_tile(rule, 16384, 1024, 1024, False)
    assert list(tile(np.int64(10), np.arange(16))) == [2, 2, 2, 3, 4, 5, 6, 7, 10, 10, 10, 11, 12, 13, 14, 15]
    assert list(tile(np.int64(2), np.arange(16))) == [2] * 16


@pytest.mark.parametrize("rep", [1, 4], ids=["a-kv-head-a-head", "grouped"])
def test_flash_attention_under_block_diffusion_differentiates_to_the_reference(rep):
    """Through the public entry with the fused backward, interpreted: rows of
    the walk whose dead steps lie in the middle (a noisy query tile between
    its own tile and the clean past, a clean key tile between the noisy
    queries that see it and its own), held, change no output and no
    gradient."""
    B_, H, KV = 1, 4, 4 // rep
    keys = jax.random.split(jax.random.key(11), 4)
    q, do = (jax.random.normal(key, (B_, 2 * L, H, D), jnp.float32) for key in keys[:2])
    k, v = (jax.random.normal(key, (B_, 2 * L, KV, D), jnp.float32) for key in keys[2:])
    assert fa.tile_fetches(RULE, 2 * L, PIECE, PIECE, True) < fa.grid_steps(RULE, 2 * L, PIECE, PIECE, True)

    def ours(q, k, v):
        return fa.flash_attention(q, k, v, causal=RULE, interpret=True, pallas_backward=True,
                                  block_q=PIECE, block_k=PIECE, block_k_bwd=PIECE)

    def reference(q, k, v):
        return fa.reference_attention(q, jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2), RULE)

    np.testing.assert_allclose(ours(q, k, v), reference(q, k, v), atol=2e-5, rtol=2e-5)
    got = jax.grad(lambda *a: jnp.sum(ours(*a) * do), (0, 1, 2))(q, k, v)
    want = jax.grad(lambda *a: jnp.sum(reference(*a) * do), (0, 1, 2))(q, k, v)
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, atol=5e-5, rtol=5e-5)
