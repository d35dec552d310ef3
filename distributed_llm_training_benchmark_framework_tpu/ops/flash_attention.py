"""Flash attention — Pallas TPU forward kernel + blockwise custom VJP.

The reference benchmarks vanilla O(S^2)-materialized attention
(``nn.MultiheadAttention``, reference ``benchmarking/train_harness.py:114-116``)
and defers "Flash Attention for 16K+ sequences" to future work
(reference ``README.md:1026-1034``). This module supplies it TPU-natively.

Forward (Pallas kernel):
- never materializes the (S, S) score matrix in HBM — K/V stream through VMEM
  in blocks while running-max/running-sum (online softmax) statistics fold
  each block into the output accumulator;
- fp32 statistics and accumulation, bf16 matmul inputs on the MXU;
- grid (batch*heads, q_blocks, k_blocks) with the k axis innermost and
  sequential, so the VMEM scratch accumulator persists across k blocks
  (TPU grids execute the trailing axis as the inner sequential loop);
- the block a grid step brings in (the DMA tile) is not the block it computes
  on: the body walks its keys in pieces of 128, held k-major, each piece's
  QK^T issued under the piece before's softmax (``_flash_fwd_kernel``);
- also emits the per-row logsumexp, the residual the backward pass needs;
- the mask is a *rule* (``MaskRule``): none, causal, block diffusion over a
  stream of a noisy and a clean copy, or a causal sliding window; inside a
  live tile it is computed from global positions; a grid step whose tile the
  rule leaves no pair in multiplies nothing and brings nothing (its blocks
  address a live tile of the same row: ``_addressed_tile``), and under a
  window the band of live tiles is the grid;
- a live tile has a *shape* (``_tile_shape``): full, or, on the diagonal of
  square tiles, lower; the forward and the fused backward run a body a shape,
  and the lower one leaves out the pieces above the piece diagonal.

Backward (custom VJP): recomputes attention probabilities tile by tile from
the saved logsumexp — the standard flash backward — with two implementations
sharing the same math: an XLA-fused ``lax.scan`` of dense jnp blocks over K
(peak memory O(S * block)), and one hand-written Pallas kernel that makes
dq, dk and dv from a single visit of each score tile, its queries walked in
pieces of 128 or 256 (``_bwd_fused_kernel``).
Which is faster is S-dependent on v5e (einsum to S=2048, the kernel from
S=4096 — docs/PERFORMANCE.md §12); ``pallas_backward=None`` auto-selects by
that crossover. The older dq and dk+dv kernel pair visits every tile twice;
ring attention's per-hop backward still runs it (dq stays local there while
dk / dv travel), and plain flash falls back to it only where the fused
kernel's resident dq row would not fit VMEM (``_fused_fits``).

Dispatch (``_resolve_interpret``): on a TPU backend the Mosaic kernels are the
only path — interpret mode is refused there, and the ``jnp`` fallbacks below
are reachable only in interpret mode. On other backends the kernels run in
Pallas interpret mode (slow but bit-honest), keeping the CPU test paths real.

Partitioning: a Mosaic kernel is a custom call GSPMD cannot split, so under a
mesh of more than one device ``flash_attention`` shard_maps itself, batch and
heads split over the axes that shard them (see ``_kernel_mesh_axes``). The dropout hash is keyed by GLOBAL
(batch, head) ids fed in as sharded data, so masks do not depend on the mesh.

Grouped-query attention: k and v come at the model's kv head count, H // KV
consecutive query heads to a kv head, and every BlockSpec that addresses k or
v sends the grid's (batch, query head) row b to row b // rep (``_kv_row``):
no repeated copy of k or v exists in front of the kernels or behind them.
"""

from __future__ import annotations

import dataclasses
import functools
import math
import operator
from typing import Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

NEG_INF = -1e30


@dataclasses.dataclass(frozen=True)
class BlockDiffusion:
    """The mask of block-diffusion training (BD3-LM, SDAR) over the stream
    ``[x_t ; x]``: positions 0..L-1 the noisy copy of a document of
    ``seq_len`` = L tokens, L..2L-1 the clean one, both cut into blocks of
    ``block`` tokens. Query i may see key j (blk = position in its copy //
    block):

      noisy -> noisy   iff blk(i) == blk(j)    (its own block, both ways)
      noisy -> clean   iff blk(j) <  blk(i)    (the clean past)
      clean -> clean   iff blk(j) <= blk(i)    (block-causal)
      clean -> noisy   never

    Every query has a live key, itself, which is what keeps a row's
    logsumexp finite (the fused backward leans on it); ``__post_init__``
    holds the two sizes to what makes that so. A query's *first* live key may
    lie anywhere: a noisy query's own block sits in the middle of a tile
    (``first_piece_live`` is False, and the forward kernel floors its running
    maximum). True pairs a head: L^2 + L * block.
    """

    seq_len: int
    block: int

    def __post_init__(self):
        if not (0 < self.block <= self.seq_len and self.seq_len % self.block == 0):
            raise ValueError(
                f"block diffusion cuts a document of seq_len={self.seq_len} into "
                f"whole blocks of block={self.block} tokens"
            )

    def _local(self, stream_pos):
        """A stream position inside its own copy."""
        L = self.seq_len
        if not isinstance(stream_pos, jax.Array):  # the host's counts (numpy)
            return stream_pos - L * (stream_pos >= L)
        return lax.select(lax.ge(stream_pos, L), lax.sub(stream_pos, L), stream_pos)

    def _blk(self, stream_pos):
        """Block number of a stream position inside its own copy."""
        B = self.block
        local = self._local(stream_pos)
        if not isinstance(stream_pos, jax.Array):
            return local // B
        if B & (B - 1) == 0:
            return lax.shift_right_logical(local, jnp.int32(B.bit_length() - 1))
        return lax.div(local, jnp.int32(B))

    def allowed(self, rows, cols):
        """The rule itself on broadcastable int32 stream positions, any
        mixture of the two copies: the ``jnp`` references' mask."""
        L = self.seq_len
        r_blk, c_blk = self._blk(rows), self._blk(cols)
        return jnp.where(
            cols < L, (rows < L) & (r_blk == c_blk),
            jnp.where(rows < L, c_blk < r_blk, c_blk <= r_blk),
        )

    def tile_live(self, q_off, bq, k_off, bk):
        """Whether the (bq, bk) tile at (q_off, k_off) holds an allowed pair.
        Tiles lie inside one copy each (``check_tiles``). Scalars of a kernel's
        grid, or numpy arrays of offsets (``tile_counts``)."""
        L = self.seq_len
        q_noisy, k_noisy = q_off < L, k_off < L
        q_lo, q_hi = self._blk(q_off), self._blk(q_off + (bq - 1))
        k_lo, k_hi = self._blk(k_off), self._blk(k_off + (bk - 1))
        same_block = (q_lo <= k_hi) & (k_lo <= q_hi)
        return ((q_noisy & k_noisy & same_block) | (q_noisy & ~k_noisy & (k_lo < q_hi))
                | (~q_noisy & ~k_noisy & (k_lo <= q_hi)))

    def live_spans(self, S: int, bq: int, bk: int, keys_inner: bool, outer):
        """Row ``outer`` of a kernel's walk (``_live_spans``) -> its live
        tiles, two spans: a noisy query tile meets the noisy key tiles of its
        own blocks and the clean past (empty for block 0), a clean one the
        clean tiles up to its own (the second span empty); a noisy key tile
        meets the noisy query tiles of its own blocks, a clean one the noisy
        queries of later blocks (empty for the last) and the clean ones from
        its own on. In tiles of the inner axis; positions inside a copy."""
        L, B = self.seq_len, self.block
        own, other = (bq, bk) if keys_inner else (bk, bq)
        half, half_other = L // own, L // other  # tiles a copy
        clean = _le(half, outer)
        if own == other and own % B == 0:
            # square tiles of whole blocks (what the Mosaic path takes), in
            # tile numbers: four operations a map where positions take twenty
            whole = int(own == B)  # a block a tile: its clean past ends a tile earlier
            if keys_inner:
                return ((_where(clean, half, outer), outer),
                        (half, _where(clean, half - 1, _add(outer, half - whole))))
            return ((_where(clean, _sub(outer, half - whole), outer), _where(clean, half - 1, outer)),
                    (_where(clean, outer, 1), _where(clean, 2 * half - 1, 0)))
        off = _mul(_sub(outer, _where(clean, half, 0)), own)
        # the tile's blocks as positions [lo, hi]: the tile's own where it
        # holds whole blocks
        lo = off if own % B == 0 else _mul(_fdiv(off, B), B)
        hi = _add(off, own - 1)
        if own % B:
            hi = _add(_mul(_fdiv(hi, B), B), B - 1)
        if keys_inner:
            past = _fdiv(_add(hi, other - B), other)  # clean key tiles before the last block
            return ((_where(clean, half_other, _fdiv(lo, other)),
                     _add(_fdiv(hi, other), _where(clean, half_other, 0))),
                    (half_other, _where(clean, half_other - 1, _add(past, half_other - 1))))
        own_first = _fdiv(lo, other)
        return ((_where(clean, _fdiv(_add(lo, B), other), own_first),
                 _where(clean, half_other - 1, _fdiv(hi, other))),
                (_where(clean, _add(own_first, half_other), 1),
                 _where(clean, 2 * half_other - 1, 0)))

    # The forward's grid under this rule. A query tile meets few of the
    # square's key tiles (its own and the clean past, or the clean tiles up to
    # its own: 9 of 16 at most in the SDAR cell's stream), so its steps walk
    # the most any query tile meets and no more, each row's live tiles packed
    # against the row's end (``_packed_tile``), as a window's band is. A key
    # tile's query tiles are nearly all of them for the first clean tiles (15
    # of 16), so the fused backward keeps the square.
    def band_steps(self, S: int, bq: int, bk: int, keys_inner: bool) -> Optional[int]:
        """Inner grid extent of the forward: the most key tiles a query tile
        meets. None for the fused backward: the square's."""
        if not keys_inner:
            return None
        spans = self.live_spans(S, bq, bk, True, np.arange(S // bq, dtype=np.int64))
        return int(sum(np.maximum(hi - lo + 1, 0) for lo, hi in spans).max())

    def key_tile(self, qi, bq: int, bk: int, step, steps: int):
        """The key tile that step ``step`` of ``steps`` brings to query tile
        ``qi``, ascending, the last live one at the last step; -1 before the
        first."""
        return _packed_tile(self.live_spans(2 * self.seq_len, bq, bk, True, qi), step, steps)

    def in_tile(self, q_off, k_off, rows, cols):
        """The rule inside one live tile, whose queries lie in one copy and
        whose keys lie in one copy: one subtract and one unsigned compare a
        score on the narrow operands' block numbers, d = blk(row) - blk(col)
        allowed iff 0 <= d - shift <= width, with (shift, width) = (0, 0)
        noisy -> noisy, (1, all) noisy -> clean, (0, all) clean -> clean. A
        clean -> noisy tile is never live."""
        L = self.seq_len
        q_noisy, k_noisy = q_off < L, k_off < L
        shift = (q_noisy & ~k_noisy).astype(jnp.int32)
        width = jnp.where(k_noisy, jnp.uint32(0), jnp.uint32(0x7FFFFFFF))
        d = lax.sub(lax.sub(self._blk(rows), shift), self._blk(cols))
        return lax.le(lax.bitcast_convert_type(d, jnp.uint32), width)

    def tile_is_lower(self, q_off, k_off):
        """Whether the live square tile at (q_off, k_off), cut into square
        pieces that hold whole blocks (``_tile_shape``), is *lower*: a tile on
        the diagonal of its two copies. No piece above its piece diagonal
        holds a pair (onto the clean copy a query sees earlier blocks, or
        earlier and its own; from noisy to noisy its own block alone, so there
        the pieces below the diagonal are empty too, and walked all the same:
        a body for them did not pay its set-up, ``PERF.md`` section 6, PR
        37); the pieces on the diagonal keep ``in_tile``. Scalars of a
        kernel's grid, or numpy arrays of offsets."""
        return self._local(q_off) == self._local(k_off)

    def check_tiles(self, S: int, *tiles: int) -> None:
        if S != 2 * self.seq_len or any(self.seq_len % t for t in tiles):
            raise ValueError(
                f"block diffusion over seq_len={self.seq_len} runs on a stream of "
                f"{2 * self.seq_len} positions in tiles that divide {self.seq_len}; "
                f"got S={S}, tiles {tiles}"
            )

    def tile_counts(self, bq: int, bk: int) -> Tuple[int, int, int]:
        """(live tiles, all tiles, true pairs) of one head's (2L, 2L) scores
        at (bq, bk) tiles: what a kernel visits, and what the rule needs."""
        S, L, B = 2 * self.seq_len, self.seq_len, self.block
        self.check_tiles(S, bq, bk)
        q_off = np.arange(0, S, bq, dtype=np.int64)[:, None]
        k_off = np.arange(0, S, bk, dtype=np.int64)[None, :]
        live = int(np.sum(self.tile_live(q_off, bq, k_off, bk)))
        return live, (S // bq) * (S // bk), L * L + L * B


@dataclasses.dataclass(frozen=True)
class SlidingWindow:
    """A causal window of ``window`` keys: query i sees key j iff
    i - window < j <= i, its own position and the window - 1 before it (the
    Hugging Face convention). Every query has a live key, itself. A query's
    *first visited* piece may hold none: a tile's keys start before
    i - window for its later queries (``first_piece_live`` is False, and the
    forward kernel floors its running maximum). True pairs a head over S
    positions: W (W + 1) / 2 + (S - W) W with W = min(window, S).

    The live tiles are a band along the diagonal, and under this rule the
    band is the grid of ``flash_fwd`` and ``flash_bwd_fused``: a query tile's
    steps walk its ``band_steps`` key tiles and no other (``key_tile``), a
    key tile's steps its query tiles (``query_tile``), where the other rules'
    grids step through every tile of the square, skip the dead ones' bodies
    and hold a live tile through them."""

    window: int

    def __post_init__(self):
        if self.window < 1:
            raise ValueError(f"a sliding window holds at least the query's own key; got {self.window}")

    def allowed(self, rows, cols):
        """The rule itself on broadcastable int32 positions: the ``jnp``
        references' mask."""
        return (cols <= rows) & (cols > rows - self.window)

    def tile_live(self, q_off, bq, k_off, bk):
        """Whether the (bq, bk) tile at (q_off, k_off) holds an allowed pair:
        its first key is not after its last query, and its last key is inside
        its first query's window. Scalars of a kernel's grid, or numpy arrays
        of offsets (``tile_counts``)."""
        return (k_off <= q_off + (bq - 1)) & (k_off + (bk - 1) > q_off - self.window)

    def in_tile(self, q_off, k_off, rows, cols):
        """The rule inside one live tile: one subtract and one unsigned
        compare a score, 0 <= row - col < window."""
        d = lax.sub(rows, cols)
        return lax.lt(lax.bitcast_convert_type(d, jnp.uint32), jnp.uint32(self.window))

    def true_pairs(self, S: int) -> int:
        W = min(self.window, S)
        return W * (W + 1) // 2 + (S - W) * W

    def tile_counts(self, S: int, bq: int, bk: int) -> Tuple[int, int, int]:
        """(live tiles, all tiles, true pairs) of one head's (S, S) scores at
        (bq, bk) tiles."""
        q_off = np.arange(0, S, bq, dtype=np.int64)[:, None]
        k_off = np.arange(0, S, bk, dtype=np.int64)[None, :]
        live = int(np.sum(self.tile_live(q_off, bq, k_off, bk)))
        return live, (S // bq) * (S // bk), self.true_pairs(S)

    # The band as a grid. Key tiles of bk that meet the keys
    # [q_off - window + 1, q_off + bq - 1] of a query tile, and query tiles of
    # bq that meet the queries [k_off, k_off + bk + window - 2] of a key tile:
    # the most any tile meets is the grid's inner extent, the same for every
    # tile; a tile near an end of the sequence meets fewer (the band's clipped
    # corner), and where bq != bk some tiles in the middle do too: those steps
    # address a live tile of the row's (``_addressed_tile``: no new DMA, the
    # block index repeats) and multiply nothing.
    def band_steps(self, S: int, bq: int, bk: int, keys_inner: bool) -> int:
        """Inner grid extent: key tiles a query tile walks (the forward), or
        query tiles a key tile walks (the fused backward)."""
        if keys_inner:
            off = np.arange(0, S, bq, dtype=np.int64)
            first = np.maximum(off - (self.window - 1), 0) // bk
            last = (off + (bq - 1)) // bk
        else:
            off = np.arange(0, S, bk, dtype=np.int64)
            first = off // bq
            last = np.minimum(off + (bk + self.window - 2), S - 1) // bq
        return int((last - first).max()) + 1

    def key_tile(self, qi, bq: int, bk: int, step, steps: int):
        """The key tile that step ``step`` of ``steps`` brings to query tile
        ``qi``: the band's last tile (the diagonal's) at the last step,
        ascending; negative in the clipped corner."""
        last = qi if bq == bk else (qi * bq + (bq - 1)) // bk
        return last - (steps - 1) + step

    def query_tile(self, ki, bq: int, bk: int, step):
        """The query tile that step ``step`` brings to key tile ``ki``: the
        diagonal's first, ascending; past the last tile in the clipped corner."""
        return (ki if bq == bk else (ki * bk) // bq) + step

    def live_spans(self, S: int, bq: int, bk: int, keys_inner: bool, outer):
        """Row ``outer`` of a kernel's walk (``_live_spans``) -> its live
        tiles, one span: the band's, clipped to the sequence. Square tiles
        (what the Mosaic path takes) in tile numbers, two operations a map."""
        reach = self.window - 1  # positions a query sees before its own
        if keys_inner:
            if bq == bk:
                return ((_max(_sub(outer, -(-reach // bk)), 0), outer),)
            first = _fdiv(_max(_sub(_mul(outer, bq), reach), 0), bk)
            return ((first, _tile_at(outer, bq, bk, last=True)),)
        if bq == bk:
            return ((outer, _min(_add(outer, (bk - 1 + reach) // bq), S // bq - 1)),)
        last = _fdiv(_min(_add(_mul(outer, bk), bk - 1 + reach), S - 1), bq)
        return ((_tile_at(outer, bk, bq), last),)

    def grid_counts(self, S: int, bq: int, bk: int, keys_inner: bool) -> Tuple[int, int]:
        """(live steps, all steps) of one head's band grid."""
        return self.tile_counts(S, bq, bk)[0], grid_steps(self, S, bq, bk, keys_inner)


#: The rule a kernel masks by: ``False`` every pair, ``True`` causal (query i
#: sees keys j <= i), a ``BlockDiffusion`` or a ``SlidingWindow``.
MaskRule = Union[bool, BlockDiffusion, SlidingWindow]
_RULES = (BlockDiffusion, SlidingWindow)  # the rules that are objects


# Which tile a grid step addresses. A grid step whose tile holds no allowed
# pair multiplies nothing (``pl.when(live)``, on the step's own program ids),
# and it brings nothing either: its blocks address a *live* tile of the same
# row of the walk, the next one the walk will reach, so that the block index
# equals a neighbour's and the pipeline issues no copy for it. The rule says
# which tiles of a row are live, in closed form beside ``tile_live``
# (``live_spans``: a span or two of consecutive tiles); the index maps and the
# host's count of fetches read one function of it (``_addressed_tile``).
# Scalars of an index map (traced int32, never negative) or numpy arrays.
def _scalar_op(traced, host):
    """One operation on tile numbers: ``lax``'s where an operand is an index
    map's scalar (a ``jnp`` operator there is a jit of its own to trace, six
    maps a differentiated call: the note above ``_fill_where``), the host's on
    Python numbers and numpy arrays."""
    def op(*operands):
        if any(isinstance(x, jax.Array) for x in operands):
            return traced(*(x if isinstance(x, jax.Array) else np.int32(x) for x in operands))
        return host(*operands)
    return op


_add, _sub, _mul = (_scalar_op(*ops) for ops in (
    (lax.add, operator.add), (lax.sub, operator.sub), (lax.mul, operator.mul)))
_fdiv = _scalar_op(lax.div, operator.floordiv)  # operands >= 0
_max, _min = _scalar_op(lax.max, np.maximum), _scalar_op(lax.min, np.minimum)
_le, _lt = _scalar_op(lax.le, operator.le), _scalar_op(lax.lt, operator.lt)
_both = _scalar_op(lax.bitwise_and, operator.and_)
_where = _scalar_op(lax.select, np.where)  # (which, a, b): a where ``which``


def _tile_at(tile, b: int, other: int, last: bool = False):
    """The tile of ``other`` positions that holds the first (``last``: the
    last) position of tile ``tile`` of ``b`` positions."""
    return tile if b == other else _fdiv(_add(_mul(tile, b), b - 1 if last else 0), other)


def _live_spans(mask: MaskRule, S: int, bq: int, bk: int, keys_inner: bool, outer):
    """The live tiles of row ``outer`` of a kernel's walk over one head's
    (S // bq, S // bk) tiles -> ((first, last), ...), spans of consecutive
    inner tiles in ascending order, one of them at least not empty (first <=
    last; every query has a live key and every key a live query). The forward
    walks a query tile's key tiles (``keys_inner``), the fused backward a key
    tile's query tiles. ``tile_live`` on the same tiles is what the tests hold
    this to."""
    if isinstance(mask, _RULES):
        return mask.live_spans(S, bq, bk, keys_inner, outer)
    if keys_inner:  # causal: the keys up to the diagonal's, the queries from it
        return ((0, _tile_at(outer, bq, bk, last=True)),)
    return ((_tile_at(outer, bk, bq), S // bq - 1),)


def _held_tile(tile, spans):
    """``tile`` where it lies in one of a row's live ``spans``; else the next
    live tile after it, the one the walk reaches next (its copy is then under
    way, or done, when the walk arrives); past the last, the last."""
    if len(spans) == 1:
        (lo, hi), = spans
        return _min(_max(tile, lo), hi)
    held = spans[0][1]
    for lo, hi in spans[1:]:
        held = _where(_le(lo, hi), hi, held)
    for lo, hi in reversed(spans):
        held = _where(_both(_le(lo, hi), _le(tile, hi)), _max(tile, lo), held)
    return held


def _packed_tile(spans, step, steps: int):
    """The tile of step ``step`` of ``steps`` where a row's live ``spans`` are
    walked in order, packed against the row's end: the steps in front of
    them, one a tile the row has fewer than the widest row's ``steps``, are
    -1 and multiply nothing."""
    lengths = [_max(_add(_sub(hi, lo), 1), 0) for lo, hi in spans]
    at = _sub(step, _sub(steps, functools.reduce(_add, lengths)))  # the live tile's number in its row
    tile, first = -1, 0
    for (lo, _), length in zip(spans, lengths):
        last = _add(first, length)
        tile = _where(_both(_le(first, at), _lt(at, last)), _add(lo, _sub(at, first)), tile)
        first = last
    return tile


def _band_steps(mask: MaskRule, S: int, bq: int, bk: int, keys_inner: bool) -> Optional[int]:
    """Inner extent of the grid a rule gives ``flash_fwd`` (``keys_inner``)
    or ``flash_bwd_fused``: a window's band both ways, block diffusion's
    packed rows forward; None where the steps are the square's tiles."""
    return mask.band_steps(S, bq, bk, keys_inner) if isinstance(mask, _RULES) else None


def _addressed_tile(mask: MaskRule, S: int, bq: int, bk: int, keys_inner: bool):
    """(outer tile, inner step) -> the inner tile whose blocks the step is
    handed, for the index maps of ``flash_fwd`` (``keys_inner``: K and V) and
    ``flash_bwd_fused`` (q, dO and the two statistics). The step's own tile
    where that is live, else a live tile of the row (``_held_tile``); without
    a mask every tile is live and this is the step. Where the rule gives the
    grid a band (``_band_steps``) the steps are the band's (``key_tile`` /
    ``query_tile``)."""
    if not mask:
        return lambda outer, step: step
    band = _band_steps(mask, S, bq, bk, keys_inner)

    def addressed(outer, step):
        if band:
            step = (mask.key_tile(outer, bq, bk, step, band) if keys_inner
                    else mask.query_tile(outer, bq, bk, step))
        return _held_tile(step, _live_spans(mask, S, bq, bk, keys_inner, outer))

    return addressed


def grid_steps(mask: MaskRule, S: int, bq: int, bk: int, keys_inner: bool) -> int:
    """Steps one head's grid makes in ``flash_fwd`` (``keys_inner``) or
    ``flash_bwd_fused``: the square's, or the band's where the rule gives one
    (``_band_steps``)."""
    outer, inner = (S // bq, S // bk) if keys_inner else (S // bk, S // bq)
    return outer * (_band_steps(mask, S, bq, bk, keys_inner) or inner)


def tile_fetches(mask: MaskRule, S: int, bq: int, bk: int, keys_inner: bool) -> int:
    """Times one head's walk changes the block index of the operand its inner
    axis walks (K in the forward, q in the fused backward), the walk's first
    step among them: the copies the pipeline issues for it, counted on the
    host from the map the kernels use (``_addressed_tile``). The live tiles,
    or fewer where a row starts on the tile the row before ended on; the grid's
    steps without a mask."""
    outer = S // (bq if keys_inner else bk)
    inner = grid_steps(mask, S, bq, bk, keys_inner) // outer
    walk = np.broadcast_to(
        _addressed_tile(mask, S, bq, bk, keys_inner)(
            np.arange(outer, dtype=np.int64)[:, None], np.arange(inner, dtype=np.int64)[None, :]),
        (outer, inner)).ravel()
    return 1 + int(np.count_nonzero(walk[1:] != walk[:-1]))


def first_piece_live(mask: MaskRule) -> bool:
    """Whether every query has a live key in the first compute piece the
    forward kernel visits for it: no mask and causal do (plain flash's tile i
    starts at row i*b and its keys at 0: key 0 is live for every query); block
    diffusion does not, nor does a sliding window (a tile's first keys lie
    before the window of its later queries). Where it holds, the running maximum is finite before
    any masked score is exponentiated, and exp2(NEG_INF * c - m) is exactly 0
    with no second select on p; where it does not, the kernel floors the
    maximum it subtracts (``_flash_fwd_kernel``)."""
    return not isinstance(mask, _RULES)


#: The shapes a live tile can have, by which of its pieces may hold a pair.
FULL, LOWER = "full", "lower"


def _tile_shape(mask: MaskRule, qi, bq: int, ki, bk: int, piece: int):
    """The third thing a rule says of (bq, bk) tile (qi, ki) of the grid,
    after whether it is live and which pairs inside it are allowed: which
    shape it has when cut into (piece, piece) pieces -> whether it is *lower*,
    a scalar of a kernel's grid (a numpy array for ``visited_units``), or
    False where the rule has no such tile. *lower*: no piece above the piece
    diagonal holds a pair (the causal diagonal tile; block diffusion's
    diagonal tiles). Else *full*: every piece may. A kernel runs a body a
    shape, and the *lower* one walks the pieces on and below the diagonal
    alone (``_piece_span``).

    Decided by what the call can observe and no knob: square tiles with tile
    i at row i*b, cut into two or more whole pieces, each of whole blocks.
    Anything else (no mask, bq != bk, the CPU tests' small tiles) is *full*
    everywhere, the body there was before."""
    if not mask or bq != bk or piece >= bq or bq % piece:
        return False
    if isinstance(mask, BlockDiffusion):
        return False if piece % mask.block else mask.tile_is_lower(qi * bq, ki * bk)
    # causal, and a sliding window (its diagonal tile is causal's; the
    # trailing-edge tile, whose pairs lie above its diagonal where the window
    # is a multiple of the tile, runs the *full* body)
    return qi == ki


def _piece_span(shape: str, i: int, n: int, keys_walked: bool) -> Tuple[int, int]:
    """[lo, hi), in pieces, of what piece ``i`` of a tile of ``n`` x ``n``
    pieces meets on the other axis: the query pieces that key piece i meets
    where the keys are walked (the forward), the key pieces that query piece
    i meets otherwise (the fused backward)."""
    if shape == LOWER:
        return (i, n) if keys_walked else (0, i + 1)
    return 0, n


def tiles_by_shape(mask: MaskRule, S: int, bq: int, bk: int, piece: int):
    """{shape: which of one head's (S // bq, S // bk) tiles are live and have
    it} as numpy bools, at (bq, bk) tiles walked in pieces of ``piece``: the
    host's count of what the kernels' grids decide a step at a time."""
    qi, ki = np.arange(S // bq)[:, None], np.arange(S // bk)[None, :]
    live = np.broadcast_to(_tile_rule(mask, qi * bq, bq, ki * bk, bk)[0], (S // bq, S // bk))
    lower = live & _tile_shape(mask, qi, bq, ki, bk, piece)
    return {FULL: live & ~lower, LOWER: lower}


def visited_units(mask: MaskRule, S: int, bq: int, bk: int, piece: int) -> Tuple[int, int, int]:
    """(units visited, all units, pairs a unit) of one head's (S, S) scores
    in a kernel that brings (bq, bk) tiles and walks them in pieces of
    ``piece``: the unit is the (piece, piece) piece where the rule gives
    tiles shapes (``_tile_shape``), else the whole tile. What the kernels
    multiply, against ``BlockDiffusion.tile_counts``'s true pairs."""
    tiles = tiles_by_shape(mask, S, bq, bk, piece)
    if not tiles[LOWER].any():
        return int(tiles[FULL].sum()), tiles[FULL].size, bq * bk
    n = bq // piece
    units = sum(
        int(tiles[shape].sum()) * (hi - lo)
        for shape in tiles
        for lo, hi in (_piece_span(shape, i, n, True) for i in range(n))
    )
    return units, tiles[FULL].size * n * n, piece * piece


# What a kernel's body does a score, a piece at a time, is written in ``lax``
# and not in ``jnp`` operators. The primitives are the same and so is the
# kernel; the trace is not: every ``jnp`` function and every operator of a
# tracer is a jit of its own, traced anew for each new shape, and a *lower*
# body has a new shape a piece (8 key pieces a forward body, 4 or 8 query
# pieces a backward one, about 20 ops each, 3 to 4 ms a trace on the chip's
# host: PERF.md section 6, PR 37). ``lax`` binds the primitive and nothing else.
def _fill_where(keep: jax.Array, x: jax.Array, fill: float) -> jax.Array:
    """``jnp.where(keep, x, fill)`` for a Python number ``fill``."""
    return lax.select(keep, x, lax.full_like(x, fill))


def _over_sublanes(reduce, x: jax.Array) -> jax.Array:
    """``reduce`` (``lax.reduce_max``, ``lax.reduce_sum``) down the first axis
    of a (n, m) piece -> (1, m)."""
    return lax.expand_dims(reduce(x, (0,)), (0,))


def _mix32(x: jax.Array) -> jax.Array:
    """32-bit integer finalizer (murmur3-style avalanche) on uint32 lanes.

    Runs per score element in the flash kernels' hot loop, so the op count
    was scrutinized: a single-multiply xorshift variant measured faster but
    showed real adjacent-element keep correlation (pair rate 0.446 vs the
    0.490 expected at rate 0.3) — biased dropout. Two multiplies is the
    floor that passes the adjacency tests in tests/test_attention_ops.py.
    """
    def xorshift(x, n):
        return lax.bitwise_xor(x, lax.shift_right_logical(x, jnp.uint32(n)))

    x = x.astype(jnp.uint32)
    x = lax.mul(xorshift(x, 16), jnp.uint32(0x7FEB352D))
    x = lax.mul(xorshift(x, 15), jnp.uint32(0x846CA68B))
    return xorshift(x, 16)


def _dropout_keep(seed, bh, rows, cols, threshold) -> jax.Array:
    """Deterministic per-element keep mask for attention-probability dropout.

    Derived from the absolute (batch*head, row, col) coordinate — NOT from
    block indices or a stateful PRNG — so the forward kernel, the jnp
    blockwise backward, and the Pallas backward kernels reproduce the exact
    same mask even though they tile the (S, S) matrix differently.
    ``seed`` is a traced uint32 scalar; ``threshold`` = keep_prob * 2^32.

    Each of (bh, row) gets its own fully-avalanched 32-bit stream base, so
    two rows (same or different heads) only ever share keep bits where two
    independent 32-bit hashes collide (~2^-32 per pair) — unlike an affine
    ``base + row*S + col`` packing, where B*H*S^2 > 2^32 forces systematic
    shifted-identical masks across heads by pigeonhole. Per-element cost is
    unchanged (one finalizer on the broadcast (rows, cols) product); the
    row mix runs on the narrow rows operand.
    """
    rowbase = _dropout_rowbase(seed, bh, rows)
    return _mix32(rowbase + cols.astype(jnp.uint32)) < threshold


def _dropout_rowbase(seed, bh, rows) -> jax.Array:
    """The (bh, row) stream base of ``_dropout_keep``: narrow, and the same
    for every column, so a kernel that walks a tile's columns in pieces makes
    it once a tile."""
    base = _mix32(seed + jnp.uint32(bh) * jnp.uint32(0x9E3779B9))
    return _mix32(lax.add(base, lax.mul(rows.astype(jnp.uint32), jnp.uint32(0x85EBCA6B))))


def _dropout_threshold(rate: float) -> jnp.uint32:
    return jnp.uint32(min(int((1.0 - rate) * 2**32), 2**32 - 1))


def _warn_seedless_dropout(dropout_rate: float, api_name: str) -> None:
    """A caller passing dropout_rate>0 without a seed gets *deterministic*
    attention; make that audible instead of silent (advisor finding r2)."""
    if dropout_rate > 0.0:
        import warnings

        warnings.warn(
            f"{api_name}: dropout_rate > 0 with dropout_seed=None — dropout "
            "is DISABLED (deterministic attention). Pass a uint32 "
            "dropout_seed to enable it.",
            stacklevel=3,
        )


def _pick_block(seq_len: int, preferred: int = 512) -> int:
    for b in (preferred, 512, 256, 128, 64, 32, 16, 8, 4, 2, 1):
        if b <= seq_len and seq_len % b == 0:
            return b
    return seq_len


# Default tile sizes, measured on v5e (tier A, S=2048, head_dim 64; see
# docs/PERFORMANCE.md): the forward kernel is fastest at 1024x1024 tiles
# (0.275 ms/layer vs 0.568 ms at 512x512 — fewer grid cells amortize per-cell
# overhead), while the blockwise backward is fastest with 512-wide K blocks
# (1024 doubles its time). Hence separate fwd/bwd defaults.
#
# In plain flash's forward that tile is the DMA tile: what a grid step brings
# into VMEM (flash_attention's block_q / block_k). What the body computes on
# at a time is a (bq, _FWD_SUB_K) piece of it (_flash_fwd_kernel). One call of
# _flash_forward in us a (1024, 1024) tile (my chip run, PR 27,
# scripts/microbench_flash_fwd.py; what the MXU allows is 2.73, the two
# products alone take 3.0; "before" is the q-major whole-tile body):
#
#   (BH, S, D, causal, dropout)             (16, 8192, 64, no, 0.1)  (64, 4096, 128, yes, 0)
#   before PR 27                                     5.72                    5.34
#   that body cut along k, sub_k 512 / 256 / 128     8.80 / 10.44 / 10.66 (at S 2048: 5.99 whole)
#   k-major, whole tile                              5.04                    5.24
#   k-major, keys in pieces of 512 / 256 / 128       4.68 / 4.78 / 4.50      4.62 / 4.69 / 4.37
#   DMA tile 2048x2048, pieces (1024 q, 128 k)       4.25                    3.74
#   ... (256 q, 128 k), above the diagonal skipped   5.78                    3.00
#
# The last two rows are faster and not taken: the pieces are unrolled, and a
# kernel of 32 to 200 of them cost 2 to 4.6 s of every run's set-up (trace,
# lower, cache read) in all three cells measured, past the benchmark's bound.
#
# Under a mask rule a live tile has a shape (_tile_shape) and, since PR 37, a
# body a shape at the same piece: us a (1024, 1024) tile at D 128, no dropout
# (my chip run, PR 37, the same script's by_shape line; in brackets the
# bundles of the compiler's schedule for the body, 1.5 a ns). Every figure
# holds the head's dead grid steps, which is why a body's time does not fall
# as far as its area. In PR 37 they were 2.2 a live tile under block
# diffusion and 0.6 causal, each with a K and a V tile's DMA; since PR 57 a
# dead step brings nothing (``_addressed_tile``) and block diffusion's forward
# walks 9 steps a query tile, 0.8 dead a live tile (``BlockDiffusion
# .band_steps``): the second column as re-measured (my chip runs, PR 57,
# 2026-10-05, the same script and the parent beside it, which read 5.71 /
# 4.40 again; k and v a row a head as in PR 37; at the cell's 4 kv rows a call
# is 9.64 ms where the parent's is 10.08):
#
#   (BH, S, rule)                  (64, 4096, causal)   (32, 16384, BlockDiffusion(8192, 4))   since PR 57
#   full, all 64 pieces of (128, 128)     4.36                 5.71 (5,085)                       4.20
#   lower, 36 of 64                       3.22                 4.40 (3,071)                       2.95
#   band, the 8 on the diagonal           -                    2.95 (929)
#
# The last row is a third body, for block diffusion's noisy -> noisy diagonal
# tiles (8 of a head's 80; they run the lower body), measured and not taken:
# every body is traced and lowered in every program that holds the kernel, and
# with the chains still in jnp the two of them (here and in the backward) cost
# the SDAR cell 3 s of warm set-up for 0.9 % of its step (PERF.md section 6,
# PR 37; what one would cost now, its lowering, is not measured).
_FWD_BLOCK_Q = 1024
_FWD_BLOCK_K = 1024
_BWD_BLOCK_K = 512
_FWD_SUB_K = 128
# The fused Pallas backward (S >= _PALLAS_BWD_MIN_SEQ) is fastest at
# 1024x1024 at both head dims the benchmark runs (PERF.md, PR 25's sweep);
# its q tile is the forward's block_q. That is its DMA tile too; the body
# walks the tile's queries in pieces (_bwd_fused_kernel, _bwd_sub_q). One
# call of _fused_backward in us a live (1024, 1024) tile (my chip runs, PR 33,
# scripts/microbench_flash_bwd.py; "products" is the five tile products with
# a cast between them and no softmax: what the MXU leaves a chain to hide in;
# "whole" is one piece, the body PR 25 wrote with PR 33's shorter chain):
#
#   (BH, S, D / Dv, causal, dropout)   (16, 8192, 64, no, 0.1)  (64, 4096, 128, yes, 0)  (32, 8192, 192 / 128, yes, 0)
#   the dq / dk+dv pair                        15.52                  13.55                   21.06
#   before PR 33 (whole, 24 ops a score)        9.85                   8.16                   13.77
#   whole, the chain of 19 (8 causal)           9.45                   8.22                   13.83
#   queries in pieces of 512 / 256 / 128        9.53 / 8.35 / 7.87     8.18 / 8.15 / 8.34     13.80 / 13.77 / 14.04
#   keys in pieces of 256 / 128                 8.82 / 8.83            8.20 / -               13.79 / -
#   pieces of 128 queries, lookahead            7.92                   (512: 8.24)            -
#   products alone, whole / in pieces of 128    7.73 / 7.98            8.19 / -               13.79 / -
#   DMA tile (2048 q, 1024 k) / (2048, 2048)    7.64 / 7.55            -                      -
#
# With dropout the chain (10 of its 19 ops the hash) is what a whole tile
# waits on, and pieces of 128 queries put it under the products: the kernel
# then runs at what the products alone take. Without dropout the tile waits
# on the MXU whole or cut, and 256 is level with the old body where the
# shorter chain whole is 0.5-0.8 % slower (more spills in its schedule). At
# D 64 without dropout 256 is the fastest too (7.67 against 7.74 whole, 7.86
# at 128), and at D 128 with dropout 128 (8.33 against 9.11 at 256, 9.89
# whole): the dropout rate decides, the head width does not. Lookahead (the
# next piece's two leading products issued first, as the forward does) is
# level: the pieces carry no chain from one to the next, so the scheduler
# overlaps them as they stand. The last row is faster and not taken: the DMA
# tile is PR 25's and the forward's, and 16 unrolled pieces a call would be
# paid in every run's set-up.
#
# The bodies a shape (PR 37; as above the forward's table; no dropout, so
# pieces of 256 queries, 16 of (256, 256) a tile):
#
#   (BH, S, D / Dv, rule)      (64, 4096, 128, causal)  (32, 8192, 192 / 128, causal)  (32, 16384, 128, BlockDiffusion(8192, 4))   since PR 57
#   full, all 16 pieces               8.33                    13.86 (15,928)                 9.34 (10,060)                              8.05
#   lower, 10 of 16                   5.80                    10.06 (10,010)                 6.76 (6,182)                               5.65
#   band, the 4 on the diagonal       -                       -                              5.00 (2,648): not taken, as in the forward
#
# The last column: the 176 dead steps a head of the square's 256 hold a live
# q, dO, lse and delta tile where each brought 576 KiB (my chip runs, PR 57,
# 2026-10-05; the parent beside it read 9.32 / 6.89): 0.57 us a dead step
# less. One call at the cells' own kv rows, parent -> PR 57: the SDAR cell's
# 21.58 -> 18.47 ms, Laguna's full layer (48 heads, causal, S 16,384) 50.05
# -> 46.45, the DeepSeek cell's 14.97 -> 14.09, mistral-7b.d2's 4.51 -> 4.22.
# An index map runs at every grid step for every operand it serves, about
# 1 ns a scalar operation: ``live_spans`` in positions (45 operations a
# block-diffusion map) cost the cell's backward 0.44 ms a call over the
# tile numbers it answers in at square tiles (17).
_FUSED_BWD_BLOCK_K = 1024
_BWD_SUB_Q = 256
_BWD_SUB_Q_DROPOUT = 128

# Backward implementation crossover, measured on v5e tier A with the dq /
# dk+dv kernel pair (docs/PERFORMANCE.md §12): the XLA-fused blockwise-einsum
# backward won at S=2048 (41.6k vs 38.4k tok/s) but the Pallas backward won
# from S=4096 up, by growing margins (+14% @4K, +45% @8K, +88% @16K) — the
# einsum path's (BH, S, bk) probability tiles become HBM-bandwidth-bound
# while a kernel keeps them in VMEM. pallas_backward=None picks by S. Not
# re-measured with the fused kernel (the benchmark's attn_kernel_roofline
# reader hard-codes the same 4096).
_PALLAS_BWD_MIN_SEQ = 4096

_LOG2_E = math.log2(math.e)


def _softmax_scale(scale: Optional[float], d_qk: int) -> float:
    """The factor on q k^T: the caller's (latent attention under YaRN gives
    its own), else 1 / sqrt(width of q and k)."""
    return 1.0 / (d_qk ** 0.5) if scale is None else scale


def _kv_row(rep: int):
    """Grid row (a batch x query head pair) -> the row of k and v it reads
    where ``rep`` query heads share a kv head, in consecutive groups
    (``jnp.repeat``'s order, and the tensor-parallel layout's): b // rep; the
    identity, the index map there was, where none is shared."""
    return (lambda b: b) if rep == 1 else (lambda b: b // rep)


def _repeat_groups(t: jax.Array, rep: int) -> jax.Array:
    """(rows, ...) kv rows -> (rows * rep, ...), each once a query head of
    its group: for the paths that take whole heads (the ``jnp`` ones and the
    kernel pair, off the timed path)."""
    return t if rep == 1 else jnp.repeat(t, rep, axis=0)


def _sum_groups(t: jax.Array, rep: int) -> jax.Array:
    """(rows * rep, ...) gradients a query head -> (rows, ...) a kv head:
    ``_repeat_groups``'s transpose."""
    return t if rep == 1 else t.reshape(-1, rep, *t.shape[1:]).sum(1)


def _fwd_sub_k(bk: int) -> int:
    """Keys of the compute piece the forward kernel walks a (bq, bk) DMA tile
    in: 128, one MXU weight tile of P. A tile no wider than that, or one it
    does not divide (the CPU tests' small tiles), is walked whole."""
    return _FWD_SUB_K if bk > _FWD_SUB_K and bk % _FWD_SUB_K == 0 else bk


def _flash_fwd_kernel(
    seed_ref, bhv_ref, q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr,
    acc_scr,
    *, bq: int, bk: int, sub_k: int, scale: float, mask: MaskRule,
    dropout_rate: float, band: Optional[int] = None,
):
    """One grid step brings the operands of a (bq, bk) score tile into VMEM
    (the DMA tile) and walks its keys in compute pieces of ``sub_k``,
    unrolled, one online-softmax update each (``_fwd_sub_k``).

    A piece is held k-major, (sub_k, bq), as the fused backward holds its
    tile: the softmax's max and sum run down the sublanes (plain vector ops,
    no cross-lane reduction), the statistics m, l and alpha are (1, bq)
    lane-dense rows, so an update a piece is cheap, and the accumulator is
    out^T, (D, bq), rescaled by a sublane broadcast and transposed once a q
    tile. (Cut along k in the q-major layout, (bq, 1) statistics and a lane
    reduction a piece made every point slower than the whole tile.)

    The next piece's QK^T is issued before this piece's softmax: the
    scheduler keeps program order across pieces, so that is what puts the
    MXU's work under the VPU's; without it the pieces are slower than the
    whole tile.

    Scores stay unscaled until the exponent: p = exp2(s * c - m), with
    c = scale * log2(e) and m the running maximum of s * c: one multiply a
    score for the softmax scale and exp's own base change. Dropout's
    1 / keep_prob rides in the subtracted maximum (p comes out pre-scaled),
    so ``l_scr`` sums p / keep_prob and ``_finalize`` takes the factor out.

    That is the body of a *full* tile. A *lower* tile (``_tile_shape``)
    runs a body of its own under its own ``pl.when``: the same pieces and the
    same update, each piece against the queries it may hold a pair with and
    no other (``_accumulate_lower``).

    The grid's last axis is a query tile's steps. Without a mask and under
    causal a step is a key tile, all of them in turn; where the rule gives
    the grid a band (``_band_steps``) the ``band`` steps are the key tiles
    the query tile meets and no other (``key_tile`` of the rule: a window's
    band, block diffusion's packed row; negative in front of a row that meets
    fewer than the widest, where the step multiplies nothing).
    """
    bh = pl.program_id(0)
    qi = pl.program_id(1)
    step = pl.program_id(2)
    steps = pl.num_programs(2)
    ki = step if band is None else mask.key_tile(qi, bq, bk, step, band)
    c = scale * _LOG2_E
    keep_prob = 1.0 - dropout_rate
    ruled = isinstance(mask, _RULES)
    causal = not ruled and mask

    @pl.when(step == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    # Tiles the rule leaves no pair in contribute nothing — skip their compute
    # entirely: with causal masking the k blocks strictly above the diagonal.
    if ruled:
        live = mask.tile_live(qi * bq, bq, ki * bk, bk)
        if band is not None:
            live &= ki >= 0
    else:
        live = (not causal) or (ki * bk < (qi + 1) * bq)

    def update(s, c0, rows, rowbase, m, l, acc):
        """One online-softmax update: the unscaled scores ``s`` of the keys
        [c0, c0 + sub_k) against the queries at ``rows`` (1, n), folded into
        those queries' statistics (1, n) and their columns of out^T."""
        cols = lax.add(
            ki * bk + c0, lax.broadcasted_iota(jnp.int32, (sub_k, 1), 0)
        )
        # No second mask on p: exp2(NEG_INF * c - m) is exactly 0 once
        # the maximum it subtracts is finite.
        if causal:
            # ``first_piece_live``: m is finite from a query's first piece.
            s = _fill_where(lax.ge(rows, cols), s, NEG_INF)
        elif ruled:
            s = _fill_where(
                mask.in_tile(qi * bq, ki * bk, rows, cols), s, NEG_INF
            )
        m_new = lax.max(m, lax.mul(_over_sublanes(lax.reduce_max, s), c))
        alpha = lax.exp2(lax.sub(m, m_new))  # (1, n)
        if not first_piece_live(mask):
            # A query may meet masked scores before its first live key:
            # its running maximum is then still a masked score's, and
            # exp2(s * c - m) would be 1. Subtract a maximum floored half
            # way to the masked value instead (a row op, not a score op):
            # masked scores still come out 0, and a row that has met a
            # live key has a maximum far above the floor. alpha is 0
            # until then (m starts at NEG_INF, below any masked score's).
            m_sub = lax.max(m_new, 0.5 * NEG_INF * c)
        else:
            m_sub = m_new
        # Attention-probability dropout (parity with the reference
        # model, train_harness.py:114-116): the softmax normalizer l
        # accumulates the UN-dropped p (dropout acts after
        # normalization, and normalization is linear, so dropping the
        # unnormalized p against the full-l divisor is exact), while the
        # output accumulator sees the dropped p / keep_prob.
        sc = lax.mul(s, c)
        if dropout_rate > 0.0:
            m_sub = lax.add(m_sub, math.log2(keep_prob))
        p = p_acc = lax.exp2(lax.sub(sc, m_sub))  # (sub_k, n) fp32
        if dropout_rate > 0.0:
            keep = lax.lt(
                _mix32(lax.add(rowbase, cols.astype(jnp.uint32))),
                _dropout_threshold(dropout_rate),
            )
            p_acc = _fill_where(keep, p, 0.0)
        l = lax.add(lax.mul(alpha, l), _over_sublanes(lax.reduce_sum, p))
        acc = lax.mul(acc, alpha)
        acc = lax.add(acc, lax.dot_general(  # out^T: V^T P
            v_ref[0, pl.ds(c0, sub_k), :], p_acc.astype(q_ref.dtype),
            (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32,
        ))
        return m_new, l, acc

    def _accumulate():
        # bf16 operands on the MXU, fp32 accumulation via
        # preferred_element_type — softmax statistics stay fp32 throughout.
        q = q_ref[0]  # (bq, d) input dtype

        def scores(c0):
            return lax.dot_general(
                k_ref[0, pl.ds(c0, sub_k), :], q, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )  # (sub_k, bq) fp32, unscaled

        # Narrow coordinate operands: the causal compare and the dropout
        # hash broadcast (1, bq) x (sub_k, 1); the row-fold mix runs per
        # query only, and once a tile: it does not depend on the key.
        rows = lax.add(qi * bq, lax.broadcasted_iota(jnp.int32, (1, bq), 1))
        rowbase = None
        if dropout_rate > 0.0:
            rowbase = _dropout_rowbase(seed_ref[0], bhv_ref[bh], rows)
        m = m_scr[:]      # (1, bq), log2 units of the scaled scores
        l = l_scr[:]
        acc = acc_scr[:]  # (d, bq) fp32
        s_next = scores(0)
        for c0 in range(0, bk, sub_k):
            s = s_next
            if c0 + sub_k < bk:
                s_next = scores(c0 + sub_k)
            m, l, acc = update(s, c0, rows, rowbase, m, l, acc)
        m_scr[:] = m
        l_scr[:] = l
        acc_scr[:] = acc

    def _accumulate_lower():
        """A *lower* tile: a key piece against the queries it may hold a pair
        with and no other (``_piece_span``), their statistics and columns of
        out^T updated where they lie, the rest left alone. A piece left out
        would have been alpha = 1 and p = 0: exact."""
        n = bk // sub_k

        def lanes(j):
            lo, hi = _piece_span(LOWER, j, n, keys_walked=True)
            return pl.ds(lo * sub_k, (hi - lo) * sub_k)

        def scores(j):
            return lax.dot_general(
                k_ref[0, pl.ds(j * sub_k, sub_k), :], q_ref[0, lanes(j), :],
                (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
            )

        s_next = scores(0)
        for j in range(n):
            s = s_next
            if j + 1 < n:
                s_next = scores(j + 1)
            at = lanes(j)
            rows = lax.add(
                qi * bq + at.start,
                lax.broadcasted_iota(jnp.int32, (1, at.size), 1),
            )
            rowbase = None
            if dropout_rate > 0.0:
                rowbase = _dropout_rowbase(seed_ref[0], bhv_ref[bh], rows)
            m_scr[:, at], l_scr[:, at], acc_scr[:, at] = update(
                s, j * sub_k, rows, rowbase,
                m_scr[:, at], l_scr[:, at], acc_scr[:, at],
            )

    lower = _tile_shape(mask, qi, bq, ki, bk, sub_k)
    if lower is False:
        pl.when(live)(_accumulate)
    else:
        pl.when(live & lower)(_accumulate_lower)
        pl.when(live & ~lower)(_accumulate)

    @pl.when(step == steps - 1)
    def _finalize():
        l = l_scr[:] * keep_prob
        l_safe = jnp.where(l == 0.0, 1.0, l)  # fully-masked rows -> zero output
        o_ref[0] = (acc_scr[:] / l_safe).T.astype(o_ref.dtype)
        # lse is logically (bq,); stored sublane-broadcast as (8, bq) because
        # TPU output blocks must tile to (8, 128).
        lse = m_scr[:] * (1.0 / _LOG2_E) + jnp.log(l_safe)
        lse_ref[0] = jnp.broadcast_to(lse, (8, bq))


def _vma_struct(shape, dtype, *like):
    """ShapeDtypeStruct carrying the union of the inputs' varying-manual-axes.

    When the kernel runs inside a vma-checked ``shard_map`` (e.g. Ulysses
    under the sequence-manual pipeline), Pallas requires out_shapes to declare
    how outputs vary across the manual mesh axes — they vary exactly as the
    operands do (the kernel is pointwise in the shard dimension)."""
    from ..utils.vma import vma_of

    return _struct(shape, dtype, vma_of(*like))


def _struct(shape, dtype, vma):
    if vma:
        return jax.ShapeDtypeStruct(shape, dtype, vma=vma)
    return jax.ShapeDtypeStruct(shape, dtype)


def _dense_mask(mask: MaskRule, rows, cols):
    """The rule on broadcastable global positions, as the ``jnp`` paths
    materialize it; None where every pair is allowed."""
    if isinstance(mask, _RULES):
        return mask.allowed(rows, cols)
    return (rows >= cols) if mask else None


def _jnp_reference_forward(
    q: jax.Array, k: jax.Array, v: jax.Array,
    mask: MaskRule, dropout_rate: float, seed: jax.Array, bhv: jax.Array,
    scale: Optional[float] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Materialized-softmax forward with the kernel's exact mask/accumulation
    semantics (same ``_dropout_keep`` coordinates, same un-dropped normalizer),
    for contexts where the Pallas HLO interpreter cannot run — vma-carrying
    manual regions in interpret mode (the interpreter's internal
    dynamic_slice rejects mixed varying/invariant operands). Never reached
    on a TPU backend (``_resolve_interpret``). Returns (out, lse) exactly as
    ``_flash_forward`` does."""
    BH, S, D = q.shape
    scale = _softmax_scale(scale, D)
    s = jnp.einsum(
        "bqd,bkd->bqk", q, k, preferred_element_type=jnp.float32
    ) * scale
    rows = lax.broadcasted_iota(jnp.int32, (S, 1), 0)
    cols = lax.broadcasted_iota(jnp.int32, (1, S), 1)
    allowed = _dense_mask(mask, rows, cols)
    if allowed is not None:
        s = jnp.where(allowed[None], s, NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    if allowed is not None:
        p = jnp.where(allowed[None], p, 0.0)
    l = jnp.sum(p, axis=-1, keepdims=True)
    if dropout_rate > 0.0:
        keep = _dropout_keep(
            seed[0], bhv[:, None, None], rows[None], cols[None],
            _dropout_threshold(dropout_rate),
        )
        p_acc = jnp.where(keep, p * (1.0 / (1.0 - dropout_rate)), 0.0)
    else:
        p_acc = p
    l_safe = jnp.where(l == 0.0, 1.0, l)
    acc = jnp.einsum(
        "bqk,bkd->bqd", p_acc.astype(q.dtype), v,
        preferred_element_type=jnp.float32,
    )
    out = (acc / l_safe).astype(q.dtype)
    lse = (m + jnp.log(l_safe))[:, :, 0]
    return out, lse


@functools.lru_cache(maxsize=64)
def _forward_call(
    BH, S, D, Dv, dtype, vma, mask, interpret, bq, bk, sub_k, scale,
    dropout_rate, rep=1,
):
    """The forward's ``pallas_call`` on (seed, bhv, q, k, v) for one shape and
    one set of static choices, made once a process. What ``pl.pallas_call``
    returns is a jit of its own, inlined where it is called: the same object
    called again on the same shapes in the same trace context
    (``_flash_forward`` sees to that) does not trace the kernel's unrolled
    bodies again, where a new one would at every call. A differentiated
    ``flash_attention`` makes two, the primal inside its jit and the forward
    rule, and a kernel's trace was a third of ``mistral-7b.d2``'s warm
    set-up in the program (PERF.md section 6, PR 37). The jaxpr a caller sees
    is the same either way. Whoever patches what a kernel's body reads
    (``_tile_shape``, ``first_piece_live``) calls ``forget_kernel_calls``.

    Under a ``SlidingWindow`` and under ``BlockDiffusion`` the grid's last
    axis is a band and not the square's row (``_band_steps``): the most key
    tiles a query tile meets. Under every rule the K and V blocks of a step
    whose tile is dead are a live tile's of the same query tile, the next the
    walk reaches (``_addressed_tile``): the block index repeats and nothing
    is fetched for the step. A causal query tile holds its diagonal tile to
    the row's end; a band's row holds its first live tile through the steps
    in front of it (a window's clipped corner tile 0).

    k and v hold BH // ``rep`` rows, a kv head each: the grid's row ``b``, a
    (batch, query head) pair, reads row b // rep of them (``_kv_row``); q,
    out and lse keep ``b``."""
    band = _band_steps(mask, S, bq, bk, True)
    kv = _kv_row(rep)
    key_tile = _addressed_tile(mask, S, bq, bk, keys_inner=True)
    key_spec = lambda b, qi, step: (kv(b), key_tile(qi, step), 0)
    return pl.pallas_call(
        functools.partial(
            _flash_fwd_kernel, bq=bq, bk=bk, sub_k=sub_k, scale=scale,
            mask=mask, dropout_rate=dropout_rate, band=band,
        ),
        out_shape=[
            _struct((BH, S, Dv), dtype, vma),
            _struct((BH, 8, S), jnp.float32, vma),
        ],
        grid=(BH, S // bq, S // bk if band is None else band),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),  # dropout seed (1,) uint32
            pl.BlockSpec(memory_space=pltpu.SMEM),  # global bh ids (BH,)
            pl.BlockSpec((1, bq, D), lambda b, qi, ki: (b, qi, 0)),
            pl.BlockSpec((1, bk, D), key_spec),
            pl.BlockSpec((1, bk, Dv), key_spec),
        ],
        out_specs=[
            pl.BlockSpec((1, bq, Dv), lambda b, qi, ki: (b, qi, 0)),
            pl.BlockSpec((1, 8, bq), lambda b, qi, ki: (b, 0, qi)),
        ],
        scratch_shapes=[
            pltpu.VMEM((1, bq), jnp.float32),  # running max, log2 units
            pltpu.VMEM((1, bq), jnp.float32),  # running sum / keep_prob
            pltpu.VMEM((Dv, bq), jnp.float32),  # output accumulator, out^T
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        name="flash_fwd",
        interpret=interpret,
    )


def _flash_forward(
    q: jax.Array, k: jax.Array, v: jax.Array,
    mask: MaskRule, interpret: bool, bq: int, bk: int,
    dropout_rate: float, seed: jax.Array, bhv: jax.Array,
    sub_k: Optional[int] = None, scale: Optional[float] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Run the Pallas kernel on (BH, S, D) q, (BKV, S, D) k and (BKV, S, Dv)
    v -> (out (BH, S, Dv), lse); BH a multiple of BKV, query row b reading kv
    row b // (BH // BKV) (grouped-query attention: the kernel's index maps,
    no repeated copy); Dv is D everywhere but latent attention, whose
    keys carry a rotary part the values lack. ``bhv`` is
    the (BH,) int32 vector of GLOBAL batch*head ids keying the dropout hash
    (arange(BH) on one device; mesh-global ids under a shard_map).
    ``sub_k`` forces the compute piece (tests and the microbench; no flag or
    config field reaches it); ``_fwd_sub_k`` chooses it otherwise."""
    BH, S, D = q.shape
    rep = BH // k.shape[0]
    scale = _softmax_scale(scale, D)
    from ..utils.vma import vma_of

    vma = vma_of(q, k, v)
    if interpret and vma:
        return _jnp_reference_forward(
            q, _repeat_groups(k, rep), _repeat_groups(v, rep), mask,
            dropout_rate, seed, bhv, scale,
        )
    call = _forward_call(
        BH, S, D, v.shape[-1], q.dtype, vma, mask, interpret, bq, bk,
        sub_k or _fwd_sub_k(bk), scale, dropout_rate, rep,
    )
    # jit keys a trace by its context too, and with no mesh set the primal
    # is traced under none and the forward rule under an empty one: name the
    # mesh that is there, so that the two are one trace.
    with jax.sharding.use_abstract_mesh(jax.sharding.get_abstract_mesh()):
        out, lse = call(seed, bhv, q, k, v)
    return out, lse[:, 0, :]


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _flash(
    opts: Tuple, q: jax.Array, k: jax.Array, v: jax.Array, seed: jax.Array,
    bhv: jax.Array,
) -> jax.Array:
    mask, interpret, bq, bk, _, _, rate, scale = opts
    out, _ = _flash_forward(
        q, k, v, mask, interpret, bq, bk, rate, seed, bhv, scale=scale
    )
    return out


#: ``jax.ad_checkpoint.checkpoint_name``s of the forward's two results, (out,
#: lse), as the backward's residuals. A Mosaic call is no ``dot_general``, so a
#: dots-class remat policy would drop them and run the whole O(S^2) kernel
#: again in the backward pass; the model's ``dots`` policy keeps these names
#: (models/tinygpt.py::apply_blocks). Outside a checkpoint a name is the
#: identity.
FLASH_RESIDUAL_NAMES = ("flash_out", "flash_lse")


def _flash_fwd_rule(opts, q, k, v, seed, bhv):
    mask, interpret, bq, bk, _, _, rate, scale = opts
    out, lse = _flash_forward(
        q, k, v, mask, interpret, bq, bk, rate, seed, bhv, scale=scale
    )
    # The kernel's results feed nothing but the two names (the primal result
    # is the named ``out``), so where a policy saves them the recompute copy
    # of the call is dead code.
    out = checkpoint_name(out, FLASH_RESIDUAL_NAMES[0])
    lse = checkpoint_name(lse, FLASH_RESIDUAL_NAMES[1])
    return out, (q, k, v, out, lse, seed, bhv)


def _tile_rule(mask: MaskRule, q_off, bq: int, k_off, bk: int):
    """The backward kernels' two uses of the rule at the (bq, bk) tile at
    (q_off, k_off) -> (whether the tile holds an allowed pair: a Python True
    without a mask; (rows, cols) -> the allowed pairs inside it, or None)."""
    if isinstance(mask, _RULES):
        return (mask.tile_live(q_off, bq, k_off, bk),
                functools.partial(mask.in_tile, q_off, k_off))
    if mask:
        return q_off + bq - 1 >= k_off, lax.ge
    return True, lambda rows, cols: None


def _bwd_dq_kernel(
    seed_ref, qoff_ref, koff_ref, bhv_ref, q_ref, k_ref, v_ref, do_ref,
    lse_ref, delta_ref, dq_ref, acc,
    *, bq: int, bk: int, scale: float, mask: MaskRule,
    dropout_rate: float,
):
    """dq = sum over k blocks of ds @ k, ds = p * (dp - delta) * scale.

    Ring attention's per-block backward (contiguous and zigzag layouts) and
    plain flash's fallback past the fused kernel's VMEM cap: the SMEM
    vectors ``qoff_ref`` (nq,) / ``koff_ref`` (nk,) carry each TILE's global
    base row/col — arange(n)*b for plain
    flash, shard-offset + arange for contiguous ring blocks, per-half-chunk
    bases for zigzag — so causal masking and the dropout hash always see
    absolute coordinates from one kernel implementation. Tiles must be
    internally contiguous (tile sizes divide the chunk size)."""
    bh = pl.program_id(0)
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)
    q_off = qoff_ref[qi]
    k_off = koff_ref[ki]

    @pl.when(ki == 0)
    def _init():
        acc[:] = jnp.zeros_like(acc)

    live, in_tile = _tile_rule(mask, q_off, bq, k_off, bk)

    @pl.when(live)
    def _accumulate():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        lse = lse_ref[0][0]      # (bq,)
        delta = delta_ref[0][0]  # (bq,)
        s = lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale
        # Narrow coordinate operands: the causal compare and the dropout
        # hash broadcast (bq,1)x(1,bk); the row-fold mix runs per-row only.
        rows = q_off + lax.broadcasted_iota(jnp.int32, (bq, 1), 0)
        cols = k_off + lax.broadcasted_iota(jnp.int32, (1, bk), 1)
        allowed = in_tile(rows, cols)
        if allowed is not None:
            s = jnp.where(allowed, s, NEG_INF)
        p = jnp.exp(s - lse[:, None])
        if allowed is not None:
            p = jnp.where(allowed, p, 0.0)
        dp = lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        if dropout_rate > 0.0:
            keep = _dropout_keep(
                seed_ref[0], bhv_ref[bh], rows, cols,
                _dropout_threshold(dropout_rate),
            )
            dp = jnp.where(keep, dp * (1.0 / (1.0 - dropout_rate)), 0.0)
        ds = (p * (dp - delta[:, None]) * scale).astype(q.dtype)
        acc[:] = acc[:] + lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )

    @pl.when(ki == nk - 1)
    def _finalize():
        dq_ref[0] = acc[:].astype(dq_ref.dtype)


def _bwd_dkv_kernel(
    seed_ref, qoff_ref, koff_ref, bhv_ref, q_ref, k_ref, v_ref, do_ref,
    lse_ref, delta_ref, dk_ref, dv_ref, dk_acc, dv_acc,
    *, bq: int, bk: int, scale: float, mask: MaskRule,
    dropout_rate: float,
):
    """dk = sum over q blocks of ds^T @ q; dv = sum of (D∘p)^T @ do.

    Shared with ring attention's per-block backward (contiguous and zigzag
    layouts) via the same SMEM tile-base vectors as _bwd_dq_kernel (see its
    docstring)."""
    bh = pl.program_id(0)
    ki = pl.program_id(1)
    qi = pl.program_id(2)
    nq = pl.num_programs(2)
    q_off = qoff_ref[qi]
    k_off = koff_ref[ki]

    @pl.when(qi == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    live, in_tile = _tile_rule(mask, q_off, bq, k_off, bk)

    @pl.when(live)
    def _accumulate():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        lse = lse_ref[0][0]
        delta = delta_ref[0][0]
        s = lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale
        # Narrow coordinate operands: the causal compare and the dropout
        # hash broadcast (bq,1)x(1,bk); the row-fold mix runs per-row only.
        rows = q_off + lax.broadcasted_iota(jnp.int32, (bq, 1), 0)
        cols = k_off + lax.broadcasted_iota(jnp.int32, (1, bk), 1)
        allowed = in_tile(rows, cols)
        if allowed is not None:
            s = jnp.where(allowed, s, NEG_INF)
        p = jnp.exp(s - lse[:, None])
        if allowed is not None:
            p = jnp.where(allowed, p, 0.0)
        dp = lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        if dropout_rate > 0.0:
            keep = _dropout_keep(
                seed_ref[0], bhv_ref[bh], rows, cols,
                _dropout_threshold(dropout_rate),
            )
            inv = 1.0 / (1.0 - dropout_rate)
            pd = jnp.where(keep, p * inv, 0.0)
            dp = jnp.where(keep, dp * inv, 0.0)
        else:
            pd = p
        dv_acc[:] = dv_acc[:] + lax.dot_general(
            pd.astype(q.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = (p * (dp - delta[:, None]) * scale).astype(q.dtype)
        dk_acc[:] = dk_acc[:] + lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )

    @pl.when(qi == nq - 1)
    def _finalize():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def _jnp_blockwise_bwd(mask, bk, rate, res, do, scale=None):
    """Blockwise flash backward as batched einsums over a K-block scan.

    Same math as the Pallas kernels, expressed as XLA-fused dense einsums:
    only (S, bk) tiles materialize, in HBM. The backward below
    ``_PALLAS_BWD_MIN_SEQ`` on a TPU (there XLA's batched-over-heads
    contractions beat the kernel pair's per-(head, tile) grid), and in
    interpret mode at any S.

    With dropout (out = (D∘P) @ V, D = keep/keep_prob): dV = (D∘P)^T dO, and
    the softmax-Jacobian identity dS = P∘(D∘dP - delta) still holds with
    delta = rowsum(dO∘out) because rowsum((D∘P)∘dP) = rowsum(dO∘out). The
    keep mask is regenerated from the same absolute-coordinate hash as the
    forward kernel, so the decomposition mismatch (fwd 1024-wide tiles, bwd
    ``bk``-wide) is invisible.
    """
    q, k, v, out, lse, seed, bhv = res
    BH, S, D = q.shape
    Dv = v.shape[-1]
    scale = _softmax_scale(scale, D)
    f32 = jnp.float32
    cd = q.dtype  # matmul operand dtype (bf16 on TPU); accumulation is fp32
    dof = do.astype(cd)
    delta = jnp.sum(
        do.astype(f32) * out.astype(f32), axis=-1
    )  # (BH, S) fp32

    nk = S // bk
    ks = k.reshape(BH, nk, bk, D).transpose(1, 0, 2, 3)  # (nk, BH, bk, D)
    vs = v.reshape(BH, nk, bk, Dv).transpose(1, 0, 2, 3)
    rows = jnp.arange(S)
    threshold = _dropout_threshold(rate)

    def one_block(dq_acc, blk):
        ki, k_b, v_b = blk
        cols = ki * bk + jnp.arange(bk)
        s = jnp.einsum("bqd,bkd->bqk", q, k_b, preferred_element_type=f32) * scale
        allowed = _dense_mask(mask, rows[:, None], cols[None, :]) if mask else None
        if allowed is not None:
            s = jnp.where(allowed[None], s, NEG_INF)
        p = jnp.exp(s - lse[:, :, None])  # (BH, S, bk) fp32
        if allowed is not None:
            p = jnp.where(allowed[None], p, 0.0)
        if rate > 0.0:
            keep = _dropout_keep(
                seed[0], bhv[:, None, None], rows[None, :, None],
                cols[None, None, :], threshold,
            )  # (BH, S, bk)
            inv = 1.0 / (1.0 - rate)
            pd = jnp.where(keep, p * inv, 0.0)
            dp_scale = jnp.where(keep, inv, 0.0)
        else:
            pd = p
            dp_scale = None
        dv_b = jnp.einsum(
            "bqk,bqd->bkd", pd.astype(cd), dof, preferred_element_type=f32
        )
        dp = jnp.einsum("bqd,bkd->bqk", dof, v_b, preferred_element_type=f32)
        if dp_scale is not None:
            dp = dp * dp_scale
        ds = (p * (dp - delta[:, :, None]) * scale).astype(cd)
        dq_acc = dq_acc + jnp.einsum("bqk,bkd->bqd", ds, k_b, preferred_element_type=f32)
        dk_b = jnp.einsum("bqk,bqd->bkd", ds, q, preferred_element_type=f32)
        return dq_acc, (dk_b, dv_b)

    # Under a vma-checked manual region the accumulator carry must match the
    # varying type the block updates produce.
    from ..utils.vma import pcast_like

    dq0 = pcast_like(jnp.zeros((BH, S, D), f32), q, k, v, do)
    dq, (dk_blocks, dv_blocks) = lax.scan(one_block, dq0, (jnp.arange(nk), ks, vs))
    dk = dk_blocks.transpose(1, 0, 2, 3).reshape(BH, S, D)
    dv = dv_blocks.transpose(1, 0, 2, 3).reshape(BH, S, Dv)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


def _bwd_sub_q(bq: int, dropout_rate: float) -> int:
    """Queries of the compute piece the fused backward walks a (bk, bq) DMA
    tile in: 128 with dropout (the hash makes the chain long), 256 without;
    the table above ``_FUSED_BWD_BLOCK_K``. Head width does not enter: the
    same piece is fastest at each. A tile no wider than the piece, or one it
    does not divide (the CPU tests' small tiles), is walked whole."""
    sub = _BWD_SUB_Q_DROPOUT if dropout_rate > 0.0 else _BWD_SUB_Q
    return sub if bq > sub and bq % sub == 0 else bq


def _bwd_fused_kernel(
    seed_ref, bhv_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
    dq_ref, dk_ref, dv_ref, dq_acc, dk_acc, dv_acc,
    *, bq: int, bk: int, sub_q: int, scale: float, mask: MaskRule,
    dropout_rate: float, band: Optional[int] = None, group: int = 1,
):
    """dq, dk and dv from ONE visit of each live (k tile, q tile): s, p, dp,
    the keep mask and ds are computed once and feed all three products
    (5 tile matmuls, 1 exp, 1 hash; the dq + dk/dv pair spends 7, 2, 2).

    Grid (BH, k tiles, q tiles), q innermost: ``dk_acc`` / ``dv_acc`` hold
    one k tile and are written out at its last q tile; ``dq_acc`` holds the
    whole (S, D) row of the (batch, head) pair in VMEM, each q tile's slice
    zeroed in the first k pass, added to in every pass (k tiles ascending,
    the order _bwd_dq_kernel sums in) and written out in the last. Tile i
    starts at row i*b: plain flash only (ring keeps the kernel pair).

    The score tile is held k-major, (bk, bq): dv and dk are then plain
    products, only dq contracts over the tile's first dim (one transpose
    where a q-major body has two), and lse / delta are (1, bq) rows that
    broadcast along sublanes. The body walks the tile's queries in compute
    pieces of ``sub_q`` lanes, unrolled (``_bwd_sub_q``): a piece's two
    leading products, its vector chain and its three trailing products, dq
    written a slice a piece, dk / dv accumulated over the pieces (the
    products' own contraction chunks, taken to the outer loop). A *lower*
    tile (``_tile_shape``) runs the same walk under its own ``pl.when`` with
    every piece's keys trimmed to those it may hold a pair with
    (``_accumulate``).

    The chain, by the score: p = exp2(s * c - lse2) with c = scale * log2(e)
    and lse2 = lse * log2(e) made on the row (one multiply for the softmax
    scale and exp's base change); ds's ``* scale`` is left to where dk and
    dq are written out; dropout's 1 / keep_prob rides in the subtracted row
    (p comes out as p / keep_prob, which is what dv's product wants), and
    ds = p' * (where(keep, dp, 0) - delta * keep_prob), the last factor on
    the row; no second select on p under a mask (exp2(NEG_INF * c - lse2) is
    exactly 0 where lse is finite, and it is for every rule: each has a live
    key for every query, causal its own position with tile i at row i*b,
    block diffusion by ``BlockDiffusion.__post_init__``); the hash's row half
    once a piece. Against the kernel pair that
    moves dk and dq by f32 rounding of the folded factors and by bf16
    rounding of ds before ``scale`` instead of after it; the keep mask is
    the same bits.

    Under a ``SlidingWindow`` the grid's last axis is the ``band`` query tiles
    a key tile meets and no other (``SlidingWindow.query_tile``; past the
    last tile in the band's clipped corner, where the step multiplies
    nothing): a q tile's slice of ``dq_acc`` is zeroed at the first key tile
    of its band and written out at the last, the diagonal's."""
    if group == 1:
        bh, axis = pl.program_id(0), 1
    else:
        member, axis = pl.program_id(1), 2
        bh = pl.program_id(0) * group + member
    ki = pl.program_id(axis)
    step = pl.program_id(axis + 1)
    nk = pl.num_programs(axis)
    steps = pl.num_programs(axis + 1)
    if band is None:
        qi = step
    else:
        qi = mask.query_tile(ki, bq, bk, step)
        inside = qi < dq_acc.shape[0] // bq
        qi = lax.select(inside, qi, lax.full_like(qi, 0))  # a slice that exists
    q_off = qi * bq
    k_off = ki * bk
    q_rows = pl.ds(pl.multiple_of(q_off, bq), bq)
    c = scale * _LOG2_E
    keep_prob = 1.0 - dropout_rate

    # Where the k tile lies in ``dk_acc`` / ``dv_acc``: they are the tile, or,
    # under a group, the kv head's whole rows. A head opens and closes its own
    # tile; a group's first and last head open and close the kv head's.
    tile_rows = slice(None) if group == 1 else pl.ds(pl.multiple_of(k_off, bk), bk)

    @pl.when(step == 0 if group == 1 else (step == 0) & (member == 0))
    def _init_kv():
        dk_acc[tile_rows, :] = jnp.zeros((bk, dk_acc.shape[1]), dk_acc.dtype)
        dv_acc[tile_rows, :] = jnp.zeros((bk, dv_acc.shape[1]), dv_acc.dtype)

    if band is None:
        first_pass = ki == 0
    else:
        first_pass = inside & (ki == lax.max(q_off - (mask.window - 1), 0) // bk)

    @pl.when(first_pass)
    def _init_q():
        dq_acc[q_rows, :] = jnp.zeros((bq, dq_acc.shape[1]), dq_acc.dtype)

    live, in_tile = _tile_rule(mask, q_off, bq, k_off, bk)
    if band is not None:
        live &= inside

    def _accumulate(shape):
        """The tile's query pieces, each against the keys it may hold a pair
        with (``_piece_span``): all of them in a *full* tile; in a *lower*
        one dk and dv take the partial rows, dq's contraction the partial
        sum. What is left out was p = ds = 0: exact."""
        n = bq // sub_q
        for r0 in range(0, bq, sub_q):
            if shape == LOWER or r0 == 0:  # a *full* tile's keys: once
                _, hi = _piece_span(shape, r0 // sub_q, n, keys_walked=False)
                keys = slice(None) if shape == FULL else pl.ds(0, hi * sub_q)
                k = k_ref[0, keys, :]
                v = v_ref[0, keys, :]
                acc_rows = keys if group == 1 else pl.ds(tile_rows.start, k.shape[0])
                # Narrow coordinate operands, as in the other kernels; key
                # positions ("cols" of the hash) run down the sublanes here.
                cols = lax.add(
                    k_off, lax.broadcasted_iota(jnp.int32, (k.shape[0], 1), 0)
                )
                hash_cols = cols.astype(jnp.uint32)
            q = q_ref[0, pl.ds(r0, sub_q), :]
            do = do_ref[0, pl.ds(r0, sub_q), :]
            s = lax.dot_general(
                k, q, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )  # (keys, sub_q) fp32, unscaled
            dp = lax.dot_general(
                v, do, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            shift = lse_ref[0, :1, pl.ds(r0, sub_q)] * _LOG2_E  # (1, sub_q)
            delta = delta_ref[0, :1, pl.ds(r0, sub_q)]
            rows = lax.add(
                q_off + r0, lax.broadcasted_iota(jnp.int32, (1, sub_q), 1)
            )
            allowed = in_tile(rows, cols)
            if allowed is not None:
                s = _fill_where(allowed, s, NEG_INF)
            sc = lax.mul(s, c)
            if dropout_rate > 0.0:
                # p / keep_prob: dv's operand as it is, and ds's with
                # keep_prob taken into delta's row.
                shift = lax.add(shift, math.log2(keep_prob))
            p = pd = lax.exp2(lax.sub(sc, shift))
            if dropout_rate > 0.0:
                keep = lax.lt(
                    _mix32(lax.add(
                        _dropout_rowbase(seed_ref[0], bhv_ref[bh], rows),
                        hash_cols,
                    )),
                    _dropout_threshold(dropout_rate),
                )
                pd = _fill_where(keep, p, 0.0)
                dp = _fill_where(keep, dp, 0.0)
                delta = lax.mul(delta, keep_prob)
            ds = lax.mul(p, lax.sub(dp, delta)).astype(q.dtype)  # ds / scale
            dv_acc[acc_rows, :] = lax.add(dv_acc[acc_rows, :], lax.dot_general(
                pd.astype(q.dtype), do, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            ))
            dk_acc[acc_rows, :] = lax.add(dk_acc[acc_rows, :], lax.dot_general(
                ds, q, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            ))
            dq_rows = pl.ds(pl.multiple_of(q_off + r0, sub_q), sub_q)
            dq_acc[dq_rows, :] = lax.add(dq_acc[dq_rows, :], lax.dot_general(
                ds, k, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            ))

    lower = _tile_shape(mask, qi, bq, ki, bk, sub_q)
    if lower is False:
        pl.when(live)(functools.partial(_accumulate, FULL))
    else:
        pl.when(live & lower)(functools.partial(_accumulate, LOWER))
        pl.when(live & ~lower)(functools.partial(_accumulate, FULL))

    @pl.when(step == steps - 1 if group == 1 else (step == steps - 1) & (member == group - 1))
    def _finalize_kv():
        dk_ref[0, tile_rows, :] = (dk_acc[tile_rows, :] * scale).astype(dk_ref.dtype)
        dv_ref[0, tile_rows, :] = dv_acc[tile_rows, :].astype(dv_ref.dtype)

    if band is None:
        last_pass = ki == nk - 1
    else:
        last_pass = inside & (ki == (q_off + (bq - 1)) // bk)

    @pl.when(last_pass)
    def _finalize_q():
        dq_ref[0, q_rows, :] = (dq_acc[q_rows, :] * scale).astype(dq_ref.dtype)


# VMEM the fused backward may take. The resident dq row is what grows with
# S: an f32 accumulator plus the double-buffered output block, lanes padded
# to 128 (``D`` is the width of q and k: 192 pads to 256). The (1024, 1024)
# tile's operand blocks, the dk / dv accumulators and Mosaic's temporaries
# (a (1024, 256) f32 piece is 1 MiB where the whole tile was 4) compile
# under 6 MiB at the three cell shapes and at the cap (under 16 before PR
# 33); 32 stays their allowance. A v5e core has 128 MiB; past the cap
# (S 65536 at head dims to 128, bf16) the kernel pair runs instead.
_FUSED_TILE_VMEM = 32 * 2**20
_FUSED_MAX_VMEM = 96 * 2**20


def _fused_vmem_bytes(S: int, D: int, dtype, grouped_dv: Optional[int] = None) -> int:
    """``grouped_dv``: v's width where dk and dv are resident rows too (the
    grouped form: ``_grouped_fits``)."""
    row = S * (4 + 2 * jnp.dtype(dtype).itemsize)
    lanes = -(-D // 128) * 128
    rows = lanes if grouped_dv is None else 2 * lanes + -(-grouped_dv // 128) * 128
    return row * rows + _FUSED_TILE_VMEM


def _fused_fits(S: int, D: int, dtype) -> bool:
    return _fused_vmem_bytes(S, D, dtype) <= _FUSED_MAX_VMEM


def _grouped_fits(S: int, D: int, Dv: int, dtype) -> bool:
    """Whether a kv head's dk and dv rows fit VMEM beside the dq row: three
    resident rows where ``_fused_fits`` counts one (S 16,384 at head dims to
    128 in bf16: 80 MiB; S 32,768 does not fit)."""
    return _fused_vmem_bytes(S, D, dtype, Dv) <= _FUSED_MAX_VMEM


@functools.lru_cache(maxsize=64)
def _fused_call(
    BH, S, D, Dv, dtypes, vma, mask, interpret, bq, bk, sub_q, scale, rate,
    rep=1, grouped=False,
):
    """The fused backward's ``pallas_call`` on (seed, bhv, q, k, v, do, lse3,
    delta3), made once a process for a shape and its static choices, as
    ``_forward_call`` and for its reason; under a ``SlidingWindow`` its
    grid's last axis is the band, as the forward's. The q, dO, lse and delta
    blocks of a step whose tile is dead are a live tile's of the same key
    tile (``_addressed_tile``; a step brings 576 KiB of them at D 128), and
    nothing is fetched for it: a causal key tile holds its diagonal's query
    tile from step 0, block diffusion's noisy ones theirs before and behind
    it, its clean ones the first noisy query tile that sees them and, between
    the noisy queries and the clean, their own; a window's clipped corner the
    last tile.

    k and v hold BH // ``rep`` rows and are read at row b // rep, as the
    forward reads them. dk and dv leave in one of two forms. ``grouped``: the
    grid is (kv rows, a group's ``rep`` heads, k tiles, q tiles), ``dk_acc``
    and ``dv_acc`` hold the kv head's whole rows and take every head of the
    group, and dk / dv are written once a kv head, (BH // rep, S, .): no sum
    behind the kernel and 1 / rep of the writes (my chip run, PR 48: 0.9 ms
    of a 22.5 ms call at the SDAR cell's shape, 1.1 of 9.5 at Laguna's
    window layer's). Else a query head, (BH, S, .), as they always have,
    for ``_fused_backward`` to sum (what a sequence too long for three
    resident rows takes)."""
    band = _band_steps(mask, S, bq, bk, False)
    kv = _kv_row(rep)
    q_tile = _addressed_tile(mask, S, bq, bk, keys_inner=False)
    if grouped:
        # grid (kv rows, the group's heads, k tiles, q tiles): dk / dv rows
        # of a kv head stay in VMEM over its group and leave once
        def rows(index):  # (g, r, ki, qi) -> (q row, kv row, ki, qi)
            return lambda g, r, ki, qi: index(g * rep + r, g, ki, qi)
        acc_rows, dkv_block = S, lambda b, g, ki, qi: (g, 0, 0)
    else:
        def rows(index):
            return lambda b, ki, qi: index(b, kv(b), ki, qi)
        acc_rows, dkv_block = bk, lambda b, g, ki, qi: (b, ki, 0)
    q_spec = pl.BlockSpec((1, bq, D), rows(lambda b, g, ki, qi: (b, q_tile(ki, qi), 0)))
    k_spec = pl.BlockSpec((1, bk, D), rows(lambda b, g, ki, qi: (g, ki, 0)))
    do_spec = pl.BlockSpec((1, bq, Dv), rows(lambda b, g, ki, qi: (b, q_tile(ki, qi), 0)))
    v_spec = pl.BlockSpec((1, bk, Dv), rows(lambda b, g, ki, qi: (g, ki, 0)))
    dk_spec = pl.BlockSpec((1, acc_rows, D), rows(dkv_block))
    dv_spec = pl.BlockSpec((1, acc_rows, Dv), rows(dkv_block))
    stat_spec = pl.BlockSpec((1, 8, bq), rows(lambda b, g, ki, qi: (b, 0, q_tile(ki, qi))))
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    return pl.pallas_call(
        functools.partial(
            _bwd_fused_kernel, bq=bq, bk=bk, sub_q=sub_q, scale=scale,
            mask=mask, dropout_rate=rate, band=band, group=rep if grouped else 1,
        ),
        out_shape=[
            _struct((BH, S, D), dtypes[0], vma),
            _struct((BH // rep if grouped else BH, S, D), dtypes[1], vma),
            _struct((BH // rep if grouped else BH, S, Dv), dtypes[2], vma),
        ],
        grid=((BH // rep, rep) if grouped else (BH,)) + (S // bk, S // bq if band is None else band),
        in_specs=[smem, smem, q_spec, k_spec, v_spec, do_spec,
                  stat_spec, stat_spec],
        out_specs=[
            pl.BlockSpec((1, S, D), rows(lambda b, g, ki, qi: (b, 0, 0))),
            dk_spec, dv_spec,
        ],
        scratch_shapes=[
            pltpu.VMEM((S, D), jnp.float32),   # dq, the whole row
            pltpu.VMEM((acc_rows, D), jnp.float32),  # dk, one k tile or the kv head's rows
            pltpu.VMEM((acc_rows, Dv), jnp.float32),  # dv
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",) + ("arbitrary",) * (3 if grouped else 2),
            vmem_limit_bytes=_fused_vmem_bytes(S, D, dtypes[0], Dv if grouped else None),
        ),
        name="flash_bwd_fused",
        interpret=interpret,
    )


def forget_kernel_calls() -> None:
    """Drop the kernels kept for the process (``_forward_call``,
    ``_fused_call``): the next call builds and traces its kernel anew. For
    tests and the microbenches, after patching what a body reads."""
    _forward_call.cache_clear()
    _fused_call.cache_clear()


def _fused_backward(
    q, k, v, do, lse3, delta3, seed, bhv, mask, rate, bq, bk, interpret,
    scale=None, sub=None, grouped=None,
):
    """The one-kernel Pallas backward on (BH, S, D) q, (BKV, S, D) k,
    (BKV, S, Dv) v and (BH, S, Dv) do -> dq (BH, S, D), dk (BKV, S, D), dv
    (BKV, S, Dv). Where query heads share kv heads the kernel sums a group's
    dk and dv itself if their rows fit VMEM (``_grouped_fits``: decided from
    S, the widths and the dtype); else they leave it a query head and the sum
    runs behind it. ``sub`` forces the compute piece and ``grouped`` the form
    (tests and the microbench; no flag or config field reaches them);
    ``_bwd_sub_q`` chooses the piece otherwise."""
    from ..utils.vma import vma_of

    BH, S, D = q.shape
    rep = BH // k.shape[0]
    if grouped is None:
        grouped = rep > 1 and _grouped_fits(S, D, v.shape[-1], q.dtype)
    dq, dk, dv = _fused_call(
        BH, S, D, v.shape[-1], (q.dtype, k.dtype, v.dtype),
        vma_of(q, k, v, do), mask, interpret, bq, bk,
        sub or _bwd_sub_q(bq, rate), _softmax_scale(scale, D), rate,
        rep, grouped,
    )(seed, bhv, q, k, v, do, lse3, delta3)
    if not grouped:
        dk, dv = _sum_groups(dk, rep), _sum_groups(dv, rep)
    return dq, dk, dv


def _pair_backward(
    q, k, v, do, lse3, delta3, seed, bhv, mask, rate, bq, bk, interpret,
    scale=None, *, q_tile_offsets=None, k_tile_offsets=None, out_dtype=None,
):
    """The dq and dk+dv kernel pair on (BH, Sq, D) queries, (BH, Sk, D) keys
    and (BH, Sk, Dv) values: every score tile visited twice. The one caller of
    ``_bwd_dq_kernel`` / ``_bwd_dkv_kernel``.

    Plain flash runs it only for shapes ``_fused_fits`` turns away, with
    tile i starting at row i*b (the default offsets) and gradients in the
    operands' dtypes. Ring attention runs it on each resident block with the
    block's global tile bases (``q_tile_offsets`` (Sq//bq,) /
    ``k_tile_offsets`` (Sk//bk,) int32: shard offsets, or the zigzag
    half-chunk bases) and ``out_dtype`` float32, the dtype its dk / dv ride
    the ring in."""
    BH, Sq, D = q.shape
    Sk, Dv = k.shape[1], v.shape[-1]
    scale = _softmax_scale(scale, D)
    seed_spec = pl.BlockSpec(memory_space=pltpu.SMEM)
    qoffs = (jnp.arange(Sq // bq, dtype=jnp.int32) * bq
             if q_tile_offsets is None else q_tile_offsets)
    koffs = (jnp.arange(Sk // bk, dtype=jnp.int32) * bk
             if k_tile_offsets is None else k_tile_offsets)
    dq_dtype, dk_dtype, dv_dtype = (
        (q.dtype, k.dtype, v.dtype) if out_dtype is None else (out_dtype,) * 3
    )
    row_specs = dict(
        q=pl.BlockSpec((1, bq, D), lambda b, qi, ki: (b, qi, 0)),
        k=pl.BlockSpec((1, bk, D), lambda b, qi, ki: (b, ki, 0)),
        v=pl.BlockSpec((1, bk, Dv), lambda b, qi, ki: (b, ki, 0)),
        do=pl.BlockSpec((1, bq, Dv), lambda b, qi, ki: (b, qi, 0)),
        stat=pl.BlockSpec((1, 8, bq), lambda b, qi, ki: (b, 0, qi)),
    )
    dq = pl.pallas_call(
        functools.partial(
            _bwd_dq_kernel, bq=bq, bk=bk, scale=scale, mask=mask,
            dropout_rate=rate,
        ),
        out_shape=_vma_struct((BH, Sq, D), dq_dtype, q, k, v, do),
        grid=(BH, Sq // bq, Sk // bk),
        in_specs=[seed_spec, seed_spec, seed_spec, seed_spec,
                  row_specs["q"], row_specs["k"], row_specs["v"],
                  row_specs["do"], row_specs["stat"], row_specs["stat"]],
        out_specs=row_specs["q"],
        scratch_shapes=[pltpu.VMEM((bq, D), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(seed, qoffs, koffs, bhv, q, k, v, do, lse3, delta3)

    col_specs = dict(
        q=pl.BlockSpec((1, bq, D), lambda b, ki, qi: (b, qi, 0)),
        k=pl.BlockSpec((1, bk, D), lambda b, ki, qi: (b, ki, 0)),
        v=pl.BlockSpec((1, bk, Dv), lambda b, ki, qi: (b, ki, 0)),
        do=pl.BlockSpec((1, bq, Dv), lambda b, ki, qi: (b, qi, 0)),
        stat=pl.BlockSpec((1, 8, bq), lambda b, ki, qi: (b, 0, qi)),
    )
    dk, dv = pl.pallas_call(
        functools.partial(
            _bwd_dkv_kernel, bq=bq, bk=bk, scale=scale, mask=mask,
            dropout_rate=rate,
        ),
        out_shape=[
            _vma_struct((BH, Sk, D), dk_dtype, q, k, v, do),
            _vma_struct((BH, Sk, Dv), dv_dtype, q, k, v, do),
        ],
        grid=(BH, Sk // bk, Sq // bq),
        in_specs=[seed_spec, seed_spec, seed_spec, seed_spec,
                  col_specs["q"], col_specs["k"], col_specs["v"],
                  col_specs["do"], col_specs["stat"], col_specs["stat"]],
        out_specs=[col_specs["k"], col_specs["v"]],
        scratch_shapes=[
            pltpu.VMEM((bk, D), jnp.float32),
            pltpu.VMEM((bk, Dv), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(seed, qoffs, koffs, bhv, q, k, v, do, lse3, delta3)
    return dq, dk, dv


def _flash_bwd_rule(opts, res, do):
    """Flash backward: recompute attention probabilities per tile from the
    saved logsumexp. ``pallas_backward`` (from S, in ``flash_attention``)
    selects the XLA-fused blockwise einsum path or the Pallas one: the fused
    kernel, or the dq / dk+dv pair where a whole dq row would not fit VMEM.
    """
    mask, interpret, bq, bk_fwd, bk, pallas_bwd, rate, scale = opts
    # seed and the bh ids are integral: no tangent.
    int_cts = (
        np.zeros((1,), jax.dtypes.float0),
        np.zeros(res[6].shape, jax.dtypes.float0),
    )
    from ..utils.vma import vma_of

    if pallas_bwd and interpret and vma_of(*res[:3], do):
        # Same limitation the forward's _jnp_reference_forward fallback works
        # around: the Pallas HLO interpreter cannot run on vma-carrying
        # operands (manual regions in interpret mode) — take the jnp backward.
        pallas_bwd = False
    q, k, v, out, lse, seed, bhv = res
    BH, S, D = q.shape
    rep = BH // k.shape[0]
    fused = pallas_bwd and _fused_fits(S, D, q.dtype)
    if not fused:  # whole heads in: off the grouped-query cells' timed path
        k, v = _repeat_groups(k, rep), _repeat_groups(v, rep)
    if not pallas_bwd:
        dq, dk, dv = _jnp_blockwise_bwd(
            mask, bk, rate, (q, k, v, out, lse, seed, bhv), do, scale)
        return dq, _sum_groups(dk, rep), _sum_groups(dv, rep), *int_cts

    delta = jnp.sum(
        do.astype(jnp.float32) * out.astype(jnp.float32), axis=-1
    )  # (BH, S)
    # lse/delta enter the kernels sublane-broadcast as (BH, 8, S) to satisfy
    # the (8, 128) input-tile constraint (same trick as the forward's output).
    lse3 = jnp.broadcast_to(lse[:, None, :], (BH, 8, S))
    delta3 = jnp.broadcast_to(delta[:, None, :], (BH, 8, S))
    args = (q, k, v, do, lse3, delta3, seed, bhv, mask, rate, bq, bk, interpret, scale)
    if fused:
        return *_fused_backward(*args), *int_cts
    dq, dk, dv = _pair_backward(*args)
    return dq, _sum_groups(dk, rep), _sum_groups(dv, rep), *int_cts


_flash.defvjp(_flash_fwd_rule, _flash_bwd_rule)


def _resolve_interpret(interpret: Optional[bool]) -> bool:
    """Kernel-mode dispatch shared by flash and ring attention.

    ``None`` means "what the backend needs": the Mosaic kernel on a TPU,
    Pallas interpret mode elsewhere (the CPU tests). Asking for interpret
    mode on a TPU backend is refused rather than honoured — a chip run must
    never measure the interpreter or the ``jnp`` fallbacks behind it.
    """
    on_tpu = jax.default_backend() == "tpu"
    if interpret is None:
        return not on_tpu
    if interpret and on_tpu:
        raise ValueError(
            "interpret=True on a TPU backend: the chip path runs the Mosaic "
            "kernels only (interpret mode is for CPU tests)"
        )
    return interpret


#: Mesh axes the model shards attention's batch dim over
#: (parallel.strategies.batch_partition_spec) and its head dim over
#: (Megatron tensor parallelism) — the same names ring_attention composes
#: with.
_BATCH_AXES = ("data", "expert")
_HEADS_AXIS = "model"


def _kernel_mesh_axes():
    """(manual axes, batch axes, heads axis) for the kernel's shard_map.

    A Mosaic kernel is a custom call: GSPMD cannot partition it, and jax
    refuses to lower one anywhere but a FULLY manual region ("Mosaic
    kernels cannot be automatically partitioned"). So whenever the context
    mesh spans more than one device the call goes manual over every axis,
    with the batch and head dims split over the axes that shard them.
    ``manual`` is empty — the call lowers bare, as it always has — on one
    device, without a mesh context, and inside an enclosing shard_map,
    which owns the layout (Ulysses is fully manual already; a nested
    shard_map under the partially-manual pipeline schedules does not
    survive jax's transpose, so pipeline x flash on several chips stays
    refused by jax itself).
    """
    m = jax.sharding.get_abstract_mesh()
    split = [n for n in m.axis_names if m.shape[n] > 1]
    if m.manual_axes or not split:
        return frozenset(), (), None
    batch = tuple(a for a in _BATCH_AXES if a in split)
    heads = _HEADS_AXIS if _HEADS_AXIS in split else None
    return frozenset(m.axis_names), batch, heads


def kv_heads_in_kernel(heads: int, kv_heads: int) -> int:
    """The head count k and v enter the kernels with under the mesh this is
    called in: the model's own ``kv_heads``, unless a heads axis splits the
    call into more shards than there are kv heads to hand out whole (its
    degree does not divide ``kv_heads``): then ``heads``, k and v repeated in
    front of the ``shard_map``. Decided from the shapes and the mesh, as
    ``flash_attention`` decides it; the models' counters read it."""
    _, _, heads_axis = _kernel_mesh_axes()
    if heads_axis is None:
        return kv_heads
    degree = jax.sharding.get_abstract_mesh().shape[heads_axis]
    return kv_heads if kv_heads % degree == 0 else heads


#: The least tile a narrow window's band takes (``_window_tile``).
_MIN_WINDOW_TILE = 256


def _window_tile(mask: MaskRule) -> Optional[int]:
    """The (queries, keys) tile of the forward and the fused backward under a
    ``SlidingWindow`` narrower than the default tile: the largest power of two
    that the window holds, and no less than ``_MIN_WINDOW_TILE``. None for any
    other rule and for a window of the default tile or more (1024: the choice
    there stays the default's). At the default tile a window of 512 multiplies
    the area of a window of 1024: every query tile meets a whole trailing tile
    and a diagonal one, 33 % of it live; at its own width 61 %."""
    if not isinstance(mask, SlidingWindow) or mask.window >= _FWD_BLOCK_Q:
        return None
    return max(_MIN_WINDOW_TILE, 1 << (mask.window.bit_length() - 1))


def pick_tiles(
    S: int, D: int, dtype, interpret: bool = False,
    pallas_backward: Optional[bool] = None, block_q: Optional[int] = None,
    block_k: Optional[int] = None, block_k_bwd: Optional[int] = None,
    causal: MaskRule = False,
) -> Tuple[int, int, int, bool]:
    """(block_q, block_k, block_k_bwd, pallas_backward) of one
    ``flash_attention`` call over S positions at q / k width D: the caller's
    where given, else the measured defaults (the table above ``_FWD_BLOCK_Q``;
    the Mosaic path's with ``interpret`` False), which divide S, and under a
    ``BlockDiffusion`` rule each copy of the document (S / 2). Under a
    ``SlidingWindow`` narrower than the default tile the forward's and the
    fused backward's tiles follow the window (``_window_tile``)."""
    if pallas_backward is None:
        # Auto: the measured S-dependent crossover (_PALLAS_BWD_MIN_SEQ).
        # Interpret mode keeps the einsum backward — the Pallas bwd kernels
        # would run under the slow HLO interpreter for no fidelity gain.
        pallas_backward = (not interpret) and S >= _PALLAS_BWD_MIN_SEQ
    whole = causal.seq_len if isinstance(causal, BlockDiffusion) else S
    narrow = _window_tile(causal)  # the band's tile, or None: the defaults
    bq = block_q or _pick_block(whole, narrow or _FWD_BLOCK_Q)
    bk = block_k or _pick_block(whole, narrow or _FWD_BLOCK_K)
    fused = pallas_backward and _fused_fits(S, D, dtype)
    bk_bwd = block_k_bwd or _pick_block(
        whole, (narrow or _FUSED_BWD_BLOCK_K) if fused else _BWD_BLOCK_K
    )
    return bq, bk, bk_bwd, pallas_backward


@functools.partial(
    jax.jit,
    static_argnames=(
        "causal", "interpret", "block_q", "block_k", "block_k_bwd",
        "pallas_backward", "dropout_rate", "scale",
    ),
)
def flash_attention(
    q: jax.Array,  # (B, S, H, D)
    k: jax.Array,  # (B, S, KV, D); H a multiple of KV
    v: jax.Array,  # (B, S, KV, Dv); Dv == D everywhere but latent attention
    causal: MaskRule = False,
    interpret: Optional[bool] = None,
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
    block_k_bwd: Optional[int] = None,
    pallas_backward: Optional[bool] = None,
    dropout_rate: float = 0.0,
    dropout_seed: Optional[jax.Array] = None,
    scale: Optional[float] = None,
) -> jax.Array:
    """Multi-head flash attention over (batch, seq, heads, head_dim) inputs.

    ``causal`` is the mask's rule (``MaskRule``): False none, True causal, a
    ``BlockDiffusion`` over a stream of S = 2L positions, whose tiles must
    lie inside one copy of the document, or a ``SlidingWindow``, under which
    the two kernels' grids walk the band of live tiles and not the square
    (as the forward's does under a ``BlockDiffusion``); a grid step whose tile
    holds no allowed pair multiplies nothing and brings nothing.

    q and k share one width, v and the output another (latent attention:
    192-wide keys over 128-wide values, no padding of either); ``scale``
    multiplies q k^T and defaults to 1 / sqrt(width of q).

    k and v come at the model's own kv head count (grouped-query attention:
    query heads [j * rep, (j + 1) * rep) read kv head j, rep = H // KV, the
    grouping ``jnp.repeat(axis=2)`` gives): the kernels' index maps find a
    query head's kv head and no repeated copy of k or v is made; dk and dv
    come back at KV heads. Only under a mesh whose heads axis does not
    divide KV are they repeated, in front of the ``shard_map``
    (``kv_heads_in_kernel``).

    Forward and backward take separate K-block sizes because their optima
    differ on v5e (see _FWD_BLOCK_* notes above).

    ``dropout_rate`` > 0 (with a uint32 scalar/1-vector ``dropout_seed``)
    applies attention-probability dropout INSIDE the kernel — parity with the
    reference's ``nn.MultiheadAttention(dropout=...)`` (train_harness.py:116)
    that earlier rounds had to document as a deviation. The keep mask is a
    stateless hash of absolute coordinates, so fwd/bwd agree despite their
    different tilings. With ``dropout_seed=None`` the rate is ignored and a
    warning is emitted (the model's deterministic/no-key dropout convention).

    Under a mesh context that spans several devices the call shard_maps
    itself (``_kernel_mesh_axes``); the (batch, head) ids the
    dropout hash sees enter as sharded iotas, so each shard hashes its
    GLOBAL ids and the loss matches a one-device run of the same batch.
    """
    B, S, H, D = q.shape
    KV = k.shape[2]
    if H % KV or v.shape[2] != KV:
        raise ValueError(
            f"{H} query heads over k {k.shape} / v {v.shape}: k and v share a "
            "head count that divides the query heads'"
        )
    interpret = _resolve_interpret(interpret)
    bq, bk, bk_bwd, pallas_backward = pick_tiles(
        S, D, q.dtype, interpret, pallas_backward, block_q, block_k, block_k_bwd, causal
    )
    if S % bq != 0 or S % bk != 0 or S % bk_bwd != 0:
        raise ValueError(
            f"block sizes (block_q={bq}, block_k={bk}, block_k_bwd={bk_bwd}) "
            f"must divide seq_len={S}"
        )
    if isinstance(causal, BlockDiffusion):
        causal.check_tiles(S, bq, bk, bk_bwd)
    if dropout_seed is None:
        _warn_seedless_dropout(dropout_rate, "flash_attention")
        dropout_rate = 0.0
        seed = jnp.zeros((1,), jnp.uint32)
    else:
        seed = jnp.asarray(dropout_seed, jnp.uint32).reshape((1,))
    if not 0.0 <= dropout_rate < 1.0:
        raise ValueError(f"dropout_rate must be in [0, 1), got {dropout_rate}")
    opts = (causal, interpret, bq, bk, bk_bwd, pallas_backward, dropout_rate, scale)

    def local(ql, kl, vl, seed_l, b_ids, h_ids):
        # (Bl, S, Hl, D) -> (Bl*Hl, S, D): one grid row per (batch, query
        # head) pair, keyed for dropout by its global id b*H + h; k and v
        # (Bl*KVl, S, .), row b // rep of them that grid row's.
        Bl, Hl = ql.shape[0], ql.shape[2]

        def to_bhsd(t):
            return t.transpose(0, 2, 1, 3).reshape(Bl * t.shape[2], S, t.shape[-1])

        bhv = (b_ids[:, None] * H + h_ids[None, :]).reshape(Bl * Hl)
        out = _flash(opts, to_bhsd(ql), to_bhsd(kl), to_bhsd(vl), seed_l, bhv)
        return out.reshape(Bl, Hl, S, vl.shape[-1]).transpose(0, 2, 1, 3)

    b_ids = jnp.arange(B, dtype=jnp.int32)
    h_ids = jnp.arange(H, dtype=jnp.int32)
    manual, batch_axes, heads_axis = _kernel_mesh_axes()
    if not manual:
        return local(q, k, v, seed, b_ids, h_ids)
    if kv_heads_in_kernel(H, KV) != KV:
        # the heads axis cuts a kv head's group: every shard needs a part of
        # a kv head's queries, so k and v enter a query head each
        k, v = jnp.repeat(k, H // KV, axis=2), jnp.repeat(v, H // KV, axis=2)
    # The id vectors ride in as P(axis)-sharded iotas: each shard's slice IS
    # its global ids, whatever the order of the axes that split the dim.
    spec = P(batch_axes or None, None, heads_axis, None)
    return jax.shard_map(
        local,
        in_specs=(spec, spec, spec, P(), P(batch_axes or None), P(heads_axis)),
        out_specs=spec,
        axis_names=manual,
    )(q, k, v, seed, b_ids, h_ids)


def reference_attention(q, k, v, causal: MaskRule = False, scale=None) -> jax.Array:
    """Materialized-softmax attention for correctness comparison (same math
    as models.tinygpt's in-model path, without dropout)."""
    scale = _softmax_scale(scale, q.shape[-1])
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32) * scale
    if isinstance(causal, _RULES):
        rows = jnp.arange(q.shape[1], dtype=jnp.int32)
        s = jnp.where(causal.allowed(rows[:, None], rows[None, :]), s, NEG_INF)
    elif causal:
        S = q.shape[1]
        mask = jnp.tril(jnp.ones((S, S), bool))
        s = jnp.where(mask, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum(
        "bhqk,bkhd->bqhd", p.astype(q.dtype), v, preferred_element_type=jnp.float32
    ).astype(q.dtype)
