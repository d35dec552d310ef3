"""The short depthwise causal convolutions of three mixers, forward and
backward, as Mosaic calls on the float32 slab (a head's lanes of a tile) that a
call holds after the taps: ``qkv_prologue`` (a KDA layer's: the convolutions,
SiLU and each head of q and k over its l2 norm, ``kda_conv_fwd`` /
``kda_conv_bwd``, a call a third of the columns each way, nothing kept but x),
``conv_silu`` (a Mamba-2 block's, with its bias), ``causal_conv`` (the same
kernels without the epilogue) and ``gated_conv`` (LFM2's whole middle, c *
conv(b * x) over the thirds of one operand: ``sconv_fwd`` / ``sconv_bwd``).
Their ``jnp`` path (another backend, or widths that are not whole 128-lane
tiles) is XLA's grouped convolution and the chains the tests hold the kernels
to; each function's docstring has its own.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .kda import head_sums, kernel_mode, over_heads  # noqa: F401  (kernel_mode: the callers')

_CONV_ROWS, _CONV_COLUMNS = 512, 512  # a tile of the convolution's kernels
L2NORM_EPS = 1e-6  # the published kernel's


def silu_l2norm(y, heads: int):
    """The ``jnp`` chain behind the convolution: y (B, S, 3 x H x d), q's, k's
    and v's columns in that order -> (q, k, v) (B, S, H x d) each: SiLU on
    all three, then q and k divided a head by the head's l2 norm in float32
    (``L2NORM_EPS`` under the root). What ``qkv_prologue``'s kernels are held
    to, and its path where they do not run."""
    a = jax.nn.silu(y)
    width = y.shape[-1] // 3
    q, k, v = (a[..., i * width:(i + 1) * width] for i in range(3))

    def l2norm(t):
        tf = t.astype(jnp.float32)
        inverse = lax.rsqrt(head_sums(tf * tf, heads) + L2NORM_EPS)
        return (tf * over_heads(inverse, width // heads)).astype(t.dtype)

    return l2norm(q), l2norm(k), v


def _conv_tile(S, width, head_dim):
    """(rows, columns) of a tile over ``width`` columns: whole heads where
    there are heads."""
    if head_dim is None:
        return math.gcd(S, _CONV_ROWS), math.gcd(width, _CONV_COLUMNS)
    heads = math.gcd(width // head_dim, max(1, _CONV_COLUMNS // head_dim))
    return math.gcd(S, _CONV_ROWS), heads * head_dim


def _window(ext, taps, K, start, rows, lanes):
    """x and y = sum_i taps_i x_{t-K+1+i}, float32, over ``rows`` rows from
    ``start`` (a multiple of 8) and the lanes ``lanes`` of the tile that
    ``ext`` holds behind 8 rows: one aligned read of the rows and the 8 before
    them, the K offsets taken of the value (there is no read at an offset that
    is not whole 8-row tiles from a start known only at run time)."""
    window = ext[pl.ds(start, rows + 8), lanes]
    x = window[8:]
    y = x * taps[K - 1:K, lanes]
    for i in range(K - 1):
        y = y + window[8 - (K - 1 - i):8 - (K - 1 - i) + rows] * taps[i:i + 1, lanes]
    return x, y


def _sigmoid(y):
    """1 / (1 + exp(-y)) in float32: the reciprocal as the EUP's approximation
    and two Newton steps, each of which squares its error (whatever the
    approximation's bits: 8 would do). The denominator lies in [1, 1 + e^80],
    so the infinities and NaNs that Mosaic's own division spends a dozen more
    vector operations an element on cannot come."""
    d = 1.0 + jnp.exp(-jnp.maximum(y, -80.0))
    r = pl.reciprocal(d, approx=True)
    r = r * (2.0 - d * r)
    return r * (2.0 - d * r)


def _lane_sum(x):
    """The sum over the lanes of a float32 (rows, d), as a column that
    multiplies back over the lanes: the XLU's lane reduction, float32 adds.
    (As three exact bfloat16 terms against a matrix of ones on the idle MXU,
    which is what ``ops/rotary.py`` found faster in its pass, these kernels
    read 1.99 | 6.90 ms a layer forward | forward + backward where this reads
    1.67 | 5.94, on a v5e at (1, 16384, 12288): the terms' splits are vector
    work, and vector work is these kernels' bound.)"""
    return jnp.sum(x, axis=-1, keepdims=True)


def _over_slabs(columns, head_dim, body):
    """``body(lanes)`` for a tile's columns a head at a time (128 lanes where
    there are no heads): a loop, so that a kernel holds one head's code."""
    d = head_dim or 128
    lax.fori_loop(0, columns // d, lambda j, _: body(pl.ds(pl.multiple_of(j * d, d), d)), None)


def _pass_rows(rows, norm):
    """Rows of a head's slab a pass of the kernels takes from its read to its
    write. Under the norm all of the tile's: the lane reduction in the middle
    of the chain makes the compiler walk it a vreg at a time. Without it a
    (512, 128) value is 64 vregs that every elementwise operation stores and
    loads again (by its schedule for a v5e 3,616 bundles a tile forward where
    128 rows a pass read 2,613, and q's and k's tiles 3,037 whole where they
    read 4,187 in passes of 128)."""
    return rows if norm else math.gcd(rows, 128)


def _conv_fwd_kernel(K, head_dim, norm, bias, x, tail, taps, y, ext):
    """A (rows, columns) tile of y_t = sum_i taps_i x_{t-K+1+i}: the tile
    behind its 8 preceding rows (zeros before the sequence) in ``ext``, read
    back a head's lanes at a time. Under ``bias`` the row behind the K taps is
    a bias a column, added to y. With ``head_dim`` the epilogue on the
    float32 slab: a = silu(y), and under ``norm`` the head's a (sum of a^2
    over its lanes + eps)^-1/2; one cast, one write."""
    rows, columns = x.shape
    ext[0:8, :] = jnp.where(pl.program_id(2) == 0, 0.0, tail[...].astype(jnp.float32))
    ext[8:, :] = x[...].astype(jnp.float32)
    n = _pass_rows(rows, norm)

    def slab(lanes):
        def rows_from(i, _):
            start = pl.multiple_of(i * n, n)
            _, a = _window(ext, taps, K, start, n, lanes)
            if bias:
                a = a + taps[K:K + 1, lanes]
            if head_dim is not None:
                a = a * _sigmoid(a)
                if norm:
                    a = a * lax.rsqrt(_lane_sum(a * a) + L2NORM_EPS)
            y[pl.ds(start, n), lanes] = a.astype(y.dtype)

        lax.fori_loop(0, rows // n, rows_from, None)

    _over_slabs(columns, head_dim, slab)


def _conv_bwd_kernel(K, head_dim, norm, bias, *refs):
    """The transposes over a tile: dx_t = sum_i taps_i dy_{t+K-1-i} (the tile
    before its 8 following rows, zeros after the sequence), and dtaps_i = sum
    over the positions of x_{t-K+1+i} dy_t, summed into a block that stays
    resident over the batch and the tiles of rows. A head's slab is walked
    from its last rows to its first, each pass handing the next its first 8
    rows of dy. With ``head_dim`` the cotangent that comes in is the
    epilogue's output's, and dy is made of it here, on the tile's rows and the
    8 after them: y, the sigmoid s and a head's inverse norm r are computed
    again from x (its 8 rows after the tile come in too), da = r (dn - a r^2
    sum_head(dn a)) under ``norm`` (dn otherwise), dy = da (s + a (1 - s)).
    Under ``bias`` y has the row behind the taps added, and that row of dtaps
    takes the sum of dy over the positions."""
    if head_dim is None:
        (x, tail, taps, dy, head), (dx, dtaps, ext) = refs[:5], refs[-3:]
    else:
        (x, tail, taps, after, dy, head), (dx, dtaps, ext) = refs[:6], refs[-3:]
    rows, columns = x.shape
    first = (pl.program_id(1) == 0) & (pl.program_id(2) == 0)
    last = pl.program_id(2) == pl.num_programs(2) - 1
    ext[0:8, :] = jnp.where(pl.program_id(2) == 0, 0.0, tail[...].astype(jnp.float32))
    ext[8:rows + 8, :] = x[...].astype(jnp.float32)
    if head_dim is not None:  # any finite rows do after the sequence's end: their dy is 0
        ext[rows + 8:, :] = after[...].astype(jnp.float32)

    @pl.when(first)
    def _():
        dtaps[...] = jnp.zeros_like(dtaps)

    n = _pass_rows(rows, norm)

    def slab(lanes):
        def pulled_back(start, count, d):
            """x and dy over ``count`` rows from ``start``, of the cotangent that came in."""
            if head_dim is None:
                return ext[pl.ds(start + 8, count), lanes], d
            x, y = _window(ext, taps, K, start, count, lanes)
            if bias:
                y = y + taps[K:K + 1, lanes]
            s = _sigmoid(y)
            a = y * s
            if norm:
                r = lax.rsqrt(_lane_sum(a * a) + L2NORM_EPS)
                d = r * (d - a * (r * r * _lane_sum(d * a)))
            return x, d * (s + a * (1.0 - s))

        def rows_from(i, after_d):
            start = pl.multiple_of((rows // n - 1 - i) * n, n)
            x, d = pulled_back(start, n, dy[pl.ds(start, n), lanes].astype(jnp.float32))
            # dy_{t+K-1-j} for the K taps j: the pass's dy before the 8 rows after it
            window = jnp.concatenate([d, after_d], axis=0)
            later = [window[K - 1 - j:K - 1 - j + n] for j in range(K)]
            acc = later[K - 1] * taps[K - 1:K, lanes]
            for j in range(K - 1):
                acc = acc + later[j] * taps[j:j + 1, lanes]
            dx[pl.ds(start, n), lanes] = acc.astype(dx.dtype)
            # x_u dy_{u+K-1-j} over the tile's u: over all tiles, every pair of dtaps_j once
            for j in range(K):
                dtaps[j:j + 1, lanes] += jnp.sum(x * later[j], axis=0, keepdims=True)
            if bias:
                dtaps[K:K + 1, lanes] += jnp.sum(d, axis=0, keepdims=True)
            return d[0:8]

        after_d = jnp.where(last, 0.0, head[:, lanes].astype(jnp.float32))
        if head_dim is not None:
            _, after_d = pulled_back(rows, 8, after_d)
        lax.fori_loop(0, rows // n, rows_from, after_d)

    _over_slabs(columns, head_dim, slab)


@functools.lru_cache(maxsize=64)
def _conv_call(backward, B, S, width, part, parts, K, dtype, head_dim, norm, interpret,
               bias=False):
    """One direction's ``pallas_call`` over ``width`` columns from column
    ``part`` x ``width`` of an x of ``parts`` x ``width``, made once a process
    (as ``flash_attention._forward_call``, and for its reason). A grid of
    (tiles of columns, batch, tiles of rows); a tile of x comes with the 8
    rows before it and the backward's cotangent with the 8 after it (clamped
    at the sequence's ends, where the kernels put zeros). Operands: forward
    (x, x, taps) -> y (B, S, width); backward (x, x, taps, [x,] dy, dy[, dx so
    far]) -> (dx (B, S, parts x width), of which this call writes its part's
    columns and keeps the rest of ``dx so far``, aliased; dtaps (K, width)).
    Under ``bias`` taps and dtaps have one more row, the bias a column."""
    rows, columns = _conv_tile(S, width, head_dim)
    offset = part * (width // columns)
    tile = lambda at: pl.BlockSpec((None, rows, columns), lambda c, b, n: (b, n, c + at))
    eight = lambda row, at: pl.BlockSpec((None, 8, columns), lambda c, b, n: (b, row(n), c + at))
    before = lambda n: jnp.maximum(n * (rows // 8) - 1, 0)
    after = lambda n: jnp.minimum((n + 1) * (rows // 8), S // 8 - 1)
    taps = lambda at: pl.BlockSpec((K + bias, columns), lambda c, b, n: (0, c + at))
    grid = (width // columns, B, S // rows)
    fused = head_dim is not None
    if not backward:
        return pl.pallas_call(
            functools.partial(_conv_fwd_kernel, K, head_dim, norm, bias), grid=grid,
            in_specs=[tile(offset), eight(before, offset), taps(offset)], out_specs=tile(0),
            out_shape=jax.ShapeDtypeStruct((B, S, width), dtype),
            scratch_shapes=[pltpu.VMEM((rows + 8, columns), jnp.float32)],
            interpret=interpret, name="kda_conv_fwd",
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "parallel")),
        )
    in_specs = [tile(offset), eight(before, offset), taps(offset)] + (
        [eight(after, offset)] if fused else []) + [tile(0), eight(after, 0)] + (
        [pl.BlockSpec(memory_space=pl.ANY)] if part else [])
    return pl.pallas_call(
        functools.partial(_conv_bwd_kernel, K, head_dim, norm, bias), grid=grid,
        in_specs=in_specs, out_specs=[tile(offset), taps(0)],
        out_shape=[jax.ShapeDtypeStruct((B, S, parts * width), dtype),
                   jax.ShapeDtypeStruct((K + bias, width), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((rows + 8 + 8 * fused, columns), jnp.float32)],
        input_output_aliases={len(in_specs) - 1: 0} if part else {},
        interpret=interpret, name="kda_conv_bwd",
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
    )


def _conv_parts(head_dim, bias=False):
    """The calls a direction over x's columns, as each one's ``norm``: one
    over all of them for the bare convolution and for the one with a bias
    (``conv_silu``: SiLU behind it, no norm); q's, k's and v's thirds apart
    under the KDA epilogue, which normalises the first two."""
    return (False,) if head_dim is None or bias else (True, True, False)


def _conv_run(backward, part, head_dim, interpret, x, taps, *cotangent, bias=False):
    norms = _conv_parts(head_dim, bias)
    (B, S, C), K = x.shape, taps.shape[0] - bias
    call = _conv_call(backward, B, S, C // len(norms), part, len(norms), K, x.dtype,
                      head_dim, norms[part], interpret, bias)
    # one trace for the primal and the forward rule: see flash_attention._flash_forward
    with jax.sharding.use_abstract_mesh(jax.sharding.get_abstract_mesh()):
        return call(x, x, taps, *cotangent)


def _conv_forward(x, taps, head_dim, interpret, bias):
    return [_conv_run(False, part, head_dim, interpret, x, taps, bias=bias)
            for part in range(len(_conv_parts(head_dim, bias)))]


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def _conv(x, taps, head_dim, interpret, bias=False):
    """x (B, S, C), taps (K, C) float32 (under ``bias`` (K + 1, C): the bias a
    column behind the taps) -> [a part's (B, S, C / parts)]."""
    return _conv_forward(x, taps, head_dim, interpret, bias)


def _conv_fwd(x, taps, head_dim, interpret, bias=False):
    return _conv_forward(x, taps, head_dim, interpret, bias), (x, taps)


def _conv_bwd(head_dim, interpret, bias, residuals, cotangents):
    """A call a part: each writes its columns of the one dx, which the next
    takes aliased, so that neither the cotangents nor the parts of dx are
    ever set side by side in a copy."""
    x, taps = residuals
    after = () if head_dim is None else (x,)
    so_far, dtaps = (), []
    for part, dy in enumerate(cotangents):
        dx, dtaps_part = _conv_run(
            True, part, head_dim, interpret, x, taps, *after, dy, dy, *so_far, bias=bias)
        so_far = (dx,)
        dtaps.append(dtaps_part)
    return dx, jnp.concatenate(dtaps, axis=-1)


_conv.defvjp(_conv_fwd, _conv_bwd)


def _conv_reference(x, taps):
    K, C = taps.shape
    return lax.conv_general_dilated(
        x, taps[:, None, :].astype(x.dtype), window_strides=(1,), padding=[(K - 1, 0)],
        dimension_numbers=("NWC", "WIO", "NWC"), feature_group_count=C)


def conv_fits(seq_len: int, taps: int, width: int) -> bool:
    """Whether the convolution's kernels take the operand: K <= 8 taps, S in
    whole 8-row tiles, and ``width`` (the columns, or a head's where the
    epilogue runs) in whole 128-lane tiles."""
    return taps <= 8 and seq_len % 8 == 0 and width % 128 == 0


def causal_conv(x, taps, *, interpret: Optional[bool] = None):
    """The depthwise causal convolution over positions, bare: x (B, S, C) in
    the compute type, taps (K, C), y_t = sum_i taps_i x_{t-K+1+i} with zeros
    before the sequence; float32 products and sums, x's dtype out.
    ``interpret`` as ``kda_flat``'s: None is XLA's grouped convolution (any
    backend, any width; on a TPU it takes minutes to compile at 12,288 groups,
    which is why the kernels exist), False the two Mosaic calls
    ``kda_conv_fwd`` / ``kda_conv_bwd`` without their epilogue, where
    ``conv_fits``. A KDA layer calls ``qkv_prologue``; this is the same
    kernels' first half, kept for the tests and ``scripts/microbench_kda.py
    --prep``, whose baseline is this and the ``jnp`` chain behind it."""
    if interpret is None or not conv_fits(x.shape[1], taps.shape[0], x.shape[2]):
        return _conv_reference(x, taps)
    return _conv(x, taps.astype(jnp.float32), None, interpret, False)[0]


def conv_silu(x, taps, bias, *, interpret: Optional[bool] = None):
    """silu(the depthwise causal convolution of x + a bias a column): what
    stands between a Mamba-2 mixer's projection and its scan (``ops/ssd.py``).
    x (B, S, C) in the compute type, taps (K, C), bias (C,) -> (B, S, C) in
    x's dtype. ``interpret`` False (True: interpreted) where ``conv_fits`` at
    128 lanes: ``causal_conv``'s two Mosaic calls with the bias as the row
    behind the taps and SiLU as their epilogue on the float32 slab, one call
    over all the columns each way (``kda_conv_fwd`` / ``kda_conv_bwd``: the
    backward computes y and the sigmoid again from x and gives the bias's
    gradient in the taps' last row). None, or an operand the kernels do not
    take: XLA's grouped convolution and ``jax.nn.silu`` in float32, which is
    also what the tests hold the kernels to."""
    if interpret is None or not conv_fits(x.shape[1], taps.shape[0], x.shape[2]):
        y = _conv_reference(x.astype(jnp.float32), taps.astype(jnp.float32))
        return jax.nn.silu(y + bias.astype(jnp.float32)).astype(x.dtype)
    both = jnp.concatenate([taps.astype(jnp.float32), bias.astype(jnp.float32)[None]], axis=0)
    return _conv(x, both, 128, interpret, True)[0]


def qkv_prologue(x, taps, heads: int, *, interpret: Optional[bool] = None):
    """What stands between a KDA layer's q, k, v projection and the
    recurrence, in one pass a direction: x (B, S, 3 x H x d) in the compute
    type, q's, k's and v's columns in that order, taps (K, 3 x H x d) ->
    (q, k, v), (B, S, H x d) each: the depthwise causal convolution
    (``causal_conv``), SiLU, and on q and k each head divided by its l2 norm
    (``L2NORM_EPS`` under the root). ``interpret`` False (True: interpreted)
    where ``conv_fits`` at the head's width: ``kda_conv_fwd`` computes all of
    it on the float32 slab it holds after the taps (the sum of squares a
    float32 lane reduction; nothing is rounded to the compute type before the
    one cast at the end) and ``kda_conv_bwd`` takes the three cotangents back
    to dx and dtaps, computing y, the sigmoid and the inverse norms again
    from x: nothing is kept but x, and each element of q, k, v is read once
    and written once a direction. A call a third of the columns (q, k, v):
    three arrays out, three cotangents in, no slice and no concatenation.
    None, or an operand the kernels do not take: XLA's grouped convolution
    and ``silu_l2norm``, the ``jnp`` chain, which is also what the tests hold
    the kernels to."""
    head_dim = x.shape[-1] // (3 * heads)
    if interpret is None or not conv_fits(x.shape[1], taps.shape[0], head_dim):
        return silu_l2norm(_conv_reference(x, taps), heads)
    return tuple(_conv(x, taps.astype(jnp.float32), head_dim, interpret, False))


_GATED_ROWS = 128  # rows of a tile of the gated convolution's kernels, all C columns wide


def _gated_prologue(b, b_tail, x, x_tail, ext):
    """v = b x of a tile in float32 behind its 8 preceding rows (zeros before
    the sequence), into ``ext``."""
    tail = b_tail[...].astype(jnp.float32) * x_tail[...].astype(jnp.float32)
    ext[0:8, :] = jnp.where(pl.program_id(1) == 0, 0.0, tail)
    ext[8:, :] = b[...].astype(jnp.float32) * x[...].astype(jnp.float32)


def _gated_fwd_kernel(K, b, b_tail, c, x, x_tail, taps, y, ext):
    """A (rows, C) tile of y = c * conv(b * x): the prologue v = b x, then a
    128-lane slab at a time the taps over it and the gate c as the epilogue;
    one cast, one write."""
    rows, columns = b.shape
    _gated_prologue(b, b_tail, x, x_tail, ext)

    def slab(lanes):
        _, w = _window(ext, taps, K, 0, rows, lanes)
        y[:, lanes] = (w * c[:, lanes].astype(jnp.float32)).astype(y.dtype)

    _over_slabs(columns, None, slab)


def _gated_bwd_kernel(K, b, b_tail, c, c_after, x, x_tail, taps, dy, dy_after, dbcx, dtaps, ext):
    """The transposes over a (rows, C) tile: v = b x and w = conv(v) computed
    again from the operand's rows and the 8 before them; dc = dy w; dw = dy c
    on the tile's rows and the 8 after them (zeros after the sequence); dv_t =
    sum_i taps_i dw_{t+K-1-i}; db = dv x, dx = dv b, written as the three
    thirds of one (rows, 3 C) tile; dtaps_i = sum over the positions of
    v_{t-K+1+i} dw_t, summed into a block that stays resident over the grid."""
    rows, columns = b.shape
    first = (pl.program_id(0) == 0) & (pl.program_id(1) == 0)
    last = pl.program_id(1) == pl.num_programs(1) - 1
    _gated_prologue(b, b_tail, x, x_tail, ext)

    @pl.when(first)
    def _():
        dtaps[...] = jnp.zeros_like(dtaps)

    def slab(j, _):
        at = lambda third: pl.ds(pl.multiple_of(third * columns + j * 128, 128), 128)
        lanes = at(0)
        v, w = _window(ext, taps, K, 0, rows, lanes)
        d = dy[:, lanes].astype(jnp.float32)
        dbcx[:, at(1)] = (d * w).astype(dbcx.dtype)
        after = jnp.where(last, 0.0, dy_after[:, lanes].astype(jnp.float32)
                          * c_after[:, lanes].astype(jnp.float32))
        window = jnp.concatenate([d * c[:, lanes].astype(jnp.float32), after], axis=0)
        later = [window[K - 1 - i:K - 1 - i + rows] for i in range(K)]  # dw_{t+K-1-i}
        dv = later[K - 1] * taps[K - 1:K, lanes]
        for i in range(K - 1):
            dv = dv + later[i] * taps[i:i + 1, lanes]
        dbcx[:, lanes] = (dv * x[:, lanes].astype(jnp.float32)).astype(dbcx.dtype)
        dbcx[:, at(2)] = (dv * b[:, lanes].astype(jnp.float32)).astype(dbcx.dtype)
        for i in range(K):
            dtaps[i:i + 1, lanes] += jnp.sum(v * later[i], axis=0, keepdims=True)

    lax.fori_loop(0, columns // 128, slab, None)


@functools.lru_cache(maxsize=16)
def _gated_call(backward, B, S, C, K, dtype, interpret):
    """One direction's ``pallas_call`` of the gated convolution over an operand
    (B, S, 3 C) whose thirds are b, c and x, in ``_conv_call``'s frame: a grid of
    (batch, tiles of rows), a tile all C columns wide; b's, c's and x's tiles
    are three block specs on the one operand (no sliced copy), b's and x's come
    with the 8 rows before them, and the backward's cotangent and c with the 8
    after (clamped at the sequence's ends, where the kernels put zeros).
    Operands: forward (bcx x 5, taps) -> y (B, S, C); backward (bcx x 6, taps,
    dy, dy) -> (dbcx (B, S, 3 C), one tile a step across its thirds; dtaps (K,
    C))."""
    rows = math.gcd(S, _GATED_ROWS)
    tile = lambda third: pl.BlockSpec((None, rows, C), lambda b, n: (b, n, third))
    eight = lambda row, third: pl.BlockSpec((None, 8, C), lambda b, n: (b, row(n), third))
    before = lambda n: jnp.maximum(n * (rows // 8) - 1, 0)
    after = lambda n: jnp.minimum((n + 1) * (rows // 8), S // 8 - 1)
    taps = pl.BlockSpec((K, C), lambda b, n: (0, 0))
    grid = (B, S // rows)
    scratch = [pltpu.VMEM((rows + 8, C), jnp.float32)]
    if not backward:
        return pl.pallas_call(
            functools.partial(_gated_fwd_kernel, K), grid=grid,
            in_specs=[tile(0), eight(before, 0), tile(1), tile(2), eight(before, 2), taps],
            out_specs=tile(0), out_shape=jax.ShapeDtypeStruct((B, S, C), dtype),
            scratch_shapes=scratch, interpret=interpret, name="sconv_fwd",
            compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "parallel")),
        )
    return pl.pallas_call(
        functools.partial(_gated_bwd_kernel, K), grid=grid,
        in_specs=[tile(0), eight(before, 0), tile(1), eight(after, 1), tile(2), eight(before, 2),
                  taps, tile(0), eight(after, 0)],
        out_specs=[pl.BlockSpec((None, rows, 3 * C), lambda b, n: (b, n, 0)), taps],
        out_shape=[jax.ShapeDtypeStruct((B, S, 3 * C), dtype),
                   jax.ShapeDtypeStruct((K, C), jnp.float32)],
        scratch_shapes=scratch, interpret=interpret, name="sconv_bwd",
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary", "arbitrary")),
    )


def _gated_run(backward, interpret, bcx, taps, *cotangent):
    (B, S, C3), K = bcx.shape, taps.shape[0]
    call = _gated_call(backward, B, S, C3 // 3, K, bcx.dtype, interpret)
    operands = (bcx,) * (6 if backward else 5)
    # one trace for the primal and the forward rule: see flash_attention._flash_forward
    with jax.sharding.use_abstract_mesh(jax.sharding.get_abstract_mesh()):
        return call(*operands, taps, *cotangent)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _gated(bcx, taps, interpret):
    return _gated_run(False, interpret, bcx, taps)


def _gated_fwd(bcx, taps, interpret):
    return _gated_run(False, interpret, bcx, taps), (bcx, taps)


def _gated_bwd(interpret, residuals, dy):
    bcx, taps = residuals
    return tuple(_gated_run(True, interpret, bcx, taps, dy, dy))  # (dbcx, dtaps)


_gated.defvjp(_gated_fwd, _gated_bwd)


def gated_conv(bcx, taps, *, interpret: Optional[bool] = None):
    """A gated short convolution's middle (LFM2's conv mixer between its two
    projections): bcx (B, S, 3 C) in the compute type, b's, c's and x's columns
    in that order, taps (K, C) -> c * conv(b * x), (B, S, C) in bcx's dtype: the
    depthwise causal convolution over positions with zeros before the sequence,
    no bias and no activation; float32 products and sums. ``interpret`` False
    (True: interpreted) where ``conv_fits`` at the C columns: the two gates are
    the prologue and the epilogue of ``causal_conv``'s kernels on the float32
    slab, one Mosaic call a direction, ``sconv_fwd`` / ``sconv_bwd``; the three
    thirds of the operand are found by the calls' block specs, the backward
    computes b x and its convolution again from the operand and gives db, dc and
    dx as one (B, S, 3 C) result beside the taps' gradient: nothing is kept but
    the operand. None, or an operand the kernels do not take: the ``jnp`` chain
    over XLA's grouped convolution, which is also what the tests hold the
    kernels to."""
    C = bcx.shape[-1] // 3
    if interpret is None or not conv_fits(bcx.shape[1], taps.shape[0], C):
        b, c, x = (bcx[..., i * C:(i + 1) * C].astype(jnp.float32) for i in range(3))
        return (c * _conv_reference(b * x, taps.astype(jnp.float32))).astype(bcx.dtype)
    return _gated(bcx, taps.astype(jnp.float32), interpret)
