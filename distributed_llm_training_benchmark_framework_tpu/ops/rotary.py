"""QK-norm and rotary in one pass over q and k (Pallas, TPU).

Between the q / k projections and the flash kernels a layer normalises each
head of q and k (``qk_norm == "head"``: an RMS norm over the head's lanes) and
rotates it (rotate-half rotary). Written in ``jnp`` (``models/tinygpt.py``:
``_rms_norm`` then ``_rope``) XLA makes a chain of f32 passes of it: a bare
convert to f32, relayouts of the two half-heads, two reduce fusions a tensor,
and the same again in remat's second run and, transposed, in the backward
(3.36 GB forward and 4.82 GB backward a layer at 16,384 rows of 32 / 4 heads x
128; what one pass needs is 0.30 and 0.45). Here it is one kernel a direction:

``qk_prologue_fwd``: a block of rows of q as (rows, H x D) and of k as (rows,
KV x D) is read once, each D-wide head taken to f32, normalised (the mean of
squares is a lane reduction), rotated in registers (rotate-half is one lane
rotation by D / 2 against sin with the half's sign folded in: no slice, no
concatenate), cast once, written once.

``qk_prologue_bwd``: the cotangent and the projection's output are read once,
the cotangent un-rotated, the norm's backward taken with the inverse rms
computed again from the input (nothing is saved but what the caller already
holds), the gradient written once; the two scale gradients leave as one
(8, D) partial sum a grid step and are added outside.

Where only the leading ``rotary_dim`` lanes of a head rotate (a partial rotary
factor: lane j pairs with j + rotary_dim / 2 inside them, the rest pass), the
head still moves as whole vregs and the pairing is two lane rotations, by
rotary_dim / 2 up and down, each against a sin that is zero off its half; the
table holds cos and sin in its first ``rotary_dim`` lanes. Whole heads lower as
they did.

f32 inside, the operands' dtype at both ends. The norm's result is not rounded
to the operands' dtype before the rotation, as the ``jnp`` chain does: one
rounding fewer. cos and sin come in as one (S, D) f32 array (``table``), built
once a step and kind of layer and not once a call.

Dispatch: ``fits`` says whether an operand is the pass's (from its shape) and
``kernel_mode`` whether this backend runs it (the Mosaic kernels on a TPU;
elsewhere the caller keeps its ``jnp`` chain, which is also the reference the
tests hold the pass to). Under a mesh of several devices ``qk_prologue``
shard_maps itself over the axes that split the batch and the heads, as
``flash_attention`` does: rows and heads are independent, so nothing is
exchanged but the sum of the scale gradients, which the shard_map's transpose
adds.
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

from ..utils.vma import pcast_like, vma_of
from .flash_attention import _kernel_mesh_axes, _struct

#: Rows of q and k a grid step moves. At 256 rows of 4096 + 512 lanes in bf16
#: the backward's three streams, double-buffered, are 14 MB of VMEM.
_BLOCK_ROWS = 256
#: Rows the body holds in registers at a time: a head's chain over 32 rows is
#: 4 f32 vregs a temporary, so nothing spills between the load and the store.
_CHUNK_ROWS = 32
_VMEM_HEADROOM = 16 * 2**20


def fits(head_dim: int, seq_len: int, rotary_dim: Optional[int] = None) -> bool:
    """Whether q and k of ``seq_len`` rows and heads of ``head_dim``, of which
    the leading ``rotary_dim`` lanes rotate (None: all), are the pass's
    operand: heads of whole 128-lane vregs, rows that cut into chunks, an even
    part of a head. A 64-wide head is not, nor the 64 of 192 lanes latent
    attention rotates."""
    part = head_dim if rotary_dim is None else rotary_dim
    return (head_dim % 128 == 0 and seq_len % _CHUNK_ROWS == 0
            and 0 < part <= head_dim and part % 2 == 0)


def _part(dim: int, rotary_dim: Optional[int]) -> Optional[int]:
    """``rotary_dim`` where it is a proper part of the head, else None."""
    return None if rotary_dim in (None, dim) else rotary_dim


def kernel_mode() -> Optional[bool]:
    """How the pass runs where it is asked, as ``qk_prologue``'s ``interpret``:
    False, the Mosaic kernels, on a TPU; None, no kernel, where the caller's
    ``jnp`` chain has to run: on another backend (the suite's tests ask for
    interpret mode themselves), and inside a region that is manual over some
    mesh axes while others still span devices (the pipeline schedules), where
    jax can neither partition a Mosaic call nor nest a shard_map for it."""
    if jax.default_backend() != "tpu":
        return None
    mesh = jax.sharding.get_abstract_mesh()
    partitioned = [n for n in mesh.axis_names if n not in mesh.manual_axes and mesh.shape[n] > 1]
    return None if mesh.manual_axes and partitioned else False


def rope_angles(
    positions: jax.Array,  # (S,) int32 token positions
    dim: int,
    theta: float,
    scaling=None,  # models.tinygpt.YarnScaling
) -> jax.Array:
    """(S, dim / 2) f32: each row's angle at the pairs (i, i + dim / 2), at
    YaRN's frequencies under a ``scaling``: what ``_rope`` and ``table`` take
    the cos and sin of."""
    half = dim // 2
    if scaling is None:
        inv_freq = 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) * 2.0 / dim))
    else:
        inv_freq = jnp.asarray(scaling.inv_freq(dim, theta), dtype=jnp.float32)
    return positions.astype(jnp.float32)[:, None] * inv_freq[None, :]


def table(positions: jax.Array, dim: int, theta: float, scaling=None,
          rotary_dim: Optional[int] = None) -> jax.Array:
    """(S, dim) f32: a row's cos in the first dim / 2 lanes, its sin in the
    rest (``_rope``'s numbers: YaRN's frequencies and its factor on cos and
    sin folded in). One array a kind of layer is what a step holds from its
    first layer's forward to its backward. Under a ``rotary_dim`` short of
    ``dim`` the angles are over ``rotary_dim``: cos in the first rotary_dim / 2
    lanes, sin in the next, zeros in the lanes that do not rotate."""
    part = _part(dim, rotary_dim) or dim
    freqs = rope_angles(positions, part, theta, scaling)
    out = jnp.concatenate((jnp.cos(freqs), jnp.sin(freqs)), axis=-1)
    if scaling is not None and scaling.cos_sin_factor != 1.0:
        out = out * scaling.cos_sin_factor
    if part != dim:
        out = jnp.pad(out, ((0, 0), (0, dim - part)))
    return out


def pass_bytes(rows: int, q_width: int, k_width: int, itemsize: int, norm: bool) -> dict:
    """The bytes one layer's pass moves over ``rows`` rows, from its shapes:
    forward each operand in and out once; backward the cotangent in and the
    gradient out, and under the norm the projection's output in as well. The
    same whatever part of a head rotates: a head moves whole."""
    operand = rows * (q_width + k_width) * itemsize
    return {"forward": 2 * operand, "backward": (3 if norm else 2) * operand}


def _block_rows(seq_len: int) -> int:
    rows = _BLOCK_ROWS
    while seq_len % rows:
        rows //= 2
    return rows


def _vmem_limit(rows: int, lanes: int, itemsize: int, streams: int) -> int:
    """Scoped VMEM for ``streams`` (rows, lanes) operands, double-buffered,
    with room for the tables, the partial sums and the body's temporaries."""
    return 2 * streams * rows * lanes * itemsize + _VMEM_HEADROOM


_TERMS = 3  # bf16 terms that add up to an f32 exactly (8 + 8 + 8 bits of mantissa)


def _lane_means(*xs: jax.Array):
    """The mean over the lanes of each f32 (rows, D), in every lane of its
    result, on the MXU, which the pass leaves idle (as a lane reduction it is
    a dozen XLU rotations a vreg, and was the body's bound): an x is split
    into three bf16 terms that add up to it exactly, set side by side and
    multiplied by a matrix of ones with the products summed in f32: an f32
    sum. Several xs are stacked by rows and share the product."""
    rows, dim = xs[0].shape
    stacked = []
    for rest in xs:
        terms = []
        for t in range(_TERMS):
            terms.append(rest.astype(jnp.bfloat16))
            if t + 1 < _TERMS:
                rest = lax.sub(rest, terms[-1].astype(jnp.float32))
        stacked.append(jnp.concatenate(terms, axis=-1))
    total = lax.dot_general(
        stacked[0] if len(xs) == 1 else jnp.concatenate(stacked, axis=0),
        jnp.ones((_TERMS * dim, dim), jnp.bfloat16),
        (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    total = lax.mul(total, jnp.float32(1.0 / dim))
    return [total[i * rows:(i + 1) * rows] for i in range(len(xs))]


def _cos_sin(packed: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """A chunk of ``table`` -> (cos, sin) over all D lanes, the halves alike
    but for sin's sign, so that rotate-half is x cos + roll(x, D / 2) sin."""
    half = packed.shape[-1] // 2
    swapped = pltpu.roll(packed, half, 1)  # [sin | cos]
    first = lax.lt(lax.broadcasted_iota(jnp.int32, packed.shape, 1), half)
    return lax.select(first, packed, swapped), lax.select(first, lax.neg(swapped), packed)


def _cos_sin_part(packed: jax.Array, part: int) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """``_cos_sin`` where the leading ``part`` lanes rotate -> (cos, sin down,
    sin up): cos is 1 and both sins 0 on the lanes that pass; ``sin down`` is
    -sin on the part's first half (against the lane part / 2 above), ``sin
    up`` +sin on its second (against the lane part / 2 below)."""
    dim, half = packed.shape[-1], part // 2
    lane = lax.broadcasted_iota(jnp.int32, packed.shape, 1)
    first, turning = lax.lt(lane, half), lax.lt(lane, part)
    above = pltpu.roll(packed, dim - half, 1)  # lane j: the table's j + half, sin for j < half
    below = pltpu.roll(packed, half, 1)  # lane j: the table's j - half, cos for half <= j < part
    zero, one = jnp.zeros_like(packed), jnp.ones_like(packed)
    return (lax.select(first, packed, lax.select(turning, below, one)),
            lax.select(first, lax.neg(above), zero),
            lax.select(first, zero, lax.select(turning, packed, zero)))


def _rotate(x: jax.Array, cos: jax.Array, sin: jax.Array, sign: float) -> jax.Array:
    """x * cos + sign * roll(x, D / 2) * sin: the rotation at +1, its
    transpose at -1 (``sin`` carries the half's sign)."""
    rolled = lax.mul(pltpu.roll(x, x.shape[-1] // 2, 1), sin)
    straight = lax.mul(x, cos)
    return lax.add(straight, rolled) if sign > 0 else lax.sub(straight, rolled)


def _rotate_part(x: jax.Array, cos, sin_down, sin_up, sign: float, part: int) -> jax.Array:
    """``_rotate`` where the leading ``part`` lanes rotate: a lane of the
    part's first half meets the lane part / 2 above it, one of its second the
    lane part / 2 below, and the sins are zero elsewhere."""
    dim, half = x.shape[-1], part // 2
    rolled = lax.add(lax.mul(pltpu.roll(x, dim - half, 1), sin_down),
                     lax.mul(pltpu.roll(x, half, 1), sin_up))
    straight = lax.mul(x, cos)
    return lax.add(straight, rolled) if sign > 0 else lax.sub(straight, rolled)


def _coefficients(packed: jax.Array, part: Optional[int]) -> Tuple[jax.Array, ...]:
    """A chunk of ``table`` -> what ``_turn`` multiplies by: whole heads
    (``part`` None) or their leading ``part`` lanes."""
    return _cos_sin(packed) if part is None else _cos_sin_part(packed, part)


def _turn(x: jax.Array, by: Tuple[jax.Array, ...], sign: float, part: Optional[int]) -> jax.Array:
    """x rotated (+1) or un-rotated (-1) by ``_coefficients``' result."""
    return _rotate(x, *by, sign) if part is None else _rotate_part(x, *by, sign, part)


def _chunks(rows: int):
    """The body's walk over a block's rows, a chunk at a time."""
    chunk = min(_CHUNK_ROWS, rows)

    def walk(body, init):
        return lax.fori_loop(
            0, rows // chunk,
            lambda i, carry: body(pl.ds(pl.multiple_of(i * chunk, chunk), chunk), carry),
            init)

    return walk


def _fwd_kernel(table_ref, *refs, dim: int, eps: float, norm: bool, part: Optional[int] = None):
    if norm:
        qs_ref, ks_ref, q_ref, k_ref, qo_ref, ko_ref = refs
    else:
        q_ref, k_ref, qo_ref, ko_ref = refs
        qs_ref = ks_ref = None

    def body(rows, carry):
        by = _coefficients(table_ref[rows, :], part)
        for x_ref, o_ref, s_ref in ((q_ref, qo_ref, qs_ref), (k_ref, ko_ref, ks_ref)):
            scale = s_ref[...] if norm else None  # (1, dim) f32
            for h in range(x_ref.shape[-1] // dim):
                lanes = slice(h * dim, (h + 1) * dim)
                x = x_ref[rows, lanes].astype(jnp.float32)
                if norm:
                    (mean_sq,) = _lane_means(lax.mul(x, x))
                    x = lax.mul(lax.mul(x, lax.rsqrt(lax.add(mean_sq, eps))), scale)
                o_ref[h, rows, :] = _turn(x, by, +1, part).astype(o_ref.dtype)
        return carry

    _chunks(q_ref.shape[0])(body, None)


def _fold8(x: jax.Array) -> jax.Array:
    """(rows, D) -> (8, D): the sum of the 8-row groups, adds of whole vregs."""
    out = x[0:8]
    for r in range(8, x.shape[0], 8):
        out = lax.add(out, x[r:r + 8])
    return out


def _bwd_kernel(table_ref, *refs, dim: int, eps: float, norm: bool, part: Optional[int] = None):
    if norm:
        (qs_ref, ks_ref, q_ref, k_ref, dqo_ref, dko_ref,
         dq_ref, dk_ref, dqs_ref, dks_ref) = refs
        streams = ((q_ref, dqo_ref, dq_ref, qs_ref), (k_ref, dko_ref, dk_ref, ks_ref))
    else:
        dqo_ref, dko_ref, dq_ref, dk_ref = refs
        streams = ((None, dqo_ref, dq_ref, None), (None, dko_ref, dk_ref, None))

    def body(rows, sums):
        by = _coefficients(table_ref[rows, :], part)
        out = []
        for (x_ref, do_ref, dx_ref, s_ref), acc in zip(streams, sums):
            scale = s_ref[...] if norm else None
            for h in range(do_ref.shape[0]):
                lanes = slice(h * dim, (h + 1) * dim)
                dy = _turn(do_ref[h, rows, :].astype(jnp.float32), by, -1, part)
                if norm:
                    # y = x r s with r = rsqrt(mean(x^2) + eps) a row, g = dy s:
                    # ds = sum dy x r; dx = r g - x r^3 mean(g x). Both means
                    # are of things r does not enter: one product gives both.
                    x = x_ref[rows, lanes].astype(jnp.float32)
                    g = lax.mul(dy, scale)
                    mean_sq, mean_gx = _lane_means(lax.mul(x, x), lax.mul(g, x))
                    r = lax.rsqrt(lax.add(mean_sq, eps))
                    acc = lax.add(acc, _fold8(lax.mul(lax.mul(dy, x), r)))
                    r3 = lax.mul(lax.mul(r, r), lax.mul(r, mean_gx))
                    dy = lax.sub(lax.mul(r, g), lax.mul(x, r3))
                dx_ref[rows, lanes] = dy.astype(dx_ref.dtype)
            out.append(acc)
        return tuple(out)

    zero = jnp.zeros((8, dim), jnp.float32) if norm else None
    sums = _chunks(dq_ref.shape[0])(body, (zero, zero))
    if norm:
        dqs_ref[...], dks_ref[...] = sums


@functools.lru_cache(maxsize=64)
def _call(backward: bool, B, S, Eq, Ek, dim, dtype, vma, eps, norm, interpret, part=None):
    """One direction's ``pallas_call``, made once a process for a shape (as
    ``flash_attention._forward_call``, and for its reason). Operands, in
    order: the table, [q scale, k scale, q, k,] [q's and k's cotangents]."""
    rows = _block_rows(S)
    grid = (S // rows, B)  # the batch innermost: the table's block stays
    table = pl.BlockSpec((rows, dim), lambda i, b: (i, 0))
    scale = pl.BlockSpec((1, dim), lambda i, b: (0, 0))
    # the projections' side: rows of all heads' lanes; the kernels' side: a
    # (rows, dim) slab a head, which is the layout flash_attention transposes to
    flat = [pl.BlockSpec((None, rows, Eq), lambda i, b: (b, i, 0)),
            pl.BlockSpec((None, rows, Ek), lambda i, b: (b, i, 0))]
    by_head = [pl.BlockSpec((None, Eq // dim, rows, dim), lambda i, b: (b, 0, i, 0)),
               pl.BlockSpec((None, Ek // dim, rows, dim), lambda i, b: (b, 0, i, 0))]
    if backward:
        results = [_struct((B, S, Eq), dtype, vma), _struct((B, S, Ek), dtype, vma)]
    else:
        results = [_struct((B, Eq // dim, S, dim), dtype, vma),
                   _struct((B, Ek // dim, S, dim), dtype, vma)]
    in_specs = [table]
    if norm:  # the projection's output, which the norm's backward reads again
        in_specs += [scale, scale] + flat
    if backward:
        in_specs += by_head
    elif not norm:
        in_specs += flat
    out_specs = list(flat if backward else by_head)
    if backward and norm:
        partial = pl.BlockSpec((None, None, 8, dim), lambda i, b: (i, b, 0, 0))
        out_specs += [partial, partial]
        results += [_struct((S // rows, B, 8, dim), jnp.float32, vma)] * 2
    streams = (3 if norm else 2) if backward else 2
    return pl.pallas_call(
        functools.partial(_bwd_kernel if backward else _fwd_kernel,
                          dim=dim, eps=eps, norm=norm, part=part),
        out_shape=results,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=_vmem_limit(rows, Eq + Ek, jnp.dtype(dtype).itemsize, streams),
        ),
        name="qk_prologue_bwd" if backward else "qk_prologue_fwd",
        interpret=interpret,
    )


def _run(backward, opts, shape, table, *operands):
    dim, eps, norm, interpret, part = opts
    call = _call(backward, *shape, dim, operands[-1].dtype,
                 vma_of(table, *operands), eps, norm, interpret, part)
    # one trace for the primal and the forward rule: see _flash_forward
    with jax.sharding.use_abstract_mesh(jax.sharding.get_abstract_mesh()):
        return call(table, *operands)


def _row(scale: jax.Array) -> jax.Array:
    return scale.astype(jnp.float32).reshape(1, -1)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _prologue(opts, q, k, q_scale, k_scale, table):
    """(B, S, H x D) q and (B, S, KV x D) k -> (B, H, S, D) and (B, KV, S, D)."""
    scales = (_row(q_scale), _row(k_scale)) if opts[2] else ()
    return tuple(_run(False, opts, (*q.shape, k.shape[-1]), table, *scales, q, k))


def _prologue_fwd(opts, q, k, q_scale, k_scale, table):
    return _prologue(opts, q, k, q_scale, k_scale, table), (q, k, q_scale, k_scale, table)


def _prologue_bwd(opts, res, cotangents):
    q, k, q_scale, k_scale, table = res
    shape = (*q.shape, k.shape[-1])
    no_table = jnp.zeros_like(table)  # made of positions: nothing flows into it
    if not opts[2]:
        dq, dk = _run(True, opts, shape, table, *cotangents)
        return dq, dk, None, None, no_table
    dq, dk, dqs, dks = _run(
        True, opts, shape, table, _row(q_scale), _row(k_scale), q, k, *cotangents)
    return (dq, dk, dqs.sum((0, 1, 2)).astype(q_scale.dtype),
            dks.sum((0, 1, 2)).astype(k_scale.dtype), no_table)


_prologue.defvjp(_prologue_fwd, _prologue_bwd)


def qk_prologue(
    q: jax.Array,  # (B, S, H x D), the projection's output
    k: jax.Array,  # (B, S, KV x D)
    q_scale: Optional[jax.Array],  # (D,) the head norm's scales; None: no norm
    k_scale: Optional[jax.Array],
    table: jax.Array,  # (S, D) f32, ``table``
    eps: float,
    interpret: bool = False,
    rotary_dim: Optional[int] = None,
) -> Tuple[jax.Array, jax.Array]:
    """-> q (B, S, H, D) and k (B, S, KV, D), each head normalised (where
    scales are given) and rotated (its leading ``rotary_dim`` lanes, by a
    ``table`` made for them; None: all of it), in one pass; differentiable in q, k and the
    scales. The kernels write a (S, D) slab a head, (B, H, S, D), which is
    what ``flash_attention`` transposes its operands to: handed back as the
    transpose of that, so that the two cancel and no relayout runs between
    the pass and the flash kernels. ``interpret`` runs the kernels in Pallas
    interpret mode (the CPU tests; refused on a TPU, as ``flash_attention``
    refuses it)."""
    if interpret and jax.default_backend() == "tpu":
        raise ValueError(
            "interpret=True on a TPU backend: the chip path runs the Mosaic "
            "kernels only (interpret mode is for CPU tests)")
    dim = table.shape[-1]
    norm = q_scale is not None
    if not fits(dim, q.shape[1], rotary_dim) or q.shape[-1] % dim or k.shape[-1] % dim:
        raise ValueError(
            f"q {q.shape} / k {k.shape} at heads of {dim} are not the pass's operand (fits)")
    opts = (dim, float(eps), norm, bool(interpret), _part(dim, rotary_dim))
    manual, batch_axes, heads_axis = _kernel_mesh_axes()

    def local(q, k, q_scale, k_scale, table):
        # the scales enter whole on every shard; varying like q, their
        # cotangent is each shard's own sum and the transpose adds them
        if norm:
            q_scale, k_scale = pcast_like(q_scale, q), pcast_like(k_scale, q)
        q, k = _prologue(opts, q, k, q_scale, k_scale, table)
        return q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3)

    if not manual:
        return local(q, k, q_scale, k_scale, table)

    rows = P(batch_axes or None, None, heads_axis)
    scale = None if not norm else P()
    return jax.shard_map(
        local,
        in_specs=(rows, rows, scale, scale, P()),
        out_specs=(P(*rows, None),) * 2,
        axis_names=manual,
        # the Pallas interpreter cannot run on operands that carry varying
        # axes: the CPU tests' shard_map does not track them (its transpose
        # then adds every shard's scale gradient, as the tracked one does)
        check_vma=not interpret,
    )(q, k, q_scale, k_scale, table)
