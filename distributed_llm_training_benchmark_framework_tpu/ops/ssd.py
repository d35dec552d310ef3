"""Mamba-2's state-space scan (the SSD form, arXiv:2405.21060) with one scalar
decay a head a position, chunkwise, forward and backward.

A head keeps a (P, N) state S, zero before the first position:

    S_t = a_t S_{t-1} + dt_t x_t B_t^T        y_t = S_t C_t

with x_t the head's P channels, ``a_t = exp(g_t)`` one number (``g_t <= 0``,
the log-decay, -exp(A_log) dt_t in the model), and B_t, C_t (N,) **shared by
the heads of a group** (head h reads group h // (H / groups)). The skip ``D
x_t`` is elementwise and the caller's. There is no delta-rule term: nothing is
subtracted from the state, no triangular system is solved, and the decay is a
scalar, so inside a chunk of C positions, with G_r the sum of g over the
chunk's positions up to r (inclusive; made by the caller's wrapper in float32,
``chunk_sums``),

    L_ri = exp(G_r - G_i)  (r >= i, else 0)
    Y    = (L o (C B^T)) (dt x) + exp(G) o (C S_0^T)
    S_C  = exp(G_C) S_0 + (dt exp(G_C - G) x)^T B

``C B^T`` is made once a group and used by its heads. **No exponential of a
positive sum is formed**: G_r - G_i is taken before the exponential (at most
0 for r >= i; the other half of the square is masked, and held at 0 before
the exponential so that nothing overflows on the way to the mask), and the
state's factors exp(G_r), exp(G_C - G_r), exp(G_C) are of sums of non-positive
terms. There is no clamp on g or dt.

Matrix products take their operands in the inputs' dtype (the compute type)
and accumulate in float32; the state, G, dt and everything elementwise are
float32.

Two implementations under one ``custom_vjp``, as ``ops/kda.py`` is built: a
``jnp`` path (any backend and any widths: a ``lax.scan`` over the chunks) and
two Pallas kernels, ``ssd_fwd`` and ``ssd_bwd``: grid (batch, groups, chunks),
the chunk axis sequential, a group's heads' states resident in VMEM across it.
A grid step is one chunk of one group: ``C B^T`` once, then the group's heads
a 128-lane slab at a time (two heads of 64 channels side by side: a per-head
factor reaches its half of the lanes by a select, so no operand is cut inside
a vreg; a product of 64 columns costs the MXU what one of 128 does). The
forward writes y and, for the backward, the states entering each chunk (in the
compute type: the backward reads them as a matrix product's operand). The
backward walks the chunks in reverse with dS resident and differentiates the
slab's own body (``jax.vjp`` of ``_slab``, traced into the kernel); ``C B^T``'s
cotangent is summed over the group's slabs and taken back to B and C once.

Operands are flat, (B, S, columns), a head's columns together (``ops/kda.py``
has the reason). x, B and C come as **one array** ``xbc`` (B, S, H P + 2 G N),
the layout the convolution in front of the scan leaves them in: the kernels'
block specs find the three parts' columns, so nothing is sliced or copied in
front of them; the backward writes three arrays and joins them once.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .kda import (  # noqa: F401  (kernel_mode: the callers')
    _by_chunk, _from_chunks, _mm, _pallas_call, kernel_mode,
)

#: What the forward keeps for the backward beside its operands, by the names a
#: remat policy may save (``models/tinygpt.py::remat_kept_names``): the output
#: and the states entering the chunks.
SSD_RESIDUAL_NAMES = ("ssd_out", "ssd_states")

#: The published chunk (``chunk_size`` of the Nemotron-H family's configs).
DEFAULT_CHUNK = 128


def chunk_sums(g: jax.Array, chunk: int) -> jax.Array:
    """g (B, S, H) float32 log-decays -> G, the running sum of g inside each
    chunk of ``chunk`` positions, the position's own included. Sums of
    non-positive float32 terms; differentiable by jax (the transpose is the
    reverse running sum)."""
    B, S, H = g.shape
    return jnp.cumsum(g.reshape(B, S // chunk, chunk, H), axis=2).reshape(B, S, H)


# A jit of its own, as ``kda._chunk`` is and for its reason: a step's program
# holds the kernels several times over, and every one after the first finds the
# body's jaxpr, its linearization and its transpose traced.
@functools.partial(jax.jit, static_argnums=(0,))
def _slab(heads, CB, x, Bm, Cm, dt, G, S0):
    """``heads`` heads of one group side by side over one chunk: CB (C, C)
    float32, the group's C B^T; x (C, heads x P); Bm, Cm (C, N); dt, G tuples
    of ``heads`` (1, C) float32 rows (lane-dense where they are stored; their
    columns are taken here, a masked sum over the lanes); S0 (heads x P, N)
    float32 -> (y (C, heads x P) in x's dtype, S_C float32): the module
    docstring's equations."""
    cd = x.dtype
    C, W = x.shape
    P = W // heads
    rows = lax.broadcasted_iota(jnp.int32, (C, C), 0)
    cols = lax.broadcasted_iota(jnp.int32, (C, C), 1)
    eye = (rows == cols).astype(jnp.float32)
    column = lambda row: jnp.sum(eye * row, axis=1, keepdims=True)  # (1, C) -> (C, 1)
    last = (lax.broadcasted_iota(jnp.int32, (1, C), 1) == C - 1).astype(jnp.float32)
    lane = lax.broadcasted_iota(jnp.int32, (1, W), 1)  # a column's, and a row's, place
    state_row = lax.broadcasted_iota(jnp.int32, (W, 1), 0)

    def over(at, values):
        """A value a head over the head's lanes (or rows): selects."""
        out = values[0]
        for j in range(1, heads):
            out = jnp.where(at >= j * P, values[j], out)
        return out

    G_col = [column(row) for row in G]
    dt_col = [column(row) for row in dt]
    total = [jnp.sum(row * last, axis=1, keepdims=True) for row in G]  # (1, 1): G_C
    xf = x.astype(jnp.float32)
    y = over(lane, [jnp.exp(c) for c in G_col]) * _mm(Cm, S0, (1, 1), cd)
    xdt = xf * over(lane, dt_col)
    for j in range(heads):
        # G_r - G_i before the exponential: <= 0 below the diagonal; above it the
        # mask's, and held at 0 so that the exponential it drops is finite
        L = jnp.where(rows >= cols, jnp.exp(jnp.minimum(G_col[j] - G[j], 0.0)), 0.0)
        mine = xdt if heads == 1 else jnp.where((lane >= j * P) & (lane < (j + 1) * P), xdt, 0.0)
        y = y + _mm(L * CB, mine, (1, 0), cd)
    later = over(lane, [jnp.exp(t - c) * d for t, c, d in zip(total, G_col, dt_col)])
    S1 = over(state_row, [jnp.exp(t) for t in total]) * S0 + _mm(xf * later, Bm, (0, 0), cd)
    return y.astype(cd), S1


def _group(x, Bm, Cm, dt, G, S0):
    """A whole group's chunk on the ``jnp`` path, a head a slab: x (Hg, C, P),
    Bm, Cm (C, N), dt, G (Hg, C), S0 (Hg, P, N) -> (y (Hg, C, P), S_C)."""
    CB = _mm(Cm, Bm, (1, 1), x.dtype)
    one = lambda x, dt, G, S0: _slab(1, CB, x, Bm, Cm, (dt[None],), (G[None],), S0)
    return jax.vmap(one)(x, dt, G, S0)


# ---------------------------------------------------------------------------
# The jnp path: a scan over the chunks, every (batch, group) at once.
# ---------------------------------------------------------------------------

def _split(opts, xbc):
    """xbc (B, S, H P + 2 G N) -> (x, B, C) by the chunk: x (N, B, G, Hg, C,
    P), B and C (N, B, G, C, N_state)."""
    C, groups, heads, P, _ = opts
    B_, S, _ = xbc.shape
    width = heads * P
    n = (xbc.shape[-1] - width) // (2 * groups)
    x = _by_chunk(xbc[..., :width], C, heads)  # (N, B, H, C, P)
    x = x.reshape(x.shape[0], B_, groups, heads // groups, C, P)
    Bm = _by_chunk(xbc[..., width:width + groups * n], C, groups)  # (N, B, G, C, n)
    Cm = _by_chunk(xbc[..., width + groups * n:], C, groups)
    return x, Bm, Cm


def _rows_by_chunk(opts, t):  # (B, S, H) float32 -> (N, B, G, Hg, C)
    C, groups, heads, _, _ = opts
    B_, S, _ = t.shape
    return t.astype(jnp.float32).reshape(
        B_, S // C, C, groups, heads // groups).transpose(1, 0, 3, 4, 2)


def _rows_from_chunks(t, shape):  # (N, B, G, Hg, C) -> (B, S, H)
    return t.transpose(1, 0, 4, 2, 3).reshape(shape)


_every_group = jax.vmap(jax.vmap(_group))  # over (batch, groups)


def _jnp_forward(opts, xbc, dt, G):
    C, groups, heads, P, _ = opts
    body = _every_group
    x, Bm, Cm = _split(opts, xbc)
    n = Bm.shape[-1]

    def step(S, xs):
        y, S1 = body(*xs, S)
        return S1, (y, S.astype(xbc.dtype))

    S0 = jnp.zeros((xbc.shape[0], groups, heads // groups, P, n), jnp.float32)
    _, (y, states) = lax.scan(
        step, S0, (x, Bm, Cm, _rows_by_chunk(opts, dt), _rows_by_chunk(opts, G)))
    N, B_ = y.shape[:2]
    y = _from_chunks(y.reshape(N, B_, heads, C, P))
    # states: (B, G, N, Hg x P, n), as the kernels keep them
    return y, states.transpose(1, 2, 0, 3, 4, 5).reshape(B_, groups, N, heads // groups * P, n)


def _jnp_backward(opts, xbc, dt, G, states, dy):
    C, groups, heads, P, _ = opts
    body = _every_group
    x, Bm, Cm = _split(opts, xbc)
    B_, n, N = xbc.shape[0], Bm.shape[-1], x.shape[0]
    states = states.reshape(B_, groups, N, heads // groups, P, n).transpose(2, 0, 1, 3, 4, 5)
    dy = _by_chunk(dy, C, heads).reshape(x.shape)

    def step(dS, xs):
        *operands, S0, d_y = xs
        _, pull_back = jax.vjp(body, *operands, S0.astype(jnp.float32))
        *grads, dS0 = pull_back((d_y, dS))
        return dS0, tuple(grads)

    _, (dx, dB, dC, ddt, dG) = lax.scan(
        step, jnp.zeros(states.shape[1:], jnp.float32),
        (x, Bm, Cm, _rows_by_chunk(opts, dt), _rows_by_chunk(opts, G), states, dy), reverse=True)
    dxbc = jnp.concatenate(
        [_from_chunks(dx.reshape(N, B_, heads, C, P)), _from_chunks(dB), _from_chunks(dC)],
        axis=-1).astype(xbc.dtype)
    return dxbc, _rows_from_chunks(ddt, dt.shape), _rows_from_chunks(dG, G.shape)


# ---------------------------------------------------------------------------
# The Pallas kernels: a grid step is one chunk of one group.
# ---------------------------------------------------------------------------

def _slab_heads(P: int) -> int:
    """Heads a 128-lane slab holds: two of 64 channels, one of 128 or more."""
    return max(1, 128 // P)


def _fwd_kernel(per_slab, x, Bm, Cm, dt, G, y, states, S):
    @pl.when(pl.program_id(2) == 0)
    def _():
        S[...] = jnp.zeros_like(S)

    width = x.shape[1] // (dt.shape[0] // per_slab)  # a slab's lanes
    CB = _mm(Cm[...], Bm[...], (1, 1), x.dtype)
    for s in range(dt.shape[0] // per_slab):
        lanes = slice(s * width, (s + 1) * width)
        heads = range(s * per_slab, (s + 1) * per_slab)
        S0 = S[lanes, :]
        states[lanes, :] = S0.astype(states.dtype)
        out, S1 = _slab(per_slab, CB, x[:, lanes], Bm[...], Cm[...],
                        tuple(dt[j:j + 1, :] for j in heads),
                        tuple(G[j:j + 1, :] for j in heads), S0)
        y[:, lanes] = out
        S[lanes, :] = S1


def _bwd_kernel(per_slab, x, Bm, Cm, dt, G, states, dy, dx, dB, dC, ddt, dG, dS):
    @pl.when(pl.program_id(2) == 0)
    def _():
        dS[...] = jnp.zeros_like(dS)

    cd = x.dtype
    width = x.shape[1] // (dt.shape[0] // per_slab)
    # float32 in front of the body: B's and C's cotangents then come back float32
    # and are summed over the group's slabs before their one cast
    Bf, Cf = Bm[...].astype(jnp.float32), Cm[...].astype(jnp.float32)
    CB = _mm(Cf, Bf, (1, 1), cd)
    dCB = dBm = dCm = None
    for s in range(dt.shape[0] // per_slab):
        lanes = slice(s * width, (s + 1) * width)
        heads = range(s * per_slab, (s + 1) * per_slab)
        _, pull_back = jax.vjp(
            functools.partial(_slab, per_slab), CB, x[:, lanes], Bf, Cf,
            tuple(dt[j:j + 1, :] for j in heads), tuple(G[j:j + 1, :] for j in heads),
            states[lanes, :].astype(jnp.float32))
        dCB_s, dx[:, lanes], dB_s, dC_s, ddt_s, dG_s, dS[lanes, :] = pull_back(
            (dy[:, lanes], dS[lanes, :]))
        for j, a, b in zip(heads, ddt_s, dG_s):
            ddt[j:j + 1, :] = a
            dG[j:j + 1, :] = b
        dCB, dBm, dCm = ((dCB_s, dB_s, dC_s) if dCB is None
                         else (dCB + dCB_s, dBm + dB_s, dCm + dC_s))
    # C B^T's cotangent, once a group: dC += dCB B, dB += dCB^T C
    dC[...] = (dCm + _mm(dCB, Bf, (1, 0), cd)).astype(dC.dtype)
    dB[...] = (dBm + _mm(dCB, Cf, (0, 0), cd)).astype(dB.dtype)


def _rows_by_step(opts, t):
    """(B, S, H) -> (B, G, N, Hg, C) float32: a grid step's block is the
    array's whole last two axes, its group's heads' rows over the chunk's
    positions (``kda._beta_by_step`` has the reason)."""
    C, groups, heads, _, _ = opts
    B_, S, _ = t.shape
    return t.astype(jnp.float32).reshape(
        B_, S // C, C, groups, heads // groups).transpose(0, 3, 1, 4, 2)


def _rows_from_steps(t, shape):  # (B, G, N, Hg, C) -> (B, S, H)
    return t.transpose(0, 2, 4, 1, 3).reshape(shape)


def _specs(opts, xbc, at):
    """The block specs of a grid step (b, g, n) over chunk ``at(n)``: x's,
    B's and C's columns of ``xbc``-shaped arrays, the rows' and the states'."""
    C, groups, heads, P, _ = opts
    Hg = heads // groups
    n = (xbc.shape[-1] - heads * P) // (2 * groups)
    x = pl.BlockSpec((None, C, Hg * P), lambda b, g, i: (b, at(i), g))
    in_xbc = lambda first: pl.BlockSpec(  # a group's n columns from column ``first``
        (None, C, n), lambda b, g, i: (b, at(i), first // n + g))
    part = pl.BlockSpec((None, C, n), lambda b, g, i: (b, at(i), g))  # of a (B, S, G n) array
    rows = pl.BlockSpec((None, None, None, Hg, C), lambda b, g, i: (b, g, at(i), 0, 0))
    states = pl.BlockSpec((None, None, None, Hg * P, n), lambda b, g, i: (b, g, at(i), 0, 0))
    return x, in_xbc(heads * P), in_xbc(heads * P + groups * n), part, rows, states, n


def _pallas_forward(opts, xbc, dt, G):
    C, groups, heads, P, interpret = opts
    B_, S, _ = xbc.shape
    N, Hg = S // C, heads // groups
    x, Bm, Cm, _, rows, states, n = _specs(opts, xbc, lambda i: i)
    return _pallas_call(
        functools.partial(_fwd_kernel, _slab_heads(P)), "ssd_fwd", interpret,
        (B_, groups, N), [x, Bm, Cm, rows, rows], [x, states],
        [jax.ShapeDtypeStruct((B_, S, heads * P), xbc.dtype),
         jax.ShapeDtypeStruct((B_, groups, N, Hg * P, n), xbc.dtype)],
        [pltpu.VMEM((Hg * P, n), jnp.float32)],
    )(xbc, xbc, xbc, _rows_by_step(opts, dt), _rows_by_step(opts, G))


def _pallas_backward(opts, xbc, dt, G, states, dy):
    C, groups, heads, P, interpret = opts
    B_, S, _ = xbc.shape
    N, Hg = S // C, heads // groups
    x, Bm, Cm, part, rows, kept, n = _specs(opts, xbc, lambda i: N - 1 - i)  # in reverse
    by_step = _rows_by_step(opts, dt), _rows_by_step(opts, G)
    shape = lambda *s: jax.ShapeDtypeStruct(s, xbc.dtype)
    dx, dB, dC, ddt, dG = _pallas_call(
        functools.partial(_bwd_kernel, _slab_heads(P)), "ssd_bwd", interpret,
        (B_, groups, N), [x, Bm, Cm, rows, rows, kept, x], [x, part, part, rows, rows],
        [shape(B_, S, heads * P), shape(B_, S, groups * n), shape(B_, S, groups * n)]
        + [jax.ShapeDtypeStruct(t.shape, jnp.float32) for t in by_step],
        [pltpu.VMEM((Hg * P, n), jnp.float32)],
    )(xbc, xbc, xbc, *by_step, states, dy)
    return (jnp.concatenate([dx, dB, dC], axis=-1), _rows_from_steps(ddt, dt.shape),
            _rows_from_steps(dG, G.shape))


# ---------------------------------------------------------------------------
# The op.
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _ssd(opts, xbc, dt, G):
    return _ssd_fwd(opts, xbc, dt, G)[0]


def _ssd_fwd(opts, xbc, dt, G):
    forward = _jnp_forward if opts[-1] is None else _pallas_forward
    y, states = forward(opts, xbc, dt, G)
    # the results feed nothing but the two names: where a policy saves them the
    # recompute's copy of the call is dead code (as ``kda``'s and the flash kernel's)
    y = checkpoint_name(y, SSD_RESIDUAL_NAMES[0])
    states = checkpoint_name(states, SSD_RESIDUAL_NAMES[1])
    return (y, states), (xbc, dt, G, states)


def _ssd_bwd(opts, residuals, cotangents):
    xbc, dt, G, states = residuals
    dy, _ = cotangents  # the states are kept for this rule, not differentiated
    backward = _jnp_backward if opts[-1] is None else _pallas_backward
    dxbc, ddt, dG = backward(opts, xbc, dt, G, states, dy)
    return dxbc, ddt.astype(dt.dtype), dG.astype(G.dtype)


_ssd.defvjp(_ssd_fwd, _ssd_bwd)


def fits(head_dim: int, state: int, heads: int, groups: int) -> bool:
    """Whether the kernels take these widths: a group's heads in whole
    128-lane slabs (heads of 64 channels in pairs, or of whole 128-lane
    tiles), a state of whole 128-lane tiles."""
    slab = _slab_heads(head_dim)
    return ((128 % head_dim == 0 or head_dim % 128 == 0) and head_dim * slab % 128 == 0
            and state % 128 == 0 and (heads // groups) % slab == 0
            and heads * head_dim % state == 0)  # B's and C's columns start on a block of N


def ssd_flat(xbc, dt, g, heads: int, groups: int, head_dim: int,
             chunk: int = DEFAULT_CHUNK, *, interpret: Optional[bool] = None,
             final_state: bool = False):
    """The scan of the module docstring over whole sequences.

    xbc (B, S, H x P + 2 x groups x N) in the compute type: x's columns (a
    head's P together), then B's (a group's N together), then C's; dt (B, S,
    H) float32, the step sizes; g (B, S, H) float32, the log-decays, <= 0. ->
    y (B, S, H x P) in xbc's dtype, without the skip term. ``interpret``: None
    the ``jnp`` path, False the Mosaic kernels (where ``fits``), True the
    kernels interpreted. S must be whole chunks: a sequence that is not is
    refused, not padded (a caller pads with g = 0, dt = 0, which add nothing).
    ``final_state``: also the states entering the chunks, (B, groups, S /
    chunk, H / groups x P, N) in the compute type, as the backward reads them
    (a test's, and a check's, way to the state)."""
    S = xbc.shape[1]
    n, rest = divmod(xbc.shape[-1] - heads * head_dim, 2 * groups)
    if S % chunk:
        raise ValueError(
            f"ssd: a sequence of {S} positions is not whole chunks of {chunk}; pad it "
            "(g = 0, dt = 0 add nothing) or choose a chunk that divides it")
    if rest or n <= 0 or heads % groups:
        raise ValueError(
            f"ssd: xbc holds H x P + 2 x groups x N columns with groups | H; got "
            f"{xbc.shape[-1]} for H={heads}, P={head_dim}, groups={groups}")
    if g.dtype != jnp.float32 or dt.dtype != jnp.float32:
        raise ValueError(f"ssd: the log-decays and the steps are float32; got {g.dtype}, {dt.dtype}")
    if interpret is not None and not fits(head_dim, n, heads, groups):
        raise ValueError(
            f"ssd: the kernels take a group's heads in whole 128-lane slabs and a state of "
            f"whole 128-lane tiles; got P={head_dim}, N={n}, {heads // groups} heads a group "
            "(the jnp path, interpret=None, takes any)")
    opts = (chunk, groups, heads, head_dim, interpret)
    y, states = _ssd(opts, xbc, dt, chunk_sums(g, chunk))
    return (y, states) if final_state else y
