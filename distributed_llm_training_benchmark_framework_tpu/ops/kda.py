"""Kimi Delta Attention's recurrence (the gated delta rule with a decay a key
channel; Kimi Linear, arXiv:2510.26692), chunkwise, forward and backward.

A head keeps a (dk, dv) state S, zero before the first position:

    S_t = (I - beta_t k_t k_t^T) diag(exp(g_t)) S_{t-1} + beta_t k_t v_t^T
    o_t = scale * S_t^T q_t

with ``g_t <= 0`` a log-decay for each of the dk key channels and ``beta_t``
in (0, 1). ``kda`` computes it a chunk of C positions at a time. Inside a
chunk, with G_r the sum of g over the chunk's positions up to r and
E_ri = exp(G_r - G_i) (a vector over the key channels, r >= i):

    A_ri = beta_r sum_c k_r k_i E_ri   (i < r)    T = (I + A)^-1
    B_ri = sum_c q_r k_i E_ri          (i <= r)
    W = T (beta k exp(G)),  U = T (beta v) - W S_0
    O = scale ((q exp(G)) S_0 + B U)
    S_C = exp(G_C) S_0 + (k exp(G_C - G))^T U

**No exponential of a positive sum of decays is formed.** E_ri does not factor
into a row's part and a column's with both bounded unless a reference point
lies between the two positions, so the lower triangle is cut into log2(C)
levels: at the level of half-size s, every block of 2s positions gives its
lower-left quadrant (rows in its upper half, columns in its lower), with the
block's middle as the reference: a row's factor is exp(sum of g from the
middle to the row), a column's exp(sum of g from after the column to the
middle), both sums of non-positive terms taken directly: one segmented scan
over the chunk's rows (``_decay_sums``: log2 C stages of float32 additions on
the vector units, each of numbers of one sign, a stage's sums the level's
exponents; never a difference of two running sums, and no matrix product),
and the quadrants of all blocks of a level are one masked matrix product (a
factor is at most 1 on every row, so the quadrant's mask is the only one).
The diagonal has E = 1. The same
levels invert I + A exactly as block forward substitution does: with T the
inverse of the block diagonal part at block size s, T - T A_level T is the
inverse at 2s. There is no clamp on g.

Matrix products take their operands in the inputs' dtype (the compute type)
and accumulate in float32; the state, the running sums of g and everything
elementwise are float32. ``beta`` enters through ``beta k`` and ``beta v``,
which the chunk's body makes from a (C, 1) column (made outside, they are two
more operands and two more gradients a head's width wide, and two float32
copies of beta over every channel that XLA keeps as arrays).

Two implementations under one ``custom_vjp``: a ``jnp`` path (any backend: a
``lax.scan`` over the chunks) and two Pallas kernels, ``kda_fwd`` and
``kda_bwd``: grid (batch, heads, chunks), the chunk axis sequential, a head's
state resident in VMEM across it. The forward writes ``o`` and, for the
backward, the state entering each chunk (in the compute type: the backward
reads it as a matrix product's operand, which is that type anyway). The
backward walks the chunks in reverse with dS resident, and differentiates the
chunk's own body (``jax.vjp`` of ``_chunk``, traced into the kernel): what it
recomputes of the forward is the chunk's intra-chunk part. Operands are (B, S,
H x d), a head's columns together (``kda_flat``; ``kda`` takes a heads axis
and reshapes): on a TPU the last two axes of an array are tiled, so a (.., H,
d) view of such an operand is another layout and every reshape a copy.

The short convolutions in front of it are ``ops/short_conv.py``'s; the head
norm's two products behind it (``head_sums``, ``over_heads``) are here.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: What the forward keeps for the backward beside its operands, by the names a
#: remat policy may save (models/tinygpt.py's ``dots`` does): the output and
#: the states entering the chunks.
KDA_RESIDUAL_NAMES = ("kda_out", "kda_states")

#: Measured on a v5e at (1, 16384, 32, 128), four heads a grid step
#: (scripts/microbench_kda.py, ms a layer forward | forward + backward: 14.65 |
#: 50.83 at 64, 9.67 | 34.55 at 128): fewer sequential steps, and half the
#: states kept.
DEFAULT_CHUNK = 128
#: Heads a grid step of the kernels walks (the same sweep at chunk 128: 11.08 |
#: 36.37 at 1, 10.12 | 34.83 at 2, 9.67 | 34.55 at 4; at 8 the forward reads
#: 9.45 and the backward's spilled registers pass the kernel's 16 MiB of VMEM,
#: 28.31 MB: a head more in a step adds its whole schedule, so the step's
#: fixed cost is all there is to win).
HEADS_PER_STEP = 4


def kernel_mode() -> Optional[bool]:
    """``kda``'s ``interpret`` where the caller has no wish of its own: False,
    the Mosaic kernels, on a TPU; None, the ``jnp`` path, elsewhere."""
    return False if jax.default_backend() == "tpu" else None


@functools.lru_cache(maxsize=None)
def _quadrants(C: int) -> np.ndarray:
    """The 0/1 masks of a chunk of C positions, (log2 C, C, C) float32: at the
    level of half-size s = 2^level, the lower-left quadrant of every aligned
    block of 2s positions (rows in its upper half, columns in its lower)."""
    L = int(math.log2(C))
    if C < 2 or 2 ** L != C:
        raise ValueError(f"kda: the chunk is a power of two of at least 2 positions; got {C}")
    i, t = np.arange(C)[:, None], np.arange(C)[None, :]
    quadrant = []
    for level in range(L):
        s = 2 ** level
        up = (i % (2 * s)) >= s
        quadrant.append((i // (2 * s) == t // (2 * s)) & up & ~up.T)
    return np.stack(quadrant).astype(np.float32)


def _mm(a, b, dims, dtype):
    """``a`` x ``b`` contracting ``dims`` = (a's, b's), operands in ``dtype``,
    float32 out."""
    return lax.dot_general(
        a.astype(dtype), b.astype(dtype), (((dims[0],), (dims[1],)), ((), ())),
        preferred_element_type=jnp.float32)


def _in_upper_half(shape, s):
    """Whether a row lies in the upper half of its aligned block of 2s rows."""
    rows = lax.broadcasted_iota(jnp.int32, shape, 0)
    return lax.ne(lax.bitwise_and(rows, jnp.int32(s)), jnp.int32(0))


def _sibling(x, s):
    """x (C, d) -> x[r ^ s]: every aligned block of s rows changes places with
    the other half of its block of 2s. Halves of whole 8-row sublane tiles
    move as tiles (a reshape of the row axis); below that, two rolls of the
    rows under the mask of the half. A permutation that is its own inverse,
    so its transpose is itself."""
    C, d = x.shape
    if s % 8 == 0:
        halves = lax.reshape(x, (C // (2 * s), 2, s, d))
        half = lambda i: lax.slice_in_dim(halves, i, i + 1, axis=1)
        return lax.reshape(lax.concatenate([half(1), half(0)], 1), (C, d))
    roll = lambda n: lax.concatenate(  # jnp.roll(x, n, 0), 0 < n < C
        [lax.slice_in_dim(x, C - n, C, axis=0), lax.slice_in_dim(x, 0, C - n, axis=0)], 0)
    return lax.select(_in_upper_half(x.shape, s), roll(s), roll(C - s))


@jax.custom_vjp
def _decay_sums(g):
    """g (C, dk) float32 -> (P_C, X_C, [a level's (C, dk) sums], the (1, dk)
    sum over the chunk): one segmented scan over the rows, log2 C stages. With
    P_s[r] the sum of g from the first row of r's aligned block of s rows to r
    and X_s[r] the sum over the rows after r to that block's end (P_1 = g,
    X_1 = 0), a stage doubles the block: a row of the upper half adds the
    lower half's total to its P, a row of the lower half the upper half's
    total to its X; a block's total (kept on every row of the block) reaches
    the other half's rows by ``_sibling``. Every addition is of numbers of
    one sign; none is a difference of two running sums. The level of
    half-size s reads P_s on its upper rows (from the block's middle to the
    row) and X_s on its lower ones (from after the row to the middle)."""
    C = g.shape[0]
    P, X, total = g, lax.full_like(g, 0.0), g
    levels = []
    for level in range(int(math.log2(C))):
        s = 2 ** level
        up = _in_upper_half(g.shape, s)
        levels.append(lax.select(up, P, X))
        other = _sibling(total, s)
        P, X = lax.select(up, lax.add(P, other), P), lax.select(up, X, lax.add(X, other))
        total = lax.add(total, other)
    return P, X, levels, lax.slice_in_dim(total, 0, 1, axis=0)


def _decay_sums_bwd(shape, cotangents):
    """The same chain run backwards (written out: jax would transpose the
    slices of ``_sibling`` and of the total's row into pads, which a kernel
    cannot hold, and would trace three passes where this is one): a stage's
    cotangents add to P and X on the rows that read them, and what its rows
    hand to ``other`` goes back through ``_sibling`` to the block's total.
    The chunk's total is the sum of every row, so its cotangent is every
    row's."""
    dP, dX, dlevels, dtotal = cotangents
    zero = lax.full_like(dP, 0.0)
    dblock = zero  # of a block's total, on every row of the block
    for level in reversed(range(len(dlevels))):
        s = 2 ** level
        up = _in_upper_half(shape, s)
        dblock = lax.add(dblock, _sibling(lax.add(dblock, lax.select(up, dP, dX)), s))
        dP = lax.add(dP, lax.select(up, dlevels[level], zero))
        dX = lax.add(dX, lax.select(up, zero, dlevels[level]))
    return (lax.add(lax.add(dP, dblock), lax.broadcast_in_dim(dtotal, shape, (0, 1))),)


_decay_sums.defvjp(lambda g: (_decay_sums(g), g.shape), _decay_sums_bwd)


# A jit of its own: a kernel walks ``heads`` heads a grid step and a step's
# program holds the kernels several times over (the layers' stacks, remat, the
# benchmark's check), and every one after the first finds the body's jaxpr,
# its linearization and its transpose traced. Mosaic inlines the calls.
@functools.partial(jax.jit, static_argnums=(1,))
def _chunk(quadrant, scale, q, k, v, g, beta, S0):
    """One head's chunk: ``quadrant`` (``_quadrants``'s masks), q, k (C, dk), v
    (C, dv), g (C, dk) float32, beta (1, C) float32 (a row: lane-dense where
    it is stored; its column is taken here, a masked sum over the lanes), S0
    (dv, dk) float32, the state **transposed** (its decay is then a row over
    the lanes) -> (o (C, dv) in q's dtype, S_C^T float32): the module
    docstring's equations."""
    cd = q.dtype
    C = q.shape[0]
    eye = (lax.broadcasted_iota(jnp.int32, (C, C), 0)
           == lax.broadcasted_iota(jnp.int32, (C, C), 1)).astype(jnp.float32)
    beta = jnp.sum(eye * beta, axis=1, keepdims=True)  # (C, 1)
    qf, kf = q.astype(jnp.float32), k.astype(jnp.float32)
    kbf, vb = kf * beta, v.astype(jnp.float32) * beta
    # exp(G_r), exp(G_C - G_r), exp(G_C), a level's factors: all of non-positive sums
    running, after, sums, total = _decay_sums(g)
    decay, later, decay_all = jnp.exp(running), jnp.exp(after), jnp.exp(total)
    B = _mm(q, k, (1, 1), cd) * eye
    T = eye
    for level, x in enumerate(sums):
        # e is a row's factor on a block's upper rows and a column's on its lower
        # ones, and at most 1 on both: the quadrant's mask takes the rest away
        e = jnp.exp(x)
        columns = kf * e
        B_level = _mm(qf * e, columns, (1, 1), cd) * quadrant[level]
        A_level = _mm(kbf * e, columns, (1, 1), cd) * quadrant[level]
        B = B + B_level
        T = T - (A_level if level == 0 else _mm(_mm(T, A_level, (1, 0), cd), T, (1, 0), cd))
    W = _mm(T, kbf * decay, (1, 0), cd)
    U = _mm(T, vb, (1, 0), cd) - _mm(W, S0, (1, 1), cd)
    o = scale * (_mm(qf * decay, S0, (1, 1), cd) + _mm(B, U, (1, 0), cd))
    S1 = decay_all * S0 + _mm(U, kf * later, (0, 0), cd)
    return o.astype(cd), S1


# ---------------------------------------------------------------------------
# The jnp path: a scan over the chunks, every (batch, head) at once.
# ---------------------------------------------------------------------------

def _by_chunk(x, C, H):  # (B, S, H * d) -> (N, B, H, C, d)
    B, S, _ = x.shape
    return x.reshape(B, S // C, C, H, -1).transpose(1, 0, 3, 2, 4)


def _from_chunks(x):  # (N, B, H, C, d) -> (B, S, H * d)
    N, B, H, C, d = x.shape
    return x.transpose(1, 0, 3, 2, 4).reshape(B, N * C, H * d)


def _beta_by_chunk(beta, C):  # (B, S, H) -> (N, B, H, 1, C)
    B, S, H = beta.shape
    return beta.astype(jnp.float32).reshape(B, S // C, C, H).transpose(1, 0, 3, 2)[..., None, :]


def _chunk_of_every_head(opts):
    """``_chunk`` over (batch, heads)."""
    C, scale, *_ = opts
    return jax.vmap(jax.vmap(functools.partial(_chunk, jnp.asarray(_quadrants(C)), scale)))


def _jnp_forward(opts, q, k, v, g, beta):
    C, H, body = opts[0], opts[-1], _chunk_of_every_head(opts)
    B, dk, dv = q.shape[0], q.shape[-1] // H, v.shape[-1] // H

    def step(S, xs):
        o, S1 = body(*xs, S)
        return S1, (o, S.astype(q.dtype))

    S0 = jnp.zeros((B, H, dv, dk), jnp.float32)
    xs = tuple(_by_chunk(x, C, H) for x in (q, k, v, g)) + (_beta_by_chunk(beta, C),)
    _, (o, states) = lax.scan(step, S0, xs)
    return _from_chunks(o), states.transpose(1, 2, 0, 3, 4)  # states: (B, H, N, dv, dk)


def _jnp_backward(opts, q, k, v, g, beta, states, do):
    C, H, body = opts[0], opts[-1], _chunk_of_every_head(opts)

    def step(dS, xs):
        *operands, S0, d_o = xs
        _, pull_back = jax.vjp(body, *operands, S0.astype(jnp.float32))
        *grads, dS0 = pull_back((d_o, dS))
        return dS0, tuple(grads)

    B, dk, dv = q.shape[0], q.shape[-1] // H, v.shape[-1] // H
    xs = tuple(_by_chunk(x, C, H) for x in (q, k, v, g)) + (
        _beta_by_chunk(beta, C), states.transpose(2, 0, 1, 3, 4), _by_chunk(do, C, H))
    _, grads = lax.scan(step, jnp.zeros((B, H, dv, dk), jnp.float32), xs, reverse=True)
    *wide, dbeta = grads  # dbeta: (N, B, H, 1, C)
    dbeta = dbeta[..., 0, :].transpose(1, 0, 3, 2).reshape(beta.shape)
    return tuple(_from_chunks(x) for x in wide) + (dbeta,)


# ---------------------------------------------------------------------------
# The Pallas kernels. A grid step is one chunk of ``heads`` heads in a row: a
# head's chain of small products leaves the MXU waiting, and the next head's
# fills it.
# ---------------------------------------------------------------------------

def _whole(a):
    """The block that is all of ``a``, the same at every grid step."""
    return pl.BlockSpec(a.shape, lambda b, h, n: (0,) * a.ndim)


def _head(ref, j, d):
    """Head j's (C, d) columns of a (C, heads x d) block."""
    return ref[:, j * d:(j + 1) * d]


def _fwd_kernel(scale, heads, *refs):
    quadrant, q, k, v, g, beta, o, states, S = refs

    @pl.when(pl.program_id(2) == 0)
    def _():
        S[...] = jnp.zeros_like(S)

    dv, dk = S.shape[1:]
    for j in range(heads):
        S0 = S[j]
        states[j] = S0.astype(states.dtype)
        out, S1 = _chunk(quadrant[...], scale, _head(q, j, dk), _head(k, j, dk), _head(v, j, dv),
                         _head(g, j, dk), beta[j:j + 1, :], S0)
        o[:, j * dv:(j + 1) * dv] = out
        S[j] = S1


def _bwd_kernel(scale, heads, *refs):
    quadrant, q, k, v, g, beta, states, do, dq, dk_, dv_, dg, dbeta, dS = refs

    @pl.when(pl.program_id(2) == 0)
    def _():
        dS[...] = jnp.zeros_like(dS)

    body = functools.partial(_chunk, quadrant[...], scale)
    dv, dk = dS.shape[1:]
    for j in range(heads):
        _, pull_back = jax.vjp(
            body, _head(q, j, dk), _head(k, j, dk), _head(v, j, dv), _head(g, j, dk),
            beta[j:j + 1, :], states[j].astype(jnp.float32))
        *grads, dbeta[j:j + 1, :], dS[j] = pull_back((_head(do, j, dv), dS[j]))
        for ref, grad, d in zip((dq, dk_, dv_, dg), grads, (dk, dk, dv, dk)):
            ref[:, j * d:(j + 1) * d] = grad


def _pallas_call(kernel, name, interpret, grid, in_specs, out_specs, out_shape, scratch):
    return pl.pallas_call(
        kernel, grid=grid, in_specs=in_specs, out_specs=out_specs, out_shape=out_shape,
        scratch_shapes=scratch, interpret=interpret, name=name,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
    )


def _geometry(opts, q, v):
    C, _, _, heads, H = opts
    B, S, _ = q.shape
    return B, S, H, q.shape[-1] // H, v.shape[-1] // H, S // C, math.gcd(H, heads)


def _beta_by_step(beta, G, C):
    """(B, S, H) -> (B, H / G, N, G, C) float32: a grid step's block is the
    array's whole last two axes, its G heads' rows over the chunk's positions
    (G of H columns would be neither a block's whole last axis nor whole
    128-lane tiles, and G lanes of 128 a layout XLA takes milliseconds to
    make). A small array; the kernels make beta k and beta v themselves."""
    B, S, H = beta.shape
    return beta.astype(jnp.float32).reshape(B, S // C, C, H // G, G).transpose(0, 3, 1, 4, 2)


def _beta_from_steps(x, shape):  # (B, H / G, N, G, C) -> (B, S, H)
    return x.transpose(0, 2, 4, 1, 3).reshape(shape)


def _pallas_forward(opts, q, k, v, g, beta):
    C, scale, interpret, *_ = opts
    B, S, H, dk, dv, N, G = _geometry(opts, q, v)
    quadrant = _quadrants(C)
    wide = lambda d: pl.BlockSpec((None, C, G * d), lambda b, h, n: (b, n, h))
    narrow = pl.BlockSpec((None, None, None, G, C), lambda b, h, n: (b, h, n, 0, 0))
    o, states = _pallas_call(
        functools.partial(_fwd_kernel, scale, G), "kda_fwd", interpret,
        (B, H // G, N),
        [_whole(quadrant), wide(dk), wide(dk), wide(dv), wide(dk), narrow],
        [wide(dv), pl.BlockSpec((None, G, None, dv, dk), lambda b, h, n: (b, h, n, 0, 0))],
        [jax.ShapeDtypeStruct((B, S, H * dv), q.dtype),
         jax.ShapeDtypeStruct((B, H, N, dv, dk), q.dtype)],
        [pltpu.VMEM((G, dv, dk), jnp.float32)],
    )(quadrant, q, k, v, g, _beta_by_step(beta, G, C))
    return o, states


def _pallas_backward(opts, q, k, v, g, beta, states, do):
    C, scale, interpret, *_ = opts
    B, S, H, dk, dv, N, G = _geometry(opts, q, v)
    quadrant = _quadrants(C)
    # the chunks in reverse
    wide = lambda d: pl.BlockSpec((None, C, G * d), lambda b, h, n: (b, N - 1 - n, h))
    narrow = pl.BlockSpec((None, None, None, G, C), lambda b, h, n: (b, h, N - 1 - n, 0, 0))
    shape = lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype)
    by_step = _beta_by_step(beta, G, C)
    *wide_grads, dbeta = _pallas_call(
        functools.partial(_bwd_kernel, scale, G), "kda_bwd", interpret,
        (B, H // G, N),
        [_whole(quadrant), wide(dk), wide(dk), wide(dv), wide(dk), narrow] + [
            pl.BlockSpec((None, G, None, dv, dk), lambda b, h, n: (b, h, N - 1 - n, 0, 0)),
            wide(dv)],
        [wide(dk), wide(dk), wide(dv), wide(dk), narrow],
        [shape(x) for x in (q, k, v, g, by_step)],
        [pltpu.VMEM((G, dv, dk), jnp.float32)],
    )(quadrant, q, k, v, g, by_step, states, do)
    return (*wide_grads, _beta_from_steps(dbeta, beta.shape))


# ---------------------------------------------------------------------------
# The op.
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _kda(opts, q, k, v, g, beta):
    return _kda_fwd(opts, q, k, v, g, beta)[0]


def _kda_fwd(opts, q, k, v, g, beta):
    forward = _jnp_forward if opts[2] is None else _pallas_forward
    o, states = forward(opts, q, k, v, g, beta)
    # The results feed nothing but the two names, so where a policy saves them
    # the recompute's copy of the call is dead code (as the flash kernel's).
    o = checkpoint_name(o, KDA_RESIDUAL_NAMES[0])
    states = checkpoint_name(states, KDA_RESIDUAL_NAMES[1])
    return o, (q, k, v, g, beta, states)


def _kda_bwd(opts, residuals, do):
    q, k, v, g, beta, states = residuals
    backward = _jnp_backward if opts[2] is None else _pallas_backward
    dq, dk, dv, dg, dbeta = backward(opts, q, k, v, g, beta, states, do)
    return dq, dk, dv, dg, dbeta.astype(beta.dtype)


_kda.defvjp(_kda_fwd, _kda_bwd)


def kda(q, k, v, g, beta, chunk: int = DEFAULT_CHUNK, **options):
    """``kda_flat`` for operands with a heads axis: q, k, g (B, S, H, dk), v (B,
    S, H, dv) -> o (B, S, H, dv). (On a TPU an array's last two axes are tiled,
    so (B, S, H, d) and (B, S, H x d) are two layouts and a reshape between
    them a copy: a caller that has its operands flat calls ``kda_flat``.)"""
    B, S, H, _ = q.shape
    flat = lambda x: x.reshape(B, S, -1)
    o = kda_flat(flat(q), flat(k), flat(v), flat(g), beta, H, chunk, **options)
    return o.reshape(B, S, H, -1)


def kda_flat(q, k, v, g, beta, heads: int, chunk: int = DEFAULT_CHUNK, *,
             scale: Optional[float] = None, interpret: Optional[bool] = None,
             heads_per_step: int = HEADS_PER_STEP):
    """The recurrence of the module docstring over whole sequences.

    q, k (B, S, H x dk) and v (B, S, H x dv) in the compute type, a head's
    columns together; g (B, S, H x dk) float32, the log-decays, <= 0; beta (B,
    S, H). -> o (B, S, H x dv) in q's dtype. ``scale`` defaults to dk^-0.5. ``interpret``: None the ``jnp`` path,
    False the Mosaic kernels, True the kernels interpreted. ``heads_per_step``: the heads a grid
    step of the kernels walks (the gcd with H is taken). S must be whole chunks: a sequence
    that is not is refused, not padded."""
    S, dk, dv = q.shape[1], q.shape[-1] // heads, v.shape[-1] // heads
    if S % chunk:
        raise ValueError(
            f"kda: a sequence of {S} positions is not whole chunks of {chunk}; pad it "
            "(g = 0, beta = 0 add nothing) or choose a chunk that divides it")
    if g.dtype != jnp.float32:
        raise ValueError(f"kda: the log-decays are float32; got {g.dtype}")
    if interpret is not None and (dk % 128 or dv % 128):
        raise ValueError(
            f"kda: the kernels take head widths that are whole 128-lane tiles; got dk={dk}, "
            f"dv={dv} (the jnp path, interpret=None, takes any)")
    opts = (chunk, float(dk ** -0.5 if scale is None else scale), interpret, heads_per_step,
            heads)
    return _kda(opts, q, k, v, g, beta)


# The head norm's two products: what a (.., H, d) view would do, without the view.
def _head_columns(heads: int, d: int) -> jax.Array:
    """(H x d, H) float32 of 0s and 1s: column c belongs to head c // d."""
    return (jnp.arange(heads * d)[:, None] // d == jnp.arange(heads)[None, :]).astype(jnp.float32)


def head_sums(x: jax.Array, heads: int) -> jax.Array:
    """(B, S, H x d) float32 -> (B, S, H): each head's sum over its d columns,
    as a product with 0s and 1s at full precision. A (.., H, d) view of the
    operand would be another layout on a TPU, and the reshape a copy of it."""
    return jnp.einsum("bsc,ch->bsh", x, _head_columns(heads, x.shape[-1] // heads),
                      precision=lax.Precision.HIGHEST)


def over_heads(t: jax.Array, d: int) -> jax.Array:
    """(B, S, H) float32 -> (B, S, H x d): a head's value over its d columns
    (``head_sums``'s transpose, the same way)."""
    return jnp.einsum("bsh,ch->bsc", t, _head_columns(t.shape[-1], d),
                      precision=lax.Precision.HIGHEST)
