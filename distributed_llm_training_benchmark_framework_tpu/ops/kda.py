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

Beside it what stands between a KDA layer's q, k, v projection and the
recurrence (``qkv_prologue``): the depthwise causal convolutions over
positions, SiLU, and each head of q and k over its l2 norm, in two more Mosaic
calls, ``kda_conv_fwd`` / ``kda_conv_bwd``, a call a third of the columns
each way. The forward makes all of it on the float32 slab (a head's lanes of
a tile) it holds after the taps and writes q, k and v once, as three arrays; the backward takes
their three cotangents, computes the convolution's output, the sigmoid and the
inverse norms again from x, and writes one dx and the taps' gradient: each
element of q, k, v is read once and written once a direction, and nothing is
kept but x. ``causal_conv`` is the same kernels without the epilogue. Their
``jnp`` path (another backend, or heads that are not whole 128-lane tiles) is
XLA's grouped convolution and ``silu_l2norm``, which is also what the tests
hold the kernels to. ``gated_conv`` is a mixer of its own on the same
convolution: c * conv(b * x) over the thirds of one operand, the two gates as
the prologue and the epilogue of two more Mosaic calls, ``sconv_fwd`` /
``sconv_bwd``.
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


# ---------------------------------------------------------------------------
# The short convolutions in front of the recurrence, and what stands between
# them and it: SiLU, and the l2norm of each head of q and k.
# ---------------------------------------------------------------------------

_CONV_ROWS, _CONV_COLUMNS = 512, 512  # a tile of the convolution's kernels
L2NORM_EPS = 1e-6  # the published kernel's


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
