"""The ``ssd`` block's mixer: Mamba-2 (Nemotron-H), the scalar-decay
state-space scan of ``ops/ssd.py`` behind the short convolution of
``ops/short_conv.py``. ``models/mixers/__init__.py`` has the table and what
each name here is asked for."""

from __future__ import annotations

import functools
import math
from typing import Any, Dict

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name

from ...ops import kda as kda_ops
from ...ops import short_conv
from ...ops import ssd as ssd_ops
from ...utils import scopes
from ..common import Params, _norm, normal

#: None by a name of its own: a half alone (``block_halves``) lies in a stack by kind.
STACKS = ()

#: ``checkpoint_name``s of in_proj's x | B | C and z after their casts (dt has none).
SSD_XBC, SSD_Z = CAST_NAMES = ("ssd_xbc", "ssd_z")
RESIDUAL_NAMES = ssd_ops.SSD_RESIDUAL_NAMES

#: The mixer's leaves (the stack 'ssd_blocks'; present instead of the
#: attention leaves): in_proj's columns [z | x B C | dt], the depthwise
#: convolution's taps and bias over x | B | C, a head's dt bias, decay rate
#: and skip, the gated norm's (d_inner,) scale; wo (out_proj) as attention's.
#: No tensor-parallel rule: under a 'model' axis they stay whole.
AXIS_RULES = {
    "blocks/ssd_win": ("layers", "embed", "ssd_in"),
    "blocks/ssd_conv": ("layers", "conv", "ssd_xbc"),
    "blocks/ssd_conv_bias": ("layers", "ssd_xbc"),
    "blocks/ssd_dt_bias": ("layers", "ssd_heads"),
    "blocks/ssd_a_log": ("layers", "ssd_heads"),
    "blocks/ssd_d": ("layers", "ssd_heads"),
    "blocks/ssd_norm": ("layers", "ssd_inner"),
    "blocks/wo": ("layers", "heads_merged", "embed"),
}


NEEDS = ("an 'ssd' layer needs ssd_heads, ssd_head_dim, ssd_state, ssd_groups dividing ssd_heads, "
         "ssd_conv >= 1, ssd_chunk >= 1, block_halves=True (a Mamba-2 block is the mixer alone)")


def check(c) -> bool:
    """Whether the config's fields give the block what is its own of ``NEEDS``."""
    return (c.ssd_heads > 0 and c.ssd_head_dim > 0 and c.ssd_state > 0 and c.ssd_groups > 0
            and c.ssd_heads % c.ssd_groups == 0 and c.ssd_conv >= 1 and c.ssd_chunk >= 1
            and c.block_halves)


def leaves(c, k, L: int, kind=None) -> Params:
    """A stack of ``ssd`` blocks: the norm's scale and the Mamba-2 mixer's
    leaves, L layers, drawn from the key iterator ``k``. The family's
    published initialisation: the filters as a depthwise Conv1d's (uniform
    within 1 / sqrt(taps), the bias too), the decay's rate exp(A_log) uniform
    on [1, 16] a head, dt's bias the inverse softplus of a step log-uniform on
    [0.001, 0.1] a head (floor 1e-4), the skip D at ones, the gated norm's
    scale at ones."""
    D, Hs, taps, W = c.n_embd, c.ssd_heads, c.ssd_conv, c.ssd_xbc
    uniform = lambda key, shape, lo, hi: jax.random.uniform(
        key, shape, jnp.float32, minval=lo, maxval=hi)
    step = jnp.maximum(
        jnp.exp(uniform(next(k), (L, Hs), math.log(1e-3), math.log(0.1))), 1e-4)
    bound = taps ** -0.5
    return dict(
        ln1_scale=jnp.ones((L, D), c.param_dtype),
        ssd_win=normal(c, next(k), (L, D, c.ssd_inner + W + Hs)),
        ssd_conv=uniform(next(k), (L, taps, W), -bound, bound).astype(c.param_dtype),
        ssd_conv_bias=uniform(next(k), (L, W), -bound, bound).astype(c.param_dtype),
        ssd_dt_bias=(step + jnp.log(-jnp.expm1(-step))).astype(c.param_dtype),
        ssd_a_log=jnp.log(uniform(next(k), (L, Hs), 1.0, 16.0)).astype(c.param_dtype),
        ssd_d=jnp.ones((L, Hs), c.param_dtype),
        ssd_norm=jnp.ones((L, c.ssd_inner), c.param_dtype),
        wo=normal(c, next(k), (L, c.ssd_inner, D)),
    )


def sublayer(c, x: jax.Array, layer: Params, *unused) -> jax.Array:
    """Norm -> Mamba-2 mixer -> residual: an ``ssd`` block, in three scopes.
    ``ssd_prep``: [z | xBC | dt] = h W_in as three products of the weight's
    column blocks (slices of the weight, not of a (B, S, 10304) result), xBC
    = silu(conv(xBC) + bias) (``ops.short_conv.conv_silu``: on a TPU the convolution,
    its bias and SiLU are one Mosaic call over the 6144 columns,
    ``kda_conv_fwd``, and one back; elsewhere XLA's convolution), dt =
    softplus(dt + dt_bias) and the log-decay g = -exp(A_log) dt a head, both
    float32 (no clamp beyond softplus). ``ssd_core``: the scan (``ops/ssd.py``:
    the Mosaic kernels on a TPU where ``ops.ssd.fits``, its ``jnp`` path
    elsewhere) over xBC as it stands: x's, B's and C's columns are found by
    the kernels' block specs. ``ssd_out``: the skip D x, the gate u = y
    silu(z), the RMS over each group's d_inner / ssd_groups channels times
    the (d_inner,) scale (gate first, then the norm), and W_out."""
    cd = c.compute_dtype
    H, P, groups = c.ssd_heads, c.ssd_head_dim, c.ssd_groups
    inner, W = c.ssd_inner, c.ssd_xbc
    proj = functools.partial(jnp.einsum, preferred_element_type=jnp.float32)
    h = _norm(c, x, layer["ln1_scale"], layer.get("ln1_bias"))
    with jax.named_scope(scopes.SSD_PREP):
        win = layer["ssd_win"].astype(cd)
        z = checkpoint_name(proj("bsd,de->bse", h, win[:, :inner]).astype(cd), SSD_Z)
        xbc = checkpoint_name(proj("bsd,de->bse", h, win[:, inner:inner + W]).astype(cd), SSD_XBC)
        dt = proj("bsd,dh->bsh", h, win[:, inner + W:])  # float32
        xbc = short_conv.conv_silu(xbc, layer["ssd_conv"], layer["ssd_conv_bias"],
                                   interpret=short_conv.kernel_mode())
        dt = jax.nn.softplus(dt + layer["ssd_dt_bias"].astype(jnp.float32))
        g = -jnp.exp(layer["ssd_a_log"].astype(jnp.float32)) * dt
    with jax.named_scope(scopes.SSD_CORE):
        fits = ssd_ops.fits(P, c.ssd_state, H, groups)
        y = ssd_ops.ssd_flat(xbc, dt, g, H, groups, P, c.ssd_chunk,
                             interpret=ssd_ops.kernel_mode() if fits else None)
    with jax.named_scope(scopes.SSD_OUT):
        # every per-channel operand stays (B, S, d_inner): see the kda mixer's sublayer
        skip = jnp.repeat(layer["ssd_d"].astype(jnp.float32), P)
        u = y.astype(jnp.float32) + skip * xbc[..., :inner].astype(jnp.float32)
        u = u * jax.nn.silu(z.astype(jnp.float32))
        u = u * kda_ops.over_heads(
            lax.rsqrt(kda_ops.head_sums(u * u, groups) / (inner // groups) + c.norm_eps),
            inner // groups)
        u = (u * layer["ssd_norm"].astype(jnp.float32)).astype(cd)
        return x + proj("bse,ed->bsd", u, layer["wo"].astype(cd)).astype(cd)


def ssd_stats(config, seq_len: int) -> Dict[str, Any]:
    """Counters of the ``ssd`` layers over sequences of ``seq_len`` tokens,
    from the config and the backend at trace time: ``layers`` of the kind,
    ``chunk`` and ``chunks`` a sequence, ``chunk_steps`` the grid steps one
    kernel call makes a sequence (a chunk of a group each), ``kernel_calls`` a
    step by name (as ``kda_stats`` counts them), ``conv_kernel_calls`` the same
    of the convolution's two, and ``saved_state_bytes`` a layer a sequence: the
    states entering the chunks, kept for the backward beside its operands."""
    c = config
    layers = (c.layer_types or ()).count(scopes.SSD)
    chunks = seq_len // c.ssd_chunk if layers else 0
    on = layers > 0 and ssd_ops.kernel_mode() is not None
    kernels = layers if on and ssd_ops.fits(
        c.ssd_head_dim, c.ssd_state, c.ssd_heads, c.ssd_groups) else 0
    convs = layers if on and short_conv.conv_fits(seq_len, c.ssd_conv, c.ssd_xbc) else 0
    return {
        "layers": layers, "chunk": c.ssd_chunk, "chunks": chunks,
        "chunk_steps": chunks * c.ssd_groups,
        "kernel_calls": {"ssd_fwd": kernels, "ssd_bwd": kernels},
        "conv_kernel_calls": {"kda_conv_fwd": convs, "kda_conv_bwd": convs},
        "saved_state_bytes": (chunks * c.ssd_inner * c.ssd_state
                              * jnp.dtype(c.compute_dtype).itemsize),
    }


def forward_flops_per_token(c, kind=None) -> float:
    """One ``ssd`` block's mixer, a token: in_proj ([z | x B C | dt]), the
    convolution's taps, out_proj, and the scan counted as the chunkwise form's
    work at the config's chunk C, with P = ssd_head_dim and N = ssd_state: C
    B^T once a group (2 C N), and a head's (L o C B^T)(dt x) (2 C P), C S_0^T
    and the state's update (2 N P each). What a kernel multiplies beyond that
    (a slab's masked half) is its choice."""
    D, H, P, N, C = c.n_embd, c.ssd_heads, c.ssd_head_dim, c.ssd_state, c.ssd_chunk
    projections = 2 * D * (c.ssd_inner + c.ssd_xbc + H) + 2 * c.ssd_inner * D
    convolution = 2 * c.ssd_conv * c.ssd_xbc
    scan = H * (2 * C * P + 4 * N * P) + c.ssd_groups * 2 * C * N
    return float(projections + convolution + scan)


def kept_bytes(c, pol: str, S: int, cbytes: int) -> int:
    """What a block keeps of a sequence of S tokens for its backward under
    the remat policy ``pol``: the states entering its chunks and its output by
    name; without remat also x | B | C before and after the convolution, z, dt
    and the sums of the log-decay in float32; under ``full_keep_kernels``
    ``SSD_XBC`` and ``SSD_Z``; under ``full`` it runs again."""
    if pol == "full":
        return 0
    kept = ssd_stats(c, S)["saved_state_bytes"] + S * c.ssd_inner * cbytes
    if pol == "none":
        kept += S * ((2 * c.ssd_xbc + c.ssd_inner) * cbytes + 2 * c.ssd_heads * 4)
    return kept + (S * (c.ssd_xbc + c.ssd_inner) * cbytes if pol == "full_keep_kernels" else 0)
