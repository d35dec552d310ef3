"""The ``conv`` layer's mixer: a gated short convolution (LFM2), whose
middle is ``ops/short_conv.py::gated_conv``. ``models/mixers/__init__.py`` has
the table and what each name here is asked for."""

from __future__ import annotations

import functools
from typing import Any, Dict

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from ...ops import short_conv
from ...utils import scopes
from ..common import Params, _norm, normal

#: Its stacks of the parameter tree, in the order ``init_params`` draws them.
STACKS = ("conv_dense_blocks", "conv_blocks")

#: ``checkpoint_name`` of the B | C | x~ projection after its cast (the gated result
#: has none: ``tinygpt._under_remat``'s second clause).
SCONV_BCX = "sconv_bcx"
CAST_NAMES = (SCONV_BCX,)
RESIDUAL_NAMES = ()  # the kernels keep nothing but their operand

#: The mixer's leaves (present instead of the attention leaves): the input
#: projection's columns [B | C | x~] and the depthwise convolution's taps over
#: the embed channels; wo as attention's. No tensor-parallel rule: under a
#: 'model' axis they stay whole.
AXIS_RULES = {
    "blocks/sconv_win": ("layers", "embed", "sconv_in"),
    "blocks/sconv_taps": ("layers", "conv", "sconv_channels"),
    "blocks/wo": ("layers", "heads_merged", "embed"),
}


NEEDS = "a 'conv' layer (a gated short convolution) needs conv_taps >= 1, no block_halves"


def check(c) -> bool:
    """Whether the config's fields give the layer what is its own of ``NEEDS``."""
    return c.conv_taps >= 1 and not c.block_halves


def leaves(c, k, L: int, kind=None) -> Params:
    """One stack's norm scales and gated-convolution leaves, L layers, drawn
    from the key iterator ``k``: the taps as a depthwise Conv1d's default
    (uniform within 1 / sqrt(taps))."""
    D, bound = c.n_embd, c.conv_taps ** -0.5
    return dict(
        ln1_scale=jnp.ones((L, D), c.param_dtype), ln2_scale=jnp.ones((L, D), c.param_dtype),
        sconv_win=normal(c, next(k), (L, D, 3 * D)),
        sconv_taps=jax.random.uniform(
            next(k), (L, c.conv_taps, D), jnp.float32, minval=-bound, maxval=bound
        ).astype(c.param_dtype),
        wo=normal(c, next(k), (L, D, D)),
    )


def sublayer(c, x: jax.Array, layer: Params, *unused) -> jax.Array:
    """Norm -> gated short convolution -> residual: a ``conv`` layer's mixer, in
    three scopes. ``sconv_in``: [B | C | x~] = h W_in, one (D, 3 D) product
    whose result after its cast has a name (``SCONV_BCX``: ``full_keep_kernels``
    keeps it, ``dots`` holds the product itself). ``sconv_core``:
    C * conv(B * x~), the depthwise causal convolution of ``conv_taps``
    positions with zeros before the sequence, no bias and no activation
    (``ops.short_conv.gated_conv``: on a TPU where ``conv_fits`` one Mosaic call a
    direction, ``sconv_fwd`` / ``sconv_bwd``, which find the three thirds of the
    operand by their block specs; elsewhere the ``jnp`` chain). ``sconv_out``:
    W_out."""
    cd = c.compute_dtype
    proj = functools.partial(jnp.einsum, preferred_element_type=jnp.float32)
    h = _norm(c, x, layer["ln1_scale"], layer.get("ln1_bias"))
    with jax.named_scope(scopes.SCONV_IN):
        bcx = checkpoint_name(
            proj("bsd,de->bse", h, layer["sconv_win"].astype(cd)).astype(cd), SCONV_BCX)
    with jax.named_scope(scopes.SCONV_CORE):
        y = short_conv.gated_conv(bcx, layer["sconv_taps"], interpret=short_conv.kernel_mode())
    with jax.named_scope(scopes.SCONV_OUT):
        return x + proj("bse,ed->bsd", y, layer["wo"].astype(cd)).astype(cd)


def sconv_stats(config, seq_len: int) -> Dict[str, Any]:
    """Counters of the ``conv`` layers over sequences of ``seq_len`` tokens,
    from the config and the backend at trace time: ``layers`` of the kind,
    ``taps``, ``layers_in_kernel`` of them whose gated convolution the Mosaic
    calls take (``kernel_calls`` a step by name, as ``kda_stats`` counts them),
    and the bytes one call moves a sequence each way at the stored width:
    forward (S, 3 D) in and (S, D) out, backward those and the (S, 3 D) result."""
    c = config
    layers = (c.layer_types or ()).count(scopes.CONV)
    taken = layers if (layers and short_conv.kernel_mode() is not None
                       and short_conv.conv_fits(seq_len, c.conv_taps, c.n_embd)) else 0
    cell = seq_len * c.n_embd * jnp.dtype(c.compute_dtype).itemsize
    return {
        "layers": layers, "taps": c.conv_taps, "layers_in_kernel": taken,
        "kernel_calls": {"sconv_fwd": taken, "sconv_bwd": taken},
        "forward_bytes": 4 * cell if layers else 0,
        "backward_bytes": 7 * cell if layers else 0,
    }


def forward_flops_per_token(c, kind=None) -> float:
    """One ``conv`` layer's mixer, a token: the input projection to B | C | x~
    (D -> 3 D), the convolution's taps over the D channels and the output
    projection (D -> D). The two gates are elementwise: not counted."""
    D = c.n_embd
    return float(2 * D * 3 * D + 2 * c.conv_taps * D + 2 * D * D)


def kept_bytes(c, pol: str, S: int, cbytes: int) -> int:
    """What a layer keeps of a sequence of S tokens for its backward under
    the remat policy ``pol``: without remat B | C | x~ and the gated result,
    under ``full_keep_kernels`` B | C | x~ (``SCONV_BCX``; the gated result
    has no name), else nothing of its own."""
    return S * {"none": 4, "full_keep_kernels": 3}.get(pol, 0) * c.n_embd * cbytes
