"""The ``kda`` layer's mixer: Kimi Delta Attention (Kimi Linear,
arXiv:2510.26692), the gated delta-rule recurrence of ``ops/kda.py`` behind
the short convolutions of ``ops/short_conv.py``. ``models/mixers/__init__.py``
has the table and what each name here is asked for."""

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
from ...utils import scopes
from ..common import Params, _norm, normal

#: Its stacks of the parameter tree, in the order ``init_params`` draws them.
STACKS = ("kda_blocks", "kda_dense_blocks")

#: ``checkpoint_name`` of the q, k, v projection in its compute-dtype form.
KDA_QKV = "kda_qkv"
CAST_NAMES = (KDA_QKV,)
RESIDUAL_NAMES = kda_ops.KDA_RESIDUAL_NAMES

#: The mixer's leaves (present instead of the attention leaves): q, k, v
#: projections on a 'qkv3' axis, their depthwise causal convolutions (filter
#: taps on 'conv'), the decay's low-rank map with its per-head rate and
#: per-channel bias, beta, the output gate's low-rank map, the head norm's
#: (kda_head_dim,) scale; wo as attention's.
AXIS_RULES = {
    "blocks/kda_wqkv": ("layers", "embed", "qkv3", "heads"),
    "blocks/kda_conv": ("layers", "qkv3", "conv", "heads"),
    "blocks/kda_wfa": ("layers", "embed", "kda_rank"),
    "blocks/kda_wfb": ("layers", "kda_rank", "heads"),
    "blocks/kda_a_log": ("layers", "kda_heads"),
    "blocks/kda_dt_bias": ("layers", "heads"),
    "blocks/kda_wb": ("layers", "embed", "kda_heads"),
    "blocks/kda_wga": ("layers", "embed", "kda_rank"),
    "blocks/kda_wgb": ("layers", "kda_rank", "heads"),
    "blocks/kda_norm": ("layers", "head_dim"),
    "blocks/wo": ("layers", "heads_merged", "embed"),
}


NEEDS = "a 'kda' layer needs kda_heads, kda_head_dim, kda_conv >= 1, kda_chunk >= 2"


def check(c) -> bool:
    """Whether the config's fields give the layer what is its own of ``NEEDS``."""
    return c.kda_heads > 0 and c.kda_head_dim > 0 and c.kda_conv >= 1 and c.kda_chunk >= 2


def leaves(c, k, L: int, kind=None) -> Params:
    """One stack's norm scales and KDA leaves, L layers, drawn from the key
    iterator ``k``. The filters start as a depthwise Conv1d's do (uniform
    within 1 / sqrt(taps)), the decay's rate exp(A_log) uniform on [1, 16] a
    head, and its bias the inverse softplus of a step log-uniform on [0.001,
    0.1] a channel: the family's published initialisation."""
    D, Hk, Dk, taps = c.n_embd, c.kda_heads, c.kda_head_dim, c.kda_conv
    uniform = lambda key, shape, lo, hi: jax.random.uniform(
        key, shape, jnp.float32, minval=lo, maxval=hi)
    step = jnp.exp(uniform(next(k), (L, Hk * Dk), math.log(1e-3), math.log(0.1)))
    bound = taps ** -0.5
    return dict(
        ln1_scale=jnp.ones((L, D), c.param_dtype), ln2_scale=jnp.ones((L, D), c.param_dtype),
        kda_wqkv=normal(c, next(k), (L, D, 3, Hk * Dk)),
        kda_conv=uniform(next(k), (L, 3, taps, Hk * Dk), -bound, bound).astype(c.param_dtype),
        kda_wfa=normal(c, next(k), (L, D, Dk)),
        kda_wfb=normal(c, next(k), (L, Dk, Hk * Dk)),
        kda_a_log=jnp.log(uniform(next(k), (L, Hk), 1.0, 16.0)).astype(c.param_dtype),
        kda_dt_bias=(step + jnp.log(-jnp.expm1(-step))).astype(c.param_dtype),
        kda_wb=normal(c, next(k), (L, D, Hk)),
        kda_wga=normal(c, next(k), (L, D, Dk)),
        kda_wgb=normal(c, next(k), (L, Dk, Hk * Dk)),
        kda_norm=jnp.ones((L, Dk), c.param_dtype),
        wo=normal(c, next(k), (L, Hk * Dk, D)),
    )


def sublayer(c, x: jax.Array, layer: Params, *unused) -> jax.Array:
    """Norm -> Kimi Delta Attention -> residual: a ``kda`` layer's mixer, in
    three scopes. ``kda_prep``: q = l2norm(silu(conv(h Wq))), k likewise, v =
    silu(conv(h Wv)) (one projection, then ``ops.short_conv.qkv_prologue``: on a TPU
    at whole 128-lane head widths the convolution, SiLU and the l2norms are
    one Mosaic call a third of the columns, ``kda_conv_fwd``, and one back,
    ``kda_conv_bwd``; elsewhere XLA's convolution and the ``jnp`` chain), the
    log-decay a key channel g = -exp(A_log) softplus(Wfb (Wfa h) + dt_bias)
    and beta = sigmoid(h Wb), g and beta float32.
    ``kda_core``: the recurrence (``ops/kda.py``: the Mosaic kernels on a TPU
    at whole 128-lane head widths, its ``jnp`` path elsewhere). ``kda_out``:
    Wo [RMSNorm over each head's values (one (kda_head_dim,) scale) x
    sigmoid(Wgb (Wga h))]."""
    B, S, _ = x.shape
    cd = c.compute_dtype
    H, Dk = c.kda_heads, c.kda_head_dim
    proj = functools.partial(jnp.einsum, preferred_element_type=jnp.float32)
    h = _norm(c, x, layer["ln1_scale"], layer.get("ln1_bias"))
    with jax.named_scope(scopes.KDA_PREP):
        # one (D, 3 H Dk) product: with q, k, v on an axis of their own XLA lays the
        # result out (3, S, H Dk) and the flat view the convolution takes is a copy
        wqkv = layer["kda_wqkv"].reshape(x.shape[-1], 3 * H * Dk).astype(cd)
        qkv = checkpoint_name(proj("bsd,de->bse", h, wqkv).astype(cd), KDA_QKV)
        taps = jnp.moveaxis(layer["kda_conv"], 0, 1).reshape(c.kda_conv, 3 * H * Dk)
        fits = Dk % 128 == 0  # the kernels' widths; else XLA's convolution and the jnp scan
        mode = kda_ops.kernel_mode() if fits else None
        q, k, v = short_conv.qkv_prologue(qkv, taps, H, interpret=mode)
        low = proj("bsd,dr->bsr", h, layer["kda_wfa"].astype(cd)).astype(cd)
        rate = proj("bsr,re->bse", low, layer["kda_wfb"].astype(cd))  # float32
        # every per-channel operand stays (B, S, H x Dk), a head's columns together: on
        # a TPU a (.., H, Dk) view of it is another layout, and a reshape a copy
        g = -jnp.repeat(jnp.exp(layer["kda_a_log"].astype(jnp.float32)), Dk) * jax.nn.softplus(
            rate + layer["kda_dt_bias"].astype(jnp.float32))
        beta = jax.nn.sigmoid(proj("bsd,dh->bsh", h, layer["kda_wb"].astype(cd)))
    with jax.named_scope(scopes.KDA_CORE):
        o = kda_ops.kda_flat(q, k, v, g, beta, H, c.kda_chunk, interpret=mode)
    with jax.named_scope(scopes.KDA_OUT):
        low = proj("bsd,dr->bsr", h, layer["kda_wga"].astype(cd)).astype(cd)
        gate = proj("bsr,re->bse", low, layer["kda_wgb"].astype(cd)).astype(cd)
        of = o.astype(jnp.float32)  # RMSNorm over each head's values, one (Dk,) scale
        of = of * kda_ops.over_heads(
            lax.rsqrt(kda_ops.head_sums(of * of, H) / Dk + c.norm_eps), Dk)
        of = of * jnp.tile(layer["kda_norm"].astype(jnp.float32), H)
        o = (of * jax.nn.sigmoid(gate.astype(jnp.float32))).astype(cd)
        return x + proj("bse,ed->bsd", o, layer["wo"].astype(cd)).astype(cd)


def kda_stats(config, seq_len: int) -> Dict[str, Any]:
    """Counters of the ``kda`` layers over sequences of ``seq_len`` tokens,
    from the config and the backend at trace time: ``layers`` of the kind,
    ``chunk`` and ``chunks`` a sequence, ``kernel_calls`` a step by name (one
    forward and one backward a layer where the kernels run, none on the ``jnp``
    path; remat's second forward is not counted), ``prep_kernel_calls`` the
    same of ``qkv_prologue``'s two (a call each for q, k and v), and
    ``saved_state_bytes`` a layer a sequence: the states entering the chunks,
    which the forward keeps for the backward beside its operands."""
    c = config
    layers = (c.layer_types or ()).count(scopes.KDA)
    chunks = seq_len // c.kda_chunk
    kernels = layers if (c.kda_head_dim % 128 == 0
                         and kda_ops.kernel_mode() is not None) else 0
    prologues = 3 * kernels if short_conv.conv_fits(seq_len, c.kda_conv, c.kda_head_dim) else 0
    return {
        "layers": layers, "chunk": c.kda_chunk, "chunks": chunks,
        "kernel_calls": {"kda_fwd": kernels, "kda_bwd": kernels},
        "prep_kernel_calls": {"kda_conv_fwd": prologues, "kda_conv_bwd": prologues},
        "saved_state_bytes": (c.kda_heads * chunks * c.kda_head_dim ** 2
                              * jnp.dtype(c.compute_dtype).itemsize),
    }


def forward_flops_per_token(c, kind=None) -> float:
    """One ``kda`` layer's mixer, a token: the projections (q, k, v; the
    decay's and the gate's low-rank maps of rank kda_head_dim; beta; the
    output), the three convolutions' taps, and the recurrence counted as the
    chunkwise form's work at the config's chunk C with d = kda_head_dim, a
    head: five products of 2 C d (K K^T, Q K^T, the two applications of the
    inverse, the intra-chunk output), three of 2 d^2 through the state, and
    2 C^2 / 3 for the triangular inverse. What a kernel multiplies beyond
    that (masked halves, its own way to the inverse) is its choice."""
    D, H, d, C = c.n_embd, c.kda_heads, c.kda_head_dim, c.kda_chunk
    projections = 2 * D * 3 * H * d + 2 * (2 * D * d + 2 * d * H * d) + 2 * D * H + 2 * H * d * D
    convolutions = 2 * c.kda_conv * 3 * H * d
    recurrence = H * (5 * 2 * C * d + 3 * 2 * d * d + 2 * C * C / 3)
    return float(projections + convolutions + recurrence)


def kept_bytes(c, pol: str, S: int, cbytes: int) -> int:
    """What a layer keeps of a sequence of S tokens for its backward under
    the remat policy ``pol``: the states entering its chunks and its output by
    name, without remat also its five operands (g in float32), under
    ``full_keep_kernels`` ``KDA_QKV``; under ``full`` it runs again."""
    if pol == "full":
        return 0
    width = c.kda_heads * c.kda_head_dim
    kept = kda_stats(c, S)["saved_state_bytes"] + S * width * cbytes
    if pol == "none":
        kept += S * width * (3 * cbytes + 4) + S * c.kda_heads * 4
    return kept + (S * 3 * width * cbytes if pol == "full_keep_kernels" else 0)
