"""What every sublayer shares, the mixers (``models/mixers/``) and the
feed-forward part alike: the two norms, dropout, ``init_params``' way to draw
a weight, the feed-forward part's two checkpoint names. It imports nothing of
the model, so that ``tinygpt`` and the mixers can both import it."""

from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax import lax

from ..utils import scopes

Params = Dict[str, Any]

#: ``checkpoint_name``s of the feed-forward part's matmul results after their
#: casts (``tinygpt.MATMUL_CAST_NAMES``): a dense SwiGLU layer's gate+up and a
#: shared expert's up product where it is not gated (``moe._shared_experts``).
MLP_GU, SHARED_U = "mlp_gu", "shared_u"


def normal(c, key: jax.Array, shape) -> jax.Array:
    """A weight as ``init_params`` draws it: normal(0, 0.02) in the parameters' dtype."""
    return (0.02 * jax.random.normal(key, shape)).astype(c.param_dtype)


def _layer_norm(x: jax.Array, scale: jax.Array, bias: jax.Array, eps: float = 1e-5) -> jax.Array:
    # fp32 statistics regardless of compute dtype (AMP-style numerics).
    xf = x.astype(jnp.float32)
    mean = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    y = (xf - mean) * lax.rsqrt(var + eps)
    return (y * scale.astype(jnp.float32) + bias.astype(jnp.float32)).astype(x.dtype)


def _rms_norm(x: jax.Array, scale: jax.Array, eps: float) -> jax.Array:
    # Llama RMSNorm: no mean subtraction, no bias; fp32 statistics (HF
    # LlamaRMSNorm computes the rsqrt in fp32 and multiplies the scale in
    # the input dtype — we keep the whole product fp32 before the downcast,
    # which agrees to within bf16 rounding).
    xf = x.astype(jnp.float32)
    y = xf * lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (y * scale.astype(jnp.float32)).astype(x.dtype)


def _norm(
    config, x: jax.Array, scale: jax.Array, bias: Optional[jax.Array]
) -> jax.Array:
    if config.norm == "rmsnorm":
        return _rms_norm(x, scale, config.norm_eps)
    return _layer_norm(x, scale, bias, config.norm_eps)


@jax.named_scope(scopes.DROPOUT)
def _dropout(x: jax.Array, rate: float, key: Optional[jax.Array], deterministic: bool) -> jax.Array:
    if deterministic or rate == 0.0 or key is None:
        return x
    keep = 1.0 - rate
    mask = jax.random.bernoulli(key, keep, x.shape)
    return jnp.where(mask, x / keep, jnp.zeros((), x.dtype)).astype(x.dtype)
