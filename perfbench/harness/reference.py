"""The plain reference: this block family's forward pass and loss in
``jax.numpy`` and float32, with no kernel, for both knob sets.

Written from the architectures' descriptions, not from the program's code;
what it shares with the program is the layout of the parameter tree (it has
to read the same weights) and the program's convention that a position's
target is its own token (``train/step.py``: targets = inputs, unshifted).

* GPT-2-style block (tinygpt-a): learned positions, LayerNorm with bias,
  fused qkv with bias, exact-erf GELU MLP, head tied to the embedding.
* Mistral/Llama-style block (mistral-7b): RMSNorm, rotary positions in the
  rotate-half convention, grouped-query attention, SwiGLU, untied head.

Attention runs one head at a time (``lax.map``), the layers run as a
``lax.scan`` over the stacked weights, and every head and layer is
rematerialized in the backward pass: the (S, S) float32 scores of a long
sequence then fit beside the training state, and the program compiles in
seconds (unrolled in Python, the tier-A gradient took minutes of every cold
set-up). None of the three changes the arithmetic.
``m`` is the dict ``build.model_shape`` returns. Call under
``jax.default_matmul_precision("highest")``: on a TPU a float32 matmul is
otherwise done in bfloat16 passes.
"""

import jax
import jax.numpy as jnp


def _norm(m, x, scale, bias):
    if m["norm"] == "rmsnorm":
        return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + m["norm_eps"]) * scale
    mean = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, -1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + m["norm_eps"]) * scale + bias


def _rotate(m, x):  # x: (S, heads, head_dim)
    half = m["head_dim"] // 2
    inv_freq = m["rope_theta"] ** (-jnp.arange(half, dtype=jnp.float32) * 2.0 / m["head_dim"])
    angle = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def _attention(m, q, k, v):  # (S, heads, head_dim) each, k/v already per query head
    S = q.shape[0]
    keep = jnp.tril(jnp.ones((S, S), bool)) if m["causal"] else None

    @jax.checkpoint
    def one_head(qkv):
        qh, kh, vh = qkv  # (S, head_dim)
        scores = qh @ kh.T / jnp.sqrt(jnp.float32(m["head_dim"]))
        if keep is not None:
            scores = jnp.where(keep, scores, -jnp.inf)
        return jax.nn.softmax(scores, -1) @ vh

    heads_first = lambda t: jnp.swapaxes(t, 0, 1)
    out = jax.lax.map(one_head, (heads_first(q), heads_first(k), heads_first(v)))
    return heads_first(out).reshape(S, -1)


def _block(m, x, w):  # x: (S, hidden); w: one layer's weights
    H, Hkv, Dh = m["heads"], m["kv_heads"], m["head_dim"]
    S = x.shape[0]
    h = _norm(m, x, w["ln1_scale"], w.get("ln1_bias"))
    if "wqkv" in w:
        qkv = jnp.einsum("sd,dce->sce", h, w["wqkv"]) + w.get("bqkv", 0.0)
        q, k, v = (qkv[:, i].reshape(S, H, Dh) for i in range(3))
    else:
        q = (h @ w["wq"] + w.get("bq", 0.0)).reshape(S, H, Dh)
        kv = jnp.einsum("sd,dce->sce", h, w["wkv"]) + w.get("bkv", 0.0)
        k, v = (kv[:, i].reshape(S, Hkv, Dh) for i in range(2))
    if m["positions"] == "rope":
        q, k = _rotate(m, q), _rotate(m, k)
    if Hkv != H:  # query head j reads key/value head j // (H / Hkv)
        k, v = (jnp.repeat(t, H // Hkv, axis=1) for t in (k, v))
    x = x + _attention(m, q, k, v) @ w["wo"] + w.get("bo", 0.0)
    h = _norm(m, x, w["ln2_scale"], w.get("ln2_bias"))
    if m["mlp"] == "swiglu":
        gate_up = jnp.einsum("sd,dcf->scf", h, w["wgu"]) + w.get("bgu", 0.0)
        h = jax.nn.silu(gate_up[:, 0]) * gate_up[:, 1]
    else:
        h = jax.nn.gelu(h @ w["wfc"] + w.get("bfc", 0.0), approximate=False)
    return x + h @ w["wproj"] + w.get("bproj", 0.0)


def logits(m, params, tokens):  # tokens: (S,) int32 -> (S, vocab) float32
    p = jax.tree.map(lambda t: t.astype(jnp.float32), params)
    x = p["wte"][tokens]
    if m["positions"] == "learned":
        x = x + p["wpe"][: tokens.shape[0]]
    x, _ = jax.lax.scan(jax.checkpoint(lambda x, w: (_block(m, x, w), None)), x, p["blocks"])
    x = _norm(m, x, p["lnf_scale"], p.get("lnf_bias"))
    return x @ (p["wte"] if m["tied_head"] else p["lm_head"]).T


def token_losses(m, params, tokens):
    """Cross-entropy of each position against its own token, (S,) float32."""
    logp = jax.nn.log_softmax(logits(m, params, tokens), -1)
    return -jnp.take_along_axis(logp, tokens[:, None], -1)[:, 0]


def loss(m, params, batch):  # batch: (B, S) int32 -> scalar mean loss
    return jnp.mean(jax.vmap(lambda tokens: token_losses(m, params, tokens))(batch))
