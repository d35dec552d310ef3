"""The plain reference for Laguna-class models: the forward pass, per-position
losses and the training loss in ``jax.numpy`` and float32; gradients are
``jax.grad`` of it.

Written from the config (huggingface.co/poolside/Laguna-XS.2, ``config.json``,
``model_type`` ``laguna``: ``layer_types``, ``num_attention_heads_per_layer``,
``gating``, ``rope_parameters`` with a ``partial_rotary_factor`` a kind,
``sliding_window``, ``mlp_layer_types``, ``moe_routed_scaling_factor``,
``shared_expert_intermediate_size``) and the layer equations those keys name,
not from the program's ``models/tinygpt.py`` / ``models/moe.py`` /
``ops/flash_attention.py`` / ``ops/rotary.py``. No kernel, no band, no sort, no
grouped matmul, no buffer: every layer's mask is materialized a block of
queries at a time over all the keys, whatever its kind; the rotation slices and
concatenates. The routed share, the leading dense layer and the shared expert
are ``reference_kda``'s (the same DeepSeek-V3 recipe's arithmetic: sigmoid
scores, the choice by score + bias, gates renormalised and times the scaling
factor, every held expert densely over every token, one shared expert
ungated). It chooses its own experts. What it shares with the program is the
layout of the parameter tree (``layer_weights``). ``m`` is the dict
``build_laguna.laguna_shape`` returns; the wrong models of the calibration and
of the tests are changes to ``m``.

x = Emb[ids], (S, D); layers 0 .. L - 1 in the published order; every sublayer
is x += f(RMSNorm(x, eps 1e-6)); no bias anywhere (``attention_bias`` false).

* *Attention of layer i*, kind t_i of ``kinds`` (``layer_types``:
  ``full_attention`` -> ``global``, ``sliding_attention`` -> ``window``), H_i
  = ``num_attention_heads_per_layer[i]`` query heads (48 global, 64 window), 8
  KV heads, d = 128, h the normed input: q = h Wq (2048 -> H_i x 128), k = h
  Wk, v = h Wv (2048 -> 1024 each). No QK-norm (**assumed**: the config has no
  key for one). Rotary on q and k with the kind's table. *window*: all 128
  lanes of a head, rotate-half (lane j with j + 64), inv_freq_i = 10000^(-2i /
  128). *global*: the first 64 lanes of a head (``partial_rotary_factor``
  0.5; rotate-half inside them, lane j with j + 32; **assumed**: the leading
  lanes rotate, the Hugging Face convention), the other 64 unrotated;
  inv_freq over dim 64 at theta 500000 under YaRN (factor 64 over 4096
  positions, ``beta_fast`` 64, ``beta_slow`` 1: frequencies whose wavelength
  turns fewer than beta_slow times over the original positions divided by
  ``factor``, those that turn more than beta_fast times kept, a linear ramp
  over the index between), cos and sin times ``attention_factor``
  1.4158883083359672 (**assumed** as the Mellum configuration applies its
  factor; the softmax scale stays 1 / sqrt(128)). KV head g serves query heads
  g H_i / 8 .. (g + 1) H_i / 8 - 1. Query i sees key j iff j <= i (*global*)
  or i - 512 < j <= i (*window*: 512 keys with its own, **assumed** the
  Hugging Face convention). softmax. **Gate**: a = sigmoid(h Wg) (2048 ->
  H_i: one scalar a head a token), each head's output times its a, then Wo
  (H_i x 128 -> 2048). **Assumed**: per head (XS.2 says ``gating`` true; the
  catalog's sibling row Laguna-S-2.1 says ``per-head``), from the sublayer's
  normed input, before Wo, no bias.
* *MLP*: layer 0 SwiGLU of width 8192 (``mlp_layer_types[0]`` dense). Every
  other layer: s = sigmoid(h Wr) over 256 (**assumed**: the config has no
  scoring key; 256 experts, 8 a token, 1 shared and 2.5 are the DeepSeek-V3
  recipe, which scores by sigmoid); the 8 largest of s + b (b the (256,)
  selection bias: a buffer at zeros); gates s at the chosen, divided by their
  sum (**assumed**: ``norm_topk_prob`` true in the sibling row), times 2.5, on
  the experts' outputs; x += sum over the chosen experts e **that this chip
  holds** of g_e Wd_e (silu(Wg_e h) * Wu_e h), experts of width 512, plus one
  shared SwiGLU expert of width 512 on every token, ungated (**assumed**).
* Final RMSNorm, untied head, over this chip's slice of the ids; cross entropy.
  No auxiliary loss (**assumed**, as the Kimi configuration).

Departures, noted: (1) a position's target is its own token, not the next one:
``train/step.py`` gives every cell of this benchmark targets = inputs,
unshifted (the source paper's harness does); the step's cost is the same. (2)
a chip that holds a part of the experts, run without the others, does not
train its routing (``routing_trained`` false): the gates are constants of the
backward pass. (3) the selection bias's update between steps is outside the
step and not built: the bias stays where it starts.

Attention runs in blocks of queries, each against all keys, and every layer is
rematerialized in the backward pass: 16,384 positions then fit beside the
training state. Call under ``jax.default_matmul_precision("highest")``.
"""

import math

import jax
import jax.numpy as jnp

from .reference_bd import _rms, _token_losses
# the DeepSeek-V3 recipe's MLPs: the same arithmetic as the Kimi configuration's
from .reference_kda import dense_sublayer, mlp_sublayer, routed_sublayer  # noqa: F401

QUERY_BLOCK = 256


def layer_weights(m, params, layer):
    """Layer ``layer``'s weights from the parameter tree's stacks: layers of
    equal leaves share one, named by its kind (the head count decides the
    shapes of wq, wg and wo) and by whether its MLP is a leading dense one,
    each in the published order."""
    def stack_of(i):
        return f"{m['kinds'][i]}_" + ("dense_" if i < m["dense_layers"] else "") + "blocks"

    name = stack_of(layer)
    at = sum(stack_of(i) == name for i in range(layer))
    return {k: v[at] for k, v in params[name].items()}


def _yarn_inv_freq(dim, theta, factor, original, beta_fast, beta_slow):
    """(dim / 2,) YaRN frequencies (arXiv:2309.00071, as transformers'
    ``_compute_yarn_parameters`` computes them)."""
    def index_that_turns(n):  # the (fractional) index whose wavelength fits n times in `original`
        return dim * math.log(original / (n * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(index_that_turns(beta_fast)), 0)
    high = min(math.ceil(index_that_turns(beta_slow)), dim - 1)
    i = jnp.arange(dim // 2, dtype=jnp.float32)
    plain = theta ** (-2.0 * i / dim)
    ramp = jnp.clip((i - low) / max(high - low, 1e-3), 0.0, 1.0)  # 0 keep, 1 interpolate
    return plain / factor * ramp + plain * (1.0 - ramp)


def rotary_table(m, kind, positions):
    """(cos, sin, lanes): cos and sin each (S, lanes / 2) of the kind's table,
    ``lanes`` the leading lanes of a head it rotates."""
    theta, lanes, yarn = dict(m["rotary"])[kind]
    scale = 1.0
    if yarn is None:
        inv_freq = theta ** (-jnp.arange(0, lanes, 2, dtype=jnp.float32) / lanes)
    else:
        factor, original, beta_fast, beta_slow, scale = yarn
        inv_freq = _yarn_inv_freq(lanes, theta, factor, original, beta_fast, beta_slow)
    angle = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]
    return jnp.cos(angle) * scale, jnp.sin(angle) * scale, lanes


def _rotate(m, x, cos, sin, lanes):
    """x: (S, heads, Dh). The leading ``lanes`` lanes rotate, lane j with j +
    lanes / 2 (``pairing`` "head", a wrong model's: with j + Dh / 2, the whole
    head's partner); the rest pass."""
    half = lanes // 2
    cos, sin = cos[:, None, :], sin[:, None, :]
    if m["pairing"] == "head" and lanes < x.shape[-1]:
        far = x.shape[-1] // 2
        a, b = x[..., :half], x[..., far:far + half]
        return jnp.concatenate(
            [a * cos - b * sin, x[..., half:far], b * cos + a * sin, x[..., far + half:]], -1)
    a, b = x[..., :half], x[..., half:lanes]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin, x[..., lanes:]], -1)


def allowed(m, kind, q_pos, k_pos):
    """(queries, keys) bool: the kind's rule."""
    seen = k_pos[None, :] <= q_pos[:, None]
    if kind == "window":
        seen &= k_pos[None, :] > q_pos[:, None] - m["window"]
    return seen


def _attention(m, kind, q, k, v):  # (S, H, Dh), (S, Hkv, Dh), (S, Hkv, Dh) -> (S, H, Dh)
    S, H, Dh = q.shape
    Hkv = k.shape[1]
    # query head n reads KV head n // (H / Hkv); ``kv_shift`` (a wrong model's) one off
    serves = ((jnp.arange(H) + m["kv_shift"]) // (H // Hkv)) % Hkv
    k, v = k[:, serves], v[:, serves]
    block = min(QUERY_BLOCK, S)
    keys = jnp.arange(S)

    @jax.checkpoint
    def one_block(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, block, 0)
        scores = jnp.einsum("qhd,khd->hqk", qb, k) * Dh ** -0.5
        mask = allowed(m, kind, start + jnp.arange(block), keys)
        scores = jnp.where(mask[None], scores, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, -1), v)

    return jax.lax.map(one_block, jnp.arange(0, S, block)).reshape(S, H, Dh)


def attention_sublayer(m, x, w, layer):
    """x + gated attention: (S, D) -> (S, D), ``w`` the weights of layer
    ``layer``, whose kind chooses the head count, the mask (``mask_kinds``,
    where a wrong model gives them apart) and the rotary table."""
    S, Hkv, Dh = x.shape[0], m["kv_heads"], m["head_dim"]
    kind, mask_kind = m["kinds"][layer], m.get("mask_kinds", m["kinds"])[layer]
    H = dict(m["heads"])[kind]
    h = _rms(x, w["ln1_scale"], m["norm_eps"])
    q, k, v = h @ w["wq"], h @ w["wkv"][:, 0], h @ w["wkv"][:, 1]
    q, k, v = q.reshape(S, H, Dh), k.reshape(S, Hkv, Dh), v.reshape(S, Hkv, Dh)
    cos, sin, lanes = rotary_table(m, kind, jnp.arange(S))
    o = _attention(m, mask_kind, _rotate(m, q, cos, sin, lanes), _rotate(m, k, cos, sin, lanes), v)
    if m["gate"] is not None:  # "head"; "raw" (a wrong model's) reads the un-normed input
        o = o * jax.nn.sigmoid((h if m["gate"] == "head" else x) @ w["wg"])[:, :, None]
    return x + o.reshape(S, H * Dh) @ w["wo"]


def embed(m, params, tokens):
    return params["wte"].astype(jnp.float32)[tokens]


def head_losses(m, params, x, tokens):
    """(S, D) the last layer's output -> (S,) cross-entropy of each position
    against its own token (departure 1)."""
    scale, head = params["lnf_scale"].astype(jnp.float32), params["lm_head"].astype(jnp.float32)
    return _token_losses(_rms(x, scale, m["norm_eps"]) @ head.T, tokens)


def _forward(m, params, tokens):
    """(S,) tokens -> (S, vocab) logits, (routed layers, E) assignment counts."""
    p = jax.tree.map(lambda t: t.astype(jnp.float32), params)
    x, assignments = embed(m, p, tokens), []
    for layer in range(m["layers"]):  # unrolled: a layer's kind is static

        @jax.checkpoint
        def one(x, w, layer=layer):
            y, statistics = mlp_sublayer(m, attention_sublayer(m, x, w, layer), w, layer)
            return y, None if statistics is None else statistics["assignments"]

        x, counts = one(x, layer_weights(m, p, layer))
        if counts is not None:
            assignments.append(counts)
    return _rms(x, p["lnf_scale"], m["norm_eps"]) @ p["lm_head"].T, jnp.stack(assignments)


def logits(m, params, tokens):
    return _forward(m, params, tokens)[0]


def loss_and_parts(m, params, batch):
    """The training loss of a (B, S) batch, mean cross-entropy (no auxiliary
    term), and what it was made from: the (B, S) per-position losses and the
    (routed layers, experts) assignment counts. A sequence at a time."""
    def one(tokens):
        out, assignments = _forward(m, params, tokens)
        return _token_losses(out, tokens), assignments

    losses, assignments = jax.lax.map(one, batch)
    return jnp.mean(losses), (losses, jnp.sum(assignments, 0))


def loss(m, params, batch):
    return loss_and_parts(m, params, batch)[0]
