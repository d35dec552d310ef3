"""The plain reference for LFM2-MoE-class models: the forward pass, per-position
losses and the training loss in ``jax.numpy`` and float32; gradients are
``jax.grad`` of it.

Written from the config (huggingface.co/LiquidAI/LFM2-8B-A1B, ``config.json``,
``model_type`` ``lfm2_moe``: ``layer_types``, ``conv_L_cache``, ``conv_bias``,
``num_dense_layers``, ``use_expert_bias``, ``norm_topk_prob``,
``routed_scaling_factor``) and the layer equations of the family's published
modelling code (``lfm2`` / ``lfm2_moe``), not from the program's
``models/tinygpt.py`` / ``models/moe.py`` / ``ops/kda.py`` /
``ops/flash_attention.py``. No kernel, no grouped convolution, no sort, no
grouped matmul, no buffer: the convolution is its shifted products written out,
the mask is materialized a block of queries at a time, every held expert runs
densely over every token. It chooses its own experts. What it shares with the
program is the layout of the parameter tree (``layer_weights``). ``m`` is the
dict ``build_lfm2.lfm2_shape`` returns; the wrong models of the calibration and
of the tests are changes to ``m`` (``WRONG``'s keys, each absent from ``m``
where the model is the right one).

x = Emb[ids], (S, D); layers in the published order; a layer is h = x +
Mixer(RMSNorm(x)), y = h + FFN(RMSNorm(h)), eps ``norm_eps`` 1e-5; no bias
anywhere.

* *conv mixer* (``layer_types`` ``conv``), u the normed input: [B | C | x~] = u
  W_in (D -> 3 D, the columns in that order: **assumed**, the family's
  ``in_proj(...).chunk(3)``), v = B * x~, w_t = sum_{i=0..K-1} taps_i v_{t-K+1+i}
  with K = ``conv_L_cache`` 3 (depthwise over the D channels, causal, zeros
  before the sequence, no bias: ``conv_bias`` false; no activation), out = (C *
  w) W_out (D -> D). taps_i here is the family's ``conv.weight[:, 0, i]``.
* *attention mixer* (``full_attention``), H query heads over Hkv KV heads of d
  = D / H: q = u Wq, k = u Wk, v = u Wv; an RMSNorm over each head's d lanes on
  q and on k, one (d,) scale each, eps ``norm_eps``, **before** rotary
  (**assumed**: the family's ``q_layernorm`` / ``k_layernorm``); rotary over the
  whole head, rotate-half (lane j with j + d / 2), inv_freq_i = theta^(-2i / d)
  at ``rope_theta`` 1e6 (**assumed**: the Hugging Face convention); KV head g
  serves query heads g H / Hkv .. (g + 1) H / Hkv - 1; causal softmax at 1 /
  sqrt(d); Wo (D -> D).
* *FFN*: the first ``dense_layers`` layers W2 (silu(W1 h) * W3 h) at
  ``intermediate_size``. Every other layer: s = sigmoid(h Wr) over the E
  experts in float32; the K' = ``num_experts_per_tok`` largest of s + b (b the
  (E,) expert bias, ``use_expert_bias``: a buffer, no gradient); gates s at the
  chosen, divided by (their sum + 1e-6) (``norm_topk_prob``; the 1e-6
  **assumed** from the family's code), times ``routed_scaling_factor`` 1.0; x
  += sum over the chosen experts e **that this chip holds** of g_e Wd_e
  (silu(Wg_e h) * Wu_e h). No shared expert.
* Final RMSNorm, the head tied to the embedding (**assumed**:
  ``tie_word_embeddings`` is not in the row; the family's default), over this
  chip's slice of the ids; cross entropy. No auxiliary loss (**assumed**).

Departures, noted: (1) a position's target is its own token, not the next one:
``train/step.py`` gives every cell of this benchmark targets = inputs,
unshifted (the source paper's harness does); the step's cost is the same. (2) a
chip that holds a part of the experts, run without the others, does not train
its routing (``routing_trained`` false): the gates are constants of the backward
pass. (3) the expert bias's update between steps (the family moves it by each
expert's load) is outside the step and not built: the bias stays where it
starts. (4) the program divides the chosen scores by max(their sum, 1e-9), this
file by their sum + 1e-6 as the family does: 5e-7 of a gate at a sum near 2,
under every limit.

Attention runs in blocks of queries, each against all keys, and every layer is
rematerialized in the backward pass: 16,384 positions at the published widths
then fit beside the training state, a layer at a time
(``check_lfm2.Reference``). Call under
``jax.default_matmul_precision("highest")``.
"""

import jax
import jax.numpy as jnp

from .reference_bd import _rms, _token_losses
from .reference_kda import dense_sublayer, embed  # noqa: F401  (the same SwiGLU layer and lookup)
from .reference_mla import _swiglu

QUERY_BLOCK = 256

#: The wrong models: key of ``m`` -> what the right model has there.
WRONG = {
    "conv_dtype": "float32",  # "bfloat16": the convolution's products and sums rounded
    "gate_b": True,  # False: v = x~
    "gate_c": True,  # False: out = w W_out
    "taps_used": None,  # 2: the last two taps alone; 4: the first tap again, one position earlier
    "tap_shift": 0,  # 1: every tap one position later (a look-ahead)
    "select_by": "biased",  # "score": the choice by s alone
    "gates_from": "score",  # "biased": the gates from s + b
    "qk_norm": "before",  # None: no QK-norm; "after": behind rotary
    "rotary": True,  # False: none
    "tied": True,  # False: the head is the leaf lm_head
    "router_dtype": "float32",  # "bfloat16": the router's logits rounded
}


def _is(m, key):
    return m.get(key, WRONG[key])


def layer_weights(m, params, layer):
    """Layer ``layer``'s weights from the parameter tree's stacks: layers of
    equal leaves share one, by (its mixer is the convolution, its MLP is a
    leading dense one), each in the published order."""
    def stack_of(i):
        return ("conv_" if m["kinds"][i] == "conv" else "") + (
            "dense_" if i < m["dense_layers"] else "") + "blocks"

    name = stack_of(layer)
    at = sum(stack_of(i) == name for i in range(layer))
    return {k: v[at] for k, v in params[name].items()}


def short_conv(m, v, taps):
    """w_t = sum_i taps_i v_{t-K+1+i}, v (S, C), taps (K, C): the shifted
    products written out, zeros before the sequence."""
    K, S = taps.shape[0], v.shape[0]
    used = _is(m, "taps_used")
    if used is not None and used < K:
        taps = taps[K - used:]
    elif used is not None:
        taps = jnp.concatenate([taps[:1]] * (used - K) + [taps])
    K, shift = taps.shape[0], _is(m, "tap_shift")
    kept = jnp.dtype(_is(m, "conv_dtype"))
    v, taps = v.astype(kept), taps.astype(kept)
    padded = jnp.concatenate([jnp.zeros((K - 1, v.shape[1]), kept), v,
                              jnp.zeros((shift, v.shape[1]), kept)])
    w = padded[shift:shift + S] * taps[0]
    for i in range(1, K):
        w = w + padded[shift + i:shift + i + S] * taps[i]
    return w.astype(jnp.float32)


def conv_sublayer(m, x, w):
    """x + the gated short convolution of RMSNorm(x): (S, D) -> (S, D)."""
    D = x.shape[-1]
    u = _rms(x, w["ln1_scale"], m["norm_eps"])
    bcx = u @ w["sconv_win"]
    b, c, xt = bcx[:, :D], bcx[:, D:2 * D], bcx[:, 2 * D:]
    mixed = short_conv(m, b * xt if _is(m, "gate_b") else xt, w["sconv_taps"])
    return x + (c * mixed if _is(m, "gate_c") else mixed) @ w["wo"]


def _rotate(x, theta):  # x (S, heads, d): rotate-half over the whole head
    S, d = x.shape[0], x.shape[-1]
    inv_freq = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angle = jnp.arange(S, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    a, b = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def _attention(q, k, v):  # (S, H, d), (S, Hkv, d), (S, Hkv, d) -> (S, H, d), causal
    S, H, d = q.shape
    serves = jnp.arange(H) // (H // k.shape[1])  # query head n reads KV head n // (H / Hkv)
    k, v = k[:, serves], v[:, serves]
    block = min(QUERY_BLOCK, S)
    keys = jnp.arange(S)

    @jax.checkpoint
    def one_block(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, block, 0)
        scores = jnp.einsum("qhd,khd->hqk", qb, k) * d ** -0.5
        seen = keys[None, :] <= (start + jnp.arange(block))[:, None]
        scores = jnp.where(seen[None], scores, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, -1), v)

    return jax.lax.map(one_block, jnp.arange(0, S, block)).reshape(S, H, d)


def attention_sublayer(m, x, w):
    """x + attention(RMSNorm(x)): (S, D) -> (S, D)."""
    S, H, Hkv, d = x.shape[0], m["heads"], m["kv_heads"], m["head_dim"]
    u = _rms(x, w["ln1_scale"], m["norm_eps"])
    q, k, v = u @ w["wq"], u @ w["wkv"][:, 0], u @ w["wkv"][:, 1]
    q, k, v = q.reshape(S, H, d), k.reshape(S, Hkv, d), v.reshape(S, Hkv, d)
    norm = lambda q, k: (_rms(q, w["q_norm"], m["norm_eps"]), _rms(k, w["k_norm"], m["norm_eps"]))
    turn = lambda t: _rotate(t, m["rope_theta"]) if _is(m, "rotary") else t
    if _is(m, "qk_norm") == "before":
        q, k = norm(q, k)
    q, k = turn(q), turn(k)
    if _is(m, "qk_norm") == "after":
        q, k = norm(q, k)
    return x + _attention(q, k, v).reshape(S, H * d) @ w["wo"]


def mixer_sublayer(m, x, w, layer):
    return (conv_sublayer if m["kinds"][layer] == "conv" else attention_sublayer)(m, x, w)


def _gate_weights(m, scores, bias):
    """(S, E) sigmoid scores -> (S, E) gate weights, (S,) margin: how far the
    last expert taken lies above the first one left, by what the choice is
    made by, as a share of the former."""
    K = m["experts_per_token"]
    biased = scores + bias
    ranked, index = jax.lax.top_k(biased if _is(m, "select_by") == "biased" else scores, K + 1)
    margin = (ranked[:, -2] - ranked[:, -1]) / jnp.abs(ranked[:, -2])
    index = index[:, :K]
    # the bias moves the choice, not the gate
    chosen = jnp.take_along_axis(scores if _is(m, "gates_from") == "score" else biased, index, -1)
    if m["norm_topk_prob"]:
        chosen = chosen / (jnp.sum(chosen, -1, keepdims=True) + 1e-6)
    chosen = chosen * m["routed_scaling"]
    return jnp.sum(jax.nn.one_hot(index, m["experts"]) * chosen[..., None], axis=1), margin


def _routed_mlp(m, h, w):  # h: (S, D) -> (S, D), the router's statistics
    kept = jnp.dtype(_is(m, "router_dtype"))
    logits = (h.astype(kept) @ w["router"].astype(kept)).astype(jnp.float32)
    gates, margin = _gate_weights(m, jax.nn.sigmoid(logits), w["router_bias"])
    if not m["routing_trained"]:
        gates = jax.lax.stop_gradient(gates)
    first, count = m["held"]
    F = m["expert_width"]

    @jax.checkpoint
    def add_expert(y, expert):
        gate_up, down, gate = expert  # (D, 2F): W_gate then W_up; (F, D); (S,)
        return y + gate[:, None] * _swiglu(h, gate_up[:, :F], gate_up[:, F:], down), None

    y, _ = jax.lax.scan(
        add_expert, jnp.zeros_like(h),
        (w["moe_wgu"][:count], w["moe_wd"][:count], gates.T[first:first + count]))
    statistics = {
        "assignments": jnp.sum(gates > 0, 0),  # (E,) how many tokens chose each expert
        "margin": jax.lax.stop_gradient(margin),
    }
    return y, statistics


def routed_sublayer(m, x, w):
    """x + the held experts' part of the routed sum: (S, D) -> (S, D), the
    router's statistics; ``w`` one layer's weights."""
    y, statistics = _routed_mlp(m, _rms(x, w["ln2_scale"], m["norm_eps"]), w)
    return x + y, statistics


def mlp_sublayer(m, x, w, layer):
    """-> (x + MLP, the router's statistics or None for a leading dense layer)."""
    if layer < m["dense_layers"]:
        return dense_sublayer(m, x, w), None
    return routed_sublayer(m, x, w)


def _head(m, params):
    return params["wte" if _is(m, "tied") else "lm_head"].astype(jnp.float32)


def head_losses(m, params, x, tokens):
    """(S, D) the last layer's output -> (S,) cross-entropy of each position
    against its own token (departure 1)."""
    scale = params["lnf_scale"].astype(jnp.float32)
    return _token_losses(_rms(x, scale, m["norm_eps"]) @ _head(m, params).T, tokens)


def _forward(m, params, tokens):
    """(S,) tokens -> (S, vocab) logits, (routed layers, E) assignment counts."""
    p = jax.tree.map(lambda t: t.astype(jnp.float32), params)
    x, assignments = embed(m, p, tokens), []
    for layer in range(m["layers"]):  # unrolled: a layer's kind is static

        @jax.checkpoint
        def one(x, w, layer=layer):
            y, statistics = mlp_sublayer(m, mixer_sublayer(m, x, w, layer), w, layer)
            return y, None if statistics is None else statistics["assignments"]

        x, counts = one(x, layer_weights(m, p, layer))
        if counts is not None:
            assignments.append(counts)
    return _rms(x, p["lnf_scale"], m["norm_eps"]) @ _head(m, p).T, jnp.stack(assignments)


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
