"""The plain reference for Kimi-Linear-class models: the forward pass,
per-position losses and the training loss in ``jax.numpy`` and float32;
gradients are ``jax.grad`` of it.

Written from the config (huggingface.co/moonshotai/Kimi-Linear-48B-A3B-Instruct,
``config.json``, ``model_type`` ``kimi_linear``: ``linear_attn_config``,
``mla_use_nope``, ``moe_router_activation_func``, ``routed_scaling_factor``)
and the layer equations of the Kimi Linear report (arXiv:2510.26692) and the
family's published code, not from the program's ``models/tinygpt.py`` /
``models/moe.py`` / ``ops/kda.py``. No kernel, no chunk, no sort, no grouped
matmul, no buffer: the recurrence is a ``lax.scan`` over the positions, one
state update a position; attention materializes its mask a block of queries
at a time; every held expert runs densely over every token. It chooses its
own experts. What it shares with the program is the layout of the parameter
tree (``layer_weights``). ``m`` is the dict ``build_kda.kda_shape`` returns;
the wrong models of the calibration and of the tests are changes to ``m``.

x = Emb[ids], (S, D); layers in the published order, numbered from 1 as
``linear_attn_config`` numbers them; every sublayer is x += f(RMSNorm(x, eps
1e-5)), no bias anywhere.

* *KDA* (layers in ``kda_layers``), h the normed input, H = 32 heads, dk = dv
  = 128: q = l2norm(silu(conv4(h Wq))), k = l2norm(silu(conv4(h Wk))), v =
  silu(conv4(h Wv)) (W 2304 -> 4096 each; conv4 a depthwise causal
  convolution over positions, kernel 4, one (4,) filter a channel, no bias:
  y_t = sum_i w_i x_{t-3+i}; l2norm over each head's 128, **assumed** eps
  1e-6 inside the root); log-decay a key channel g_t = -exp(A_log_h)
  softplus(Wfb (Wfa h_t) + dt_bias) (2304 -> 128 -> 4096; A_log (32,), dt_bias
  (4096,)), alpha_t = exp(g_t) in (0, 1); beta_t = sigmoid(h_t Wb) (2304 ->
  32); state a head S_0 = 0, S_t = (I - beta_t k_t k_t^T) diag(alpha_t)
  S_{t-1} + beta_t k_t v_t^T (128 x 128), o_t = S_t^T q_t / sqrt(128); y_t =
  Wo [RMSNorm_head(o_t; one (128,) scale) * sigmoid(Wgb (Wga h_t))] (2304 ->
  128 -> 4096; Wo 4096 -> 2304). **Assumed** (not config keys; the family's
  published code): the two low-rank maps' rank 128 (= ``head_dim``), no bias
  on them, the q scale, and how A_log (log of uniform [1, 16]), dt_bias
  (inverse softplus of a step log-uniform in [0.001, 0.1]) and the filters
  (uniform within 1 / sqrt(4)) start: the program's seeded weights, which
  this reference is handed.
* *Latent attention, NoPE* (layers in ``full_attn_layers``): q = h Wq to 32 x
  192, [latent | 64 shared columns] = h W_kv_a, the 512-wide latent
  RMS-normed and expanded a head to [k 128 | v 128], k = [128 from the latent,
  the 64 shared columns], **no rotary on the 64** (``mla_use_nope``), scale
  1 / sqrt(192) (``rope_scaling`` null: no factor), causal, softmax, Wo.
* *MLP*: layer 1 SwiGLU of width 9216 (``first_k_dense_replace`` 1). Every
  other layer: s = sigmoid(h Wr) over 256; the 8 largest of s + b (b the (256,)
  selection bias: a buffer, zeros at the seeded start; one group, so no group
  step); gates s at the chosen, divided by their sum, times 2.446; x += sum
  over the chosen experts e **that this chip holds** of g_e Wd_e (silu(Wg_e h)
  * Wu_e h), experts of width 1024, plus one shared expert of width 1024 on
  every token.
* Final RMSNorm, untied head, over this chip's slice of the ids; cross entropy.
  No auxiliary loss (**assumed**: the config has no coefficient and the bias is
  the family's balancer).

Departures, noted: (1) a position's target is its own token, not the next one:
``train/step.py`` gives every cell of this benchmark targets = inputs,
unshifted (the source paper's harness does); the step's cost is the same. (2)
a chip that holds a part of the experts, run without the others, does not
train its routing (``routing_trained`` false): the gates are constants of the
backward pass. (3) the selection bias's update between steps (the family's
balancer) is outside the step and not built: the bias stays where it starts.

The recurrence's scan is cut into segments whose entry states are kept and
whose inside is recomputed in the backward pass (``SEGMENT`` positions: a
choice of memory, not of arithmetic), attention runs in blocks of queries, and
every layer is rematerialized: 16,384 positions then fit beside the training
state. Call under ``jax.default_matmul_precision("highest")``.
"""

import jax
import jax.numpy as jnp

from .reference_bd import _rms, _token_losses
from .reference_mla import _attention, _swiglu  # blocked causal softmax at the scale m gives

SEGMENT = 256


def layer_weights(m, params, layer):
    """Layer ``layer``'s weights from the parameter tree's stacks: layers of
    equal leaves share one, by (its mixer is KDA, its MLP is a leading dense
    one), each in the published order."""
    def stack_of(i):
        return ("kda_" if m["kinds"][i] == "kda" else "") + (
            "dense_" if i < m["dense_layers"] else "") + "blocks"

    name = stack_of(layer)
    at = sum(stack_of(i) == name for i in range(layer))
    return {k: v[at] for k, v in params[name].items()}


def _conv(x, taps):  # x (S, C), taps (K, C): y_t = sum_i taps_i x_{t-K+1+i}, zeros before 0
    K, S = taps.shape[0], x.shape[0]
    padded = jnp.concatenate([jnp.zeros((K - 1, x.shape[1]), x.dtype), x])
    return sum(padded[i:i + S] * taps[i] for i in range(K))


def delta_rule(m, q, k, v, g, beta):
    """The recurrence position by position: q, k, g (S, H, dk), v (S, H, dv),
    beta (S, H) -> o (S, H, dv). ``state_dtype`` (a wrong model's) rounds the
    state after every position."""
    S, H, dk = q.shape
    kept = jnp.finfo(jnp.dtype(m["state_dtype"]))  # reduce_precision: a cast pair XLA may drop

    def position(state, x):  # state (H, dk, dv)
        q_t, k_t, v_t, g_t, b_t = x
        state = state * jnp.exp(g_t)[:, :, None]
        u = b_t[:, None] * (v_t - jnp.einsum("hk,hkv->hv", k_t, state))
        state = jax.lax.reduce_precision(state + k_t[:, :, None] * u[:, None, :], kept.nexp, kept.nmant)
        return state, jnp.einsum("hk,hkv->hv", q_t, state) * dk ** -0.5

    @jax.checkpoint
    def segment(state, xs):
        return jax.lax.scan(position, state, xs)

    n = max(1, S // SEGMENT)
    cut = lambda x: x.reshape(n, S // n, *x.shape[1:])
    _, o = jax.lax.scan(segment, jnp.zeros((H, dk, v.shape[-1]), jnp.float32),
                        tuple(cut(x) for x in (q, k, v, g, beta)))
    return o.reshape(S, H, -1)


def kda_sublayer(m, x, w):
    """x + KDA(RMSNorm(x)): (S, D) -> (S, D), ``w`` one KDA layer's weights."""
    S, H, d = x.shape[0], m["kda_heads"], m["kda_head_dim"]
    h = _rms(x, w["ln1_scale"], m["norm_eps"])
    q, k, v = (jax.nn.silu(_conv(h @ w["kda_wqkv"][:, i], w["kda_conv"][i])).reshape(S, H, d)
               for i in range(3))
    l2norm = lambda t: t * jax.lax.rsqrt(jnp.sum(t * t, -1, keepdims=True) + m["l2_eps"])
    g = -jnp.exp(w["kda_a_log"])[:, None] * jax.nn.softplus(
        (h @ w["kda_wfa"]) @ w["kda_wfb"] + w["kda_dt_bias"]).reshape(S, H, d)
    beta = jax.nn.sigmoid(h @ w["kda_wb"])
    o = delta_rule(m, l2norm(q), l2norm(k), v, g, beta)
    gate = jax.nn.sigmoid((h @ w["kda_wga"]) @ w["kda_wgb"])
    o = _rms(o, w["kda_norm"], m["norm_eps"]).reshape(S, H * d) * gate
    return x + o @ w["wo"]


def latent_sublayer(m, x, w):
    """x + latent attention(RMSNorm(x)), NoPE: (S, D) -> (S, D)."""
    S, H = x.shape[0], m["heads"]
    Dn, Dr, Dv, R = m["qk_nope"], m["qk_rope"], m["v_head"], m["kv_lora"]
    if not m["nope"]:
        raise ValueError("this reference is the NoPE model's: mla_use_nope true")
    h = _rms(x, w["ln1_scale"], m["norm_eps"])
    q = (h @ w["wq"]).reshape(S, H, Dn + Dr)
    down = h @ w["wkv_a"]
    up = (_rms(down[:, :R], w["kv_norm"], m["norm_eps"]) @ w["wkv_b"]).reshape(S, H, Dn + Dv)
    k = jnp.concatenate([up[..., :Dn], jnp.broadcast_to(down[:, None, R:], (S, H, Dr))], -1)
    return x + _attention(m, q, k, up[..., Dn:]) @ w["wo"]


def mixer_sublayer(m, x, w, layer):
    return (kda_sublayer if m["kinds"][layer] == "kda" else latent_sublayer)(m, x, w)


def _gate_weights(m, scores, bias):
    """(S, E) sigmoid scores -> (S, E) gate weights, (S,) margin: how far the
    last expert taken lies above the first one left, by score + bias, as a
    share of the former."""
    K = m["experts_per_token"]
    ranked, index = jax.lax.top_k(scores + bias, K + 1)
    margin = (ranked[:, -2] - ranked[:, -1]) / jnp.abs(ranked[:, -2])
    index = index[:, :K]
    chosen = jnp.take_along_axis(scores, index, -1)  # the bias moves the choice, not the gate
    if m["norm_topk_prob"]:
        chosen = chosen / jnp.sum(chosen, -1, keepdims=True)
    chosen = chosen * m["routed_scaling"]
    return jnp.sum(jax.nn.one_hot(index, m["experts"]) * chosen[..., None], axis=1), margin


def _routed_mlp(m, h, w):  # h: (S, D) -> (S, D), the router's statistics
    if m["router_score"] != "sigmoid":
        raise ValueError("this reference scores by sigmoid")
    gates, margin = _gate_weights(m, jax.nn.sigmoid(h @ w["router"]), w["router_bias"])
    if not m["routing_trained"]:
        gates = jax.lax.stop_gradient(gates)
    first, count = m["held"]
    F, Fs = m["expert_width"], m["shared_width"]

    @jax.checkpoint
    def add_expert(y, expert):
        gate_up, down, gate = expert  # (D, 2F): W_gate then W_up; (F, D); (S,)
        return y + gate[:, None] * _swiglu(h, gate_up[:, :F], gate_up[:, F:], down), None

    y, _ = jax.lax.scan(
        add_expert, jnp.zeros_like(h),
        (w["moe_wgu"][:count], w["moe_wd"][:count], gates.T[first:first + count]))
    if Fs and m.get("shared", True):  # a shares' test counts the shared expert once
        y = y + _swiglu(h, w["shared_wgu"][:, :Fs], w["shared_wgu"][:, Fs:], w["shared_wd"])
    statistics = {
        "assignments": jnp.sum(gates > 0, 0),  # (E,) how many tokens chose each expert
        "margin": jax.lax.stop_gradient(margin),
    }
    return y, statistics


def routed_sublayer(m, x, w):
    """x + the held experts' part of the routed sum + the shared expert: (S,
    D) -> (S, D), the router's statistics; ``w`` one layer's weights."""
    y, statistics = _routed_mlp(m, _rms(x, w["ln2_scale"], m["norm_eps"]), w)
    return x + y, statistics


def dense_sublayer(m, x, w):
    h = _rms(x, w["ln2_scale"], m["norm_eps"])
    return x + _swiglu(h, w["wgu"][:, 0], w["wgu"][:, 1], w["wproj"])


def mlp_sublayer(m, x, w, layer):
    """-> (x + MLP, the router's statistics or None for a leading dense layer)."""
    if layer < m["dense_layers"]:
        return dense_sublayer(m, x, w), None
    return routed_sublayer(m, x, w)


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
            y, statistics = mlp_sublayer(m, mixer_sublayer(m, x, w, layer), w, layer)
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
