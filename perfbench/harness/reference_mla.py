"""The plain reference for DeepSeek-V2-class blocks: forward pass, per-position
losses and the full training loss in ``jax.numpy`` and float32.

Written from the layer equations of the source repository's
``modeling_deepseek.py`` (huggingface.co/deepseek-ai/DeepSeek-V2-Lite), not
from the program's ``models/tinygpt.py`` / ``models/moe.py``. No kernel, no
sort, no grouped matmul, no buffer: every held expert runs densely over every
token and a token's routed output is the sum of those experts' outputs times
its gate weights, which are zero for the experts it did not choose. What it
shares with the program is the layout of the parameter tree and the
convention that a position's target is its own token. ``m`` is the dict
``build_mla.mla_shape`` returns; the wrong models of the calibration and of
the tests are changes to ``m``.

One layer, hidden state x (S, D), no bias anywhere, h = RMSNorm(x, eps):

* attention, H heads: q = h W_q -> (S, H, 192) = [q_nope 128 | q_pe 64]
  (``q_lora_rank`` null). h W_kva -> (S, 576) = [c 512 | k_pe 64]; c =
  RMSNorm(c) with its own scale (``kv_a_layernorm``); c W_kvb -> (S, H, 256) =
  [k_nope 128 | v 128]. Rotary (rotate-half) on q_pe and on the one k_pe,
  which all heads share; k = [k_nope | k_pe]; o = softmax_causal(q k^T scale)
  v -> (S, H, 128); x + o W_o. YaRN: f_i = base^(-2i/64); ramp_i = clip((i -
  low) / (high - low), 0, 1), low / high the floor / ceiling of 64
  ln(original / (2 pi n)) / (2 ln base) at n = beta_fast / beta_slow;
  inv_freq_i = (f_i / factor) ramp_i + f_i (1 - ramp_i); cos and sin times
  mscale(factor, mscale) / mscale(factor, mscale_all_dim); scale = 192^-0.5
  mscale(factor, mscale_all_dim)^2, mscale(s, a) = 0.1 a ln s + 1.
* layers 0 .. dense_layers - 1: x + W_d (silu(W_g h2) * (W_u h2)).
* the others: p = softmax(h2 W_r) over all ``experts``; the
  ``experts_per_token`` largest keep their value, not renormalised
  (``norm_topk_prob`` false), times ``routed_scaling``; y = shared(h2) + sum
  over the chosen experts e **that this chip holds** of p_e expert_e(h2);
  what the experts held elsewhere would add is left out, as in the program.
* training loss = mean cross-entropy + ``aux_coef`` x the mean over routed
  layers of: mean over sequences of E sum_e f_e P_e, f_e the sequence's share
  of its S x K assignments on expert e, P_e its mean probability (``seq_aux``).

Departures, noted: (1) the source de-interleaves q_pe / k_pe before
rotate-half; with seeded weights that is one fixed permutation of both and
leaves q k^T unchanged. (2) the source adds every routed layer's auxiliary
term; this repository averages them over the routed layers, as it does for
olmoe-1b-7b (``aux_coef`` is assumed anyway). (3) a chip that holds a part of
the experts, run without the others, does not train its routing
(``routing_trained`` false): the gates and the load-balance term are constants
of the backward pass, because the gradient through the gates would be the
held experts' part of a sum the deployment makes over its chips.

Attention runs in blocks of queries, each against all keys, and every layer
is rematerialized in the backward pass: S 8192 then fits beside the training
state. Call under ``jax.default_matmul_precision("highest")``.
"""

import math

import jax
import jax.numpy as jnp

QUERY_BLOCK = 512


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def yarn_inv_freq(m, dim):
    """(dim / 2,) rotary frequencies, blended as YaRN blends them."""
    i = jnp.arange(dim // 2, dtype=jnp.float32)
    plain = m["rope_theta"] ** (-2.0 * i / dim)
    yarn = m["yarn"]
    if yarn is None:
        return plain
    low, high = yarn_ramp_ends(m, dim)
    ramp = jnp.clip((i - low) / max(high - low, 1e-3), 0.0, 1.0)
    return plain / yarn["factor"] * ramp + plain * (1.0 - ramp)


def yarn_ramp_ends(m, dim):
    yarn = m["yarn"]

    def index_turning(n):  # the frequency index that turns n times over the original context
        return dim * math.log(yarn["original_max_position_embeddings"] / (n * 2 * math.pi)) / (
            2 * math.log(m["rope_theta"]))

    return (max(math.floor(index_turning(yarn["beta_fast"])), 0),
            min(math.ceil(index_turning(yarn["beta_slow"])), dim - 1))


def _mscale(factor, mscale):
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def _rotate(m, x):  # x: (S, heads, dim), rotate-half over the whole of dim
    dim = x.shape[-1]
    angle = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * yarn_inv_freq(m, dim)[None, :]
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    if m["yarn"] is not None:
        factor = (_mscale(m["yarn"]["factor"], m["yarn"]["mscale"])
                  / _mscale(m["yarn"]["factor"], m["yarn"]["mscale_all_dim"]))
        cos, sin = cos * factor, sin * factor
    a, b = x[..., : dim // 2], x[..., dim // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def _attention(m, q, k, v):  # (S, H, Dqk), (S, H, Dqk), (S, H, Dv) -> (S, H * Dv)
    S = q.shape[0]
    block = min(QUERY_BLOCK, S)
    scale = m["softmax_scale"] if m["yarn"] is not None else q.shape[-1] ** -0.5
    keys = jnp.arange(S)

    @jax.checkpoint
    def one_block(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, block, 0)
        scores = jnp.einsum("qhd,khd->hqk", qb, k) * scale
        if m["causal"]:
            rows = start + jnp.arange(block)
            scores = jnp.where(rows[None, :, None] >= keys[None, None, :], scores, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, -1), v)

    out = jax.lax.map(one_block, jnp.arange(0, S, block))
    return out.reshape(S, -1)


def _block_attention(m, x, w):  # x: (S, D); w: one layer's weights
    S, H = x.shape[0], m["heads"]
    Dn, Dr, Dv, R = m["qk_nope"], m["qk_rope"], m["v_head"], m["kv_lora"]
    h = _rms(x, w["ln1_scale"], m["norm_eps"])
    q = (h @ w["wq"]).reshape(S, H, Dn + Dr)
    down = h @ w["wkv_a"]
    latent, k_pe = down[:, :R], down[:, None, R:]
    if m["latent_norm"]:
        latent = _rms(latent, w["kv_norm"], m["norm_eps"])
    up = (latent @ w["wkv_b"]).reshape(S, H, Dn + Dv)
    k = jnp.concatenate([up[..., :Dn], jnp.broadcast_to(k_pe, (S, H, Dr))], -1)
    if m["rope_whole_head"]:  # a wrong model: rotary over all 192, not over the last 64
        q, k = _rotate(m, q), _rotate(m, k)
    else:
        q = jnp.concatenate([q[..., :Dn], _rotate(m, q[..., Dn:])], -1)
        k = jnp.concatenate([up[..., :Dn],
                             jnp.broadcast_to(_rotate(m, k_pe), (S, H, Dr))], -1)
    return x + _attention(m, q, k, up[..., Dn:]) @ w["wo"]


def _swiglu(h, gate, up, down):
    return (jax.nn.silu(h @ gate) * (h @ up)) @ down


def _gate_weights(m, probs):  # (S, E) router probabilities -> (S, E) gate weights
    chosen, index = jax.lax.top_k(probs, m["experts_per_token"])
    if m["norm_topk_prob"]:
        chosen = chosen / jnp.sum(chosen, -1, keepdims=True)
    chosen = chosen * m["routed_scaling"]
    return jnp.sum(jax.nn.one_hot(index, m["experts"]) * chosen[..., None], axis=1)


def _routed_mlp(m, h, w):  # h: (S, D) -> (S, D), the router's statistics
    probs = jax.nn.softmax(h @ w["router"], -1)
    gates = _gate_weights(m, probs)
    if not m["routing_trained"]:
        probs, gates = jax.lax.stop_gradient((probs, gates))
    first, count = m["held"]
    F = m["expert_width"]

    @jax.checkpoint
    def add_expert(y, expert):
        gate_up, down, gate = expert  # (D, 2F): W_gate then W_up; (F, D); (S,)
        return y + gate[:, None] * _swiglu(h, gate_up[:, :F], gate_up[:, F:], down), None

    y, _ = jax.lax.scan(
        add_expert, jnp.zeros_like(h),
        (w["moe_wgu"][:count], w["moe_wd"][:count], gates.T[first:first + count]))
    if m["shared_width"]:
        Fs = m["shared_width"]
        y = y + _swiglu(h, w["shared_wgu"][:, :Fs], w["shared_wgu"][:, Fs:], w["shared_wd"])
    S, K = h.shape[0], m["experts_per_token"]
    statistics = {
        "assignments": jnp.sum(gates > 0, 0),  # (E,) how many tokens chose each expert
        "balance": m["experts"] * jnp.sum(jnp.sum(gates > 0, 0) / (S * K) * jnp.mean(probs, 0)),
    }
    return y, statistics


def _dense_block(m, x, w):
    x = _block_attention(m, x, w)
    h = _rms(x, w["ln2_scale"], m["norm_eps"])
    return x + _swiglu(h, w["wgu"][:, 0], w["wgu"][:, 1], w["wproj"])


def _routed_block(m, x, w):
    x = _block_attention(m, x, w)
    y, statistics = _routed_mlp(m, _rms(x, w["ln2_scale"], m["norm_eps"]), w)
    return x + y, statistics


def _forward(m, params, tokens):  # (S,) int32 -> (S, vocab) logits, per-layer statistics
    p = jax.tree.map(lambda t: t.astype(jnp.float32), params)
    x = p["wte"][tokens]
    if m["dense_layers"]:
        x, _ = jax.lax.scan(jax.checkpoint(lambda x, w: (_dense_block(m, x, w), None)),
                            x, p["dense_blocks"])
    x, statistics = jax.lax.scan(jax.checkpoint(lambda x, w: _routed_block(m, x, w)),
                                 x, p["blocks"])
    return _rms(x, p["lnf_scale"], m["norm_eps"]) @ p["lm_head"].T, statistics


def _token_losses(logits, tokens):
    logp = jax.nn.log_softmax(logits, -1)
    return -jnp.take_along_axis(logp, tokens[:, None], -1)[:, 0]


def logits(m, params, tokens):
    return _forward(m, params, tokens)[0]


def token_losses(m, params, tokens):
    """Cross-entropy of each position against its own token, (S,) float32."""
    return _token_losses(logits(m, params, tokens), tokens)


def token_losses_and_counts(m, params, tokens):
    """``token_losses`` and the (routed layers, experts) assignment counts."""
    out, statistics = _forward(m, params, tokens)
    return _token_losses(out, tokens), statistics["assignments"]


def loss(m, params, batch):
    """The full training loss of a (B, S) batch: mean cross-entropy plus the
    load-balance term, per sequence, averaged over sequences and layers."""
    def one(tokens):
        out, statistics = _forward(m, params, tokens)
        return _token_losses(out, tokens), statistics["balance"]

    losses, balance = jax.lax.map(one, batch)  # balance: (sequences, layers)
    return jnp.mean(losses) + m["aux_coef"] * jnp.mean(balance)
