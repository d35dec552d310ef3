"""The plain reference for SDAR-class blocks under block-diffusion training:
the stream, the mask, the forward pass, per-position losses and the weighted
training loss in ``jax.numpy`` and float32; gradients are ``jax.grad`` of it.

Written from the layer equations of the Qwen3-MoE block that ``sdar_moe``
derives from (huggingface.co/JetLM/SDAR-30B-A3B-Chat, ``modeling_sdar_moe.py``)
and from the block-diffusion objective of BD3-LM (arXiv:2503.09573) as SDAR
trains it, not from the program's ``models/tinygpt.py`` / ``models/moe.py`` /
``ops/flash_attention.py``. No kernel, no sort, no grouped matmul, no buffer:
the mask is materialized a block of queries at a time, every held expert runs
densely over every token and a token's routed output is the sum of those
experts' outputs times its gate weights, which are zero for the experts it did
not choose. It draws nothing: the noise (each block's t, the masked positions)
is the program's, handed in, and it chooses its own experts. What it shares
with the program is the layout of the parameter tree. ``m`` is the dict
``build_bd.bd_shape`` returns; the wrong models of the calibration and of the
tests are changes to ``m``. ``attention_sublayer``, ``routed_sublayer``,
``embed`` and ``head_losses`` are the pieces the whole-model functions are made
of: a check that feeds the program one layer at a time calls them itself.

A document x of L tokens, blocks of B tokens, t_b the level of block b, a
token of block b replaced by ``mask_id`` with probability t_b: x_t. The model
runs once over the stream [x_t ; x] of 2L tokens. Stream position p is in copy
p // L (0 noisy, 1 clean) at position p mod L of its copy, in block (p mod L)
// B. Query i may see key j:

  noisy -> noisy iff blk(i) == blk(j);   noisy -> clean iff blk(j) <  blk(i);
  clean -> clean iff blk(j) <= blk(i);   clean -> noisy never.

One layer, hidden state x (2L, D), no bias anywhere, h = RMSNorm(x, eps):

* attention, H query heads and Hkv key / value heads of Dh: q = h W_q -> (2L,
  H, Dh), k, v = h W_k, h W_v -> (2L, Hkv, Dh); q and k RMS-normed over Dh, a
  head at a time, each with one learned (Dh,) scale; rotary (rotate-half,
  theta) at the position inside the copy, p mod L; key / value head n serves
  query heads n H / Hkv .. (n + 1) H / Hkv - 1; scores times Dh^-0.5, masked,
  softmax; x + concat(o) W_o.
* experts: p = softmax(h2 W_r) over all ``experts``; the ``experts_per_token``
  largest, renormalised to sum 1 (``norm_topk_prob``); y = sum over the chosen
  experts e **that this chip holds** of g_e W_d,e (silu(W_g,e h2) * W_u,e h2);
  what the experts held elsewhere would add is left out, as in the program.
* logits = RMSNorm(x[:L]) W_head^T: only the noisy copy goes through the head.
* training loss = (1 / L) sum over masked i of CE(logits[i], x[i]) / t_blk(i),
  averaged over the batch's documents, + ``aux_coef`` x the mean over layers
  of E sum_e f_e P_e (f_e the share of the batch's stream tokens' assignments
  on expert e, P_e its mean probability).

Departures, noted: (1) the released chat models shift nothing: a masked
position predicts its own token (the MDLM convention BD3-LM and SDAR keep);
the autoregressive checkpoint SDAR starts from predicted the next one. (2) a
chip that holds a part of the experts, run without the others, does not train
its routing (``routing_trained`` false): the gates and the load-balance term
are constants of the backward pass, because the gradient through the gates
would be the held experts' part of a sum the deployment makes over its chips.
(3) the source adds every layer's auxiliary term; this repository averages
them over the layers, as it does for olmoe-1b-7b and deepseek-v2-lite
(``aux_coef`` is assumed anyway).

Attention runs in blocks of queries, each against all keys, and every layer is
rematerialized in the backward pass: a stream of 16,384 then fits beside the
training state. Call under ``jax.default_matmul_precision("highest")``.
"""

import jax
import jax.numpy as jnp

QUERY_BLOCK = 256


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def allowed(m, q_pos, k_pos, S):
    """(queries, keys) bool from stream positions: the rule above, or one of the
    calibration's wrong ones."""
    L, B = S // 2, m["block"]
    if m["mask"] == "causal":
        return q_pos[:, None] >= k_pos[None, :]
    q_copy, q_blk = (q_pos // L)[:, None], ((q_pos % L) // B)[:, None]
    k_copy, k_blk = (k_pos // L)[None, :], ((k_pos % L) // B)[None, :]
    past = k_blk <= q_blk if m["mask"] == "block_diffusion_le" else k_blk < q_blk
    return (((q_copy == 0) & (k_copy == 0) & (q_blk == k_blk))
            | ((q_copy == 0) & (k_copy == 1) & past)
            | ((q_copy == 1) & (k_copy == 1) & (k_blk <= q_blk)))


def _rotate(m, x, positions):  # x: (S, heads, Dh), rotate-half
    dim = x.shape[-1]
    inv_freq = m["rope_theta"] ** (-jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    angle = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    a, b = x[..., : dim // 2], x[..., dim // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def _attention(m, q, k, v):  # (S, H, Dh), (S, Hkv, Dh), (S, Hkv, Dh) -> (S, H * Dh)
    S, H, Dh = q.shape
    Hkv = k.shape[1]
    q = q.reshape(S, Hkv, H // Hkv, Dh)  # query head n * (H / Hkv) + g reads kv head n
    block = min(QUERY_BLOCK, S)
    keys = jnp.arange(S)

    @jax.checkpoint
    def one_block(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, block, 0)
        scores = jnp.einsum("qngd,knd->ngqk", qb, k) * Dh ** -0.5
        mask = allowed(m, start + jnp.arange(block), keys, S)
        scores = jnp.where(mask[None, None], scores, -jnp.inf)
        return jnp.einsum("ngqk,knd->qngd", jax.nn.softmax(scores, -1), v)

    out = jax.lax.map(one_block, jnp.arange(0, S, block))
    return out.reshape(S, H * Dh)


def attention_sublayer(m, x, w):
    """x + attention: (S, D) -> (S, D), ``w`` one layer's weights."""
    S, H, Hkv, Dh = x.shape[0], m["heads"], m["kv_heads"], m["head_dim"]
    h = _rms(x, w["ln1_scale"], m["norm_eps"])
    q, k, v = h @ w["wq"], h @ w["wkv"][:, 0], h @ w["wkv"][:, 1]
    if m["qk_norm"] == "whole":  # a wrong model: one RMS over the whole projected vector
        q = _rms(q, jnp.tile(w["q_norm"], H), m["norm_eps"])
        k = _rms(k, jnp.tile(w["k_norm"], Hkv), m["norm_eps"])
    q, k, v = q.reshape(S, H, Dh), k.reshape(S, Hkv, Dh), v.reshape(S, Hkv, Dh)
    if m["qk_norm"] == "head":
        q, k = _rms(q, w["q_norm"], m["norm_eps"]), _rms(k, w["k_norm"], m["norm_eps"])
    positions = jnp.arange(S)  # along the stream: a wrong model, unless taken inside each copy
    if m["positions"] == "per_copy":
        positions = positions % (S // 2)
    return x + _attention(m, _rotate(m, q, positions), _rotate(m, k, positions), v) @ w["wo"]


def _gate_weights(m, probs):  # (S, E) router probabilities -> (S, E) gate weights, (S,) margin
    chosen, index = jax.lax.top_k(probs, m["experts_per_token"] + 1)
    # how far the last expert taken lies above the first one left, as a share of it
    margin = 1.0 - chosen[:, -1] / chosen[:, -2]
    chosen, index = chosen[:, :-1], index[:, :-1]
    if m["norm_topk_prob"]:
        chosen = chosen / jnp.sum(chosen, -1, keepdims=True)
    return jnp.sum(jax.nn.one_hot(index, m["experts"]) * chosen[..., None], axis=1), margin


def _routed_mlp(m, h, w):  # h: (S, D) -> (S, D), the router's statistics
    probs = jax.nn.softmax(h @ w["router"], -1)
    gates, margin = _gate_weights(m, probs)
    if not m["routing_trained"]:
        probs, gates = jax.lax.stop_gradient((probs, gates))
    first, count = m["held"]
    F = m["expert_width"]

    @jax.checkpoint
    def add_expert(y, expert):
        gate_up, down, gate = expert  # (D, 2F): W_gate then W_up; (F, D); (S,)
        out = (jax.nn.silu(h @ gate_up[:, :F]) * (h @ gate_up[:, F:])) @ down
        return y + gate[:, None] * out, None

    y, _ = jax.lax.scan(
        add_expert, jnp.zeros_like(h),
        (w["moe_wgu"][:count], w["moe_wd"][:count], gates.T[first:first + count]))
    statistics = {
        "assignments": jnp.sum(gates > 0, 0),  # (E,) how many tokens chose each expert
        "probability": jnp.sum(probs, 0),  # (E,) summed over the tokens
        # (S,) a token whose margin is within a precision's rounding may take
        # another expert there: a check leaves such tokens out by this number
        "margin": jax.lax.stop_gradient(margin),
    }
    return y, statistics


def routed_sublayer(m, x, w):
    """x + the held experts' part of the routed sum: (S, D) -> (S, D), the
    router's statistics; ``w`` one layer's weights."""
    y, statistics = _routed_mlp(m, _rms(x, w["ln2_scale"], m["norm_eps"]), w)
    return x + y, statistics


def _layer(m, x, w):
    x, statistics = routed_sublayer(m, attention_sublayer(m, x, w), w)
    statistics.pop("margin")
    return x, statistics


def embed(m, params, tokens, masked):
    """(L,) clean tokens and (L,) bool masked positions -> the (2L, D)
    embeddings of the stream [noisy copy ; clean copy]."""
    stream = jnp.concatenate([jnp.where(masked, m["mask_id"], tokens), tokens])
    return params["wte"].astype(jnp.float32)[stream]


def head_losses(m, params, x, tokens):
    """(2L, D) the last layer's output -> (L,) cross-entropy of each noisy
    position against its own clean token, masked or not: only the noisy copy
    goes through the final norm and the head."""
    L = tokens.shape[0]
    scale, head = params["lnf_scale"].astype(jnp.float32), params["lm_head"].astype(jnp.float32)
    return _token_losses(_rms(x[:L], scale, m["norm_eps"]) @ head.T, tokens)


def _forward(m, params, tokens, masked):
    """(L,) clean tokens and (L,) bool masked positions -> (L, vocab) logits of
    the noisy copy, per-layer statistics over the 2L stream tokens."""
    p = jax.tree.map(lambda t: t.astype(jnp.float32), params)
    L = tokens.shape[0]
    x, statistics = jax.lax.scan(
        jax.checkpoint(lambda x, w: _layer(m, x, w)), embed(m, p, tokens, masked), p["blocks"])
    return _rms(x[:L], p["lnf_scale"], m["norm_eps"]) @ p["lm_head"].T, statistics


def _token_losses(logits, tokens):
    logp = jax.nn.log_softmax(logits, -1)
    return -jnp.take_along_axis(logp, tokens[:, None], -1)[:, 0]


def logits(m, params, tokens, masked):
    return _forward(m, params, tokens, masked)[0]


def weighted_loss(losses, t, masked, block, weight="1/t", over="document"):
    """(..., L) per-position losses -> the objective's (1 / L) sum over the
    masked positions of loss / t of its block, averaged over documents.
    ``weight`` "one" (no 1 / t) and ``over`` "masked" (divided by the masked
    count, not by L) are the calibration's wrong objectives."""
    weights = jnp.where(masked, 1.0 / jnp.repeat(t, block, axis=-1), 0.0)
    if weight != "1/t":
        weights = jnp.where(masked, 1.0, 0.0)
    count = losses.shape[-1] if over == "document" else jnp.sum(masked, -1)
    return jnp.mean(jnp.sum(losses * weights, -1) / count)


def loss_and_parts(m, params, batch, t, masked):
    """The full training loss of a (B, L) batch under the given noise (t (B, L
    // block), masked (B, L)): the weighted cross-entropy plus the load-balance
    term over the whole batch's stream tokens, averaged over layers; and what
    it was made from: the (B, L) per-position losses of the noisy copy and the
    (layers, experts) assignment counts. A document at a time."""
    def one(args):
        tokens, masked = args
        out, statistics = _forward(m, params, tokens, masked)
        return _token_losses(out, tokens), statistics

    # statistics: (documents, layers, E)
    losses, statistics = jax.lax.map(one, (batch, masked))
    n_tokens = 2 * batch.size
    assignments = jnp.sum(statistics["assignments"], 0)
    share = assignments / (n_tokens * m["experts_per_token"])
    mean_probability = jnp.sum(statistics["probability"], 0) / n_tokens
    balance = m["experts"] * jnp.sum(share * mean_probability, -1)  # (layers,)
    weighted = weighted_loss(losses, t, masked, m["block"], m["loss_weight"], m["loss_over"])
    return weighted + m["aux_coef"] * jnp.mean(balance), (losses, assignments)


def loss(m, params, batch, t, masked):
    return loss_and_parts(m, params, batch, t, masked)[0]
