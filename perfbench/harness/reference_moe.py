"""The plain reference for OLMoE-class blocks: forward pass, per-position
losses and the full training loss in ``jax.numpy`` and float32.

Written from the architecture's description (allenai/OLMoE-1B-7B-0125-Instruct
``config.json``, ``model_type`` olmoe; the OLMoE report, arXiv:2409.02060), not
from the program's ``models/moe.py``. No sort, no grouped matmul, no capacity:
every expert runs densely over every token and a token's output is the sum of
the experts' outputs times its gate weights, which are zero for the experts it
did not choose. What it shares with the program is the layout of the
parameter tree and the convention that a position's target is its own token.

One layer, for hidden state x of one token (pre-norm, no bias anywhere):

* attention: h = RMSNorm(x); q = RMSNorm_q(h W_q), k = RMSNorm_k(h W_k), each
  over the whole projected vector with its own learned scale (QK-norm);
  v = h W_v; heads of ``head_dim``; rotary positions (rotate-half) on q and
  k; causal softmax attention; x = x + o W_o.
* routed MLP: h = RMSNorm(x); p = softmax(h W_r) over the experts; the
  ``experts_per_token`` largest p keep their value, **not renormalised**
  (``norm_topk_prob`` false), the rest are 0; y = sum_e p_e (silu(h W_gate,e) *
  (h W_up,e)) W_down,e; x = x + y.
* training loss = mean cross-entropy + ``aux_coef`` * load-balance +
  ``z_coef`` * z-loss, each averaged over layers. Load-balance = E * sum_e f_e
  P_e over the whole batch, f_e the share of the N x K assignments that chose
  expert e, P_e the mean of p_e over tokens; z-loss = mean over tokens of
  logsumexp(logits)^2. Departure, noted: HF ``load_balancing_loss_func`` divides
  the counts by N and not by N x K, so its value is K times this one; the
  issue that brought the configuration wrote f_e to sum to 1, as here.

The experts run as a ``lax.scan`` over the stacked expert weights and the
layers as a scan over the stacked layers, both rematerialized: 4096 tokens x
64 experts then fit beside the training state. ``m`` is the dict
``build_moe.moe_shape`` returns. Call under
``jax.default_matmul_precision("highest")``.
"""

import jax
import jax.numpy as jnp

from .reference import _attention, _norm, _rotate  # the same equations as mistral-7b's


def _gate_weights(m, probs):  # (S, E) router probabilities -> (S, E) gate weights
    chosen, index = jax.lax.top_k(probs, m["experts_per_token"])
    if m["norm_topk_prob"]:
        chosen = chosen / jnp.sum(chosen, -1, keepdims=True)
    return jnp.sum(jax.nn.one_hot(index, m["experts"]) * chosen[..., None], axis=1)


def _routed_mlp(m, h, w):  # h: (S, hidden) -> (S, hidden), the router's statistics
    logits = h @ w["router"]
    gates = _gate_weights(m, jax.nn.softmax(logits, -1))

    @jax.checkpoint
    def add_expert(y, expert):
        gate_up, down, gate = expert  # (hidden, 2F): W_gate then W_up; (F, hidden); (S,)
        gu = h @ gate_up
        F = down.shape[0]
        return y + gate[:, None] * ((jax.nn.silu(gu[:, :F]) * gu[:, F:]) @ down), None

    y, _ = jax.lax.scan(add_expert, jnp.zeros_like(h), (w["moe_wgu"], w["moe_wd"], gates.T))
    statistics = {
        "assignments": jnp.sum(gates > 0, 0),  # (E,) how many tokens chose each expert
        "probability": jnp.sum(jax.nn.softmax(logits, -1), 0),  # (E,) summed over tokens
        "z": jnp.sum(jax.nn.logsumexp(logits, -1) ** 2),
    }
    return y, statistics


def _block_attention(m, x, w):  # x: (S, hidden); w: one layer's weights
    H, Dh, S = m["heads"], m["head_dim"], x.shape[0]
    h = _norm(m, x, w["ln1_scale"], None)
    q, k, v = (h @ w["wqkv"][:, i] for i in range(3))
    if m["qk_norm"]:
        q, k = _norm(m, q, w["q_norm"], None), _norm(m, k, w["k_norm"], None)
    q, k, v = (t.reshape(S, H, Dh) for t in (q, k, v))
    return x + _attention(m, _rotate(m, q), _rotate(m, k), v) @ w["wo"]


def _block(m, x, w):
    x = _block_attention(m, x, w)
    y, statistics = _routed_mlp(m, _norm(m, x, w["ln2_scale"], None), w)
    return x + y, statistics


def _forward(m, params, tokens):  # (S,) int32 -> (S, vocab) logits, per-layer statistics
    p = jax.tree.map(lambda t: t.astype(jnp.float32), params)
    x, statistics = jax.lax.scan(jax.checkpoint(lambda x, w: _block(m, x, w)),
                                 p["wte"][tokens], p["blocks"])
    return _norm(m, x, p["lnf_scale"], None) @ p["lm_head"].T, statistics


def _token_losses(logits, tokens):
    logp = jax.nn.log_softmax(logits, -1)
    return -jnp.take_along_axis(logp, tokens[:, None], -1)[:, 0]


def logits(m, params, tokens):
    return _forward(m, params, tokens)[0]


def token_losses(m, params, tokens):
    """Cross-entropy of each position against its own token, (S,) float32."""
    return _token_losses(logits(m, params, tokens), tokens)


def loss(m, params, batch):
    """The full training loss of a (B, S) batch: mean cross-entropy plus the
    two router terms, whose statistics are taken over the whole batch."""
    def one(tokens):
        out, statistics = _forward(m, params, tokens)
        return _token_losses(out, tokens), statistics

    losses, statistics = jax.vmap(one)(batch)
    tokens = batch.size
    total = jax.tree.map(lambda t: jnp.sum(t, 0), statistics)  # over sequences: (layers, ...)
    share = total["assignments"] / (tokens * m["experts_per_token"])
    balance = m["experts"] * jnp.sum(share * total["probability"] / tokens, -1)  # (layers,)
    z = total["z"] / tokens
    return jnp.mean(losses) + m["aux_coef"] * jnp.mean(balance) + m["z_coef"] * jnp.mean(z)
