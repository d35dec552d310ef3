"""The plain reference for Mellum-2-class models: the forward pass, per-position
losses and the training loss in ``jax.numpy`` and float32; gradients are
``jax.grad`` of it.

Written from the config (huggingface.co/JetBrains/Mellum2-12B-A2.5B-Instruct,
``config.json``, ``model_type`` ``mellum``: ``layer_types``, ``sliding_window``,
``rope_parameters``) and the layer equations of the Qwen3-MoE lineage its keys
belong to, not from the program's ``models/tinygpt.py`` / ``models/moe.py`` /
``ops/flash_attention.py``. No kernel, no band, no sort, no grouped matmul, no
buffer: every layer's mask is materialized a block of queries at a time over
all the keys, whatever its kind; the routed share is ``reference_bd``'s (the
same Qwen3-MoE block: every held expert densely over every token, a token's
output the sum of their outputs times its gate weights). It chooses its own
experts. What it shares with the program is the layout of the parameter tree.
``m`` is the dict ``build_mellum.mellum_shape`` returns; the wrong models of the
calibration and of the tests are changes to ``m``. ``attention_sublayer``,
``routed_sublayer``, ``embed`` and ``head_losses`` are the pieces the
whole-model functions are made of: a check that feeds the program one layer at
a time calls them itself.

x = Emb[ids], (S, D). Layer l has kind t(l) of ``kinds`` (``layer_types``:
``sliding_attention`` -> ``window``, ``full_attention`` -> ``global``), no bias
anywhere, h = RMSNorm(x, eps 1e-6):

* attention: q = h W_q -> (S, H, Dh) (2304 -> 32 x 128), k, v = h W_k, h W_v
  -> (S, Hkv, Dh) (4 x 128); q and k RMS-normed over Dh, a head at a time, each
  with one learned (Dh,) scale (**assumed**: Qwen3-MoE's QK-norm has no config
  key and is always on); rotary in the rotate-half convention with the kind's
  table: *window* inv_freq_i = theta^(-2i / Dh); *global* YaRN: frequencies
  whose wavelength turns fewer than beta_slow times over the original 8192
  positions divided by ``factor``, those that turn more than beta_fast times
  kept, a linear ramp over the index between (the closed form of
  ``_yarn_inv_freq``), and cos and sin multiplied by ``attention_factor``; the
  softmax scale stays Dh^-0.5. Key / value head n serves query heads n H / Hkv
  .. (n + 1) H / Hkv - 1. Query i sees key j iff j <= i (*global*) or
  i - window < j <= i (*window*: ``window`` keys with its own, the Hugging
  Face convention, **assumed**). softmax; x + concat(o) W_o.
* experts: p = softmax(h2 W_r) over all ``experts``; the ``experts_per_token``
  largest, renormalised to sum 1 (``norm_topk_prob``); y = sum over the chosen
  experts e **that this chip holds** of g_e W_d,e (silu(W_g,e h2) * W_u,e h2),
  experts of width 896, every layer routed (``mlp_layer_types`` all ``sparse``;
  ``intermediate_size`` 7168 is used by no layer).
* logits = RMSNorm(x) W_head^T (untied), over this chip's slice of the ids.
* training loss = mean cross-entropy + ``aux_coef`` x the mean over layers of
  E sum_e f_e P_e (**assumed** 0.001: the config has no key for it).

Departures, noted: (1) a position's target is its own token, not the next one:
``train/step.py`` gives every cell of this benchmark targets = inputs,
unshifted (the source paper's harness does); the step's cost is the same. (2)
a chip that holds a part of the experts, run without the others, does not
train its routing (``routing_trained`` false): the gates and the load-balance
term are constants of the backward pass. (3) the source adds every layer's
auxiliary term; this repository averages them over the layers. (4)
``described_as`` names an MTP head; the config has no key for it: not built.

Attention runs in blocks of queries, each against all keys, and every layer is
rematerialized in the backward pass: 16,384 positions then fit beside the
training state. Call under ``jax.default_matmul_precision("highest")``.
"""

import math

import jax
import jax.numpy as jnp

from .reference_bd import _rms, _token_losses, routed_sublayer  # the Qwen3-MoE routed share

QUERY_BLOCK = 256


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
    """(cos, sin), each (S, Dh / 2), of the kind's table."""
    theta, yarn = dict(m["rotary"])[kind]
    dim, scale = m["head_dim"], 1.0
    if yarn is None:
        inv_freq = theta ** (-jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    else:
        factor, original, beta_fast, beta_slow, scale = yarn
        inv_freq = _yarn_inv_freq(dim, theta, factor, original, beta_fast, beta_slow)
    angle = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]
    return jnp.cos(angle) * scale, jnp.sin(angle) * scale


def _rotate(x, cos, sin):  # x: (S, heads, Dh), rotate-half
    half = x.shape[-1] // 2
    a, b = x[..., :half], x[..., half:]
    cos, sin = cos[:, None, :], sin[:, None, :]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def allowed(m, kind, q_pos, k_pos):
    """(queries, keys) bool: the kind's rule."""
    seen = k_pos[None, :] <= q_pos[:, None]
    if kind == "window":
        seen &= k_pos[None, :] > q_pos[:, None] - m["window"]
    return seen


def _attention(m, kind, q, k, v):  # (S, H, Dh), (S, Hkv, Dh), (S, Hkv, Dh) -> (S, H * Dh)
    S, H, Dh = q.shape
    Hkv = k.shape[1]
    q = q.reshape(S, Hkv, H // Hkv, Dh)  # query head n * (H / Hkv) + g reads kv head n
    block = min(QUERY_BLOCK, S)
    keys = jnp.arange(S)

    @jax.checkpoint
    def one_block(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, block, 0)
        scores = jnp.einsum("qngd,knd->ngqk", qb, k) * Dh ** -0.5
        mask = allowed(m, kind, start + jnp.arange(block), keys)
        scores = jnp.where(mask[None, None], scores, -jnp.inf)
        return jnp.einsum("ngqk,knd->qngd", jax.nn.softmax(scores, -1), v)

    out = jax.lax.map(one_block, jnp.arange(0, S, block))
    return out.reshape(S, H * Dh)


def attention_sublayer(m, x, w, layer):
    """x + attention: (S, D) -> (S, D), ``w`` the weights of layer ``layer``,
    whose kind chooses the mask (``mask_kinds``, where a wrong model gives
    them apart) and the rotary table."""
    S, H, Hkv, Dh = x.shape[0], m["heads"], m["kv_heads"], m["head_dim"]
    kind, mask_kind = m["kinds"][layer], m.get("mask_kinds", m["kinds"])[layer]
    h = _rms(x, w["ln1_scale"], m["norm_eps"])
    q, k, v = h @ w["wq"], h @ w["wkv"][:, 0], h @ w["wkv"][:, 1]
    q, k, v = q.reshape(S, H, Dh), k.reshape(S, Hkv, Dh), v.reshape(S, Hkv, Dh)
    q, k = _rms(q, w["q_norm"], m["norm_eps"]), _rms(k, w["k_norm"], m["norm_eps"])
    cos, sin = rotary_table(m, kind, jnp.arange(S))
    return x + _attention(m, mask_kind, _rotate(q, cos, sin), _rotate(k, cos, sin), v) @ w["wo"]


def embed(m, params, tokens):
    return params["wte"].astype(jnp.float32)[tokens]


def head_losses(m, params, x, tokens):
    """(S, D) the last layer's output -> (S,) cross-entropy of each position
    against its own token (departure 1)."""
    scale, head = params["lnf_scale"].astype(jnp.float32), params["lm_head"].astype(jnp.float32)
    return _token_losses(_rms(x, scale, m["norm_eps"]) @ head.T, tokens)


def _forward(m, params, tokens):
    """(S,) tokens -> (S, vocab) logits, per-layer router statistics."""
    p = jax.tree.map(lambda t: t.astype(jnp.float32), params)
    x, statistics = embed(m, p, tokens), []
    for layer in range(m["layers"]):  # unrolled: a layer's kind is static
        w = {k: v[layer] for k, v in p["blocks"].items()}

        @jax.checkpoint
        def one(x, w, layer=layer):
            y, stats = routed_sublayer(m, attention_sublayer(m, x, w, layer), w)
            stats.pop("margin")
            return y, stats

        x, stats = one(x, w)
        statistics.append(stats)
    statistics = jax.tree.map(lambda *s: jnp.stack(s), *statistics)
    return _rms(x, p["lnf_scale"], m["norm_eps"]) @ p["lm_head"].T, statistics


def logits(m, params, tokens):
    return _forward(m, params, tokens)[0]


def loss_and_parts(m, params, batch):
    """The full training loss of a (B, S) batch: mean cross-entropy plus the
    load-balance term over the whole batch's tokens, averaged over layers; and
    what it was made from: the (B, S) per-position losses and the (layers,
    experts) assignment counts. A sequence at a time."""
    def one(tokens):
        out, statistics = _forward(m, params, tokens)
        return _token_losses(out, tokens), statistics

    losses, statistics = jax.lax.map(one, batch)  # statistics: (sequences, layers, E)
    assignments = jnp.sum(statistics["assignments"], 0)
    share = assignments / (batch.size * m["experts_per_token"])
    mean_probability = jnp.sum(statistics["probability"], 0) / batch.size
    balance = m["experts"] * jnp.sum(share * mean_probability, -1)  # (layers,)
    return jnp.mean(losses) + m["aux_coef"] * jnp.mean(balance), (losses, assignments)


def loss(m, params, batch):
    return loss_and_parts(m, params, batch)[0]
