"""The plain reference for Nemotron-H-class hybrids (``model_type``
``nemotron_h``): the forward pass, per-position losses and the training loss
in ``jax.numpy`` and float32; gradients are ``jax.grad`` of it.

Written from the config (huggingface.co/nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16,
``config.json``: ``hybrid_override_pattern``, the ``mamba_*`` / ``ssm_*`` /
``n_groups`` / ``conv_kernel`` sizes, ``mlp_hidden_act`` ``relu2``,
``routed_scaling_factor``) and the layer equations of the family's published
modelling code and of Mamba-2 (arXiv:2405.21060), not from the program's
``models/tinygpt.py`` / ``models/moe.py`` / ``ops/ssd.py``. No kernel, no
chunk, no sort, no grouped matmul, no buffer: the scan is a ``lax.scan`` over
the positions, one state update a position; attention materializes its mask a
block of queries at a time; every held expert runs densely over every token.
It chooses its own experts. What it shares with the program is the layout of
the parameter tree (``layer_weights``). ``m`` is the dict
``build_nemotron.nemotron_shape`` returns; the wrong models of the calibration
and of the tests are changes to ``m``.

x = Emb[ids], (S, D). **Each block is one sublayer alone**: x += f(RMSNorm(x,
eps 1e-5)), f by the block's letter in ``hybrid_override_pattern`` (``kinds``:
M ``ssd``, E ``mlp``, * ``global``); no bias but the convolution's.

* *M, the Mamba-2 mixer*, h the normed input, H = 64 heads of P = 64 channels
  (d_inner 4096), G = 8 groups, N = 128: [z | xBC | dt] = h W_in (2688 -> 4096
  | 6144 | 64); xBC = silu(conv4(xBC) + b) (a depthwise causal convolution over
  positions, 4 taps and a bias a channel: y_t = sum_i w_i x_{t-3+i}); x (S, H,
  P), B and C (S, G, N), head h reading group h // 8; dt_t = softplus(dt_t +
  dt_bias) a head; a_t = exp(-exp(A_log) dt_t) a head; a head's state S_0 = 0,
  S_t = a_t S_{t-1} + dt_t x_t B_t^T (64 x 128), y_t = S_t C_t + D x_t (D a
  scalar a head); u = y * silu(z) (the gate first), RMS over each group's 512
  channels (eps 1e-5), times a (4096,) scale; out = u W_out (4096 -> 2688).
* *\\*, attention*: 32 query heads over 2 KV heads of 128 (q and the output
  4096 wide), causal softmax at 1 / sqrt(128), **no rotary and no position
  table** (**assumed**: the config carries ``rope_theta`` and the family's code
  reads it nowhere in these layers; the scan carries the order), W_o.
* *E, the routed feed-forward part*: s = sigmoid(h W_r) over 128; the 6 largest
  of s + b (b the (128,) selection bias: a buffer, zeros at the seeded start;
  one group, so no group step); gates s at the chosen, divided by their sum,
  times 2.5; x += sum over the chosen experts e **that this chip holds** of g_e
  W_down,e relu(W_up,e h)^2 (experts of width 1856, **not gated**), plus one
  shared expert of the same form 3712 wide on every token.
* Final RMSNorm, untied head, over this chip's slice of the ids; cross entropy.
  No auxiliary loss (**assumed**: the bias is the family's balancer).

Departures, noted: (1) a position's target is its own token, not the next one:
``train/step.py`` gives every cell of this benchmark targets = inputs,
unshifted (the source paper's harness does); the step's cost is the same. (2)
a chip that holds a part of the experts, run without the others, does not
train its routing (``routing_trained`` false): the gates are constants of the
backward pass. (3) the selection bias's update between steps (the family's
balancer) is outside the step and not built: the bias stays where it starts.

The scan is cut into segments whose entry states are kept and whose inside is
recomputed in the backward pass (``SEGMENT`` positions: a choice of memory, not
of arithmetic), attention runs in blocks of queries, and every block is
rematerialized: 16,384 positions then fit beside the training state. Call under
``jax.default_matmul_precision("highest")``.
"""

import jax
import jax.numpy as jnp

from .reference_bd import _rms, _token_losses
from .reference_kda import _conv, _gate_weights  # the taps' sum; sigmoid scores + bias -> gates

SEGMENT = 256
QUERY_BLOCK = 512
STACKS = {"ssd": "ssd_blocks", "mlp": "mlp_blocks", "global": "global_blocks"}


def layer_weights(m, params, layer):
    """Block ``layer``'s weights from the parameter tree's stacks: the blocks
    of one kind share one, each in the published order."""
    kind = m["kinds"][layer]
    at = m["kinds"][:layer].count(kind)
    return {k: v[at] for k, v in params[STACKS[kind]].items()}


def state_scan(m, x, B, C, dt, log_a):
    """The scan position by position: x (S, H, P), B, C (S, H, N) (a group's
    row repeated over its heads), dt, log_a (S, H) -> y (S, H, P), without the
    skip. ``state_dtype`` (a wrong model's) rounds the state after every
    position."""
    S, H, P = x.shape
    kept = jnp.finfo(jnp.dtype(m["state_dtype"]))  # reduce_precision: a cast pair XLA may drop

    def position(state, t):  # state (H, P, N)
        x_t, B_t, C_t, dt_t, a_t = t
        state = jnp.exp(a_t)[:, None, None] * state + (dt_t[:, None] * x_t)[:, :, None] * B_t[:, None, :]
        state = jax.lax.reduce_precision(state, kept.nexp, kept.nmant)
        return state, jnp.einsum("hpn,hn->hp", state, C_t)

    @jax.checkpoint
    def segment(state, ts):
        return jax.lax.scan(position, state, ts)

    n = max(1, S // SEGMENT)
    cut = lambda t: t.reshape(n, S // n, *t.shape[1:])
    _, y = jax.lax.scan(segment, jnp.zeros((H, P, B.shape[-1]), jnp.float32),
                        tuple(cut(t) for t in (x, B, C, dt, log_a)))
    return y.reshape(S, H, P)


def ssd_sublayer(m, x, w):
    """x + Mamba2(RMSNorm(x)): (S, D) -> (S, D), ``w`` one M block's weights."""
    S = x.shape[0]
    H, P, G, N = m["ssd_heads"], m["ssd_head_dim"], m["ssd_groups"], m["ssd_state"]
    inner = H * P
    h = _rms(x, w["ln1_scale"], m["norm_eps"])
    projected = h @ w["ssd_win"]
    z, xbc, dt = projected[:, :inner], projected[:, inner:inner + inner + 2 * G * N], projected[:, -H:]
    xbc = _conv(xbc, w["ssd_conv"])
    if m.get("conv_bias", True):  # a wrong model leaves it out
        xbc = xbc + w["ssd_conv_bias"]
    xbc = jax.nn.silu(xbc)
    xs = xbc[:, :inner].reshape(S, H, P)
    shift = m.get("group_shift", 0)  # a wrong model's heads read the next group's B and C
    by_head = lambda t: jnp.roll(jnp.repeat(t.reshape(S, G, N), H // G, axis=1), shift * (H // G), 1)
    B, C = by_head(xbc[:, inner:inner + G * N]), by_head(xbc[:, inner + G * N:])
    dt = jax.nn.softplus(dt + w["ssd_dt_bias"])
    y = state_scan(m, xs, B, C, dt, -jnp.exp(w["ssd_a_log"]) * dt)
    if m.get("skip", True):
        y = y + w["ssd_d"][:, None] * xs
    y = y.reshape(S, inner)
    gate = jax.nn.silu(z)
    grouped = lambda u: _rms(u.reshape(S, G, inner // G), 1.0, m["norm_eps"]).reshape(S, inner)
    if m.get("gate_first", True):
        u = grouped(y * gate) * w["ssd_norm"]
    else:  # a wrong model: the norm, then the gate
        u = grouped(y) * w["ssd_norm"] * gate
    return x + u @ w["wo"]


def _attention(m, q, k, v):
    """q (S, H, d), k, v (S, KV, d) -> (S, H d): causal softmax, a block of
    queries at a time, each kv head under its H / KV query heads."""
    S, H, d = q.shape
    KV = k.shape[1]
    block = min(QUERY_BLOCK, S)
    keys = jnp.arange(S)
    q = q.reshape(S, KV, H // KV, d)

    @jax.checkpoint
    def one_block(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, block, 0)
        scores = jnp.einsum("qgrd,kgd->grqk", qb, k) * d ** -0.5
        rows = start + jnp.arange(block)
        scores = jnp.where(rows[None, None, :, None] >= keys[None, None, None, :], scores, -jnp.inf)
        return jnp.einsum("grqk,kgd->qgrd", jax.nn.softmax(scores, -1), v)

    return jax.lax.map(one_block, jnp.arange(0, S, block)).reshape(S, H * d)


def _rotate(x, theta):  # (S, heads, d): rotate-half over the whole head; a wrong model's
    S, _, d = x.shape
    angle = jnp.arange(S, dtype=jnp.float32)[:, None] * theta ** (-2.0 * jnp.arange(d // 2) / d)
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    a, b = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def attention_sublayer(m, x, w):
    """x + attention(RMSNorm(x)), no positions: (S, D) -> (S, D)."""
    S, H, KV, d = x.shape[0], m["heads"], m["kv_heads"], m["head_dim"]
    h = _rms(x, w["ln1_scale"], m["norm_eps"])
    q = (h @ w["wq"]).reshape(S, H, d)
    k, v = ((h @ w["wkv"][:, i]).reshape(S, KV, d) for i in range(2))
    if m.get("rotary") is not None:  # a wrong model: the config's rope_theta applied
        q, k = _rotate(q, m["rotary"]), _rotate(k, m["rotary"])
    return x + _attention(m, q, k, v) @ w["wo"]


def _relu2_mlp(m, h, up, down):
    a = jax.nn.relu(h @ up)
    return (a * a if m.get("squared", True) else a) @ down  # a wrong model: relu alone


def _routed_mlp(m, h, w):  # h: (S, D) -> (S, D), the router's statistics
    if m["router_score"] != "sigmoid":
        raise ValueError("this reference scores by sigmoid")
    gates, margin = _gate_weights(m, jax.nn.sigmoid(h @ w["router"]), w["router_bias"])
    if not m["routing_trained"]:
        gates = jax.lax.stop_gradient(gates)
    first, count = m["held"]

    @jax.checkpoint
    def add_expert(y, expert):
        up, down, gate = expert  # (D, F), (F, D), (S,)
        return y + gate[:, None] * _relu2_mlp(m, h, up, down), None

    y, _ = jax.lax.scan(
        add_expert, jnp.zeros_like(h),
        (w["moe_wu"][:count], w["moe_wd"][:count], gates.T[first:first + count]))
    if m["shared_width"] and m.get("shared", True):  # a shares' test counts the shared expert once
        y = y + _relu2_mlp(m, h, w["shared_wu"], w["shared_wd"])
    statistics = {
        "assignments": jnp.sum(gates > 0, 0),  # (E,) how many tokens chose each expert
        "margin": jax.lax.stop_gradient(margin),
    }
    return y, statistics


def routed_sublayer(m, x, w):
    """x + the held experts' part of the routed sum + the shared expert: (S,
    D) -> (S, D), the router's statistics; ``w`` one E block's weights."""
    y, statistics = _routed_mlp(m, _rms(x, w["ln2_scale"], m["norm_eps"]), w)
    return x + y, statistics


def sublayer(m, x, w, layer):
    """Block ``layer`` -> (x + f(RMSNorm(x)), the router's statistics or None)."""
    kind = m["kinds"][layer]
    if kind == "mlp":
        return routed_sublayer(m, x, w)
    return (ssd_sublayer if kind == "ssd" else attention_sublayer)(m, x, w), None


def embed(m, params, tokens):
    return params["wte"].astype(jnp.float32)[tokens]


def head_losses(m, params, x, tokens):
    """(S, D) the last block's output -> (S,) cross-entropy of each position
    against its own token (departure 1)."""
    scale, head = params["lnf_scale"].astype(jnp.float32), params["lm_head"].astype(jnp.float32)
    return _token_losses(_rms(x, scale, m["norm_eps"]) @ head.T, tokens)


def _forward(m, params, tokens):
    """(S,) tokens -> (S, vocab) logits, (routed blocks, E) assignment counts."""
    p = jax.tree.map(lambda t: t.astype(jnp.float32), params)
    x, assignments = embed(m, p, tokens), []
    for layer in range(m["layers"]):  # unrolled: a block's kind is static

        @jax.checkpoint
        def one(x, w, layer=layer):
            y, statistics = sublayer(m, x, w, layer)
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
    (routed blocks, experts) assignment counts. A sequence at a time."""
    def one(tokens):
        out, assignments = _forward(m, params, tokens)
        return _token_losses(out, tokens), assignments

    losses, assignments = jax.lax.map(one, batch)
    return jnp.mean(losses), (losses, jnp.sum(assignments, 0))


def loss(m, params, batch):
    return loss_and_parts(m, params, batch)[0]
