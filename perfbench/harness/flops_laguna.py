"""Operations and bytes of a Laguna-class model, of its attention kernels by
kind of layer and of its rotary pass, from shapes: the benchmark's own count.
``m`` is the dict ``build_laguna.laguna_shape`` returns.

Conventions as in ``flops.py``: a multiply-add is 2 operations, the backward
pass is twice the forward, recomputation is not counted. As in
``flops_mellum.py`` a layer's scores count the TRUE pairs of its kind's rule
over a sequence of S: a *global* layer S (S + 1) / 2 a head, a *window* layer
W (W + 1) / 2 + (S - W) W with W = min(window, S). What is new here: each kind
at its own head count, the output gate's projection (2 D H a token), a leading
dense layer, and the rotary pass's bytes a kind. A routed layer counts what
this chip computes: the router over all experts, the shared expert, and the
routed rows its held experts see, ``experts_per_token x held / experts`` a
token at uniform routing.
"""


def heads(m, kind):
    return dict(m["heads"])[kind]


def true_pairs(m, kind):
    """Allowed (query, key) pairs a head a sequence under the kind's rule."""
    S = m["seq_len"]
    W = min(m["window"], S) if kind == "window" else S
    return W * (W + 1) // 2 + (S - W) * W


def expected_routed_rows_per_token(m):
    return m["experts_per_token"] * m["held"][1] / m["experts"]


def attention_forward_flops_per_token(m, kind):
    """One attention sublayer of ``kind``: q, k, v, the gate, the output, and
    the scores over the kind's true pairs."""
    D, H, Hkv, Dh = m["hidden"], heads(m, kind), m["kv_heads"], m["head_dim"]
    gate = 2 * D * H if m["gate"] else 0
    return (2 * D * H * Dh + 2 * D * 2 * Hkv * Dh + gate + 2 * H * Dh * D
            + 4 * true_pairs(m, kind) / m["seq_len"] * H * Dh)


def forward_flops_per_token(m):
    D = m["hidden"]
    attention = sum(attention_forward_flops_per_token(m, kind) for kind in m["kinds"])
    dense = m["dense_layers"] * 6 * D * m["dense_width"]
    routed = m["moe_layers"] * (
        2 * D * m["experts"] + 6 * D * m["shared_width"]
        + expected_routed_rows_per_token(m) * 6 * D * m["expert_width"])
    return float(attention + dense + routed + 2 * D * m["vocab"])


def train_flops_per_token(m):
    return 3.0 * forward_flops_per_token(m)


def _kernel_cost(m, kind, sequences):
    """(flops, bytes) one step's ``flash_fwd`` and ``flash_bwd_fused`` calls of
    the layers of ``kind`` need over ``sequences`` sequences, at that kind's
    head count. A head's forward is q k^T and p v over the true pairs, 4 x
    pairs x Dh; its backward, as one fused pass needs it (the FlashAttention-2
    count), s, dp, dv, dk, dq: 10 x pairs x Dh. What the kernels multiply
    beside the true pairs (a live tile's masked part) is their choice and not
    counted. Bytes: q, k, v, o (and do, dq, dk, dv) over the S positions in the
    2-byte compute type, once each, plus the f32 rows (K and V enter the
    kernels repeated to all the query heads, so they are counted at the
    kind's ``heads``)."""
    S, Dh = m["seq_len"], m["head_dim"]
    calls = sequences * heads(m, kind) * m["kinds"].count(kind)
    flops = calls * (4 + 10) * true_pairs(m, kind) * Dh
    forward_bytes = 4 * S * Dh * 2 + S * 4
    backward_bytes = 8 * S * Dh * 2 + 2 * S * 4
    return float(flops), calls * float(forward_bytes + backward_bytes)


def window_kernel_cost(m, sequences):
    return _kernel_cost(m, "window", sequences)


def global_kernel_cost(m, sequences):
    return _kernel_cost(m, "global", sequences)


def prologue_call_bytes(m, kind, sequences):
    """The bytes one call of the rotary pass (``qk_prologue_fwd`` or
    ``qk_prologue_bwd``) of a layer of ``kind`` needs over ``sequences``
    sequences: q and k in and out once in the 2-byte compute type (no norm
    stage here: the backward reads the cotangents and writes the gradients),
    whatever part of a head rotates: a head moves whole."""
    rows = sequences * m["seq_len"]
    return float(2 * rows * (heads(m, kind) + m["kv_heads"]) * m["head_dim"] * 2)
