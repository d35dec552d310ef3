"""Operations and bytes of a Mellum-2-class model, of its attention kernels by
kind of layer and of its held experts' matmuls, from shapes: the benchmark's
own count. ``m`` is the dict ``build_mellum.mellum_shape`` returns.

Conventions as in ``flops.py``: a multiply-add is 2 operations, the backward
pass is twice the forward, recomputation is not counted. What is new here: a
layer's scores count the TRUE pairs of its kind's rule over a sequence of S: a
*global* layer S (S + 1) / 2 a head (the exact count; ``flops.py`` halves S^2),
a *window* layer W (W + 1) / 2 + (S - W) W with W = min(window, S). A routed
layer counts what this chip computes: the router over all experts and the
routed rows its held experts see, ``experts_per_token x held / experts`` a
token at uniform routing.
"""


def true_pairs(m, kind):
    """Allowed (query, key) pairs a head a sequence under the kind's rule."""
    S = m["seq_len"]
    W = min(m["window"], S) if kind == "window" else S
    return W * (W + 1) // 2 + (S - W) * W


def expected_routed_rows_per_token(m):
    return m["experts_per_token"] * m["held"][1] / m["experts"]


def forward_flops_per_token(m):
    D, H, Hkv, Dh = m["hidden"], m["heads"], m["kv_heads"], m["head_dim"]
    per_token = (
        2 * D * H * Dh + 2 * D * 2 * Hkv * Dh + 2 * H * Dh * D  # q, k and v, output
        + 2 * D * m["experts"]  # router
        + expected_routed_rows_per_token(m) * 6 * D * m["expert_width"]
    )
    scores = sum(4 * true_pairs(m, kind) / m["seq_len"] * H * Dh for kind in m["kinds"])
    return float(m["layers"] * per_token + scores + 2 * D * m["vocab"])


def train_flops_per_token(m):
    return 3.0 * forward_flops_per_token(m)


def _kernel_cost(m, kind, sequences):
    """(flops, bytes) one step's ``flash_fwd`` and ``flash_bwd_fused`` calls of
    the layers of ``kind`` need over ``sequences`` sequences. A head's forward
    is q k^T and p v over the true pairs, 4 x pairs x Dh; its backward, as one
    fused pass needs it (the FlashAttention-2 count), s, dp, dv, dk, dq: 10 x
    pairs x Dh. What the kernels multiply beside the true pairs (a live
    tile's masked part) is their choice and not counted. Bytes: q, k, v, o
    (and do, dq, dk, dv) over the S positions in the 2-byte compute type, once
    each, plus the f32 rows (K and V enter the kernels repeated to all the
    query heads, so they are counted at ``heads``)."""
    S, Dh = m["seq_len"], m["head_dim"]
    calls = sequences * m["heads"] * m["kinds"].count(kind)
    flops = calls * (4 + 10) * true_pairs(m, kind) * Dh
    forward_bytes = 4 * S * Dh * 2 + S * 4
    backward_bytes = 8 * S * Dh * 2 + 2 * S * 4
    return float(flops), calls * float(forward_bytes + backward_bytes)


def window_kernel_cost(m, sequences):
    return _kernel_cost(m, "window", sequences)


def global_kernel_cost(m, sequences):
    return _kernel_cost(m, "global", sequences)
