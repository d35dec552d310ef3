"""Operations and bytes of an SDAR-class model under block-diffusion training,
of its attention kernels and of its held experts' matmuls, from shapes: the
benchmark's own count. ``m`` is the dict ``build_bd.bd_shape`` returns.

Conventions as in ``flops.py``: a multiply-add is 2 operations, the backward
pass is twice the forward, recomputation is not counted. What is new here:
everything is counted by the DATA token (a document of L tokens is L tokens of
throughput; the second copy in the stream is the method's cost), so each
layer's matmuls count twice a data token, the scores count the rule's true
pairs, L^2 + L * block a head a document, and only the noisy copy goes
through the head. A routed layer counts what this chip computes: the router
over all experts and the routed rows its held experts see:
``experts_per_token x held / experts`` a stream token at uniform routing.
"""


def true_pairs(m):
    """Allowed (query, key) pairs a head a document: noisy -> noisy L * block,
    noisy -> clean and clean -> clean together L^2."""
    L = m["seq_len"]
    return L * L + L * m["block"]


def expected_routed_rows_per_stream_token(m):
    return m["experts_per_token"] * m["held"][1] / m["experts"]


def forward_flops_per_token(m):
    D, H, Hkv, Dh = m["hidden"], m["heads"], m["kv_heads"], m["head_dim"]
    per_stream_token = (
        2 * D * H * Dh + 2 * D * 2 * Hkv * Dh + 2 * H * Dh * D  # q, k and v, output
        + 2 * D * m["experts"]  # router
        + expected_routed_rows_per_stream_token(m) * 6 * D * m["expert_width"]
    )
    scores = 4 * true_pairs(m) / m["seq_len"] * H * Dh  # q k^T and p v, a data token
    return float(m["layers"] * (2 * per_stream_token + scores) + 2 * D * m["vocab"])


def train_flops_per_token(m):
    return 3.0 * forward_flops_per_token(m)


def bd_kernel_cost(m, documents):
    """(flops, bytes) one step's ``flash_fwd`` and ``flash_bwd_fused`` calls
    need over ``documents`` documents, all layers. A head's forward is q k^T
    and p v over the true pairs, 4 x pairs x Dh; its backward, as one fused
    pass needs it (the FlashAttention-2 count), s, dp, dv, dk, dq: 10 x pairs
    x Dh. Tiles the kernels visit beside the true pairs (a live tile's masked
    part) are their choice and not counted. Bytes: q, k, v, o (and do, dq, dk,
    dv) over the stream's 2 L positions in the 2-byte compute type, once
    each, plus the f32 rows (K and V enter the kernels repeated to all the
    query heads, so they are counted at ``heads``)."""
    S, Dh = 2 * m["seq_len"], m["head_dim"]
    calls = documents * m["heads"] * m["layers"]
    flops = calls * (4 + 10) * true_pairs(m) * Dh
    forward_bytes = 4 * S * Dh * 2 + S * 4
    backward_bytes = 8 * S * Dh * 2 + 2 * S * 4
    return float(flops), calls * float(forward_bytes + backward_bytes)
