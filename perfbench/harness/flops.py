"""Operations and bytes the model and its attention kernels need, from shapes.

The benchmark's own copy of the program's ``utils/flops.py`` arithmetic (so a
later PR cannot move MFU by editing the program's), plus the attention
kernel's operations and bytes for its roofline share.

Conventions: a multiply-add is 2 operations; the backward pass is twice the
forward; recomputed operations (remat) are not counted; a causal mask halves
the score work. ``m`` is the plain dict ``build.model_shape`` returns.
"""


def forward_flops_per_token(m):
    D, H, Hkv, Dh, F = m["hidden"], m["heads"], m["kv_heads"], m["head_dim"], m["mlp_hidden"]
    mlp = 6 * D * F if m["mlp"] == "swiglu" else 4 * D * F  # gate+up+down | fc+proj
    attn_tokens = m["seq_len"] / 2 if m["causal"] else m["seq_len"]
    per_layer = (
        2 * D * H * Dh  # q projection
        + 2 * D * 2 * Hkv * Dh  # k and v projections
        + 2 * H * Dh * D  # output projection
        + mlp
        + 4 * attn_tokens * H * Dh  # q.k^T and probs.v
    )
    return float(m["layers"] * per_layer + 2 * D * m["vocab"])  # + the head


def train_flops_per_token(m):
    return 3.0 * forward_flops_per_token(m)


def attention_pass_cost(m, sequences, passes):
    """(flops, bytes) one step's attention kernels need, for ``passes`` a
    subset of ("fwd", "bwd") and ``sequences`` per chip per step, all layers.

    Forward: q.k^T and p.v, 4*S^2*Dh per head. Backward, as one fused pass
    would need it (the FlashAttention-2 count): s, dp, dv, dk, dq, 10*S^2*Dh.
    The program's two backward kernels recompute s and dp once more each; that
    is their choice and not counted. Bytes: q, k, v, o (and do, dq, dk, dv) in
    the 2-byte compute type, once each, plus the f32 log-sum-exp rows.
    """
    S, H, Dh = m["seq_len"], m["heads"], m["head_dim"]
    calls = sequences * H * m["layers"]
    share = 0.5 if m["causal"] else 1.0
    flops = bytes_ = 0.0
    if "fwd" in passes:
        flops += calls * 4 * S * S * Dh * share
        bytes_ += calls * (4 * S * Dh * 2 + S * 4)
    if "bwd" in passes:
        flops += calls * 10 * S * S * Dh * share
        bytes_ += calls * (8 * S * Dh * 2 + S * 4)
    return flops, bytes_


def roofline_seconds(flops, bytes_, peak):
    """Least time on the chip, and which of its two limits sets it."""
    t_compute = flops / peak["bf16_flops"]
    t_memory = bytes_ / peak["hbm_bytes_per_s"]
    return max(t_compute, t_memory), ("compute" if t_compute >= t_memory else "memory")
