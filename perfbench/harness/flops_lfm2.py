"""Operations and bytes of an LFM2-MoE-class stack, of its gated convolution's
kernels, of its attention layers' kernels and of its held experts' matmuls,
from shapes: the benchmark's own count. ``m`` is the dict
``build_lfm2.lfm2_shape`` returns.

Conventions as in ``flops.py``: a multiply-add is 2 operations, the backward
pass is twice the forward, recomputation is not counted. An attention layer's
scores count the true pairs of causal, S (S + 1) / 2 a head. A routed layer
counts what this chip computes: the router over all experts and the routed rows
its held experts see, ``experts_per_token x held / experts`` a token at uniform
routing; an expert is SwiGLU, three matrices, 6 D F a row. A convolution mixer's
two gates are elementwise and not counted in the model's FLOPs; its taps are (2
K D a token, as the other cells count theirs).
"""

from .flops_mla import held_expert_matmul_cost  # noqa: F401  (SwiGLU experts: the same arithmetic)


def conv_mixer_forward_flops_per_token(m):
    D = m["hidden"]
    return float(2 * D * 3 * D + 2 * m["taps"] * D + 2 * D * D)  # W_in, the taps, W_out


def attention_projection_flops_per_token(m):
    D, H, KV, d = m["hidden"], m["heads"], m["kv_heads"], m["head_dim"]
    return float(2 * D * (H + 2 * KV) * d + 2 * H * d * D)


def attention_kernel_forward_flops_per_token(m):
    return float(4 * (m["seq_len"] + 1) / 2 * m["heads"] * m["head_dim"])


def expected_routed_rows_per_token(m):
    return m["experts_per_token"] * m["held"][1] / m["experts"]


def routed_forward_flops_per_token(m):
    D = m["hidden"]
    return float(2 * D * m["experts"]
                 + expected_routed_rows_per_token(m) * 6 * D * m["expert_width"])


def forward_flops_per_token(m):
    kinds = m["kinds"]
    attention = attention_projection_flops_per_token(m) + attention_kernel_forward_flops_per_token(m)
    return float(kinds.count("conv") * conv_mixer_forward_flops_per_token(m)
                 + kinds.count("global") * attention
                 + m["dense_layers"] * 6 * m["hidden"] * m["dense_width"]
                 + m["moe_layers"] * routed_forward_flops_per_token(m)
                 + 2 * m["hidden"] * m["vocab"])


def train_flops_per_token(m):
    return 3.0 * forward_flops_per_token(m)


def sconv_kernel_cost(m, sequences, forwards=1):
    """(flops, bytes) one step's ``sconv_fwd`` and ``sconv_bwd`` calls need over
    ``sequences`` sequences, all conv layers, the forward run ``forwards`` times
    a layer (2 where the remat policy runs it again). Vector operations an
    element of the (S, D) result, with K taps: forward b x (1), the taps (2 K -
    1) and the gate (1); backward b x and the taps again (2 K), dc (1), dw (1),
    the taps' transpose (2 K - 1), db and dx (2) and the taps' gradient (2 K).
    Bytes, every operand and result once in the 2-byte compute type: forward the
    (S, 3 D) operand in and (S, D) out; backward the operand, the (S, D)
    cotangent and the (S, 3 D) result."""
    S, D, K = m["seq_len"], m["hidden"], m["taps"]
    calls = sequences * m["kinds"].count("conv")
    flops = calls * S * D * (forwards * (2 * K + 1) + (6 * K + 3))
    return float(flops), calls * float(S * D * 2 * (forwards * 4 + 7))


def global_kernel_cost(m, sequences):
    """(flops, bytes) one step's ``flash_fwd`` and ``flash_bwd_fused`` calls of
    the attention layers need over ``sequences`` sequences. A head's forward is
    q k^T and p v over the true pairs, 4 x pairs x d; its backward, as one
    fused pass needs it (the FlashAttention-2 count), s, dp, dv, dk, dq: 10 x
    pairs x d. Bytes: q, o (and do, dq) at the query heads, k, v (and dk, dv)
    at their own head count (the kernels' index maps find a query head's kv
    head), over the S positions in the 2-byte compute type, once each, plus
    the f32 rows."""
    S, H, KV, d = m["seq_len"], m["heads"], m["kv_heads"], m["head_dim"]
    layers = sequences * m["kinds"].count("global")
    flops = layers * H * (4 + 10) * (S * (S + 1) / 2) * d
    forward_bytes = (2 * H + 2 * KV) * S * d * 2 + H * S * 4
    backward_bytes = (4 * H + 4 * KV) * S * d * 2 + 2 * H * S * 4
    return float(flops), layers * float(forward_bytes + backward_bytes)
