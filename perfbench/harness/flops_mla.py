"""Operations and bytes of a DeepSeek-V2-class model, of its attention
kernels and of its held experts' matmuls, from shapes: the benchmark's own
count. ``m`` is the dict ``build_mla.mla_shape`` returns.

Conventions as in ``flops.py``: a multiply-add is 2 operations, the backward
pass is twice the forward, recomputation is not counted, a causal mask halves
the score work. A routed layer counts what this chip computes: the router
over all experts, the shared experts, and the routed rows its held experts
see: ``experts_per_token x held / experts`` a token at uniform routing.
"""


def attention_projection_flops_per_token(m):
    D, H, R = m["hidden"], m["heads"], m["kv_lora"]
    qk = m["qk_nope"] + m["qk_rope"]
    return (2 * D * H * qk  # q, whole rank
            + 2 * D * (R + m["qk_rope"])  # down to [latent | rotary key]
            + 2 * R * H * (m["qk_nope"] + m["v_head"])  # latent up to [k_nope | v]
            + 2 * H * m["v_head"] * D)  # output


def attention_core_flops_per_token(m):
    attn_tokens = m["seq_len"] / 2 if m["causal"] else m["seq_len"]
    return 2 * attn_tokens * m["heads"] * (m["qk_nope"] + m["qk_rope"] + m["v_head"])


def expected_routed_rows_per_token(m):
    return m["experts_per_token"] * m["held"][1] / m["experts"]


def forward_flops_per_token(m):
    D = m["hidden"]
    attention = attention_projection_flops_per_token(m) + attention_core_flops_per_token(m)
    routed_layer = (2 * D * m["experts"] + 6 * D * m["shared_width"]
                    + expected_routed_rows_per_token(m) * 6 * D * m["expert_width"])
    return float(m["layers"] * attention + m["dense_layers"] * 6 * D * m["dense_width"]
                 + m["moe_layers"] * routed_layer + 2 * D * m["vocab"])


def train_flops_per_token(m):
    return 3.0 * forward_flops_per_token(m)


def mla_kernel_cost(m, sequences):
    """(flops, bytes) one step's ``flash_fwd`` and ``flash_bwd_fused`` calls
    need over ``sequences`` sequences, all layers. A head's forward is q k^T
    over the keys' width and p v over the values': 2 S^2 (Dqk + Dv); its
    backward, as one fused pass needs it, s and dq and dk over Dqk, dp and dv
    over Dv: 2 S^2 (3 Dqk + 2 Dv). Bytes: q, k, dq, dk at Dqk and v, o, do, dv
    at Dv in the 2-byte compute type, once each a pass, plus the f32 rows."""
    S, Dqk, Dv = m["seq_len"], m["qk_nope"] + m["qk_rope"], m["v_head"]
    calls = sequences * m["heads"] * m["layers"]
    share = 0.5 if m["causal"] else 1.0
    flops = calls * share * 2 * S * S * ((Dqk + Dv) + (3 * Dqk + 2 * Dv))
    forward_bytes = S * 2 * (2 * Dqk + 2 * Dv) + S * 4
    backward_bytes = S * 2 * (4 * Dqk + 4 * Dv) + 2 * S * 4
    return flops, calls * float(forward_bytes + backward_bytes)


def held_expert_matmul_cost(m, rows, layer_steps):
    """(flops, bytes) of the six grouped matmuls a routed layer runs a step
    (gate+up and down forward, and for each the gradient of its rows and of
    its weights) over ``rows`` routed rows in all: the rows the program
    counted over ``layer_steps`` runs of a layer, since how many land on the
    held experts is data. A row's forward is 6 D F operations. Bytes as
    ``flops_moe.expert_matmul_cost``: every operand and result once in 2
    bytes: rows of ``hidden``, ``2 * width`` and ``width`` columns, and the
    held experts' weights once a matmul."""
    D, F, held = m["hidden"], m["expert_width"], m["held"][1]
    per_row = (D + 2 * F) + (F + D)
    weights = layer_steps * held * (D * 2 * F + F * D)
    return 3.0 * rows * 6 * D * F, 2.0 * 3 * (rows * per_row + weights)
