"""Operations and bytes of an OLMoE-class model and of its expert matmuls,
from shapes: the benchmark's own count, independent of the program's
``utils/flops.py``. ``m`` is the dict ``build_moe.moe_shape`` returns.

Conventions as in ``flops.py``: a multiply-add is 2 operations, the backward
pass is twice the forward, recomputation is not counted, a causal mask halves
the score work. A routed MLP counts its *active* parameters: the router and
the ``experts_per_token`` experts a token visits, three matmuls each (gate,
up, down); the other experts' weights do no work for that token.
"""


def expert_forward_flops_per_token(m):
    return m["experts_per_token"] * 6 * m["hidden"] * m["mlp_hidden"]


def forward_flops_per_token(m):
    D, H, Hkv, Dh = m["hidden"], m["heads"], m["kv_heads"], m["head_dim"]
    attn_tokens = m["seq_len"] / 2 if m["causal"] else m["seq_len"]
    per_layer = (
        2 * D * H * Dh  # q projection
        + 2 * D * 2 * Hkv * Dh  # k and v projections
        + 2 * H * Dh * D  # output projection
        + 2 * D * m["experts"]  # router
        + expert_forward_flops_per_token(m)
        + 4 * attn_tokens * H * Dh  # q.k^T and probs.v
    )
    return float(m["layers"] * per_layer + 2 * D * m["vocab"])  # + the head


def train_flops_per_token(m):
    return 3.0 * forward_flops_per_token(m)


def expert_matmul_cost(m, tokens):
    """(flops, bytes) the expert matmuls of one step over ``tokens`` tokens
    need, forward and backward, all layers. Six grouped matmuls a layer: gate+up
    and down forward, and for each the gradient of its rows and of its weights.
    Bytes: every operand and result of the six once, in the 2-byte compute
    type: rows of ``hidden``, ``2 * width`` and ``width`` columns, and each
    expert's weights (read by the forward and by the rows' gradient, written
    by the weights' gradient). The activation between the two matmuls and the
    casts of the f32 weights are not matmul traffic and are not counted; the
    time they take under the scope ``experts`` is."""
    D, F, E = m["hidden"], m["mlp_hidden"], m["experts"]
    rows = tokens * m["experts_per_token"]
    flops = 3.0 * tokens * expert_forward_flops_per_token(m)
    gate_up = rows * (D + 2 * F) + E * D * 2 * F  # rows in or out, result, weights
    down = rows * (F + D) + E * F * D
    return m["layers"] * flops, m["layers"] * 2.0 * 3 * (gate_up + down)
