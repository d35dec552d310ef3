"""Operations and bytes of a Nemotron-H-class hybrid, of its scan's kernels,
of its attention blocks' kernels and of its held experts' matmuls, from
shapes: the benchmark's own count. ``m`` is the dict
``build_nemotron.nemotron_shape`` returns.

Conventions as in ``flops.py``: a multiply-add is 2 operations, the backward
pass is twice the forward, recomputation is not counted. An attention block's
scores count the true pairs of causal, S (S + 1) / 2 a head. A routed block
counts what this chip computes: the router over all experts, the shared
expert, and the routed rows its held experts see, ``experts_per_token x held /
experts`` a token at uniform routing; an expert is **not gated**, two matrices,
4 D F a row. The scan is counted as **the work, not the kernel**: the
chunkwise form at a chunk of 128 positions (``CHUNK``, the config's), whatever
chunk and whatever slabs the program's kernels take.
"""

CHUNK = 128  # the chunkwise form's, fixed here: the count does not follow the kernels' tuning


def scan_forward_flops_per_token(m):
    """A Mamba-2 block's scan, forward, a token, all heads, with C = CHUNK, P
    the head's channels, N the state's columns; a product of (m, k) x (k, n) is
    2 m k n, divided by the chunk's C tokens:

    * C B^T, once a group, (C, N) x (N, C): 2 C N a group
    * (L o C B^T)(dt x), (C, C) x (C, P): 2 C P a head
    * C S_0^T, (C, N) x (N, P): 2 N P a head
    * (dt exp(G_C - G) x)^T B, (P, C) x (C, N): 2 N P a head

    3.41M a token at 64 heads of 64 in 8 groups over a state of 128."""
    C, P, N = CHUNK, m["ssd_head_dim"], m["ssd_state"]
    return float(m["ssd_heads"] * (2 * C * P + 4 * N * P) + m["ssd_groups"] * 2 * C * N)


def scan_backward_flops_per_token(m):
    """The same a token backward: each of the four products is transposed twice
    (one product for each operand's gradient); C B^T, which a backward that
    keeps only the states makes again, is recomputation and not counted."""
    return 2.0 * scan_forward_flops_per_token(m)


def ssd_projection_flops_per_token(m):
    D, inner = m["hidden"], m["ssd_heads"] * m["ssd_head_dim"]
    xbc = inner + 2 * m["ssd_groups"] * m["ssd_state"]
    return float(2 * D * (inner + xbc + m["ssd_heads"])  # in_proj: z | x B C | dt
                 + 2 * m["ssd_conv"] * xbc  # the convolution's taps
                 + 2 * inner * D)  # out_proj


def attention_forward_flops_per_token(m):
    D, H, KV, d, S = m["hidden"], m["heads"], m["kv_heads"], m["head_dim"], m["seq_len"]
    return float(2 * D * (H + 2 * KV) * d + 2 * H * d * D + 4 * (S + 1) / 2 * H * d)


def expected_routed_rows_per_token(m):
    return m["experts_per_token"] * m["held"][1] / m["experts"]


def routed_forward_flops_per_token(m):
    D = m["hidden"]
    return float(2 * D * m["experts"] + 4 * D * m["shared_width"]
                 + expected_routed_rows_per_token(m) * 4 * D * m["expert_width"])


def forward_flops_per_token(m):
    kinds = m["kinds"]
    mixer = ssd_projection_flops_per_token(m) + scan_forward_flops_per_token(m)
    return float(kinds.count("ssd") * mixer
                 + kinds.count("global") * attention_forward_flops_per_token(m)
                 + kinds.count("mlp") * routed_forward_flops_per_token(m)
                 + 2 * m["hidden"] * m["vocab"])


def train_flops_per_token(m):
    return 3.0 * forward_flops_per_token(m)


def ssd_kernel_cost(m, sequences):
    """(flops, bytes) one step's ``ssd_fwd`` and ``ssd_bwd`` calls need over
    ``sequences`` sequences, all Mamba-2 blocks: the operations of the two
    functions above; bytes x and y (H P columns) and B and C (G N) in the
    2-byte compute type, dt and the running sums of log a (H) in float32, once
    forward; those (y aside) with dy, dx, dB, dC (2 bytes), ddt and the sums'
    gradient (4) once backward. What the forward keeps for the backward (the
    states entering the chunks) is the kernels' choice and not counted."""
    S, H = m["seq_len"], m["ssd_heads"]
    wide, narrow = H * m["ssd_head_dim"], m["ssd_groups"] * m["ssd_state"]
    calls = sequences * m["kinds"].count("ssd")
    flops = calls * S * (scan_forward_flops_per_token(m) + scan_backward_flops_per_token(m))
    forward_bytes = S * (2 * (2 * wide + 2 * narrow) + 2 * H * 4)
    backward_bytes = S * (2 * (3 * wide + 4 * narrow) + 4 * H * 4)
    return flops, calls * float(forward_bytes + backward_bytes)


def global_kernel_cost(m, sequences):
    """(flops, bytes) one step's ``flash_fwd`` and ``flash_bwd_fused`` calls of
    the attention blocks need over ``sequences`` sequences. A head's forward is
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


def held_expert_matmul_cost(m, rows, layer_steps):
    """(flops, bytes) of the six grouped matmuls a routed block runs a step (up
    and down forward, and for each the gradient of its rows and of its
    weights) over ``rows`` routed rows in all: the rows the program counted
    over ``layer_steps`` runs of a block. A row's forward is 4 D F operations
    (not gated). Bytes: every operand and result once in 2 bytes: rows of
    ``hidden`` and ``width`` columns in and out of each matmul, and the held
    experts' weights once a matmul."""
    D, F, held = m["hidden"], m["expert_width"], m["held"][1]
    per_row = (D + F) + (F + D)
    weights = layer_steps * held * (D * F + F * D)
    return 3.0 * rows * 4 * D * F, 2.0 * 3 * (rows * per_row + weights)
