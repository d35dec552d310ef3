"""Operations and bytes of a Kimi-Linear-class model, of its recurrence's
kernels, of its latent-attention layer's kernels and of its held experts'
matmuls, from shapes: the benchmark's own count. ``m`` is the dict
``build_kda.kda_shape`` returns.

Conventions as in ``flops.py``: a multiply-add is 2 operations, the backward
pass is twice the forward, recomputation is not counted. A latent-attention
layer's scores count the true pairs of causal, S (S + 1) / 2 a head. A routed
layer counts what this chip computes: the router over all experts, the shared
expert, and the routed rows its held experts see, ``experts_per_token x held /
experts`` a token at uniform routing. The recurrence is counted as **the
work, not the kernel**: the chunkwise form at a chunk of 64 positions
(``CHUNK``), whatever chunk and whatever way to the intra-chunk products the
program's kernels take.
"""

from . import flops_mla

CHUNK = 64  # the chunkwise form's, fixed here: the count does not follow the kernels' tuning


def recurrence_forward_flops_per_token(m):
    """A KDA layer's recurrence, forward, a token, all heads, with C = CHUNK
    and d = the head's key and value width; a product of (m, k) x (k, n) is 2
    m k n, divided by the chunk's C tokens:

    * K K^T, the keys' decayed Gram matrix (C, d) x (d, C): 2 C d
    * Q K^T likewise: 2 C d
    * (I + A)^-1 by forward substitution, C^3 / 3 multiply-adds: 2 C^2 / 3
    * W = T (beta k exp(G)), (C, C) x (C, d): 2 C d
    * T (beta v), (C, C) x (C, d): 2 C d
    * W S_0, (C, d) x (d, d): 2 d^2
    * (q exp(G)) S_0: 2 d^2
    * B U, (C, C) x (C, d): 2 C d
    * (k exp(G_C - G))^T U, (d, C) x (C, d): 2 d^2

    183 k a token a head at C 64, d 128."""
    C, d = CHUNK, m["kda_head_dim"]
    return float(m["kda_heads"] * (5 * 2 * C * d + 3 * 2 * d * d + 2 * C * C / 3))


def recurrence_backward_flops_per_token(m):
    """The same a token backward: each of the eight products above is
    transposed twice (one product for each operand's gradient) and the
    inverse's gradient is two (C, C) x (C, C) products, -T^T dT T^T: 2 x the
    forward's eight and 4 C^2; the forward's own intra-chunk products, which a
    backward that keeps only the states recomputes, are recomputation and not
    counted."""
    C, d = CHUNK, m["kda_head_dim"]
    return float(m["kda_heads"] * (2 * (5 * 2 * C * d + 3 * 2 * d * d) + 4 * C * C))


def kda_projection_flops_per_token(m):
    D, H, d = m["hidden"], m["kda_heads"], m["kda_head_dim"]
    return float(2 * D * 3 * H * d  # q, k, v
                 + 2 * (2 * D * d + 2 * d * H * d)  # the decay's and the gate's low-rank maps
                 + 2 * D * H  # beta
                 + 2 * m["kda_conv"] * 3 * H * d  # the three convolutions' taps
                 + 2 * H * d * D)  # output


def expected_routed_rows_per_token(m):
    return m["experts_per_token"] * m["held"][1] / m["experts"]


def forward_flops_per_token(m):
    D, S = m["hidden"], m["seq_len"]
    kda = kda_projection_flops_per_token(m) + recurrence_forward_flops_per_token(m)
    latent = (flops_mla.attention_projection_flops_per_token(m)
              + 2 * (S + 1) / 2 * m["heads"] * (m["qk_nope"] + m["qk_rope"] + m["v_head"]))
    routed_layer = (2 * D * m["experts"] + 6 * D * m["shared_width"]
                    + expected_routed_rows_per_token(m) * 6 * D * m["expert_width"])
    return float(m["kinds"].count("kda") * kda + m["kinds"].count("global") * latent
                 + m["dense_layers"] * 6 * D * m["dense_width"]
                 + m["moe_layers"] * routed_layer + 2 * D * m["vocab"])


def train_flops_per_token(m):
    return 3.0 * forward_flops_per_token(m)


def kda_kernel_cost(m, sequences):
    """(flops, bytes) one step's ``kda_fwd`` and ``kda_bwd`` calls need over
    ``sequences`` sequences, all KDA layers: the operations of the two
    functions above; bytes q, k, v, o in the 2-byte compute type, g and beta in
    float32, once forward; those (o aside) with do, dq, dk, dv (2 bytes), dg
    and dbeta (4) once backward. What the forward keeps for the backward (the
    states entering the chunks) is the kernels' choice and not counted."""
    S, H, d = m["seq_len"], m["kda_heads"], m["kda_head_dim"]
    calls = sequences * m["kinds"].count("kda")
    flops = calls * S * (recurrence_forward_flops_per_token(m)
                         + recurrence_backward_flops_per_token(m))
    forward_bytes = S * H * (4 * d * 2 + d * 4 + 4)
    backward_bytes = S * H * ((3 * d * 2 + d * 4 + 4) + 4 * d * 2 + d * 4 + 4)
    return flops, calls * float(forward_bytes + backward_bytes)


def global_kernel_cost(m, sequences):
    """``flops_mla.mla_kernel_cost`` for the latent-attention layers alone: the
    flash kernels at 192-wide keys over 128-wide values, causal."""
    return flops_mla.mla_kernel_cost({**m, "layers": m["kinds"].count("global")}, sequences)
