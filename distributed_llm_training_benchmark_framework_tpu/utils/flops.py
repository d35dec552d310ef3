"""Analytic model-FLOPs accounting and MFU (model FLOPs utilization).

The reference's metric surface stops at tokens/sec and a transfer proxy
(reference ``benchmarking/train_harness.py:399-413``) — it never relates
throughput to what the silicon could do. We add the standard accounting:

- ``train_flops_per_token(config)``: analytic fwd+bwd FLOPs per token for the
  TinyGPT architecture (matmul-dominated terms only, the PaLM/Chinchilla
  convention). Backward is counted as 2x forward; rematerialized recompute is
  deliberately NOT counted — MFU measures useful model FLOPs, so remat shows
  up as lower MFU, not higher FLOPs.
- ``device_peak_tflops(device_kind)``: bf16 peak per chip, from the one
  hardware table (``utils.platform.CHIP_SPECS``).
- MFU = achieved model TFLOP/s/chip ÷ peak TFLOP/s/chip.

Counting detail (per token, forward):
- per layer: QKV projection ``2*D*3D``, attention output projection ``2*D*D``,
  MLP ``2*(D*4D + 4D*D)`` → ``24*D^2`` total matmul FLOPs;
- attention itself: ``QK^T`` is S MACs per head-dim per key → ``2*S*D``, and
  ``probs @ V`` another ``2*S*D`` → ``4*S*D`` per layer;
- LM head (weight-tied, counted once): ``2*D*V``;
- MoE variant: the MLP term runs ``top_k`` experts per token plus a
  ``2*D*E`` router.

Training multiplies forward by 3 (bwd ≈ 2x fwd for matmuls).
"""

from __future__ import annotations

from typing import Optional

from .platform import chip_spec


def device_peak_tflops(device_kind: str) -> Optional[float]:
    """bf16 peak TFLOP/s for a device kind; None off a TPU (CPU hosts), an
    error for a TPU kind the table does not hold (utils.platform)."""
    spec = chip_spec(device_kind)
    return spec.bf16_tflops if spec else None


def device_usd_per_chip_hour(device_kind: str) -> Optional[float]:
    """On-demand $/chip-hour (the reference's cost-efficiency metric,
    reference README.md:270-276, uses its cloud's A10 rate the same way);
    None off a TPU or where no list price is published."""
    spec = chip_spec(device_kind)
    return spec.usd_per_chip_hour if spec else None


def tokens_per_dollar(
    tokens_per_sec_per_chip: float, device_kind: str
) -> Optional[float]:
    """Training cost efficiency: tokens processed per on-demand dollar.

    The reference publishes this per arm (reference README.md:270-276,
    tokens/$ at the A10's hourly rate); computed here from the same
    per-chip throughput the rest of the metric surface uses.
    """
    price = device_usd_per_chip_hour(device_kind)
    if price is None or tokens_per_sec_per_chip <= 0:
        return None
    return tokens_per_sec_per_chip * 3600.0 / price


def forward_flops_per_token(config) -> float:
    """Analytic forward-pass FLOPs per token.

    Generalized over the architecture-family knobs (models.tinygpt): GQA
    shrinks the K/V projection to ``2*kv_heads*head_dim`` columns, SwiGLU's
    MLP runs three matrices (``6*D*F`` vs GELU's ``4*D*F``), a routed MLP is
    ``expert_top_k`` such MLPs plus the router (active parameters), and RoPE adds
    no matmul FLOPs (elementwise rotation — not counted, per the
    PaLM/Chinchilla convention). The LM head term is ``2*D*V`` tied or
    untied alike. Defaults reproduce the original TinyGPT accounting
    exactly (kv=H, F=4D, gelu -> 8*D^2 attention projections + 16*D^2 MLP).
    """
    if getattr(config, "latent_attention", False) or getattr(config, "first_k_dense", 0) or (
            getattr(config, "block_diffusion", None) is not None
            or getattr(config, "head_width", None) is not None
            or getattr(config, "layer_types", None) is not None):
        if getattr(config, "block_halves", False):
            return _halves_forward_flops_per_token(config)
        return _deepseek_forward_flops_per_token(config)
    D, L, V, S = config.n_embd, config.n_layer, config.vocab_size, config.block_size
    H = config.n_head
    Hkv = getattr(config, "kv_heads", H) or H
    F = getattr(config, "mlp_dim", 4 * D) or 4 * D
    Dh = D // H
    if getattr(config, "mlp_act", "gelu") == "swiglu":
        mlp = 2 * (2 * D * F + F * D)  # gate + up + down
    else:
        mlp = 2 * (D * F + F * D)
    if getattr(config, "n_experts", 0) > 0:  # k active experts + the router
        mlp = config.expert_top_k * mlp + 2 * D * config.n_experts
    # Causal masking halves the score-matrix work: the flash/ring kernels
    # skip fully-masked tiles (ops/flash_attention.py `live`), so charging
    # full S would overstate MFU on --causal runs by up to ~1.5x at 16K.
    # The exact executed fraction is (S + block)/2S; the standard 1/2
    # accounting (PaLM-style MFU) is used so causal and non-causal rows
    # stay comparable across block sizes.
    attn_tokens = S / 2 if getattr(config, "causal", False) else S
    per_layer = (
        2 * D * (H * Dh)  # Q projection
        + 2 * D * (2 * Hkv * Dh)  # K/V projections
        + 2 * (H * Dh) * D  # attention output projection
        + mlp
        + 4 * attn_tokens * (H * Dh)  # QK^T and probs@V
    )
    return float(L * per_layer + 2 * D * V)


def _deepseek_forward_flops_per_token(c) -> float:
    """A DeepSeek-V2-class config: latent attention (the three projections at
    their own widths, scores over qk_dim and values over v_dim), leading
    dense layers, and routed layers counted by their ACTIVE parameters on
    this chip: the router over all experts, the shared experts, and the
    expert_top_k * held / n_experts routed rows a token the held experts see
    at uniform routing.

    Or a config trained by block diffusion, counted by the DATA token: every
    layer runs over the stream of two copies (2 x its matmuls a data token), a
    document of S tokens has S^2 + S * block true pairs a head (S + block keys
    a data token), and only the noisy copy goes through the head.

    Or a stack of more than one kind of layer (``layer_types``): a ``window``
    layer's scores are counted over its true pairs, W (W + 1) / 2 + (S - W) W
    a head a sequence of S with W = min(sliding_window, S), the exact count
    (causal's S / 2 keys a token is the convention for the global ones); each
    kind at its own head count (``layer_heads``), with the output gate's
    projection where the config has one (``attn_gate``: 2 D H a token). Rotary
    over a part of a head is elementwise, as whole heads' is: not counted. A
    ``kda`` or ``conv`` layer's mixer by its own count."""
    D, H, S = c.n_embd, c.n_head, c.block_size
    attn_tokens = S / 2 if c.causal else S
    copies = 1
    if c.block_diffusion is not None:
        attn_tokens, copies = S + c.block_diffusion.block, 2
    if c.latent_attention:
        R, Dn, Dr, Dv = c.kv_lora_rank, c.qk_nope_head_dim, c.qk_rope_head_dim, c.v_dim
        projections = (2 * D * H * (Dn + Dr) + 2 * D * (R + Dr) + 2 * R * H * (Dn + Dv)
                       + 2 * H * Dv * D)
        scores = 2 * attn_tokens * H * (Dn + Dr + Dv)
    elif getattr(c, "layer_heads", None) or getattr(c, "attn_gate", False):
        return _by_kind_forward_flops_per_token(c, attn_tokens, copies)
    else:
        Dh = c.head_dim
        projections = 2 * D * (H + 2 * c.kv_heads) * Dh + 2 * H * Dh * D
        scores = 4 * attn_tokens * H * Dh
        windows = (c.layer_types or ()).count("window")
        if windows:  # the mean over the stack: window layers by their true pairs
            scores *= (windows * _window_tokens(c) / attn_tokens + c.n_layer - windows) / c.n_layer
    kda_layers = (c.layer_types or ()).count("kda")
    conv_layers = (c.layer_types or ()).count("conv")
    return float(
        (c.n_layer - kda_layers - conv_layers) * (copies * projections + scores)
        + kda_layers * kda_forward_flops_per_token(c)
        + conv_layers * conv_forward_flops_per_token(c)
        + copies * _mlp_forward_flops_per_token(c)
        + 2 * D * c.vocab_size
    )


def _window_tokens(c) -> float:
    """Keys a token of a ``window`` layer meets, the mean over a sequence."""
    S = c.block_size
    W = min(c.sliding_window, S)
    return (W * (W + 1) / 2 + (S - W) * W) / S


def _mlp_forward_flops_per_token(c) -> float:
    """The MLPs of the whole depth a token: the leading dense layers', and the
    routed layers' by their active parameters on this chip."""
    D, F = c.n_embd, c.mlp_dim
    matrices = 6 if c.mlp_act == "swiglu" else 4  # gated: three matrices; gelu, relu2: two
    if c.n_experts > 0:
        routed_rows = c.expert_top_k * c.n_experts_held / c.n_experts
        shared = getattr(c, "shared_dim", c.n_shared_experts * F)
        mlp = 2 * D * c.n_experts + matrices * D * (F * routed_rows + shared)
    else:
        mlp = matrices * D * F
    dense = 6 * D * (c.dense_mlp_hidden or 0)
    layers = getattr(c, "n_mlp_layers", c.n_layer)  # under block_halves the 'mlp' blocks
    return c.first_k_dense * dense + (layers - c.first_k_dense) * mlp


def _by_kind_forward_flops_per_token(c, attn_tokens: float, copies: int) -> float:
    """A stack whose attention kinds have head counts of their own and, maybe,
    the per-head output gate: every attention layer at its kind's count
    (``copies`` of the matmuls a data token, as the caller counts them)."""
    D, Dh = c.n_embd, c.head_dim
    attention = 0.0
    for kind in c.layer_types or (None,) * c.n_layer:
        if kind == "kda":
            attention += kda_forward_flops_per_token(c)
            continue
        H = c.heads(kind)
        tokens = _window_tokens(c) if kind == "window" else attn_tokens
        attention += copies * (2 * D * (H + 2 * c.kv_heads) * Dh + 2 * H * Dh * D
                               + (2 * D * H if c.attn_gate else 0)) + 4 * tokens * H * Dh
    return float(attention + copies * _mlp_forward_flops_per_token(c) + 2 * D * c.vocab_size)


def _halves_forward_flops_per_token(c) -> float:
    """A stack whose blocks are one sublayer alone (``block_halves``): every
    mixer block by its kind (an ``ssd`` block by ``ssd_forward_flops_per_token``,
    an attention block's projections and its scores over causal's S / 2 keys a
    token, or a window's true pairs), the ``mlp`` blocks by
    ``_mlp_forward_flops_per_token``, and the head."""
    D, Dh, S = c.n_embd, c.head_dim, c.block_size
    mixers = 0.0
    for kind in c.layer_types:
        if kind == "ssd":
            mixers += ssd_forward_flops_per_token(c)
        elif kind != "mlp":
            H = c.heads(kind)
            tokens = _window_tokens(c) if kind == "window" else S / 2
            mixers += 2 * D * (H + 2 * c.kv_heads) * Dh + 2 * H * Dh * D + 4 * tokens * H * Dh
    return float(mixers + _mlp_forward_flops_per_token(c) + 2 * D * c.vocab_size)


def ssd_forward_flops_per_token(c) -> float:
    """One ``ssd`` block's mixer, a token: in_proj ([z | x B C | dt]), the
    convolution's taps, out_proj, and the scan counted as the chunkwise form's
    work at the config's chunk C, with P = ssd_head_dim and N = ssd_state: C
    B^T once a group (2 C N), and a head's (L o C B^T)(dt x) (2 C P), C S_0^T
    and the state's update (2 N P each). What a kernel multiplies beyond that
    (a slab's masked half) is its choice."""
    D, H, P, N, C = c.n_embd, c.ssd_heads, c.ssd_head_dim, c.ssd_state, c.ssd_chunk
    projections = 2 * D * (c.ssd_inner + c.ssd_xbc + H) + 2 * c.ssd_inner * D
    convolution = 2 * c.ssd_conv * c.ssd_xbc
    scan = H * (2 * C * P + 4 * N * P) + c.ssd_groups * 2 * C * N
    return float(projections + convolution + scan)


def conv_forward_flops_per_token(c) -> float:
    """One ``conv`` layer's mixer, a token: the input projection to B | C | x~
    (D -> 3 D), the convolution's taps over the D channels and the output
    projection (D -> D). The two gates are elementwise: not counted."""
    D = c.n_embd
    return float(2 * D * 3 * D + 2 * c.conv_taps * D + 2 * D * D)


def kda_forward_flops_per_token(c) -> float:
    """One ``kda`` layer's mixer, a token: the projections (q, k, v; the
    decay's and the gate's low-rank maps of rank kda_head_dim; beta; the
    output), the three convolutions' taps, and the recurrence counted as the
    chunkwise form's work at the config's chunk C with d = kda_head_dim, a
    head: five products of 2 C d (K K^T, Q K^T, the two applications of the
    inverse, the intra-chunk output), three of 2 d^2 through the state, and
    2 C^2 / 3 for the triangular inverse. What a kernel multiplies beyond
    that (masked halves, its own way to the inverse) is its choice."""
    D, H, d, C = c.n_embd, c.kda_heads, c.kda_head_dim, c.kda_chunk
    projections = 2 * D * 3 * H * d + 2 * (2 * D * d + 2 * d * H * d) + 2 * D * H + 2 * H * d * D
    convolutions = 2 * c.kda_conv * 3 * H * d
    recurrence = H * (5 * 2 * C * d + 3 * 2 * d * d + 2 * C * C / 3)
    return float(projections + convolutions + recurrence)


def train_flops_per_token(config) -> float:
    """fwd+bwd FLOPs per token (bwd = 2x fwd; remat recompute not counted)."""
    return 3.0 * forward_flops_per_token(config)


def achieved_tflops_per_sec(
    tokens_per_sec_per_chip: float, flops_per_token: float
) -> float:
    """Model TFLOP/s per chip actually delivered at a given throughput."""
    return tokens_per_sec_per_chip * flops_per_token / 1e12


def mfu_pct(
    tokens_per_sec_per_chip: float,
    flops_per_token: float,
    device_kind: str,
) -> Optional[float]:
    """Model-FLOPs utilization in percent, or None off a TPU (CPU hosts)."""
    peak = device_peak_tflops(device_kind)
    if peak is None or flops_per_token <= 0 or tokens_per_sec_per_chip <= 0:
        return None
    return 100.0 * achieved_tflops_per_sec(tokens_per_sec_per_chip, flops_per_token) / peak
