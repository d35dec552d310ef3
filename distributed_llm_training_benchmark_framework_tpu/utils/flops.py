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

Per token, forward, in the reference's geometry: a layer's projections ``8*D^2``, scores
and values ``4*S*D`` (its mixer's own count: ``models/mixers/``), MLP ``16*D^2`` (routed:
``top_k`` experts and a ``2*D*E`` router), the LM head ``2*D*V`` once. Training is 3x that.
"""

from __future__ import annotations

import collections
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
    """Analytic forward-pass FLOPs per token: one sum over the config's
    layers. A layer's mixer by its kind's own count (the table's module,
    ``models/mixers/``; attention's takes the kind's heads, a window's true
    pairs, the output gate, latent widths and block diffusion's two copies),
    its MLP by ``_mlp_forward_flops_per_token`` (SwiGLU three matrices, GELU
    two; a routed MLP by its active parameters), and the LM head once, tied or
    untied alike (under block diffusion only the noisy copy goes through it).
    Under ``block_halves`` a block is one of the two. Elementwise work (RoPE,
    norms) is not counted: the PaLM/Chinchilla convention."""
    from ..models import mixers

    c = config
    kinds = c.layer_types or (None,) * c.n_layer
    layers = collections.Counter(kind for kind in kinds if c.halves(kind)[0])  # those with a mixer
    mixing = sum(n * mixers.of(kind).forward_flops_per_token(c, kind) for kind, n in layers.items())
    copies = 2 if c.block_diffusion is not None else 1  # the MLPs run over the stream too
    return float(mixing + copies * _mlp_forward_flops_per_token(c) + 2 * c.n_embd * c.vocab_size)


def _mlp_forward_flops_per_token(c) -> float:
    """The MLPs of the whole depth a token: the leading dense layers', and the
    routed layers' by their ACTIVE parameters on this chip: the router over
    all experts, the shared experts, and the expert_top_k * held / n_experts
    routed rows a token the held experts see at uniform routing."""
    D, F = c.n_embd, c.mlp_dim
    matrices = 6 if c.mlp_act == "swiglu" else 4  # gated: three matrices; gelu, relu2: two
    if c.n_experts > 0:
        routed_rows = c.expert_top_k * c.n_experts_held / c.n_experts
        mlp = 2 * D * c.n_experts + matrices * D * (F * routed_rows + c.shared_dim)
    else:
        mlp = matrices * D * F
    dense = 6 * D * (c.dense_mlp_hidden or 0)
    return c.first_k_dense * dense + (c.n_mlp_layers - c.first_k_dense) * mlp


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
