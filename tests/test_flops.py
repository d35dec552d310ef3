"""Model-FLOPs / MFU accounting tests (utils.flops + metrics wiring).

The reference has no FLOPs metric anywhere (its metric surface is
``train_harness.py:399-413``); these pin down our additive accounting so the
published MFU numbers are backed by a checked formula.
"""

import pytest

from distributed_llm_training_benchmark_framework_tpu.models import get_model_config
from distributed_llm_training_benchmark_framework_tpu.utils import flops as flops_mod
from distributed_llm_training_benchmark_framework_tpu.utils import metrics as metrics_mod


def test_forward_flops_matches_hand_count_tier_s():
    # Tier S: V=512, D=128, H=4, L=2; seq 64.
    cfg = get_model_config("S", 64)
    D, L, V, S = 128, 2, 512, 64
    per_layer = 6 * D * D + 2 * D * D + 16 * D * D + 4 * S * D
    expected = L * per_layer + 2 * D * V
    assert flops_mod.forward_flops_per_token(cfg) == float(expected)
    assert flops_mod.train_flops_per_token(cfg) == 3.0 * expected


def test_tier_a_flops_magnitude():
    # Tier A at seq 2048 ≈ 1.8 GFLOP/token for fwd+bwd — the number the
    # round-1 verdict derived by hand; the formula must land in that range.
    cfg = get_model_config("A", 2048)
    per_tok = flops_mod.train_flops_per_token(cfg)
    assert 1.5e9 < per_tok < 2.2e9


def test_moe_flops_counts_topk_experts():
    dense = get_model_config("S", 64)
    moe = get_model_config("S", 64, n_experts=4, expert_top_k=2)
    # top_k=2 doubles the MLP term and adds a router; everything else equal.
    D, L = 128, 2
    delta = flops_mod.forward_flops_per_token(moe) - flops_mod.forward_flops_per_token(dense)
    expected_delta = L * (2 * 2 * (8 * D * D) + 2 * D * 4 - 16 * D * D)
    assert delta == float(expected_delta)


def test_moe_flops_follow_the_experts_activation():
    """SwiGLU experts are three matmuls each (gate, up, down), not two."""
    from distributed_llm_training_benchmark_framework_tpu.models.tinygpt import TinyGPTConfig

    knobs = dict(vocab_size=512, n_embd=128, n_head=4, n_layer=2, block_size=64,
                 mlp_act="swiglu", mlp_hidden=96, bias=False)
    dense = TinyGPTConfig(**knobs)
    moe = TinyGPTConfig(**knobs, n_experts=8, expert_top_k=2, capacity_factor=None)
    D, F, L = 128, 96, 2
    delta = flops_mod.forward_flops_per_token(moe) - flops_mod.forward_flops_per_token(dense)
    assert delta == float(L * ((2 - 1) * 6 * D * F + 2 * D * 8))


def test_device_peak_table():
    assert flops_mod.device_peak_tflops("TPU v5 lite") == 197.0
    assert flops_mod.device_peak_tflops("TPU v4") == 275.0
    assert flops_mod.device_peak_tflops("TPU v6 lite") == 918.0
    assert flops_mod.device_peak_tflops("cpu") is None
    assert flops_mod.device_peak_tflops("Interpreter") is None


@pytest.mark.parametrize(
    "kind,peak", [("TPU v5 lite", 197.0), ("TPU v5", 459.0)],
)
def test_tpu_kinds_match_exactly(kind, peak):
    """'TPU v5' is the v5p's own name, not a catch-all row for every v5:
    the v5e ('TPU v5 lite') no longer inherits 459 by substring, nor the
    other way round."""
    assert flops_mod.device_peak_tflops(kind) == peak


def test_unknown_tpu_kind_raises_in_every_reader():
    """One table (utils.platform.CHIP_SPECS): a TPU it does not hold is an
    error — never a neighbouring generation's peak, capacity or price."""
    from distributed_llm_training_benchmark_framework_tpu.utils import (
        memory as memory_mod,
        platform as platform_mod,
    )

    for read in (
        flops_mod.device_peak_tflops, flops_mod.device_usd_per_chip_hour,
        memory_mod.device_hbm_bytes, platform_mod.device_peak_hbm_gbps,
        platform_mod.device_peak_flops,
        lambda kind: flops_mod.mfu_pct(1000.0, 1e9, kind),
    ):
        with pytest.raises(ValueError, match="unknown TPU device_kind"):
            read("TPU v9 mega")


def test_mfu_pct_known_and_unknown_device():
    # 23,564 tok/s/chip at 1.83 GFLOP/token on v5e (197 TFLOP/s) ≈ 21.9%.
    got = flops_mod.mfu_pct(23564.0, 1.83e9, "TPU v5 lite")
    assert abs(got - 100.0 * (23564.0 * 1.83e9 / 1e12) / 197.0) < 1e-9
    assert flops_mod.mfu_pct(23564.0, 1.83e9, "cpu") is None


def test_compute_result_carries_flops_fields():
    r = metrics_mod.compute_result(
        strategy="ddp", world_size=1, rank=0, seq_len=2048, tier="A",
        steps=10, per_device_batch=1, grad_accum=4,
        step_times=[0.5], losses=[6.0],
        device_kind="TPU v5 lite", backend="tpu",
        flops_per_token=1.8e9, dropout=0.1, attention_impl="flash",
    )
    d = r.to_dict()
    assert d["flops_per_token"] == 1.8e9
    assert d["dropout"] == 0.1
    # tokens/step = 1*4*2048 = 8192; tps = 16384; tflops = 16384*1.8e9/1e12
    assert abs(d["model_tflops_per_sec_per_chip"] - 16384 * 1.8e9 / 1e12) < 1e-6
    assert d["mfu_pct"] > 0

    cpu = metrics_mod.compute_result(
        strategy="ddp", world_size=1, rank=0, seq_len=2048, tier="A",
        steps=10, per_device_batch=1, grad_accum=4,
        step_times=[0.5], losses=[6.0],
        device_kind="cpu", backend="cpu", flops_per_token=1.8e9,
    )
    assert cpu.mfu_pct == 0.0


def test_tokens_per_dollar():
    import pytest
    from distributed_llm_training_benchmark_framework_tpu.utils import flops

    assert flops.device_usd_per_chip_hour("TPU v5 lite") == 1.20
    assert flops.device_usd_per_chip_hour("cpu") is None
    # 42k tok/s on v5e at $1.20/hr -> 126M tokens/$
    tpd = flops.tokens_per_dollar(42000.0, "TPU v5 lite")
    assert tpd == pytest.approx(42000.0 * 3600 / 1.2)
    assert flops.tokens_per_dollar(42000.0, "cpu") is None
    assert flops.tokens_per_dollar(0.0, "TPU v5 lite") is None
