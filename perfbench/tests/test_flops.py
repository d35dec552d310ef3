"""The FLOPs and attention ops/bytes arithmetic against hand-worked numbers."""

import pytest

from perfbench.harness import build, flops, manifest, peaks


def shape(cell):
    _, workload, config = manifest.load_cell(cell)
    return build.model_shape(workload, config)


def test_tinygpt_a_forward_flops_by_hand():
    # per layer: qkv 2*1024*3072 + out 2*1024*1024 + mlp 4*1024*4096 = 25,165,824
    # attention 4*2048*1024 = 8,388,608; 16 layers; head 2*1024*32000 = 65,536,000
    assert flops.forward_flops_per_token(shape("tinygpt-a.seq2048")) == 16 * (25165824 + 8388608) + 65536000
    # PR 21's seq-8192 row: 3.0 GFLOP a token, attention 53% of the model
    m = shape("tinygpt-a.seq8192")
    assert flops.train_flops_per_token(m) == pytest.approx(3.0e9, rel=0.02)
    attention = 16 * 4 * 8192 * 1024
    assert attention / flops.forward_flops_per_token(m) == pytest.approx(0.53, abs=0.01)


def test_mistral_7b_forward_flops_by_hand():
    # per layer: q 2*4096*4096, kv 2*4096*2*1024, out 2*4096*4096, swiglu 6*4096*14336,
    # causal attention 4*(4096/2)*4096; head 2*4096*32768
    layer = 33554432 + 16777216 + 33554432 + 352321536 + 33554432
    assert flops.forward_flops_per_token(shape("mistral-7b.d2")) == 2 * layer + 268435456
    assert flops.forward_flops_per_token(shape("mistral-7b.fsdp4")) == 8 * layer + 268435456
    # ISSUE 22: 3.62 GFLOP a token at depth 2, 12.1 at depth 8
    assert flops.train_flops_per_token(shape("mistral-7b.d2")) == pytest.approx(3.62e9, rel=0.01)
    assert flops.train_flops_per_token(shape("mistral-7b.fsdp4")) == pytest.approx(12.1e9, rel=0.01)


def test_parameter_counts_by_hand():
    # what the cells' files and PERF.md state: 218.1M a layer, 268.4M embedding + head
    layer = 4096 * 4096 * 2 + 4096 * 2 * 1024 + 3 * 4096 * 14336 + 2 * 4096
    assert round(layer / 1e6, 1) == 218.1
    assert round(2 * 32768 * 4096 / 1e6, 1) == 268.4
    assert round((2 * layer + 2 * 32768 * 4096 + 4096) / 1e6, 1) == 704.7


def test_attention_kernel_cost_by_hand():
    m = shape("tinygpt-a.seq8192")
    # one sequence, 16 heads, 16 layers: 256 calls; forward 4*S^2*Dh, backward 10*S^2*Dh
    f, b = flops.attention_pass_cost(m, 1, ("fwd",))
    assert f == 256 * 4 * 8192 * 8192 * 64
    assert b == 256 * (4 * 8192 * 64 * 2 + 8192 * 4)
    f2, b2 = flops.attention_pass_cost(m, 1, ("fwd", "bwd"))
    assert f2 == 256 * 14 * 8192 * 8192 * 64
    assert b2 == b + 256 * (8 * 8192 * 64 * 2 + 8192 * 4)
    # a causal mask halves the operations and none of the bytes
    causal = shape("mistral-7b.d2")
    f3, _ = flops.attention_pass_cost(causal, 2, ("fwd",))
    assert f3 == 2 * 32 * 2 * 4 * 4096 * 4096 * 128 / 2


def test_roofline_says_which_bound():
    v5e = peaks.peaks("TPU v5 lite")
    assert flops.roofline_seconds(197e12, 1.0, v5e) == (1.0, "compute")
    assert flops.roofline_seconds(1.0, 819e9, v5e) == (1.0, "memory")
    seconds, bound = flops.roofline_seconds(
        *flops.attention_pass_cost(shape("tinygpt-a.seq8192"), 1, ("fwd", "bwd")), v5e
    )
    assert bound == "compute" and seconds == pytest.approx(0.0781, rel=0.01)


def test_unknown_device_kind_is_an_error():
    assert peaks.peaks("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(ValueError, match="no published peaks"):
        peaks.peaks("TPU v9")
