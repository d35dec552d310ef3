"""What the LFM2-8B-A1B cell brings to the benchmark: its configuration file
against the catalog's entry, ``count_params`` against the file's arithmetic, its
FLOP and byte counts against a hand count, the reference's convolution and
attention against the equations written out, the readers of the new scopes,
kernels and counter on hand-made events and on a step recorded on the chip, the
cell's own initial check at a tiny size, the driver's parts by dotted name, and
the cell's dry run."""

import gzip
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench.harness import (
    build, build_lfm2, check_lfm2, flops, flops_lfm2, manifest, reference_lfm2, sconv_scopes,
)
from perfbench.harness.trace_reduce import OPS_LINE, Event, Trace

CELL = "lfm2-8b-a1b.share4-seq16384"
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
CATALOG_FILE = "/opt/skills/guides/model-configs/architectures.jsonl"
REDUCED = ["num_hidden_layers", "num_experts", "vocab_size"]
NEW_METRICS = ["sconv_time_pct", "sconv_proj_time_pct", "sconv_kernel_time_pct",
               "sconv_kernel_roofline", "sconv_global_time_pct", "sconv_global_kernel_roofline",
               "sconv_held_expert_matmul_roofline", "sconv_layers_in_kernel"]
APPENDED_TO = ["recompute_time_pct", "moe_router_time_pct", "moe_dispatch_time_pct",
               "expert_load_max_over_mean", "held_rows_over_expected", "remat_dropped_gb",
               "recompute_attention_time_pct", "recompute_mlp_time_pct"]
PEAKS = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}


def cell_shape():
    _, workload, config = manifest.load_cell(CELL)
    return build_lfm2.lfm2_shape(workload, config), workload, config


def test_config_file_holds_the_published_widths_and_cuts_three_counts():
    shape, workload, config = cell_shape()
    published = dict(
        conv_L_cache=3, conv_bias=False, hidden_size=2048, intermediate_size=7168,
        max_position_embeddings=128000, model_type="lfm2_moe", moe_intermediate_size=1792,
        norm_eps=1e-05, norm_topk_prob=True, num_attention_heads=32, num_dense_layers=2,
        num_experts_per_tok=4, num_key_value_heads=8, rope_theta=1000000,
        routed_scaling_factor=1, use_expert_bias=True)
    assert {k: config[k] for k in published} == published
    types = config["layer_types"]
    assert (len(types), types.count("conv"), types.count("full_attention")) == (24, 18, 6)
    assert types[1:6] == ["conv", "full_attention", "conv", "conv", "conv"]
    if os.path.exists(CATALOG_FILE):  # every key of the catalog's entry, letter for letter
        with open(CATALOG_FILE) as f:
            row = next(r for r in map(json.loads, f) if r["name"] == "LFM2-8B-A1B")
        kept = {k: v for k, v in row["config"].items() if k not in REDUCED}
        assert {k: config[k] for k in kept} == kept and config["source"] == row["source_url"]
        assert [row["config"][k] for k in REDUCED] == [24, 32, 65536]
    assert list(config["reduced"]) == REDUCED
    assert (config["num_hidden_layers"], config["num_experts"], config["vocab_size"]) == (
        5, 8, 16384)
    assert (config["num_hidden_layers_published"], config["num_experts_published"],
            config["vocab_size_published"], config["experts_held_first"],
            config["first_layer_kept"]) == (24, 32, 65536, 0, 1)
    assert "the four chips of one v5e host sharing each layer" in config["deployment"]
    assert "layers 1-5 in the published order" in config["reduced"]["num_hidden_layers"]
    for assumed in ("tie_word_embeddings", "qk_norm", "rotary_convention", "conv_columns",
                    "gate_sum_eps", "router_aux_loss", "expert_bias_update", "conv_init"):
        assert assumed in ", ".join(config["assumed"]), assumed
    every = ", ".join(config["assumed"])
    for key in config:  # a key that is no config key of the catalog's is explained
        if key not in published and key not in ("name", "source", "builder", "deployment",
                                                "reduced", "assumed", "layer_types", *REDUCED):
            assert key in every, key
    entry = [c for c in manifest.load_manifest()["configs"] if c["name"] == "lfm2-8b-a1b"][0]
    assert entry["reduced"] == REDUCED and entry["source"] == config["source"]
    assert entry["file"] == "perfbench/configs/lfm2-8b-a1b.json"
    assert (workload["depth"], workload["seq_len"], workload["micro_batch_per_chip"],
            workload["grad_accum"], workload["chips"], workload["sync_every"],
            workload["warmup_steps"], workload["strategy"]) == (5, 16384, 2, 1, 1, 5, 5, "zero2")
    assert shape["kinds"] == ("conv", "global", "conv", "conv", "conv")
    assert (shape["dense_layers"], shape["moe_layers"], shape["taps"]) == (1, 4, 3)
    assert (shape["experts"], shape["held"], shape["experts_per_token"]) == (32, (0, 8), 4)
    assert (shape["heads"], shape["kv_heads"], shape["head_dim"]) == (32, 8, 64)


def test_the_builder_makes_the_program_s_config_and_count_params_is_the_arithmetic():
    _, workload, config = cell_shape()
    c = build_lfm2.lfm2_config(workload, config)
    assert (c.n_embd, c.n_head, c.kv_heads, c.head_dim, c.mlp_dim, c.n_layer) == (
        2048, 32, 8, 64, 1792, 5)
    assert (c.first_k_dense, c.dense_mlp_hidden, c.conv_taps, c.qk_norm) == (1, 7168, 3, "head")
    assert c.layer_groups == (("conv_dense_blocks", (0,)), ("blocks", (1,)),
                              ("conv_blocks", (2, 3, 4)))
    assert c.experts_held == (0, 8) and not c.trains_routing and c.router_score == "sigmoid"
    assert c.tie_embeddings and c.n_shared_experts == 0 and c.routed_scaling_factor == 1.0
    shapes = jax.eval_shape(lambda: __import__(
        "distributed_llm_training_benchmark_framework_tpu.models.tinygpt", fromlist=["x"]
    ).init_params(c, jax.random.key(0)))
    parameters = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes))
    assert parameters == config["parameter_count"] == 507_820_288
    assert round(parameters * 16 / 1e9, 2) == 8.13
    assert "507,820,288" in config["parameter_arithmetic"]


def test_flops_and_bytes_against_a_hand_count():
    shape, _, _ = cell_shape()
    S, D = 16384, 2048
    conv = 2 * D * 6144 + 2 * 3 * D + 2 * D * D
    attention = 2 * D * (32 + 16) * 64 + 2 * D * D + 4 * (S + 1) / 2 * 32 * 64
    dense, routed = 6 * D * 7168, 2 * D * 32 + 1.0 * 6 * D * 1792
    forward = 4 * conv + attention + dense + 4 * routed + 2 * D * 16384
    assert flops_lfm2.forward_flops_per_token(shape) == forward
    assert flops_lfm2.train_flops_per_token(shape) == 3 * forward
    assert round(3 * forward * 32768 / 1e12, 1) == 45.8 and round(3 * forward / 1e9, 2) == 1.40
    # the issue's 46.9 TF counts the kernels' backward as the fused pass needs it (10 / 4, not 2)
    fused = 3 * forward + 0.5 * 4 * (S + 1) / 2 * 32 * 64
    assert round(fused * 32768 / 1e12, 1) == 46.9 and round(fused / 1e9, 2) == 1.43
    # the gated convolution's kernels: two sequences a step, four layers
    ops, moved = flops_lfm2.sconv_kernel_cost(shape, 2)
    assert moved == 8 * S * D * 2 * (4 + 7) and ops == 8 * S * D * (7 + 21)
    ops2, moved2 = flops_lfm2.sconv_kernel_cost(shape, 2, forwards=2)
    assert moved2 == 8 * S * D * 2 * (8 + 7) and ops2 == 8 * S * D * (14 + 21)
    assert flops.roofline_seconds(ops2, moved2, PEAKS)[1] == "memory"
    assert round(1e3 * flops.roofline_seconds(ops2, moved2, PEAKS)[0], 2) == 9.83  # ms a step
    ops, moved = flops_lfm2.global_kernel_cost(shape, 2)
    assert ops == 2 * 32 * 14 * (S * (S + 1) / 2) * 64
    assert moved == 2 * ((2 * 32 + 2 * 8) * S * 64 * 2 + 32 * S * 4
                         + (4 * 32 + 4 * 8) * S * 64 * 2 + 2 * 32 * S * 4)
    ops, moved = flops_lfm2.held_expert_matmul_cost(shape, 4 * 32768.0, 4)
    assert ops == 3 * 4 * 32768 * 6 * D * 1792
    assert moved == 2 * 3 * (4 * 32768 * (D + 2 * 1792 + 1792 + D) + 4 * 8 * 3 * D * 1792)


def test_the_references_convolution_and_attention_are_the_equations_written_out():
    rng = np.random.default_rng(0)
    v, taps = rng.normal(size=(10, 4)), rng.normal(size=(3, 4))
    want = np.zeros_like(v)
    for t in range(10):
        for i in range(3):
            if t - 2 + i >= 0:
                want[t] += taps[i] * v[t - 2 + i]
    got = reference_lfm2.short_conv({}, jnp.asarray(v, jnp.float32), jnp.asarray(taps, jnp.float32))
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5, atol=1e-6)
    late = reference_lfm2.short_conv({"tap_shift": 1}, jnp.asarray(v, jnp.float32),
                                     jnp.asarray(taps, jnp.float32))
    np.testing.assert_allclose(np.asarray(late)[:-1], want[1:], rtol=1e-5, atol=1e-6)
    # attention: 4 heads of 8 over 2 KV heads, QK-norm per head before rotary, causal
    m = {"heads": 4, "kv_heads": 2, "head_dim": 8, "norm_eps": 1e-5, "rope_theta": 1e6}
    D, S = 32, 12
    w = {"ln1_scale": rng.normal(size=D) * 0.1 + 1, "wq": rng.normal(size=(D, 32)) * 0.2,
         "wkv": rng.normal(size=(D, 2, 16)) * 0.2, "q_norm": rng.normal(size=8) * 0.1 + 1,
         "k_norm": rng.normal(size=8) * 0.1 + 1, "wo": rng.normal(size=(32, D)) * 0.2}
    x = rng.normal(size=(S, D))
    with jax.default_matmul_precision("highest"):
        got = np.asarray(reference_lfm2.attention_sublayer(
            m, jnp.asarray(x, jnp.float32), jax.tree.map(lambda t: jnp.asarray(t, jnp.float32), w)))
    rms = lambda t, s: t / np.sqrt((t * t).mean(-1, keepdims=True) + 1e-5) * s
    h = rms(x, w["ln1_scale"])
    q = rms((h @ w["wq"]).reshape(S, 4, 8), w["q_norm"])
    k = rms((h @ w["wkv"][:, 0]).reshape(S, 2, 8), w["k_norm"])
    val = (h @ w["wkv"][:, 1]).reshape(S, 2, 8)
    angle = np.arange(S)[:, None] * 1e6 ** (-np.arange(0, 8, 2) / 8)[None, :]
    def turn(t):
        a, b = t[..., :4], t[..., 4:]
        c, s = np.cos(angle)[:, None, :], np.sin(angle)[:, None, :]
        return np.concatenate([a * c - b * s, b * c + a * s], -1)
    q, k = turn(q), turn(k)
    out = np.zeros((S, 4, 8))
    for head in range(4):
        scores = q[:, head] @ k[:, head // 2].T / np.sqrt(8)
        scores = np.where(np.tril(np.ones((S, S), bool)), scores, -np.inf)
        e = np.exp(scores - scores.max(-1, keepdims=True))
        out[:, head] = e / e.sum(-1, keepdims=True) @ val[:, head // 2]
    np.testing.assert_allclose(got, x + out.reshape(S, 32) @ w["wo"], rtol=2e-4, atol=2e-5)


def test_the_references_gates_divide_by_the_sum_plus_a_millionth_and_the_bias_moves_the_choice():
    m = {"experts_per_token": 2, "experts": 4, "norm_topk_prob": True, "routed_scaling": 1.0}
    scores = jnp.asarray([[0.6, 0.5, 0.4, 0.1]])
    gates, _ = reference_lfm2._gate_weights(m, scores, jnp.zeros(4))
    np.testing.assert_allclose(np.asarray(gates[0]), [0.6 / 1.100001, 0.5 / 1.100001, 0, 0], rtol=1e-6)
    gates, _ = reference_lfm2._gate_weights(m, scores, jnp.asarray([0.0, 0.0, 0.0, 1.0]))
    np.testing.assert_allclose(np.asarray(gates[0]), [0.6 / 0.700001, 0, 0, 0.1 / 0.700001], rtol=1e-6)


HLO_TEXT = """HloModule jit_train_step
ENTRY %main {
  %fusion.1 = f32[8,128]{1,0} fusion(%p0), kind=kLoop, calls=%f1, metadata={op_name="jit(train_step)/jvp(attention)/conv/sconv_in/dot_general"}
  %sconv_fwd.2 = f32[8,128]{1,0} custom-call(%fusion.1), custom_call_target="tpu_custom_call", metadata={op_name="jit(train_step)/jvp(attention)/conv/sconv_core/pallas_call"}
  %fusion.3 = f32[8,128]{1,0} fusion(%sconv_fwd.2), kind=kLoop, calls=%f3, metadata={op_name="jit(train_step)/jvp(attention)/conv/sconv_out/dot_general"}
  %flash_fwd.4 = f32[8,128]{1,0} custom-call(%fusion.3), custom_call_target="tpu_custom_call", metadata={op_name="jit(train_step)/jvp(attention)/global/pallas_call"}
  %flash_bwd_fused.5 = f32[8,128]{1,0} custom-call(%flash_fwd.4), custom_call_target="tpu_custom_call", metadata={op_name="jit(train_step)/transpose(jvp(attention))/global/pallas_call"}
  %sconv_fwd.6 = f32[8,128]{1,0} custom-call(%flash_bwd_fused.5), custom_call_target="tpu_custom_call", metadata={op_name="jit(train_step)/rematted_computation/attention/conv/sconv_core/pallas_call"}
  %sconv_bwd.7 = f32[8,128]{1,0} custom-call(%sconv_fwd.6), custom_call_target="tpu_custom_call", metadata={op_name="jit(train_step)/transpose(jvp(attention))/conv/sconv_core/pallas_call"}
  %fusion.8 = f32[8,128]{1,0} fusion(%sconv_bwd.7), kind=kLoop, calls=%f8, metadata={op_name="jit(train_step)/jvp(attention)/global/mul"}
  %gmm.9 = f32[8,128]{1,0} custom-call(%fusion.8), custom_call_target="tpu_custom_call", metadata={op_name="jit(train_step)/jvp(mlp)/experts/jit(gmm)/pallas_call"}
  ROOT %fusion.10 = f32[8,128]{1,0} fusion(%gmm.9), kind=kLoop, calls=%f10, metadata={op_name="jit(train_step)/optimizer/mul"}
}
"""
TARGET = 'custom_call_target="tpu_custom_call"'
DURATIONS = [("fusion.1", 2.0), ("sconv_fwd.2", 0.5), ("fusion.3", 1.0), ("flash_fwd.4", 2.0),
             ("flash_bwd_fused.5", 4.0), ("sconv_fwd.6", 0.5), ("sconv_bwd.7", 1.0),
             ("fusion.8", 1.0), ("gmm.9", 2.0), ("fusion.10", 6.0)]  # busy 20.0
STATS = {"layers": 4, "taps": 3, "layers_in_kernel": 4}


def hand_trace(durations):
    at, events = 0.0, []
    for name, seconds in durations:
        kind = f"custom-call(...), {TARGET}" if "fusion" not in name else "fusion(...)"
        events.append(Event(f"%{name} = f32[8,128]{{1,0}} {kind}", at, at + seconds))
        at += seconds
    return Trace({"/device:TPU:0": {"XLA Ops": events}})


def test_shares_of_the_new_scopes_and_the_counter():
    trace = hand_trace(DURATIONS)
    run = {"hlo_text": HLO_TEXT, "peaks": None, "sconv_stats": STATS}
    read = lambda name: manifest.metric_reader(name)(trace, run)
    assert read("sconv_time_pct") == pytest.approx(100 * 5.0 / 20)
    assert read("sconv_proj_time_pct") == pytest.approx(100 * 3.0 / 20)
    assert read("sconv_kernel_time_pct") == pytest.approx(100 * 2.0 / 20)
    assert read("sconv_global_time_pct") == pytest.approx(100 * 7.0 / 20)
    assert read("sconv_layers_in_kernel") == 4
    reduced = sconv_scopes.found(trace, run)
    assert reduced["experts"] == 2.0 and reduced["kernels"]["sconv_fwd"] == 1.0
    assert reduced["calls"] == {"sconv_fwd": 2, "sconv_bwd": 1, "flash_fwd": 1,
                                "flash_bwd_fused": 1}
    for name in ("sconv_kernel_roofline", "sconv_global_kernel_roofline",
                 "sconv_held_expert_matmul_roofline"):
        assert read(name) is None  # no peaks off the chip


def test_a_program_without_the_scopes_or_the_counters_gives_the_readers_nothing():
    """Another model, or the parent of the PR that brought them: nothing, and
    no exception."""
    other = HLO_TEXT.replace("/conv/", "/kda/")
    trace = hand_trace(DURATIONS)
    for name in NEW_METRICS:
        read = manifest.metric_reader(name)
        assert read(trace, {"hlo_text": other, "peaks": PEAKS}) is None, name
        assert read(Trace({}), {"hlo_text": other, "peaks": PEAKS}) is None, name
        assert read(trace, {}) is None, name


def test_the_roofline_readers_divide_the_least_time_by_their_own_calls():
    shape, workload, _ = cell_shape()
    trace = hand_trace([("fusion.1", 0.01), ("sconv_fwd.2", 0.01), ("fusion.3", 0.01),
                        ("flash_fwd.4", 0.1), ("flash_bwd_fused.5", 0.25), ("sconv_fwd.6", 0.01),
                        ("sconv_bwd.7", 0.04), ("fusion.8", 0.01), ("gmm.9", 0.3),
                        ("fusion.10", 0.01)])
    run = {"hlo_text": HLO_TEXT, "workload": workload, "shape": shape, "traced_steps": 5,
           "peaks": PEAKS, "held_rows_traced": 5 * 4 * 32768.0}
    least = lambda cost: flops.roofline_seconds(*cost, PEAKS)[0]
    # two forward calls a backward call in this trace: the second forward is in the least time
    assert manifest.metric_reader("sconv_kernel_roofline")(trace, run) == pytest.approx(
        100 * least(flops_lfm2.sconv_kernel_cost(shape, 10, forwards=2)) / 0.06)
    assert manifest.metric_reader("sconv_global_kernel_roofline")(trace, run) == pytest.approx(
        100 * least(flops_lfm2.global_kernel_cost(shape, 10)) / 0.35)
    held = least(flops_lfm2.held_expert_matmul_cost(shape, 5 * 4 * 32768.0, 5 * 4))
    assert manifest.metric_reader("sconv_held_expert_matmul_roofline")(trace, run) == pytest.approx(
        100 * held / 0.3)
    for name in ("sconv_kernel_roofline", "sconv_global_kernel_roofline",
                 "sconv_held_expert_matmul_roofline"):
        assert 0 < manifest.metric_reader(name)(trace, run) < 100


def test_the_accepted_readers_given_this_cell_read_its_trace():
    _, workload, _ = cell_shape()
    text = HLO_TEXT.replace("optimizer/mul", "jvp(mlp)/dispatch/gather")
    trace = hand_trace(DURATIONS)
    run = {"hlo_text": text, "expert_load_max_over_mean": 1.3, "held_rows_over_expected": 1.01,
           "peaks": PEAKS, "workload": workload}
    read = lambda name: manifest.metric_reader(name)(trace, run)
    assert read("moe_dispatch_time_pct") == pytest.approx(30.0)
    assert read("recompute_time_pct") == pytest.approx(2.5)
    assert read("recompute_attention_time_pct") == pytest.approx(2.5)
    assert read("expert_load_max_over_mean") == 1.3
    assert read("held_rows_over_expected") == 1.01


@pytest.fixture(scope="module")
def recorded():
    """A step of this cell recorded on the v5e (``tools/record_kernel_trace.py``, PR 54)."""
    with gzip.open(os.path.join(DATA, "trace_lfm2.json.gz"), "rt") as f:
        record = json.load(f)
    trace = Trace({record["plane"]: {OPS_LINE: [Event(*row) for row in record["ops"]]}})
    return trace, record


def test_the_readers_on_a_step_recorded_on_the_chip(recorded):
    """Two sequences a step through four conv layers and one attention layer
    under ``full_keep_kernels``: a conv layer's forward call runs twice (remat
    keeps the operand's rows, not the gated result) and its backward once; the
    flash pair once a sequence's layer."""
    trace, record = recorded
    shape, workload, _ = cell_shape()
    run = {"hlo_text": record["hlo_text"], "workload": workload, "shape": shape,
           "traced_steps": 1, "peaks": PEAKS, "sconv_stats": STATS}
    reduced = sconv_scopes.found(trace, run)
    assert reduced["busy"] == pytest.approx(record["expected"]["busy_s"], rel=1e-9)
    assert dict(reduced["calls"]) == {
        k: v for k, v in record["expected"]["mosaic_calls"].items() if k in reduced["calls"]}
    assert reduced["calls"]["sconv_bwd"] == 4 and reduced["calls"]["sconv_fwd"] == 8
    assert reduced["calls"]["flash_fwd"] == 1 and reduced["calls"]["flash_bwd_fused"] == 1
    read = lambda name: manifest.metric_reader(name)(trace, run)
    assert 15 < read("sconv_time_pct") < 45 and 10 < read("sconv_global_time_pct") < 40
    assert read("sconv_proj_time_pct") + read("sconv_kernel_time_pct") <= read("sconv_time_pct")
    assert 1 < read("sconv_kernel_time_pct") < 15
    for name in ("sconv_kernel_roofline", "sconv_global_kernel_roofline"):
        assert 5 < read(name) < 100, name


def test_the_driver_takes_its_parts_by_dotted_name():
    _, workload, _ = cell_shape()
    assert workload["driver"] == "perfbench.harness.laguna_loop:run"  # D10: no ninth copy
    parts = {name: manifest.resolve(dotted) for name, dotted in workload["parts"].items()}
    assert parts["shape"] is build_lfm2.lfm2_shape and parts["tiny"] is build_lfm2.tiny_lfm2
    assert parts["check"] is check_lfm2.check_initial
    assert parts["flops"] is flops_lfm2.train_flops_per_token
    assert parts["counters"] is check_lfm2.program_counters and "state" not in parts


@pytest.fixture(scope="module")
def tiny_state():
    from perfbench.harness import correct

    _, workload, config = manifest.load_cell(CELL)
    workload, config = build_lfm2.tiny_lfm2(*build.tiny(workload, config))
    shape = build_lfm2.lfm2_shape(workload, config)
    state, _, tokens = build.build_state(workload, config, jax.devices()[:1], 7)
    return state, shape, correct.first_micro_batch(state, tokens, workload)


def test_initial_check_passes_the_program(tiny_state):
    ok, numbers = check_lfm2.check_initial(*tiny_state)
    assert ok and numbers["held_overflow"] == 0.0
    for name in check_lfm2.TOLERANCE:
        assert numbers[f"{name}_err"] <= check_lfm2.TOLERANCE[name], name
    assert {f"held_rows_over_expected.layer{i}" for i in (1, 2, 3, 4)} <= set(numbers)
    assert {"conv_out_err.layer0", "conv_out_err.layer4", "global_out_err.layer1"} <= set(numbers)


@pytest.mark.parametrize("change, seen_by", [
    ({"gate_b": False}, "conv_out"),
    ({"gate_c": False}, "conv_out"),
    ({"taps_used": 2}, "conv_out"),
    ({"taps_used": 4}, "conv_out"),
    ({"tap_shift": 1}, "conv_first"),
    ({"qk_norm": None}, "global_out"),
    ({"qk_norm": "after"}, "global_grad"),
    ({"rotary": False}, "global_out"),
    ({"norm_topk_prob": False}, "moe_out"),
    ({"held": (2, 3)}, "held_rows"),
], ids=["no-b-gate", "no-c-gate", "two-taps", "four-taps", "taps-late", "no-qk-norm",
        "norm-after-rotary", "no-rotary", "not-renormalised", "one-expert-fewer"])
def test_initial_check_refuses_a_wrong_reference(tiny_state, change, seen_by):
    state, shape, batch = tiny_state
    numbers = check_lfm2.check_initial_numbers(state, {**shape, **change}, batch)
    assert seen_by in check_lfm2.refused_by(numbers), numbers


def test_benchmark_entries_name_the_cell_and_its_metrics_in_this_order():
    benchmark = manifest.load_manifest()
    entry = [w for w in benchmark["workloads"] if w["name"] == CELL][0]
    assert (entry["config"], entry["traffic"], entry["chips"]) == (
        "lfm2-8b-a1b", "share4-seq16384", 1)
    assert len(entry["why"]) <= 200 and "\n" not in entry["why"]
    names = [m["name"] for m in benchmark["per_layer"]]
    mine = [m["name"] for m in benchmark["per_layer"] if m.get("workloads") == [CELL]]
    assert mine == NEW_METRICS and names[-len(NEW_METRICS):] == NEW_METRICS
    listed = [m["name"] for m in benchmark["per_layer"]
              if CELL in m.get("workloads", []) and m["name"] not in NEW_METRICS]
    assert listed == APPENDED_TO
    assert all(m["workloads"][-1] == CELL for m in benchmark["per_layer"] if m["name"] in APPENDED_TO)
    for name in NEW_METRICS:
        module = __import__(f"perfbench.metrics.{name}", fromlist=["read"])
        declared = [m for m in benchmark["per_layer"] if m["name"] == name][0]
        assert (module.LAYER, module.UNIT, module.MOVES) == (
            declared["layer"], declared["unit"], declared["moves"])
    assert benchmark["configs"][-1]["name"] == "lfm2-8b-a1b"
    assert benchmark["workloads"][-1]["name"] == CELL
    assert sum(w["chips"] == 4 for w in benchmark["workloads"]) == 1


def test_dry_run_of_the_cell_on_the_cpu():
    run = subprocess.run(
        [sys.executable, "perfbench/run.py", "--workload", CELL, "--seed", "5400000019",
         "--seconds", "1", "--trace", "1", "--allow-cpu"],
        cwd=manifest.ROOT, capture_output=True, text=True, timeout=900,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert run.returncode == 0, run.stderr[-2000:]
    last = json.loads(run.stdout.strip().splitlines()[-1])
    assert last["device"]["platform"] == "cpu" and last["failed"] == 0 and last["correct"] is True
    assert last["metrics"] == {}  # a dry run reports no metric
    assert "initial check ok=True" in run.stdout and "initial check, reading / limit:" in run.stdout
    for number in ("'held_overflow': 0.0", "'conv_out_err'", "'conv_first_err'", "'conv_grad_err'",
                   "'conv_first_grad_err'", "'global_out_err'", "'global_grad_err'",
                   "'dense_out_err'", "'dense_grad_err'", "'moe_out_err'", "'expert_grad_err'",
                   "'held_rows_err'", "'per_position_err'", "'loss_err'"):
        assert number in run.stdout, number
    assert "layers ('conv', 'global', 'conv', 'conv', 'conv')" in run.stdout
    assert "('conv_dense_blocks', 1), ('blocks', 1), ('conv_blocks', 3)" in run.stdout
    assert "held assignments that did not fit: 0" in run.stdout
    assert "perfbench: sconv_stats:" in run.stdout and "perfbench: attn_mask_stats:" in run.stdout
    for name in ("held_rows_over_expected", "expert_load_max_over_mean", "sconv_layers_in_kernel"):
        assert f"not reported: {name}" in run.stdout, name
