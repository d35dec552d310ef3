"""What the Laguna-XS.2 cell brings to the benchmark: its configuration file
against the catalog's entry, its FLOP and byte counts against a brute-force
count, its reference's masks, rotation and blocked attention against the whole
matrix, the readers of the new scope, kernels and counters on hand-made
events, the cell's own initial check at a tiny size, the driver's parts by
dotted name, and the cell's dry run."""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench.harness import (
    build, build_laguna, check_laguna, flops, flops_laguna, lg_scopes, manifest, reference_laguna,
)
from perfbench.harness.trace_reduce import Event, Trace

CELL = "laguna-xs.2.share16-seq16384"
CATALOG_FILE = "/opt/skills/guides/model-configs/architectures.jsonl"
REDUCED = ["num_hidden_layers", "num_experts", "vocab_size"]
NEW_METRICS = ["lg_window_time_pct", "lg_global_time_pct", "lg_window_kernel_roofline",
               "lg_global_kernel_roofline", "lg_window_live_fill_pct", "lg_gate_time_pct",
               "lg_prologue_time_pct", "lg_prologue_roofline"]
PEAKS = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}


def cell_shape():
    _, workload, config = manifest.load_cell(CELL)
    return build_laguna.laguna_shape(workload, config), workload, config


def test_config_file_holds_the_published_widths_and_cuts_three_counts():
    shape, workload, config = cell_shape()
    published = dict(
        hidden_size=2048, num_attention_heads=48, num_key_value_heads=8, head_dim=128,
        intermediate_size=8192, moe_intermediate_size=512, shared_expert_intermediate_size=512,
        num_experts_per_tok=8, sliding_window=512, rms_norm_eps=1e-06, tie_word_embeddings=False,
        model_type="laguna", max_position_embeddings=262144, attention_bias=False, gating=True,
        moe_routed_scaling_factor=2.5, moe_apply_router_weight_on_input=False,
        partial_rotary_factor=0.5)
    assert {k: config[k] for k in published} == published
    assert config["rope_parameters"] == {
        "full_attention": {"rope_theta": 500000, "rope_type": "yarn", "factor": 64,
                           "original_max_position_embeddings": 4096, "beta_slow": 1, "beta_fast": 64,
                           "attention_factor": 1.4158883083359672, "partial_rotary_factor": 0.5},
        "sliding_attention": {"rope_type": "default", "rope_theta": 10000, "partial_rotary_factor": 1},
        "original_max_position_embeddings": 4096}
    assert config["layer_types"] == (["full_attention"] + ["sliding_attention"] * 3) * 10
    assert config["num_attention_heads_per_layer"] == [48, 64, 64, 64] * 10
    assert config["mlp_layer_types"] == ["dense"] + ["sparse"] * 39
    if os.path.exists(CATALOG_FILE):  # every key of the catalog's entry, letter for letter
        with open(CATALOG_FILE) as f:
            row = next(r for r in map(json.loads, f) if r["name"] == "Laguna-XS.2")
        kept = {k: v for k, v in row["config"].items() if k not in REDUCED}
        assert {k: config[k] for k in kept} == kept and config["source"] == row["source_url"]
    assert list(config["reduced"]) == REDUCED
    assert (config["num_hidden_layers"], config["num_experts"], config["vocab_size"]) == (5, 16, 12544)
    assert (config["num_hidden_layers_published"], config["num_experts_published"],
            config["vocab_size_published"]) == (40, 256, 100352)
    assert "sixteen chips (a v5e 4 x 4 slice) sharing each layer" in config["deployment"]
    for assumed in ("gating_granularity", "qk_norm", "rotary_lanes", "attention_factor",
                    "window_convention", "router_score", "norm_topk_prob", "n_shared_experts",
                    "router_aux_loss_coef"):
        assert assumed in config["assumed"], assumed
    entry = [c for c in manifest.load_manifest()["configs"] if c["name"] == "laguna-xs.2"][0]
    assert entry["reduced"] == REDUCED and entry["source"] == config["source"]
    assert (workload["depth"], workload["seq_len"], workload["micro_batch_per_chip"],
            workload["grad_accum"], workload["chips"]) == (5, 16384, 1, 1, 1)
    assert shape["kinds"] == ("global", "window", "window", "window", "global")
    assert dict(shape["heads"]) == {"global": 48, "window": 64} and shape["window"] == 512
    assert dict(shape["rotary"]) == {
        "window": (10000.0, 128, None),
        "global": (500000.0, 64, (64.0, 4096, 64.0, 1.0, 1.4158883083359672))}
    assert (shape["experts"], shape["held"], shape["experts_per_token"]) == (256, (0, 16), 8)
    assert (shape["dense_layers"], shape["moe_layers"], shape["gate"]) == (1, 4, "head")


def test_the_builder_makes_the_program_s_config_of_the_cell():
    _, workload, config = cell_shape()
    c = build_laguna.laguna_config(workload, config)
    assert (c.n_embd, c.kv_heads, c.head_dim, c.mlp_dim, c.n_layer) == (2048, 8, 128, 512, 5)
    assert (c.heads("global"), c.heads("window")) == (48, 64) and c.attn_gate
    assert c.layer_types == ("global", "window", "window", "window", "global")
    assert c.sliding_window == 512 and c.first_k_dense == 1 and c.dense_mlp_hidden == 8192
    full = c.rotary("global")
    assert full.rotary_dim == 64 and full.scaling.cos_sin_factor == 1.4158883083359672
    assert c.rotary("window").scaling is None and c.rotary("window").rotary_dim is None
    assert c.rotary("window").theta == 10000.0 and full.theta == 500000.0
    assert c.experts_held == (0, 16) and not c.trains_routing and c.router_score == "sigmoid"
    assert c.routed_scaling_factor == 2.5 and c.n_shared_experts == 1 and c.remat == "none"
    attention = lambda H: 2048 * H * 128 * 2 + 2 * 2048 * 1024 + 2048 * H + 2 * 2048
    routed = 2048 * 256 + 256 + 3 * 2048 * 512 + 16 * 3 * 2048 * 512
    parameters = (attention(48) + 3 * 2048 * 8192 + 3 * (attention(64) + routed)
                  + attention(48) + routed + 2 * 12544 * 2048 + 2048)
    assert round(parameters / 1e6, 1) == 490.3 and round(parameters * 16 / 1e9, 2) == 7.84
    shapes = jax.eval_shape(lambda: __import__(
        "distributed_llm_training_benchmark_framework_tpu.models.tinygpt", fromlist=["x"]
    ).init_params(c, jax.random.key(0)))
    assert sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes)) == parameters


def test_flops_against_a_brute_force_count():
    shape, _, _ = cell_shape()
    small = {**shape, "seq_len": 200, "window": 37}
    i, j = np.arange(200)[:, None], np.arange(200)[None, :]
    assert flops_laguna.true_pairs(small, "window") == int(((j <= i) & (j > i - 37)).sum())
    assert flops_laguna.true_pairs(small, "global") == int((j <= i).sum())
    S, D, Dh = 16384, 2048, 128
    window, whole = flops_laguna.true_pairs(shape, "window"), flops_laguna.true_pairs(shape, "global")
    assert (window, whole) == (8257792, S * (S + 1) // 2) and round(100 * window / whole, 1) == 6.2
    projections = lambda H: 2 * D * H * Dh * 2 + 2 * D * 2 * 8 * Dh + 2 * D * H
    want = (2 * projections(48) + 3 * projections(64) + 6 * D * 8192
            + 4 * (2 * D * 256 + 6 * D * 512 + 0.5 * 6 * D * 512)
            + 4 * Dh * (3 * 64 * window + 2 * 48 * whole) / S + 2 * D * 12544)
    assert flops_laguna.forward_flops_per_token(shape) == pytest.approx(want, rel=1e-12)
    assert round(want / 1e6) == 991 and round(3 * want * S / 1e12, 1) == 48.7
    kernels = 4 * Dh * (3 * 64 * window + 2 * 48 * whole) / S
    assert round(100 * (kernels + 2 * projections(48) + 3 * projections(64)) / want) == 80
    for cost, pairs, layers, H in ((flops_laguna.window_kernel_cost, window, 3, 64),
                                   (flops_laguna.global_kernel_cost, whole, 2, 48)):
        operations, moved = cost(shape, 5)
        assert operations == 5 * layers * H * 14 * pairs * Dh
        assert moved == 5 * layers * H * (12 * S * Dh * 2 + 3 * S * 4)
    assert flops.roofline_seconds(*flops_laguna.global_kernel_cost(shape, 5), PEAKS)[1] == "compute"
    assert flops_laguna.prologue_call_bytes(shape, "global", 1) == 2 * S * (48 + 8) * Dh * 2
    assert flops_laguna.prologue_call_bytes(shape, "window", 2) == 2 * 2 * S * (64 + 8) * Dh * 2


TINY_FILE = build_laguna.tiny_laguna({}, {
    **cell_shape()[2], "hidden_size": 32, "vocab_size": 64})[1]
TINY = {**build_laguna.laguna_shape({"seq_len": 96, "held_rows_factor": 4.0, "depth": 5}, TINY_FILE),
        "window": 20}


def test_the_references_masks_rotation_and_grouping():
    pos = jnp.arange(96)
    i, j = np.arange(96)[:, None], np.arange(96)[None, :]
    np.testing.assert_array_equal(reference_laguna.allowed(TINY, "global", pos, pos), j <= i)
    np.testing.assert_array_equal(
        reference_laguna.allowed(TINY, "window", pos, pos), (j <= i) & (j > i - 20))
    x = jax.random.normal(jax.random.key(1), (96, 3, 16))
    cos, sin, lanes = reference_laguna.rotary_table(TINY, "global", pos)
    assert lanes == 8 and cos.shape == (96, 4)
    turned = reference_laguna._rotate(TINY, x, cos, sin, lanes)
    np.testing.assert_array_equal(turned[..., 8:], x[..., 8:])  # the other half passes
    np.testing.assert_allclose(turned[5, 1, 2], x[5, 1, 2] * cos[5, 2] - x[5, 1, 6] * sin[5, 2], rtol=1e-6)
    np.testing.assert_allclose(turned[5, 1, 6], x[5, 1, 6] * cos[5, 2] + x[5, 1, 2] * sin[5, 2], rtol=1e-6)
    across = reference_laguna._rotate({**TINY, "pairing": "head"}, x, cos, sin, lanes)
    np.testing.assert_allclose(across[5, 1, 2], x[5, 1, 2] * cos[5, 2] - x[5, 1, 10] * sin[5, 2], rtol=1e-6)
    cos, sin, lanes = reference_laguna.rotary_table(TINY, "window", pos)
    assert lanes == 16 and float(jnp.abs(cos[0] - 1).max()) == 0.0  # plain: no factor on cos
    for layer in range(5):  # the tree's stacks by kind
        w = reference_laguna.layer_weights(TINY, {
            "global_dense_blocks": {"i": jnp.array([0])}, "window_blocks": {"i": jnp.array([1, 2, 3])},
            "global_blocks": {"i": jnp.array([4])}}, layer)
        assert int(w["i"]) == layer


@pytest.mark.parametrize("kind, heads", [("window", 8), ("global", 6)])
def test_blocked_attention_matches_the_whole_matrix(kind, heads, monkeypatch):
    keys = jax.random.split(jax.random.key(0), 3)
    q = jax.random.normal(keys[0], (96, heads, 8))
    k, v = (jax.random.normal(key, (96, 2, 8)) for key in keys[1:])
    monkeypatch.setattr(reference_laguna, "QUERY_BLOCK", 32)
    blocked = reference_laguna._attention(TINY, kind, q, k, v)
    rep = heads // 2  # KV head g serves query heads g rep .. (g + 1) rep - 1
    scores = jnp.einsum("qhd,khd->hqk", q, jnp.repeat(k, rep, 1)) * 8 ** -0.5
    mask = reference_laguna.allowed(TINY, kind, jnp.arange(96), jnp.arange(96))
    probs = jax.nn.softmax(jnp.where(mask[None], scores, -jnp.inf), -1)
    whole = jnp.einsum("hqk,khd->qhd", probs, jnp.repeat(v, rep, 1))
    np.testing.assert_allclose(blocked, whole, atol=1e-5)
    shifted = reference_laguna._attention({**TINY, "kv_shift": 1}, kind, q, k, v)
    assert float(jnp.abs(shifted - whole).max()) > 1e-2


STEP = "jit(train_step)"
HLO_TEXT = """HloModule jit_train_step

ENTRY %main.1 (p0: f32[8,128]) -> f32[8,128] {
  %p0 = f32[8,128]{1,0} parameter(0)
  %fusion.1 = f32[8,128]{1,0} fusion(%p0), kind=kLoop, calls=%f1, metadata={op_name="jit(train_step)/jvp(attention)/window/dot_general"}
  %qk_prologue_fwd.2 = f32[8,128]{1,0} custom-call(%fusion.1), custom_call_target="tpu_custom_call", metadata={op_name="jit(train_step)/jvp(attention)/window/qk_prologue/pallas_call"}
  %flash_fwd.3 = f32[8,128]{1,0} custom-call(%qk_prologue_fwd.2), custom_call_target="tpu_custom_call", metadata={op_name="jit(train_step)/jvp(attention)/window/jit(flash_attention)/pallas_call"}
  %flash_bwd_fused.4 = f32[8,128]{1,0} custom-call(%flash_fwd.3), custom_call_target="tpu_custom_call", metadata={op_name="jit(train_step)/transpose(jvp(attention))/window/pallas_call"}
  %fusion.5 = f32[8,128]{1,0} fusion(%flash_bwd_fused.4), kind=kLoop, calls=%f5, metadata={op_name="jit(train_step)/jvp(attention)/window/attn_gate/mul"}
  %qk_prologue_fwd.6 = f32[8,128]{1,0} custom-call(%fusion.5), custom_call_target="tpu_custom_call", metadata={op_name="jit(train_step)/jvp(attention)/global/qk_prologue/pallas_call"}
  %qk_prologue_bwd.7 = f32[8,128]{1,0} custom-call(%qk_prologue_fwd.6), custom_call_target="tpu_custom_call", metadata={op_name="jit(train_step)/transpose(jvp(attention))/global/qk_prologue/pallas_call"}
  %flash_fwd.8 = f32[8,128]{1,0} custom-call(%qk_prologue_bwd.7), custom_call_target="tpu_custom_call", metadata={op_name="jit(train_step)/jvp(attention)/global/jit(flash_attention)/pallas_call"}
  %flash_bwd_fused.9 = f32[8,128]{1,0} custom-call(%flash_fwd.8), custom_call_target="tpu_custom_call", metadata={op_name="jit(train_step)/transpose(jvp(attention))/global/pallas_call"}
  %fusion.10 = f32[8,128]{1,0} fusion(%flash_bwd_fused.9), kind=kLoop, calls=%f10, metadata={op_name="jit(train_step)/transpose(jvp(attention))/global/attn_gate/logistic"}
  %gmm.11 = f32[8,128]{1,0} custom-call(%fusion.10), custom_call_target="tpu_custom_call", metadata={op_name="jit(train_step)/jvp(mlp)/experts/jit(gmm)/pallas_call"}
  ROOT %fusion.12 = f32[8,128]{1,0} fusion(%gmm.11), kind=kLoop, calls=%f12, metadata={op_name="jit(train_step)/optimizer/mul"}
}
"""
TARGET = 'custom_call_target="tpu_custom_call"'
DURATIONS = [("fusion.1", 1.0), ("qk_prologue_fwd.2", 0.5), ("flash_fwd.3", 1.0),
             ("flash_bwd_fused.4", 2.0), ("fusion.5", 0.5), ("qk_prologue_fwd.6", 0.25),
             ("qk_prologue_bwd.7", 0.25), ("flash_fwd.8", 3.0), ("flash_bwd_fused.9", 5.0),
             ("fusion.10", 0.5), ("gmm.11", 2.0), ("fusion.12", 4.0)]  # busy 20.0
STATS = {"window": {"layers": 3, "heads": 64, "fwd_tile": (512, 512), "bwd_tile": (512, 512),
                    "true_pairs": 8257792, "fwd_pairs_multiplied": 13369344,
                    "bwd_pairs_multiplied": 14417920}}


def hand_trace(durations):
    at, events = 0.0, []
    for name, seconds in durations:
        kind = f"custom-call(...), {TARGET}" if "fusion" not in name else "fusion(...)"
        events.append(Event(f"%{name} = f32[8,128]{{1,0}} {kind}", at, at + seconds))
        at += seconds
    return Trace({"/device:TPU:0": {"XLA Ops": events}})


def test_shares_of_the_new_scopes_and_the_counters():
    trace = hand_trace(DURATIONS)
    run = {"hlo_text": HLO_TEXT, "peaks": None, "attn_mask_stats": STATS}
    read = lambda name: manifest.metric_reader(name)(trace, run)
    assert read("lg_window_time_pct") == pytest.approx(100 * 5.0 / 20)
    assert read("lg_global_time_pct") == pytest.approx(100 * 9.0 / 20)
    assert read("lg_gate_time_pct") == pytest.approx(100 * 1.0 / 20)  # forward and backward
    assert read("lg_window_live_fill_pct") == pytest.approx(59.44, abs=0.01)
    reduced = lg_scopes.found(trace, run)
    assert reduced["calls"]["window", "qk_prologue_fwd"] == 1 and reduced["calls"]["global", "flash_fwd"] == 1
    for name in ("lg_window_kernel_roofline", "lg_global_kernel_roofline", "lg_prologue_roofline"):
        assert read(name) is None  # no peaks off the chip
    # the counter of a program whose tiles do not follow the window gives nothing
    old = {"window": {k: v for k, v in STATS["window"].items() if "tile" not in k}}
    assert manifest.metric_reader("lg_window_live_fill_pct")(trace, {"attn_mask_stats": old}) is None


def test_a_program_without_the_scopes_or_the_counters_gives_the_readers_nothing():
    """Another model, or the parent of the PR that brought them: nothing, and
    no exception."""
    other = HLO_TEXT.replace("/attn_gate/", "/")
    trace = hand_trace(DURATIONS)
    for name in NEW_METRICS:
        read = manifest.metric_reader(name)
        assert read(trace, {"hlo_text": other, "peaks": PEAKS}) is None, name
        assert read(Trace({}), {"hlo_text": other, "peaks": PEAKS}) is None, name
        assert read(trace, {}) is None, name


def test_the_roofline_readers_divide_the_least_time_by_each_kinds_own_calls():
    shape, workload, _ = cell_shape()
    trace = hand_trace([("fusion.1", 0.01), ("qk_prologue_fwd.2", 0.002), ("flash_fwd.3", 0.02),
                        ("flash_bwd_fused.4", 0.03), ("qk_prologue_fwd.6", 0.001),
                        ("qk_prologue_bwd.7", 0.001), ("flash_fwd.8", 0.1), ("flash_bwd_fused.9", 0.2),
                        ("fusion.10", 0.01), ("gmm.11", 0.05)])
    run = {"hlo_text": HLO_TEXT, "workload": workload, "shape": shape, "traced_steps": 5,
           "peaks": PEAKS}
    least = lambda cost: flops.roofline_seconds(*cost(shape, 5), PEAKS)[0]
    assert manifest.metric_reader("lg_window_kernel_roofline")(trace, run) == pytest.approx(
        100 * least(flops_laguna.window_kernel_cost) / 0.05)
    assert manifest.metric_reader("lg_global_kernel_roofline")(trace, run) == pytest.approx(
        100 * least(flops_laguna.global_kernel_cost) / 0.3)
    moved = (flops_laguna.prologue_call_bytes(shape, "window", 1)
             + 2 * flops_laguna.prologue_call_bytes(shape, "global", 1))
    assert manifest.metric_reader("lg_prologue_roofline")(trace, run) == pytest.approx(
        100 * moved / 819e9 / 0.004)
    assert manifest.metric_reader("lg_prologue_time_pct")(trace, run) == pytest.approx(
        100 * 0.004 / 0.424)
    # a step whose layers all ran the jnp chain: the two prologue readers say nothing
    chain = HLO_TEXT.replace("qk_prologue/pallas_call", "mul")
    no_pass = hand_trace([(n.replace("qk_prologue_fwd", "fusion").replace("qk_prologue_bwd", "fusion"), s)
                          for n, s in DURATIONS])
    chain = chain.replace("%qk_prologue_fwd.", "%fusion.").replace("%qk_prologue_bwd.", "%fusion.")
    for name in ("lg_prologue_roofline", "lg_prologue_time_pct"):
        assert manifest.metric_reader(name)(no_pass, {**run, "hlo_text": chain}) is None


def test_the_accepted_readers_given_this_cell_read_its_trace():
    _, workload, _ = cell_shape()
    text = HLO_TEXT.replace("optimizer/mul", "jvp(mlp)/dispatch/gather").replace(
        "jvp(attention)/window/dot_general", "rematted_computation/attention/window/dot_general")
    trace = hand_trace(DURATIONS)
    run = {"hlo_text": text, "expert_load_max_over_mean": 1.7, "held_rows_over_expected": 1.02,
           "peaks": PEAKS, "workload": workload}
    read = lambda name: manifest.metric_reader(name)(trace, run)
    assert read("moe_dispatch_time_pct") == pytest.approx(20.0)
    assert read("recompute_time_pct") == pytest.approx(5.0)
    assert read("expert_load_max_over_mean") == 1.7
    assert read("held_rows_over_expected") == 1.02


def test_the_driver_takes_its_parts_by_dotted_name():
    _, workload, _ = cell_shape()
    assert workload["driver"] == "perfbench.harness.laguna_loop:run"
    parts = {name: manifest.resolve(dotted) for name, dotted in workload["parts"].items()}
    assert parts["shape"] is build_laguna.laguna_shape and parts["tiny"] is build_laguna.tiny_laguna
    assert parts["check"] is check_laguna.check_initial
    assert parts["flops"] is flops_laguna.train_flops_per_token
    assert parts["counters"] is check_laguna.program_counters
    import perfbench.harness.laguna_loop as loop

    source = open(loop.__file__).read()
    for model in ("build_laguna", "reference_laguna", "flops_laguna", "check_laguna"):
        assert f"import {model}" not in source and f", {model}" not in source, model


@pytest.fixture(scope="module")
def tiny_state():
    from perfbench.harness import correct

    _, workload, config = manifest.load_cell(CELL)
    workload, config = build_laguna.tiny_laguna(*build.tiny(workload, config))
    shape = build_laguna.laguna_shape(workload, config)
    state, _, tokens = build.build_state(workload, config, jax.devices()[:1], 7)
    return state, shape, correct.first_micro_batch(state, tokens, workload)


def test_initial_check_passes_the_program(tiny_state):
    ok, numbers = check_laguna.check_initial(*tiny_state)
    assert ok and numbers["held_overflow"] == 0.0
    for name in check_laguna.TOLERANCE:
        assert numbers[f"{name}_err"] <= check_laguna.TOLERANCE[name], name
    assert {f"held_rows_over_expected.layer{i}" for i in range(1, 5)} <= set(numbers)
    assert {"global_dense_out_err.layer0", "window_out_err.layer2", "global_out_err.layer4"} <= set(numbers)


@pytest.mark.parametrize("change, seen_by", [
    ({"window": 47}, "window_out"),
    ({"window": 49}, "window_out"),
    ({"mask_kinds": ("global",) * 5}, "window_out"),
    ({"gate": None}, "global_dense_out"),
    ({"gate": "raw"}, "global_out"),
    ({"pairing": "head"}, "global_grad"),
    ({"kv_shift": 1}, "window_out"),
    ({"routed_scaling": 1.0}, "moe_out"),
    ({"shared": False}, "shared_out"),
    ({"held": (2, 3)}, "held_rows"),
], ids=["window-1", "window+1", "no-window", "no-gate", "gate-from-x", "pairing", "grouping",
        "no-scaling", "no-shared", "one-expert-fewer"])
def test_initial_check_refuses_a_wrong_reference(tiny_state, change, seen_by):
    state, shape, batch = tiny_state
    numbers = check_laguna.check_initial_numbers(state, {**shape, **change}, batch)
    assert seen_by in check_laguna.refused_by(numbers), numbers


def test_benchmark_entries_name_the_cell_and_its_metrics():
    benchmark = manifest.load_manifest()
    entry = [w for w in benchmark["workloads"] if w["name"] == CELL][0]
    assert (entry["config"], entry["traffic"], entry["chips"]) == ("laguna-xs.2", "share16-seq16384", 1)
    assert len(entry["why"]) <= 200
    names = [m["name"] for m in benchmark["per_layer"]]
    mine = [m["name"] for m in benchmark["per_layer"] if m.get("workloads") == [CELL]]
    assert mine == NEW_METRICS and names[names.index(NEW_METRICS[0]):][:8] == NEW_METRICS
    listed = [m["name"] for m in benchmark["per_layer"]
              if CELL in m.get("workloads", []) and m["name"] not in NEW_METRICS]
    assert listed == ["recompute_time_pct", "moe_router_time_pct", "moe_dispatch_time_pct",
                      "expert_load_max_over_mean", "held_rows_over_expected"]
    for name in NEW_METRICS:
        module = __import__(f"perfbench.metrics.{name}", fromlist=["read"])
        declared = [m for m in benchmark["per_layer"] if m["name"] == name][0]
        assert (module.LAYER, module.UNIT, module.MOVES) == (
            declared["layer"], declared["unit"], declared["moves"])


def test_dry_run_of_the_cell_on_the_cpu():
    run = subprocess.run(
        [sys.executable, "perfbench/run.py", "--workload", CELL, "--seed", "4700000019",
         "--seconds", "1", "--trace", "1", "--allow-cpu"],
        cwd=manifest.ROOT, capture_output=True, text=True, timeout=900,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert run.returncode == 0, run.stderr[-2000:]
    last = json.loads(run.stdout.strip().splitlines()[-1])
    assert last["device"]["platform"] == "cpu" and last["failed"] == 0 and last["correct"] is True
    assert last["metrics"] == {}  # a dry run reports no metric
    assert "initial check ok=True" in run.stdout and "initial check, reading / limit:" in run.stdout
    for number in ("'held_overflow': 0.0", "'window_out_err'", "'global_out_err'",
                   "'global_dense_out_err'", "'window_first_err'", "'window_grad_err'",
                   "'global_grad_err'", "'first_grad_err'", "'dense_out_err'", "'moe_out_err'",
                   "'shared_out_err'", "'expert_grad_err'", "'held_rows_err'", "'per_position_err'",
                   "'loss_err'"):
        assert number in run.stdout, number
    assert "layers ('global', 'window', 'window', 'window', 'global')" in run.stdout
    assert "('global_dense_blocks', 1), ('window_blocks', 3), ('global_blocks', 1)" in run.stdout
    assert "held assignments that did not fit: 0" in run.stdout
    assert "perfbench: attn_mask_stats:" in run.stdout and "perfbench: qk_prologue_stats:" in run.stdout
    for name in ("held_rows_over_expected", "expert_load_max_over_mean", "lg_window_live_fill_pct"):
        assert f"not reported: {name}" in run.stdout, name
