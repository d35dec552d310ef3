"""What the Mellum2-12B-A2.5B cell brings to the benchmark: its configuration
file against the catalog's entry, its FLOP and byte counts against a
brute-force count, its reference's masks and blocked attention against the
whole matrix, the readers of the new scopes, kernels and counters on hand-made
events, the cell's own initial check at a tiny size, and the cell's dry run."""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench.harness import build, build_mellum, flops, flops_mellum, manifest, reference_mellum, sw_scopes
from perfbench.harness.trace_reduce import Event, Trace

CELL = "mellum2-12b-a2.5b.share4-seq16384"
CATALOG_FILE = "/opt/skills/guides/model-configs/architectures.jsonl"
REDUCED = ["num_hidden_layers", "num_experts", "vocab_size"]
NEW_METRICS = ["sw_window_time_pct", "sw_global_time_pct", "sw_window_kernel_roofline",
               "sw_global_kernel_roofline", "sw_live_fill_pct", "sw_dead_steps_per_live",
               "sw_held_expert_matmul_roofline"]
PEAKS = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}


def cell_shape():
    _, workload, config = manifest.load_cell(CELL)
    return build_mellum.mellum_shape(workload, config), workload, config


def test_config_file_holds_the_published_widths_and_cuts_three_counts():
    shape, workload, config = cell_shape()
    published = dict(
        hidden_size=2304, num_attention_heads=32, num_key_value_heads=4, head_dim=128,
        moe_intermediate_size=896, intermediate_size=7168, num_experts_per_tok=8, sliding_window=1024,
        rms_norm_eps=1e-06, tie_word_embeddings=False, norm_topk_prob=True, model_type="mellum",
        max_position_embeddings=131072, max_window_layers=0, use_sliding_window=True,
        attention_bias=False, hidden_act="silu")
    assert {k: config[k] for k in published} == published
    assert config["rope_parameters"] == {
        "full_attention": {"rope_type": "yarn", "rope_theta": 500000, "factor": 16,
                           "original_max_position_embeddings": 8192, "beta_fast": 32, "beta_slow": 1,
                           "attention_factor": 1.2772588722239782},
        "sliding_attention": {"rope_type": "default", "rope_theta": 500000}}
    assert config["layer_types"] == (["sliding_attention"] * 3 + ["full_attention"]) * 7
    assert config["mlp_layer_types"] == ["sparse"] * 28
    if os.path.exists(CATALOG_FILE):  # every key of the catalog's entry, letter for letter
        with open(CATALOG_FILE) as f:
            row = next(r for r in map(json.loads, f) if r["name"] == "Mellum2-12B-A2.5B-Instruct")
        kept = {k: v for k, v in row["config"].items() if k not in REDUCED}
        assert {k: config[k] for k in kept} == kept and config["source"] == row["source_url"]
    assert list(config["reduced"]) == REDUCED
    assert (config["num_hidden_layers"], config["num_experts"], config["vocab_size"]) == (4, 16, 24576)
    assert (config["num_hidden_layers_published"], config["num_experts_published"],
            config["vocab_size_published"]) == (28, 64, 98304)
    assert "four chips of one v5e host sharing each layer" in config["deployment"]
    for assumed in ("qk_norm", "window_convention", "router_aux_loss_coef", "intermediate_size",
                    "mtp_head"):
        assert assumed in config["assumed"], assumed
    entry = [c for c in manifest.load_manifest()["configs"] if c["name"] == "mellum2-12b-a2.5b"][0]
    assert entry["reduced"] == REDUCED and entry["source"] == config["source"]
    assert (workload["depth"], workload["seq_len"], workload["micro_batch_per_chip"],
            workload["grad_accum"], workload["chips"]) == (4, 16384, 1, 1, 1)
    assert shape["kinds"] == ("window", "window", "window", "global") and shape["window"] == 1024
    assert dict(shape["rotary"]) == {
        "window": (500000.0, None),
        "global": (500000.0, (16.0, 8192, 32.0, 1.0, 1.2772588722239782))}
    assert (shape["experts"], shape["held"], shape["experts_per_token"]) == (64, (0, 16), 8)


def test_the_builder_makes_the_program_s_config_of_the_cell():
    _, workload, config = cell_shape()
    c = build_mellum.mellum_config(workload, config)
    assert (c.n_embd, c.n_head, c.kv_heads, c.head_dim, c.mlp_dim, c.n_layer) == (2304, 32, 4, 128, 896, 4)
    assert c.layer_types == ("window", "window", "window", "global") and c.sliding_window == 1024
    assert c.rotary("global").scaling.cos_sin_factor == 1.2772588722239782
    assert c.rotary("window").scaling is None and c.experts_held == (0, 16) and not c.trains_routing
    parameters = (4 * (2304 * 4096 * 2 + 2 * 2304 * 512 + 2 * 128 + 2 * 2304 + 2304 * 64
                       + 16 * 3 * 2304 * 896) + 2 * 24576 * 2304 + 2304)
    assert round(parameters / 1e6, 1) == 595.2 and round(parameters * 16 / 1e9, 2) == 9.52


def test_flops_against_a_brute_force_count():
    shape, _, _ = cell_shape()
    small = {**shape, "seq_len": 200, "window": 37}
    i, j = np.arange(200)[:, None], np.arange(200)[None, :]
    assert flops_mellum.true_pairs(small, "window") == int(((j <= i) & (j > i - 37)).sum())
    assert flops_mellum.true_pairs(small, "global") == int((j <= i).sum())
    assert flops_mellum.true_pairs({**small, "window": 500}, "window") == int((j <= i).sum())
    S, D, H, Dh = 16384, 2304, 32, 128
    window, whole = flops_mellum.true_pairs(shape, "window"), flops_mellum.true_pairs(shape, "global")
    assert (window, whole) == (16253440, S * (S + 1) // 2) and round(100 * window / whole, 1) == 12.1
    want = (4 * (2 * D * H * Dh * 2 + 2 * D * 2 * 4 * Dh + 2 * D * 64 + 2 * 6 * D * 896)
            + 4 * H * Dh * (3 * window + whole) / S + 2 * D * 24576)
    assert flops_mellum.forward_flops_per_token(shape) == pytest.approx(want, rel=1e-12)
    assert flops_mellum.train_flops_per_token(shape) == 3 * flops_mellum.forward_flops_per_token(shape)
    head = 2 * D * 24576 / want
    assert round(100 * head) == 20  # of the model FLOPs at depth 4
    for cost, pairs, layers in ((flops_mellum.window_kernel_cost, window, 3),
                                (flops_mellum.global_kernel_cost, whole, 1)):
        operations, moved = cost(shape, 5)
        assert operations == 5 * layers * H * 14 * pairs * Dh
        assert moved == 5 * layers * H * (12 * S * Dh * 2 + 3 * S * 4)
        assert flops.roofline_seconds(operations, moved, PEAKS)[1] == "compute"


TINY = {**build_mellum.mellum_shape(
    {"seq_len": 96, "held_rows_factor": 4.0, "depth": 4},
    build_mellum.tiny_mellum({}, {**cell_shape()[2], "hidden_size": 32, "num_attention_heads": 4,
                                  "num_key_value_heads": 2, "head_dim": 8, "vocab_size": 64})[1]),
        "window": 20}


def test_the_references_masks_are_the_two_rules():
    pos = jnp.arange(96)
    i, j = np.arange(96)[:, None], np.arange(96)[None, :]
    np.testing.assert_array_equal(reference_mellum.allowed(TINY, "global", pos, pos), j <= i)
    np.testing.assert_array_equal(
        reference_mellum.allowed(TINY, "window", pos, pos), (j <= i) & (j > i - 20))


@pytest.mark.parametrize("kind", ["window", "global"])
def test_blocked_attention_matches_the_whole_matrix(kind, monkeypatch):
    keys = jax.random.split(jax.random.key(0), 3)
    q = jax.random.normal(keys[0], (96, 4, 8))
    k, v = (jax.random.normal(key, (96, 2, 8)) for key in keys[1:])
    monkeypatch.setattr(reference_mellum, "QUERY_BLOCK", 32)
    blocked = reference_mellum._attention(TINY, kind, q, k, v)
    scores = jnp.einsum("qhd,khd->hqk", q, jnp.repeat(k, 2, 1)) * 8 ** -0.5
    mask = reference_mellum.allowed(TINY, kind, jnp.arange(96), jnp.arange(96))
    probs = jax.nn.softmax(jnp.where(mask[None], scores, -jnp.inf), -1)
    whole = jnp.einsum("hqk,khd->qhd", probs, jnp.repeat(v, 2, 1)).reshape(96, 32)
    np.testing.assert_allclose(blocked, whole, atol=1e-5)


STEP = "jit(train_step)"
HLO_TEXT = """HloModule jit_train_step

ENTRY %main.1 (p0: f32[8,128]) -> f32[8,128] {
  %p0 = f32[8,128]{1,0} parameter(0)
  %fusion.1 = f32[8,128]{1,0} fusion(%p0), kind=kLoop, calls=%f1, metadata={op_name="jit(train_step)/jvp(attention)/window/dot_general"}
  %flash_fwd.2 = f32[8,128]{1,0} custom-call(%fusion.1), custom_call_target="tpu_custom_call", metadata={op_name="jit(train_step)/jvp(attention)/window/jit(flash_attention)/pallas_call"}
  %flash_bwd_fused.3 = f32[8,128]{1,0} custom-call(%flash_fwd.2), custom_call_target="tpu_custom_call", metadata={op_name="jit(train_step)/transpose(jvp(attention))/window/pallas_call"}
  %flash_fwd.4 = f32[8,128]{1,0} custom-call(%flash_bwd_fused.3), custom_call_target="tpu_custom_call", metadata={op_name="jit(train_step)/jvp(attention)/global/jit(flash_attention)/pallas_call"}
  %flash_bwd_fused.5 = f32[8,128]{1,0} custom-call(%flash_fwd.4), custom_call_target="tpu_custom_call", metadata={op_name="jit(train_step)/transpose(jvp(attention))/global/pallas_call"}
  %gmm.6 = f32[8,128]{1,0} custom-call(%flash_bwd_fused.5), custom_call_target="tpu_custom_call", metadata={op_name="jit(train_step)/jvp(mlp)/experts/jit(gmm)/pallas_call"}
  %fusion.7 = f32[8,128]{1,0} fusion(%gmm.6), kind=kLoop, calls=%f7, metadata={op_name="jit(train_step)/jvp(mlp)/router/dot_general"}
  ROOT %fusion.8 = f32[8,128]{1,0} fusion(%fusion.7), kind=kLoop, calls=%f8, metadata={op_name="jit(train_step)/optimizer/mul"}
}
"""
TARGET = 'custom_call_target="tpu_custom_call"'
DURATIONS = [("fusion.1", 1.0), ("flash_fwd.2", 1.0), ("flash_bwd_fused.3", 2.0), ("flash_fwd.4", 3.0),
             ("flash_bwd_fused.5", 5.0), ("gmm.6", 2.0), ("fusion.7", 2.0), ("fusion.8", 4.0)]
STATS = {"window": {"layers": 3, "true_pairs": 16253440, "fwd_live_tiles": 31, "fwd_grid_steps": 32,
                    "fwd_pairs_multiplied": 25165824, "bwd_live_tiles": 31, "bwd_grid_steps": 32,
                    "bwd_pairs_multiplied": 26214400}}


def hand_trace(durations):
    at, events = 0.0, []
    for name, seconds in durations:
        kind = f"custom-call(...), {TARGET}" if "fusion" not in name else "fusion(...)"
        events.append(Event(f"%{name} = f32[8,128]{{1,0}} {kind}", at, at + seconds))
        at += seconds
    return Trace({"/device:TPU:0": {"XLA Ops": events}})


@pytest.mark.parametrize("op_name, expected", [
    (f"{STEP}/jvp(attention)/window/dot_general", "window"),
    (f"{STEP}/transpose(jvp(attention))/global/pallas_call", "global"),
    (f"{STEP}/rematted_computation/attention/window/mul", "window"),
    (f"{STEP}/jvp(mlp)/combine/add;{STEP}/jvp(attention)/global/add", "global"),
    (f"{STEP}/jvp(attention)/dot_general", None),  # a stack of one kind
    (f"{STEP}/window/attention/mul", None),  # the kind lies under attention, not over it
    (f"{STEP}/jvp(mlp)/window/mul", None),
    ("", None),
])
def test_kind_of(op_name, expected):
    assert sw_scopes.kind_of(op_name) == expected


def test_shares_of_the_new_scopes_and_the_counters():
    trace = hand_trace(DURATIONS)
    run = {"hlo_text": HLO_TEXT, "peaks": None, "attn_mask_stats": STATS}
    read = lambda name: manifest.metric_reader(name)(trace, run)
    assert read("sw_window_time_pct") == pytest.approx(20.0)
    assert read("sw_global_time_pct") == pytest.approx(40.0)
    assert read("sw_live_fill_pct") == pytest.approx(63.27, abs=0.01)
    assert read("sw_dead_steps_per_live") == pytest.approx(2 / 62)
    full_grid = {"window": {**STATS["window"], "fwd_grid_steps": 256, "bwd_grid_steps": 256}}
    assert manifest.metric_reader("sw_dead_steps_per_live")(
        trace, {"attn_mask_stats": full_grid}) == pytest.approx(7.26, abs=0.01)
    for name in ("sw_window_kernel_roofline", "sw_global_kernel_roofline",
                 "sw_held_expert_matmul_roofline"):
        assert read(name) is None  # no peaks off the chip


def test_a_program_without_the_scopes_or_the_counter_gives_the_readers_nothing():
    """Another model, or the parent of the PR that brought them: nothing, and
    no exception."""
    other = HLO_TEXT.replace("/window/", "/").replace("/global/", "/")
    trace = hand_trace(DURATIONS)
    for name in NEW_METRICS:
        read = manifest.metric_reader(name)
        assert read(trace, {"hlo_text": other, "peaks": PEAKS}) is None, name
        assert read(Trace({}), {"hlo_text": other, "peaks": PEAKS}) is None, name
        assert read(trace, {}) is None, name


def test_the_roofline_readers_divide_the_least_time_by_each_kinds_own_calls():
    shape, workload, _ = cell_shape()
    trace = hand_trace([("fusion.1", 0.01), ("flash_fwd.2", 0.02), ("flash_bwd_fused.3", 0.03),
                        ("flash_fwd.4", 0.1), ("flash_bwd_fused.5", 0.2), ("gmm.6", 0.05)])
    rows = 5 * 4 * 32768.0
    run = {"hlo_text": HLO_TEXT, "workload": workload, "shape": shape, "traced_steps": 5,
           "peaks": PEAKS, "held_rows_traced": rows}
    least = lambda cost: flops.roofline_seconds(*cost(shape, 5), PEAKS)[0]
    assert manifest.metric_reader("sw_window_kernel_roofline")(trace, run) == pytest.approx(
        100 * least(flops_mellum.window_kernel_cost) / 0.05)
    assert manifest.metric_reader("sw_global_kernel_roofline")(trace, run) == pytest.approx(
        100 * least(flops_mellum.global_kernel_cost) / 0.3)
    from perfbench.harness import flops_mla
    experts = flops.roofline_seconds(*flops_mla.held_expert_matmul_cost(shape, rows, 20), PEAKS)[0]
    assert manifest.metric_reader("sw_held_expert_matmul_roofline")(trace, run) == pytest.approx(
        100 * experts / 0.05)
    assert manifest.metric_reader("sw_held_expert_matmul_roofline")(
        trace, {**run, "held_rows_traced": 0.0}) is None


def test_the_accepted_readers_given_this_cell_read_its_trace():
    _, workload, _ = cell_shape()
    text = HLO_TEXT.replace("optimizer/mul", "jvp(mlp)/dispatch/gather").replace(
        "jvp(attention)/window/dot_general", "rematted_computation/attention/window/dot_general")
    trace = hand_trace(DURATIONS)
    run = {"hlo_text": text, "expert_load_max_over_mean": 1.7, "held_rows_over_expected": 1.02,
           "peaks": PEAKS, "workload": workload}
    read = lambda name: manifest.metric_reader(name)(trace, run)
    assert read("moe_router_time_pct") == pytest.approx(10.0)
    assert read("moe_dispatch_time_pct") == pytest.approx(20.0)
    assert read("recompute_time_pct") == pytest.approx(5.0)
    assert read("expert_load_max_over_mean") == 1.7
    assert read("held_rows_over_expected") == 1.02
    assert read("held_expert_matmul_roofline") is None  # its scopes are latent attention's


@pytest.fixture(scope="module")
def tiny_state():
    from perfbench.harness import correct, mellum_loop

    _, workload, config = manifest.load_cell(CELL)
    workload, config = build.tiny(workload, config)
    workload, config = build_mellum.tiny_mellum(workload, config)
    shape = build_mellum.mellum_shape(workload, config)
    state, _, tokens = mellum_loop.build_state(workload, config, jax.devices()[:1], 7)
    return state, shape, correct.first_micro_batch(state, tokens, workload)


def test_initial_check_passes_the_program(tiny_state):
    from perfbench.harness import mellum_loop

    ok, numbers = mellum_loop.check_initial(*tiny_state)
    assert ok and numbers["held_overflow"] == 0.0
    for name in mellum_loop.TOLERANCE:
        assert numbers[f"{name}_err"] <= mellum_loop.TOLERANCE[name], name
    assert {f"held_rows_over_expected.layer{i}" for i in range(4)} <= set(numbers)


@pytest.mark.parametrize("change, seen_by", [
    ({"window": 47}, "window_out"),
    ({"window": 49}, "window_out"),
    ({"mask_kinds": ("window",) * 4}, "global_out"),
    ({"mask_kinds": ("global",) * 4}, "window_out"),
    ({"norm_topk_prob": False}, "moe_out"),
    ({"held": (2, 3)}, "held_rows"),
], ids=["window-1", "window+1", "window-on-global", "no-window", "gates", "one-expert-fewer"])
def test_initial_check_refuses_a_wrong_reference(tiny_state, change, seen_by):
    from perfbench.harness import mellum_loop

    state, shape, batch = tiny_state
    numbers = mellum_loop.check_initial_numbers(state, {**shape, **change}, batch)
    assert seen_by in mellum_loop.refused_by(numbers), numbers


def test_benchmark_entries_name_the_cell_and_its_metrics():
    benchmark = manifest.load_manifest()
    entry = [w for w in benchmark["workloads"] if w["name"] == CELL][0]
    assert (entry["config"], entry["traffic"], entry["chips"]) == (
        "mellum2-12b-a2.5b", "share4-seq16384", 1)
    assert len(entry["why"]) <= 200 and benchmark["workloads"][-1] == entry
    mine = [m["name"] for m in benchmark["per_layer"] if m.get("workloads") == [CELL]]
    assert mine == NEW_METRICS == [m["name"] for m in benchmark["per_layer"][-7:]]
    listed = [m["name"] for m in benchmark["per_layer"]
              if CELL in m.get("workloads", []) and m["name"] not in NEW_METRICS]
    assert listed == ["recompute_time_pct", "moe_router_time_pct", "moe_dispatch_time_pct",
                      "expert_load_max_over_mean", "held_rows_over_expected"]
    roofline = [m for m in benchmark["per_layer"] if m["name"] == "attn_kernel_roofline"][0]
    assert CELL not in roofline["workloads"] and len(roofline["workloads"]) == 6
    assert sum(w["chips"] == 4 for w in benchmark["workloads"]) == 1 and len(benchmark["workloads"]) == 8
    for name in NEW_METRICS:
        module = __import__(f"perfbench.metrics.{name}", fromlist=["read"])
        declared = [m for m in benchmark["per_layer"] if m["name"] == name][0]
        assert (module.LAYER, module.UNIT, module.MOVES) == (
            declared["layer"], declared["unit"], declared["moves"])


def test_dry_run_of_the_cell_on_the_cpu():
    run = subprocess.run(
        [sys.executable, "perfbench/run.py", "--workload", CELL, "--seed", "3800000019",
         "--seconds", "1", "--trace", "1", "--allow-cpu"],
        cwd=manifest.ROOT, capture_output=True, text=True, timeout=600,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert run.returncode == 0, run.stderr[-2000:]
    last = json.loads(run.stdout.strip().splitlines()[-1])
    assert last["device"]["platform"] == "cpu" and last["failed"] == 0 and last["correct"] is True
    assert last["metrics"] == {}  # a dry run reports no metric
    assert "initial check ok=True" in run.stdout
    for number in ("'held_overflow': 0.0", "'window_out_err'", "'global_out_err'", "'window_first_err'",
                   "'window_grad_err'", "'global_grad_err'", "'first_grad_err'", "'moe_out_err'",
                   "'expert_grad_err'", "'held_rows_err'", "'per_position_err'", "'loss_err'"):
        assert number in run.stdout, number
    assert "layers ('window', 'window', 'window', 'global')" in run.stdout
    assert "held assignments that did not fit: 0" in run.stdout
    for name in ("held_rows_over_expected", "expert_load_max_over_mean", "sw_live_fill_pct",
                 "sw_dead_steps_per_live"):
        assert f"not reported: {name}" in run.stdout, name
