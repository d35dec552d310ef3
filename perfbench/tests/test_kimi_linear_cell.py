"""What the Kimi-Linear-48B-A3B cell brings to the benchmark: its configuration
file against the catalog's entry, the arithmetic in its files against
``count_params``, its FLOP and byte counts written out, its reference's
recurrence and routing on small cases, the readers of the new scopes, kernels
and counter on hand-made events, the cell's own initial check at a tiny size,
and the cell's dry run."""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench.harness import build, build_kda, flops, flops_kda, flops_mla, kda_scopes, manifest
from perfbench.harness import reference_kda
from perfbench.harness.trace_reduce import Event, Trace

CELL = "kimi-linear-48b-a3b.share32-seq16384"
CATALOG_FILE = "/opt/skills/guides/model-configs/architectures.jsonl"
REDUCED = ["num_hidden_layers", "num_experts", "vocab_size"]
NEW_METRICS = ["kda_time_pct", "kda_prep_time_pct", "kda_kernel_time_pct", "kda_kernel_roofline",
               "kda_global_time_pct", "kda_global_kernel_roofline", "kda_saved_state_mb",
               "kda_held_expert_matmul_roofline"]
PEAKS = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
KINDS = ("kda", "kda", "kda", "global", "kda")


def cell_shape():
    _, workload, config = manifest.load_cell(CELL)
    return build_kda.kda_shape(workload, config), workload, config


def test_config_file_holds_the_published_widths_and_cuts_three_counts():
    shape, workload, config = cell_shape()
    published = dict(
        hidden_size=2304, num_attention_heads=32, num_key_value_heads=32, head_dim=72,
        kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128, q_lora_rank=None,
        intermediate_size=9216, moe_intermediate_size=1024, num_experts_per_token=8,
        num_shared_experts=1, first_k_dense_replace=1, routed_scaling_factor=2.446,
        moe_router_activation_func="sigmoid", moe_renormalize=True, use_grouped_topk=True,
        num_expert_group=1, topk_group=1, mla_use_nope=True, rope_scaling=None, rms_norm_eps=1e-05,
        tie_word_embeddings=False, model_type="kimi_linear", hidden_act="silu")
    assert {k: config[k] for k in published} == published
    linear = config["linear_attn_config"]
    assert (linear["num_heads"], linear["head_dim"], linear["short_conv_kernel_size"]) == (32, 128, 4)
    assert linear["full_attn_layers"] == [4, 8, 12, 16, 20, 24, 27] and len(linear["kda_layers"]) == 20
    if os.path.exists(CATALOG_FILE):  # every key of the catalog's entry, letter for letter
        with open(CATALOG_FILE) as f:
            row = next(r for r in map(json.loads, f) if r["name"] == "Kimi-Linear-48B-A3B-Instruct")
        kept = {k: v for k, v in row["config"].items() if k not in REDUCED}
        assert {k: config[k] for k in kept} == kept and config["source"] == row["source_url"]
    assert list(config["reduced"]) == REDUCED
    assert (config["num_hidden_layers"], config["num_experts"], config["vocab_size"]) == (5, 8, 20480)
    assert (config["num_hidden_layers_published"], config["num_experts_published"],
            config["vocab_size_published"]) == (27, 256, 163840)
    assert "32 v5e chips sharing each layer" in config["deployment"]
    for assumed in ("kda_low_rank", "kda_l2norm_eps", "kda_q_scale", "kda_init", "router_aux_loss",
                    "selection_bias_update", "kda_norm_scale_init"):
        assert assumed in config["assumed"], assumed
    entry = [c for c in manifest.load_manifest()["configs"] if c["name"] == "kimi-linear-48b-a3b"][0]
    assert entry["reduced"] == REDUCED and entry["source"] == config["source"]
    assert (workload["depth"], workload["seq_len"], workload["micro_batch_per_chip"],
            workload["grad_accum"], workload["chips"]) == (5, 16384, 1, 1, 1)
    assert shape["kinds"] == KINDS and (shape["dense_layers"], shape["moe_layers"]) == (1, 4)
    assert (shape["experts"], shape["held"], shape["experts_per_token"]) == (256, (0, 8), 8)
    assert shape["softmax_scale"] == 192 ** -0.5 and shape["nope"] and not shape["routing_trained"]


def test_the_arithmetic_in_the_files_is_what_the_tree_holds():
    """602.4M parameters by the matrices' arithmetic, written out; the tree
    holds 1,024 more: the four routed layers' (256,) selection bias, a buffer
    that is a leaf."""
    from distributed_llm_training_benchmark_framework_tpu.models import tinygpt

    _, workload, config = cell_shape()
    c = build_kda.kimi_config(workload, config)
    assert (c.n_embd, c.n_head, c.qk_dim, c.v_dim, c.mlp_dim, c.n_layer) == (2304, 32, 192, 128, 1024, 5)
    assert c.layer_types == KINDS and (c.kda_heads, c.kda_head_dim, c.kda_conv) == (32, 128, 4)
    assert c.experts_held == (0, 8) and c.n_experts == 256 and not c.trains_routing
    assert c.kda_chunk == 128 and "kda_chunk" not in workload  # the op's own default
    assert workload["remat"] == "full_keep_kernels"  # the strategy carries it to the model
    assert c.mla_nope and c.router_score == "sigmoid"
    D = 2304
    kda = (3 * D * 4096 + 4096 * D + 2 * (D * 128 + 128 * 4096) + D * 32 + 3 * 4 * 4096
           + 32 + 4096 + 128)  # q k v, out, two low-rank maps, beta, filters, A_log, dt_bias, norm
    latent = D * 32 * 192 + D * (512 + 64) + 512 + 512 * 32 * 256 + 32 * 128 * D
    shared, router, held = 3 * D * 1024, D * 256, 8 * 3 * D * 1024
    dense, norms = 3 * D * 9216, 2 * D
    routed = shared + router + held
    layers = [kda + dense, kda + routed, kda + routed, latent + routed, kda + routed]
    total = sum(layers) + 5 * norms + 2 * 20480 * D + D
    assert [round(x / 1e6, 2) for x in (kda, latent, shared, router, held, dense)] == [
        39.51, 29.11, 7.08, 0.59, 56.62, 63.7]
    assert round(total / 1e6, 1) == 602.4 and round(total * 16 / 1e9, 2) == 9.64
    shapes = jax.eval_shape(lambda k: tinygpt.init_params(c, k), jax.random.key(0))
    assert tinygpt.count_params(shapes) == total + 4 * 256
    for text in (workload["depth_arithmetic"], config["reduced"]["num_experts"]):
        assert "602.4M" in text or "0.25 routed rows" in text


def test_flops_written_out():
    shape, _, _ = cell_shape()
    S, D, H = 16384, 2304, 32
    recurrence = H * (5 * 2 * 64 * 128 + 3 * 2 * 128 * 128 + 2 * 64 * 64 / 3)
    kda = 2 * D * 3 * 4096 + 2 * (2 * D * 128 + 2 * 128 * 4096) + 2 * D * 32 + 2 * 4 * 3 * 4096 \
        + 2 * 4096 * D + recurrence
    latent = (2 * D * H * 192 + 2 * D * 576 + 2 * 512 * H * 256 + 2 * H * 128 * D
              + 2 * (S + 1) / 2 * H * 320)
    routed = 2 * D * 256 + 6 * D * 1024 + 0.25 * 6 * D * 1024
    want = 4 * kda + latent + 6 * D * 9216 + 4 * routed + 2 * D * 20480
    assert flops_kda.forward_flops_per_token(shape) == pytest.approx(want, rel=1e-12)
    assert flops_kda.recurrence_forward_flops_per_token(shape) == pytest.approx(recurrence)
    assert round(recurrence / H) == 182955 and flops_kda.CHUNK == 64  # whatever chunk the kernels take
    step_tf = 3 * want * S / 1e12
    assert 42.0 < step_tf < 42.8  # TF a step
    assert round(100 * 4 * kda / want) == 39 and round(100 * 2 * D * 20480 / want) == 11
    operations, moved = flops_kda.kda_kernel_cost(shape, 5)
    assert operations == 5 * 4 * S * (recurrence + flops_kda.recurrence_backward_flops_per_token(shape))
    forward = S * H * (4 * 128 * 2 + 128 * 4 + 4)
    backward = S * H * (3 * 128 * 2 + 128 * 4 + 4 + 4 * 128 * 2 + 128 * 4 + 4)
    assert moved == 5 * 4 * (forward + backward)
    assert flops.roofline_seconds(operations, moved, PEAKS)[1] == "memory"
    assert 2.0e-3 < flops.roofline_seconds(operations / 20, moved / 20, PEAKS)[0] < 3.5e-3  # a layer a step
    assert flops_kda.global_kernel_cost(shape, 5) == flops_mla.mla_kernel_cost(
        {**shape, "layers": 1}, 5)


TINY = build_kda.kda_shape(
    *build_kda.tiny_kda({"seq_len": 96, "held_rows_factor": 4.0},
                        {**cell_shape()[2], "hidden_size": 32, "num_attention_heads": 4,
                         "num_key_value_heads": 4, "head_dim": 8, "vocab_size": 64,
                         "intermediate_size": 48}))


def test_the_references_recurrence_is_the_equation_position_by_position(monkeypatch):
    """Against the state update written with explicit matrices, a position at a
    time in numpy; segments of any length give the same."""
    keys = jax.random.split(jax.random.key(0), 5)
    S, H, d = 24, 2, 8
    q, k, v = (np.asarray(jax.random.normal(key, (S, H, d)), np.float64) for key in keys[:3])
    g = -np.exp(np.asarray(jax.random.normal(keys[3], (S, H, d)), np.float64))
    beta = 1 / (1 + np.exp(-np.asarray(jax.random.normal(keys[4], (S, H)), np.float64)))
    want = np.zeros((S, H, d))
    for h in range(H):
        state = np.zeros((d, d))
        for t in range(S):
            kt = k[t, h][:, None]
            state = (np.eye(d) - beta[t, h] * kt @ kt.T) @ (np.exp(g[t, h])[:, None] * state) \
                + beta[t, h] * kt @ v[t, h][None, :]
            want[t, h] = state.T @ q[t, h] / np.sqrt(d)
    f32 = lambda x: jnp.asarray(x, jnp.float32)
    for segment in (256, 8):
        monkeypatch.setattr(reference_kda, "SEGMENT", segment)
        with jax.default_matmul_precision("highest"):
            got = reference_kda.delta_rule(TINY, f32(q), f32(k), f32(v), f32(g), f32(beta))
        np.testing.assert_allclose(got, want, atol=2e-5)


def test_the_references_gates_are_sigmoid_scores_chosen_with_the_bias():
    scores = jax.nn.sigmoid(jax.random.normal(jax.random.key(1), (50, 8)))
    m = {**TINY, "experts": 8, "experts_per_token": 3}
    gates, margin = reference_kda._gate_weights(m, scores, jnp.zeros((8,)))
    assert bool(jnp.all(jnp.sum(gates > 0, -1) == 3)) and bool(jnp.all(margin >= 0))
    np.testing.assert_allclose(gates.sum(-1), 2.446, rtol=1e-6)
    biased, _ = reference_kda._gate_weights(m, scores, jnp.zeros((8,)).at[5].set(10.0))
    assert bool(jnp.all(biased[:, 5] > 0))
    picked = biased > 0
    np.testing.assert_allclose(
        biased, 2.446 * picked * scores / jnp.sum(picked * scores, -1, keepdims=True), rtol=1e-6)


STEP = "jit(train_step)"
HLO_TEXT = """HloModule jit_train_step

ENTRY %main.1 (p0: f32[8,128]) -> f32[8,128] {
  %p0 = f32[8,128]{1,0} parameter(0)
  %fusion.1 = f32[8,128]{1,0} fusion(%p0), kind=kLoop, calls=%f1, metadata={op_name="jit(train_step)/jvp(attention)/kda/kda_prep/dot_general"}
  %kda_fwd.2 = f32[8,128]{1,0} custom-call(%fusion.1), custom_call_target="tpu_custom_call", metadata={op_name="jit(train_step)/jvp(attention)/kda/kda_core/pallas_call"}
  %kda_bwd.3 = f32[8,128]{1,0} custom-call(%kda_fwd.2), custom_call_target="tpu_custom_call", metadata={op_name="jit(train_step)/transpose(jvp(attention))/kda/kda_core/pallas_call"}
  %flash_fwd.4 = f32[8,128]{1,0} custom-call(%kda_bwd.3), custom_call_target="tpu_custom_call", metadata={op_name="jit(train_step)/jvp(attention)/global/mla_core/jit(flash_attention)/pallas_call"}
  %flash_bwd_fused.5 = f32[8,128]{1,0} custom-call(%flash_fwd.4), custom_call_target="tpu_custom_call", metadata={op_name="jit(train_step)/transpose(jvp(attention))/global/mla_core/pallas_call"}
  %gmm.6 = f32[8,128]{1,0} custom-call(%flash_bwd_fused.5), custom_call_target="tpu_custom_call", metadata={op_name="jit(train_step)/jvp(mlp)/experts/jit(gmm)/pallas_call"}
  %fusion.7 = f32[8,128]{1,0} fusion(%gmm.6), kind=kLoop, calls=%f7, metadata={op_name="jit(train_step)/jvp(attention)/kda/kda_out/dot_general"}
  ROOT %fusion.8 = f32[8,128]{1,0} fusion(%fusion.7), kind=kLoop, calls=%f8, metadata={op_name="jit(train_step)/optimizer/mul"}
}
"""
TARGET = 'custom_call_target="tpu_custom_call"'
DURATIONS = [("fusion.1", 1.0), ("kda_fwd.2", 1.0), ("kda_bwd.3", 3.0), ("flash_fwd.4", 1.0),
             ("flash_bwd_fused.5", 3.0), ("gmm.6", 2.0), ("fusion.7", 1.0), ("fusion.8", 8.0)]
STATS = {"layers": 4, "chunk": 128, "chunks": 128, "kernel_calls": {"kda_fwd": 4, "kda_bwd": 4},
         "saved_state_bytes": 32 * 128 * 128 * 128 * 2}


def hand_trace(durations):
    at, events = 0.0, []
    for name, seconds in durations:
        kind = f"custom-call(...), {TARGET}" if "fusion" not in name else "fusion(...)"
        events.append(Event(f"%{name} = f32[8,128]{{1,0}} {kind}", at, at + seconds))
        at += seconds
    return Trace({"/device:TPU:0": {"XLA Ops": events}})


@pytest.mark.parametrize("op_name, expected", [
    (f"{STEP}/jvp(attention)/kda/kda_prep/dot_general", ("kda", "kda_prep")),
    (f"{STEP}/transpose(jvp(attention))/kda/kda_core/pallas_call", ("kda", "kda_core")),
    (f"{STEP}/rematted_computation/attention/kda/kda_out/mul", ("kda", "kda_out")),
    (f"{STEP}/jvp(attention)/kda/mul", ("kda", None)),  # the sublayer's norm
    (f"{STEP}/jvp(attention)/global/mla_proj/dot_general", ("global", None)),
    (f"{STEP}/jvp(mlp)/combine/add;{STEP}/jvp(attention)/global/add", ("global", None)),
    (f"{STEP}/jvp(attention)/dot_general", (None, None)),  # a stack of one kind
    (f"{STEP}/kda/attention/mul", (None, None)),  # the kind lies under attention, not over it
    ("", (None, None)),
])
def test_kind_and_part(op_name, expected):
    assert kda_scopes.kind_and_part(op_name) == expected


def test_shares_of_the_new_scopes_and_the_counter():
    trace = hand_trace(DURATIONS)
    run = {"hlo_text": HLO_TEXT, "peaks": None, "kda_stats": STATS}
    read = lambda name: manifest.metric_reader(name)(trace, run)
    assert read("kda_time_pct") == pytest.approx(30.0)
    assert read("kda_prep_time_pct") == pytest.approx(5.0)
    assert read("kda_kernel_time_pct") == pytest.approx(20.0)
    assert read("kda_global_time_pct") == pytest.approx(20.0)
    assert read("kda_saved_state_mb") == pytest.approx(134.217728)
    for name in ("kda_kernel_roofline", "kda_global_kernel_roofline",
                 "kda_held_expert_matmul_roofline"):
        assert read(name) is None  # no peaks off the chip


def test_a_program_without_the_scopes_or_the_counter_gives_the_readers_nothing():
    """Another model, or the parent of the PR that brought them: nothing, and
    no exception."""
    other = HLO_TEXT.replace("/kda/", "/").replace("/global/", "/")
    trace = hand_trace(DURATIONS)
    for name in NEW_METRICS:
        read = manifest.metric_reader(name)
        assert read(trace, {"hlo_text": other, "peaks": PEAKS}) is None, name
        assert read(Trace({}), {"hlo_text": other, "peaks": PEAKS}) is None, name
        assert read(trace, {}) is None, name


def test_the_roofline_readers_divide_the_least_time_by_their_own_calls():
    shape, workload, _ = cell_shape()
    trace = hand_trace([("fusion.1", 0.01), ("kda_fwd.2", 0.1), ("kda_bwd.3", 0.3),
                        ("flash_fwd.4", 0.1), ("flash_bwd_fused.5", 0.2), ("gmm.6", 0.05)])
    rows = 5 * 4 * 4096.0
    run = {"hlo_text": HLO_TEXT, "workload": workload, "shape": shape, "traced_steps": 5,
           "peaks": PEAKS, "held_rows_traced": rows}
    least = lambda cost: flops.roofline_seconds(*cost(shape, 5), PEAKS)[0]
    assert manifest.metric_reader("kda_kernel_roofline")(trace, run) == pytest.approx(
        100 * least(flops_kda.kda_kernel_cost) / 0.4)
    assert manifest.metric_reader("kda_global_kernel_roofline")(trace, run) == pytest.approx(
        100 * least(flops_kda.global_kernel_cost) / 0.3)
    experts = flops.roofline_seconds(*flops_mla.held_expert_matmul_cost(shape, rows, 20), PEAKS)[0]
    assert manifest.metric_reader("kda_held_expert_matmul_roofline")(trace, run) == pytest.approx(
        100 * experts / 0.05)
    assert manifest.metric_reader("kda_held_expert_matmul_roofline")(
        trace, {**run, "held_rows_traced": 0.0}) is None


def test_the_accepted_readers_given_this_cell_read_its_trace():
    _, workload, _ = cell_shape()
    text = HLO_TEXT.replace("optimizer/mul", "jvp(mlp)/dispatch/gather").replace(
        "jvp(attention)/kda/kda_prep/dot_general", "rematted_computation/attention/kda/kda_prep/mul")
    trace = hand_trace(DURATIONS)
    run = {"hlo_text": text, "expert_load_max_over_mean": 1.2, "held_rows_over_expected": 1.01,
           "peaks": PEAKS, "workload": workload}
    read = lambda name: manifest.metric_reader(name)(trace, run)
    assert read("moe_dispatch_time_pct") == pytest.approx(40.0)
    assert read("moe_router_time_pct") == pytest.approx(0.0)
    assert read("recompute_time_pct") == pytest.approx(5.0)
    assert read("expert_load_max_over_mean") == 1.2
    assert read("held_rows_over_expected") == 1.01


@pytest.fixture(scope="module")
def tiny_state():
    from perfbench.harness import correct

    _, workload, config = manifest.load_cell(CELL)
    workload, config = build_kda.tiny_kda(*build.tiny(workload, config))
    shape = build_kda.kda_shape(workload, config)
    from perfbench.harness import kda_loop

    state, _, tokens = kda_loop.build_state(workload, config, jax.devices()[:1], 7)
    return state, shape, correct.first_micro_batch(state, tokens, workload)


def test_initial_check_reads_every_sublayer(tiny_state):
    from perfbench.harness import kda_loop

    numbers = kda_loop.check_initial_numbers(*tiny_state)
    assert numbers["held_overflow"] == 0 and 0.0 < numbers["mixer_input_scale_min"] <= 1.0
    # the driver starts the KDA layers' head-norm scales where the config file says (a stand-in)
    for stack in ("kda_blocks", "kda_dense_blocks"):
        assert float(jnp.abs(tiny_state[0].params[stack]["kda_norm"] - 0.03).max()) < 1e-6
    for name in kda_loop.TOLERANCE:
        assert f"{name}_err" in numbers and np.isfinite(numbers[f"{name}_err"]), name
    # at a tiny size the readings are bfloat16's over a handful of keys: a loose bound here;
    # the cell's limits are calibrated at the published widths
    assert max(numbers[f"{name}_err"] for name in kda_loop.TOLERANCE) < 0.1
    assert {f"held_rows_over_expected.layer{i}" for i in range(1, 5)} <= set(numbers)
    for leaf in kda_loop.KDA_LEAVES:
        assert f"grad_err.kda.{leaf}" in numbers


@pytest.mark.parametrize("change, seen_by", [
    ({"norm_topk_prob": False}, "moe_out"),
    ({"routed_scaling": 1.0}, "moe_out"),
    ({"shared": False}, "moe_out"),
    ({"held": (2, 3)}, "held_rows"),
    ({"l2_eps": 1.0}, "kda_out"),
], ids=["gates", "no-factor", "no-shared-expert", "one-expert-fewer", "l2norm-eps"])
def test_initial_check_refuses_a_wrong_reference(tiny_state, change, seen_by):
    from perfbench.harness import kda_loop

    state, shape, batch = tiny_state
    numbers = kda_loop.check_initial_numbers(state, {**shape, **change}, batch)
    assert seen_by in kda_loop.refused_by(numbers), numbers


def test_benchmark_entries_name_the_cell_and_its_metrics():
    benchmark = manifest.load_manifest()
    entry = [w for w in benchmark["workloads"] if w["name"] == CELL][0]
    assert (entry["config"], entry["traffic"], entry["chips"]) == (
        "kimi-linear-48b-a3b", "share32-seq16384", 1)
    assert len(entry["why"]) <= 200 and benchmark["workloads"][-1] == entry
    mine = [m["name"] for m in benchmark["per_layer"] if m.get("workloads") == [CELL]]
    assert mine == NEW_METRICS == [m["name"] for m in benchmark["per_layer"][-8:]]
    listed = [m["name"] for m in benchmark["per_layer"]
              if CELL in m.get("workloads", []) and m["name"] not in NEW_METRICS]
    assert listed == ["recompute_time_pct", "moe_router_time_pct", "moe_dispatch_time_pct",
                      "expert_load_max_over_mean", "held_rows_over_expected"]
    assert sum(w["chips"] == 4 for w in benchmark["workloads"]) == 1 and len(benchmark["workloads"]) == 9
    assert len(benchmark["configs"]) == 7
    for name in NEW_METRICS:
        module = __import__(f"perfbench.metrics.{name}", fromlist=["read"])
        declared = [m for m in benchmark["per_layer"] if m["name"] == name][0]
        assert (module.LAYER, module.UNIT, module.MOVES) == (
            declared["layer"], declared["unit"], declared["moves"])


def test_dry_run_of_the_cell_on_the_cpu():
    run = subprocess.run(
        [sys.executable, "perfbench/run.py", "--workload", CELL, "--seed", "3800000019",
         "--seconds", "1", "--trace", "1", "--allow-cpu"],
        cwd=manifest.ROOT, capture_output=True, text=True, timeout=900,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert run.returncode == 0, run.stderr[-2000:]
    last = json.loads(run.stdout.strip().splitlines()[-1])
    assert last["device"]["platform"] == "cpu" and last["failed"] == 0
    assert last["metrics"] == {}  # a dry run reports no metric
    for number in ("'held_overflow': 0.0", "'kda_out_err'", "'kda_grad_err'", "'global_out_err'",
                   "'global_grad_err'", "'dense_out_err'", "'dense_grad_err'", "'moe_out_err'",
                   "'expert_grad_err'", "'held_rows_err'", "'per_position_err'", "'loss_err'"):
        assert number in run.stdout, number
    assert "layers ('kda', 'kda', 'kda', 'global', 'kda')" in run.stdout
    assert "stacks [('kda_dense_blocks', 1), ('kda_blocks', 3), ('blocks', 1)]" in run.stdout
    assert "held assignments that did not fit: 0" in run.stdout
    for name in ("held_rows_over_expected", "expert_load_max_over_mean", "kda_saved_state_mb"):
        assert f"not reported: {name}" in run.stdout, name
