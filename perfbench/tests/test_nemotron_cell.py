"""What the Nemotron-3-Nano cell brings to the benchmark: its configuration file
against the catalog's entry, ``count_params`` against the arithmetic, its FLOP
and byte counts against a hand count, the reference's scan and grouped norm
against the equations written out, the readers of the new scopes, kernels and
counters on hand-made events, the cell's own initial check at a tiny size, the
driver's parts by dotted name, and the cell's dry run."""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench.harness import (
    build, build_nemotron, check_nemotron, flops, flops_nemotron, manifest, reference_nemotron,
    ssd_scopes,
)
from perfbench.harness.trace_reduce import Event, Trace

CELL = "nemotron-3-nano-30b-a3b.share16-seq16384"
CATALOG_FILE = "/opt/skills/guides/model-configs/architectures.jsonl"
REDUCED = ["num_hidden_layers", "n_routed_experts", "vocab_size"]
NEW_METRICS = ["ssd_time_pct", "ssd_prep_time_pct", "ssd_kernel_time_pct", "ssd_kernel_roofline",
               "ssd_conv_time_pct", "ssd_out_time_pct", "ssd_global_time_pct",
               "ssd_global_kernel_roofline", "ssd_saved_state_mb", "ssd_held_expert_matmul_roofline"]
PEAKS = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
PATTERN = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"


def cell_shape():
    _, workload, config = manifest.load_cell(CELL)
    return build_nemotron.nemotron_shape(workload, config), workload, config


def test_config_file_holds_the_published_widths_and_cuts_three_counts():
    shape, workload, config = cell_shape()
    published = dict(
        hidden_size=2688, num_attention_heads=32, num_key_value_heads=2, head_dim=128,
        mamba_num_heads=64, mamba_head_dim=64, n_groups=8, ssm_state_size=128, conv_kernel=4,
        chunk_size=128, expand=2, intermediate_size=1856, moe_intermediate_size=1856,
        moe_shared_expert_intermediate_size=3712, n_shared_experts=1, num_experts_per_tok=6,
        routed_scaling_factor=2.5, norm_topk_prob=True, n_group=1, topk_group=1, norm_eps=1e-05,
        mlp_hidden_act="relu2", mamba_hidden_act="silu", use_conv_bias=True, use_bias=False,
        tie_word_embeddings=False, model_type="nemotron_h", hybrid_override_pattern=PATTERN,
        rope_theta=10000, partial_rotary_factor=1, time_step_min=0.001, time_step_max=0.1,
        time_step_floor=0.0001, rescale_prenorm_residual=True, max_position_embeddings=262144)
    assert {k: config[k] for k in published} == published
    assert (PATTERN.count("M"), PATTERN.count("E"), PATTERN.count("*"), len(PATTERN)) == (23, 23, 6, 52)
    if os.path.exists(CATALOG_FILE):  # every key of the catalog's entry, letter for letter
        with open(CATALOG_FILE) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "NVIDIA-Nemotron-3-Nano-30B-A3B-BF16")
        kept = {k: v for k, v in row["config"].items() if k not in REDUCED}
        assert {k: config[k] for k in kept} == kept and config["source"] == row["source_url"]
        assert [row["config"][k] for k in REDUCED] == [52, 128, 131072]
    assert list(config["reduced"]) == REDUCED
    assert (config["num_hidden_layers"], config["n_routed_experts"], config["vocab_size"]) == (
        9, 8, 16384)
    assert (config["num_hidden_layers_published"], config["n_routed_experts_published"],
            config["vocab_size_published"], config["experts_held_first"]) == (52, 128, 131072, 0)
    assert "16 chips (a v5e 4 x 4 slice) sharing each layer" in config["deployment"]
    assert "blocks 0-8" in config["reduced"]["num_hidden_layers"]
    for assumed in ("attention_positions", "mamba_d_inner", "mamba_norm", "mamba_dt_clamp",
                    "mamba_init", "rescale_prenorm_residual", "embedding_scale_init",
                    "router_aux_loss", "selection_bias_update"):
        assert assumed in config["assumed"], assumed
    every = ", ".join(config["assumed"])
    for key in config:  # a key that is no config key of the catalog's is explained
        if key not in published and key not in ("name", "source", "builder", "deployment",
                                                "reduced", "assumed"):
            if not os.path.exists(CATALOG_FILE) or key not in row["config"]:
                assert key in every, key
    entry = [c for c in manifest.load_manifest()["configs"]
             if c["name"] == "nemotron-3-nano-30b-a3b"][0]
    assert entry["reduced"] == REDUCED and entry["source"] == config["source"]
    assert entry["file"] == "perfbench/configs/nemotron-3-nano-30b-a3b.json"
    assert (workload["depth"], workload["seq_len"], workload["micro_batch_per_chip"],
            workload["grad_accum"], workload["chips"], workload["sync_every"],
            workload["warmup_steps"]) == (9, 16384, 1, 1, 1, 5, 5)
    assert shape["kinds"] == ("ssd", "mlp", "ssd", "mlp", "ssd", "global", "mlp", "ssd", "mlp")
    assert (shape["experts"], shape["held"], shape["experts_per_token"]) == (128, (0, 8), 6)
    assert (shape["ssd_heads"], shape["ssd_head_dim"], shape["ssd_groups"], shape["ssd_state"],
            shape["chunk"], shape["moe_layers"]) == (64, 64, 8, 128, 128, 4)


def test_the_builder_makes_the_program_s_config_and_count_params_is_the_arithmetic():
    _, workload, config = cell_shape()
    c = build_nemotron.nemotron_config(workload, config)
    assert (c.n_embd, c.n_head, c.kv_heads, c.head_dim, c.mlp_dim, c.n_layer) == (
        2688, 32, 2, 128, 1856, 9)
    assert c.block_halves and c.pos_embed == "none" and c.mlp_act == "relu2"
    assert (c.ssd_inner, c.ssd_xbc, c.ssd_chunk, c.shared_dim) == (4096, 6144, 128, 3712)
    assert c.layer_groups == (("ssd_blocks", (0, 2, 4, 7)), ("mlp_blocks", (1, 3, 6, 8)),
                              ("global_blocks", (5,)))
    assert c.experts_held == (0, 8) and not c.trains_routing and c.router_score == "sigmoid"
    assert c.routed_scaling_factor == 2.5 and c.n_shared_experts == 1 and c.remat == "none"
    D = 2688
    mixer = D * (4096 + 6144 + 64) + 4096 * D + 4 * 6144 + 6144 + 3 * 64 + 4096 + D
    routed = D * 128 + 128 + 2 * D * 3712 + 8 * 2 * D * 1856 + D
    attention = D * 4096 + 2 * D * 256 + 4096 * D + D
    parameters = 4 * mixer + 4 * routed + attention + 2 * 16384 * D + D
    assert (round(mixer / 1e6, 2), round(routed / 1e6, 2), round(attention / 1e6, 2)) == (
        38.74, 100.13, 23.40)
    assert round(parameters / 1e6, 1) == 667.0 and round(parameters * 16 / 1e9, 2) == 10.67
    shapes = jax.eval_shape(lambda: __import__(
        "distributed_llm_training_benchmark_framework_tpu.models.tinygpt", fromlist=["x"]
    ).init_params(c, jax.random.key(0)))
    assert sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes)) == parameters


def test_flops_against_a_hand_count():
    shape, _, _ = cell_shape()
    S, D = 16384, 2688
    scan = 64 * (2 * 128 * 64 + 2 * 2 * 128 * 64) + 8 * 2 * 128 * 128
    mixer = 2 * D * 10304 + 2 * 4 * 6144 + 2 * 4096 * D + scan
    attention = 2 * D * (32 + 4) * 128 + 2 * 4096 * D + 4 * (S + 1) / 2 * 32 * 128
    routed = 2 * D * 128 + 4 * D * 3712 + 0.375 * 4 * D * 1856
    want = 4 * mixer + attention + 4 * routed + 2 * D * 16384
    assert flops_nemotron.scan_forward_flops_per_token(shape) == scan == 3407872
    assert flops_nemotron.forward_flops_per_token(shape) == pytest.approx(want, rel=1e-12)
    assert round(want / 1e6) == 785 and round(3 * want * S / 1e12, 1) == 38.6
    assert round(100 * 4 * mixer / want) == 41 and round(attention / 1e6) == 181
    # a tiny size, every term written out: 2 heads of 4 in 1 group over a state of 8
    tiny = {**shape, "hidden": 16, "heads": 2, "kv_heads": 1, "head_dim": 8, "ssd_heads": 2,
            "ssd_head_dim": 4, "ssd_groups": 1, "ssd_state": 8, "ssd_conv": 4, "expert_width": 8,
            "shared_width": 12, "experts": 4, "held": (0, 2), "experts_per_token": 2, "vocab": 32,
            "seq_len": 8, "kinds": ("ssd", "mlp", "global")}
    by_hand = ((2 * 16 * (8 + 24 + 2) + 2 * 4 * 24 + 2 * 8 * 16
                + 2 * (2 * 128 * 4 + 4 * 8 * 4) + 2 * 128 * 8)
               + (2 * 16 * 4 + 4 * 16 * 12 + 1.0 * 4 * 16 * 8)
               + (2 * 16 * (2 + 2) * 8 + 2 * 16 * 16 + 4 * 4.5 * 2 * 8) + 2 * 16 * 32)
    assert flops_nemotron.forward_flops_per_token(tiny) == by_hand
    operations, moved = flops_nemotron.ssd_kernel_cost(shape, 5)
    assert operations == 5 * 4 * S * 3 * scan
    assert moved == 5 * 4 * S * (2 * (2 * 4096 + 2 * 1024) + 2 * 64 * 4
                                 + 2 * (3 * 4096 + 4 * 1024) + 4 * 64 * 4)
    assert flops.roofline_seconds(operations, moved, PEAKS)[1] == "memory"
    operations, moved = flops_nemotron.global_kernel_cost(shape, 5)
    assert operations == 5 * 32 * 14 * (S * (S + 1) / 2) * 128
    assert moved == 5 * ((6 * 32 + 6 * 2) * S * 128 * 2 + 3 * 32 * S * 4)
    assert flops.roofline_seconds(operations, moved, PEAKS)[1] == "compute"
    operations, moved = flops_nemotron.held_expert_matmul_cost(shape, 1000, 4)
    assert operations == 3 * 1000 * 4 * D * 1856
    assert moved == 2 * 3 * (1000 * 2 * (D + 1856) + 4 * 8 * 2 * D * 1856)


TINY_FILE = build_nemotron.tiny_nemotron({}, {
    **cell_shape()[2], "hidden_size": 32, "num_attention_heads": 4, "num_key_value_heads": 2,
    "head_dim": 8, "vocab_size": 64})[1]
TINY = build_nemotron.nemotron_shape({"seq_len": 64, "held_rows_factor": 4.0, "depth": 9}, TINY_FILE)


def tiny_weights(kind, seed=0):
    D, H, P, G, N = 32, TINY["ssd_heads"], TINY["ssd_head_dim"], TINY["ssd_groups"], TINY["ssd_state"]
    inner, xbc = H * P, H * P + 2 * G * N
    keys = iter(jax.random.split(jax.random.key(seed), 16))
    normal = lambda *shape: 0.3 * jax.random.normal(next(keys), shape)
    if kind == "ssd":
        return {"ln1_scale": 1.0 + normal(D), "ssd_win": normal(D, inner + xbc + H),
                "ssd_conv": normal(4, xbc), "ssd_conv_bias": normal(xbc), "ssd_dt_bias": normal(H),
                "ssd_a_log": normal(H), "ssd_d": 1.0 + normal(H), "ssd_norm": 1.0 + normal(inner),
                "wo": normal(inner, D)}
    return {"ln1_scale": 1.0 + normal(D), "wq": normal(D, 32), "wkv": normal(D, 2, 16),
            "wo": normal(32, D)}


def test_the_references_mixer_is_the_equations_written_out():
    """``ssd_sublayer`` against the recurrence and the grouped norm in numpy,
    position by position and head by head; a block of queries against the
    whole score matrix with each kv head under its two query heads."""
    w = jax.tree.map(np.asarray, tiny_weights("ssd"))
    H, P, G, N = TINY["ssd_heads"], TINY["ssd_head_dim"], TINY["ssd_groups"], TINY["ssd_state"]
    inner = H * P
    x = np.asarray(jax.random.normal(jax.random.key(9), (24, 32)), np.float64)
    with jax.default_matmul_precision("highest"):
        got = np.asarray(reference_nemotron.ssd_sublayer({**TINY, "seq_len": 24}, jnp.asarray(x, jnp.float32), w))
    silu = lambda t: t / (1 + np.exp(-t))
    h = x / np.sqrt((x * x).mean(-1, keepdims=True) + 1e-5) * w["ln1_scale"]
    p = h @ w["ssd_win"]
    z, xbc, dt = p[:, :inner], p[:, inner:-H], p[:, -H:]
    padded = np.concatenate([np.zeros((3, xbc.shape[1])), xbc])
    xbc = silu(sum(padded[i:i + 24] * w["ssd_conv"][i] for i in range(4)) + w["ssd_conv_bias"])
    dt = np.log1p(np.exp(dt + w["ssd_dt_bias"]))
    y = np.zeros((24, inner))
    for head in range(H):
        group, state = head // (H // G), np.zeros((P, N))
        for t in range(24):
            xs = xbc[t, head * P:(head + 1) * P]
            B = xbc[t, inner + group * N:inner + (group + 1) * N]
            C = xbc[t, inner + G * N + group * N:inner + G * N + (group + 1) * N]
            state = np.exp(-np.exp(w["ssd_a_log"][head]) * dt[t, head]) * state + dt[t, head] * np.outer(xs, B)
            y[t, head * P:(head + 1) * P] = state @ C + w["ssd_d"][head] * xs
    u = (y * silu(z)).reshape(24, G, inner // G)
    u = (u / np.sqrt((u * u).mean(-1, keepdims=True) + 1e-5)).reshape(24, inner) * w["ssd_norm"]
    np.testing.assert_allclose(got, x + u @ w["wo"], rtol=2e-4, atol=2e-5)
    wa = tiny_weights("global", 1)
    with jax.default_matmul_precision("highest"):
        got = np.asarray(reference_nemotron.attention_sublayer({**TINY, "seq_len": 24}, jnp.asarray(x, jnp.float32), wa))
    wa = jax.tree.map(lambda t: np.asarray(t, np.float64), wa)
    h = x / np.sqrt((x * x).mean(-1, keepdims=True) + 1e-5) * wa["ln1_scale"]
    q, k, v = (h @ wa["wq"]).reshape(24, 4, 8), (h @ wa["wkv"][:, 0]).reshape(24, 2, 8), (
        h @ wa["wkv"][:, 1]).reshape(24, 2, 8)
    out = np.zeros((24, 4, 8))
    for head in range(4):
        scores = q[:, head] @ k[:, head // 2].T / np.sqrt(8)
        scores = np.where(np.tril(np.ones((24, 24), bool)), scores, -np.inf)
        e = np.exp(scores - scores.max(-1, keepdims=True))
        out[:, head] = e / e.sum(-1, keepdims=True) @ v[:, head // 2]
    np.testing.assert_allclose(got, x + out.reshape(24, 32) @ wa["wo"], rtol=2e-4, atol=2e-5)


HLO_TEXT = """HloModule jit_train_step
ENTRY %main {
  %fusion.1 = f32[8,128]{1,0} fusion(%p0), kind=kLoop, calls=%f1, metadata={op_name="jit(train_step)/jvp(attention)/ssd/ssd_prep/dot_general"}
  %kda_conv_fwd.2 = f32[8,128]{1,0} custom-call(%fusion.1), custom_call_target="tpu_custom_call", metadata={op_name="jit(train_step)/jvp(attention)/ssd/ssd_prep/pallas_call"}
  %ssd_fwd.3 = f32[8,128]{1,0} custom-call(%kda_conv_fwd.2), custom_call_target="tpu_custom_call", metadata={op_name="jit(train_step)/jvp(attention)/ssd/ssd_core/pallas_call"}
  %fusion.4 = f32[8,128]{1,0} fusion(%ssd_fwd.3), kind=kLoop, calls=%f4, metadata={op_name="jit(train_step)/jvp(attention)/ssd/ssd_out/mul"}
  %flash_fwd.5 = f32[8,128]{1,0} custom-call(%fusion.4), custom_call_target="tpu_custom_call", metadata={op_name="jit(train_step)/jvp(attention)/global/pallas_call"}
  %flash_bwd_fused.6 = f32[8,128]{1,0} custom-call(%flash_fwd.5), custom_call_target="tpu_custom_call", metadata={op_name="jit(train_step)/transpose(jvp(attention))/global/pallas_call"}
  %ssd_bwd.7 = f32[8,128]{1,0} custom-call(%flash_bwd_fused.6), custom_call_target="tpu_custom_call", metadata={op_name="jit(train_step)/transpose(jvp(attention))/ssd/ssd_core/pallas_call"}
  %kda_conv_bwd.8 = f32[8,128]{1,0} custom-call(%ssd_bwd.7), custom_call_target="tpu_custom_call", metadata={op_name="jit(train_step)/transpose(jvp(attention))/ssd/ssd_prep/pallas_call"}
  %gmm.9 = f32[8,128]{1,0} custom-call(%kda_conv_bwd.8), custom_call_target="tpu_custom_call", metadata={op_name="jit(train_step)/jvp(mlp)/experts/jit(gmm)/pallas_call"}
  ROOT %fusion.10 = f32[8,128]{1,0} fusion(%gmm.9), kind=kLoop, calls=%f10, metadata={op_name="jit(train_step)/optimizer/mul"}
}
"""
TARGET = 'custom_call_target="tpu_custom_call"'
DURATIONS = [("fusion.1", 1.0), ("kda_conv_fwd.2", 0.5), ("ssd_fwd.3", 1.5), ("fusion.4", 1.0),
             ("flash_fwd.5", 2.0), ("flash_bwd_fused.6", 4.0), ("ssd_bwd.7", 3.0),
             ("kda_conv_bwd.8", 1.0), ("gmm.9", 2.0), ("fusion.10", 4.0)]  # busy 20.0
STATS = {"layers": 4, "chunk": 128, "chunks": 128, "chunk_steps": 1024,
         "saved_state_bytes": 128 * 4096 * 128 * 2}


def hand_trace(durations):
    at, events = 0.0, []
    for name, seconds in durations:
        kind = f"custom-call(...), {TARGET}" if "fusion" not in name else "fusion(...)"
        events.append(Event(f"%{name} = f32[8,128]{{1,0}} {kind}", at, at + seconds))
        at += seconds
    return Trace({"/device:TPU:0": {"XLA Ops": events}})


def test_shares_of_the_new_scopes_and_the_counter():
    trace = hand_trace(DURATIONS)
    run = {"hlo_text": HLO_TEXT, "peaks": None, "ssd_stats": STATS}
    read = lambda name: manifest.metric_reader(name)(trace, run)
    assert read("ssd_time_pct") == pytest.approx(100 * 8.0 / 20)
    assert read("ssd_prep_time_pct") == pytest.approx(100 * 2.5 / 20)
    assert read("ssd_kernel_time_pct") == pytest.approx(100 * 4.5 / 20)
    assert read("ssd_conv_time_pct") == pytest.approx(100 * 1.5 / 20)
    assert read("ssd_out_time_pct") == pytest.approx(100 * 1.0 / 20)
    assert read("ssd_global_time_pct") == pytest.approx(100 * 6.0 / 20)
    assert read("ssd_saved_state_mb") == pytest.approx(134.217728)
    reduced = ssd_scopes.found(trace, run)
    assert reduced["experts"] == 2.0 and reduced["kernels"]["ssd_bwd"] == 3.0
    for name in ("ssd_kernel_roofline", "ssd_global_kernel_roofline",
                 "ssd_held_expert_matmul_roofline"):
        assert read(name) is None  # no peaks off the chip


def test_a_program_without_the_scopes_or_the_counters_gives_the_readers_nothing():
    """Another model, or the parent of the PR that brought them: nothing, and
    no exception."""
    other = HLO_TEXT.replace("/ssd/", "/kda/")
    trace = hand_trace(DURATIONS)
    for name in NEW_METRICS:
        read = manifest.metric_reader(name)
        assert read(trace, {"hlo_text": other, "peaks": PEAKS}) is None, name
        assert read(Trace({}), {"hlo_text": other, "peaks": PEAKS}) is None, name
        assert read(trace, {}) is None, name


def test_the_roofline_readers_divide_the_least_time_by_their_own_calls():
    shape, workload, _ = cell_shape()
    trace = hand_trace([("fusion.1", 0.01), ("kda_conv_fwd.2", 0.01), ("ssd_fwd.3", 0.05),
                        ("fusion.4", 0.01), ("flash_fwd.5", 0.1), ("flash_bwd_fused.6", 0.2),
                        ("ssd_bwd.7", 0.15), ("kda_conv_bwd.8", 0.01), ("gmm.9", 0.05),
                        ("fusion.10", 0.01)])
    run = {"hlo_text": HLO_TEXT, "workload": workload, "shape": shape, "traced_steps": 5,
           "peaks": PEAKS, "held_rows_traced": 5 * 4 * 6144.0}
    least = lambda cost: flops.roofline_seconds(*cost(shape, 5), PEAKS)[0]
    assert manifest.metric_reader("ssd_kernel_roofline")(trace, run) == pytest.approx(
        100 * least(flops_nemotron.ssd_kernel_cost) / 0.2)
    assert manifest.metric_reader("ssd_global_kernel_roofline")(trace, run) == pytest.approx(
        100 * least(flops_nemotron.global_kernel_cost) / 0.3)
    held = flops.roofline_seconds(
        *flops_nemotron.held_expert_matmul_cost(shape, 5 * 4 * 6144.0, 5 * 4), PEAKS)[0]
    assert manifest.metric_reader("ssd_held_expert_matmul_roofline")(trace, run) == pytest.approx(
        100 * held / 0.05)
    for name in ("ssd_kernel_roofline", "ssd_global_kernel_roofline",
                 "ssd_held_expert_matmul_roofline"):
        assert 0 < manifest.metric_reader(name)(trace, run) < 100


def test_the_accepted_readers_given_this_cell_read_its_trace():
    _, workload, _ = cell_shape()
    text = HLO_TEXT.replace("optimizer/mul", "jvp(mlp)/dispatch/gather").replace(
        "jvp(attention)/ssd/ssd_out/mul", "rematted_computation/attention/ssd/ssd_out/mul")
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
    assert workload["driver"] == "perfbench.harness.laguna_loop:run"  # D10: no eighth copy
    parts = {name: manifest.resolve(dotted) for name, dotted in workload["parts"].items()}
    assert parts["shape"] is build_nemotron.nemotron_shape
    assert parts["tiny"] is build_nemotron.tiny_nemotron
    assert parts["check"] is check_nemotron.check_initial
    assert parts["flops"] is flops_nemotron.train_flops_per_token
    assert parts["counters"] is check_nemotron.program_counters


@pytest.fixture(scope="module")
def tiny_state():
    from perfbench.harness import correct

    _, workload, config = manifest.load_cell(CELL)
    workload, config = build_nemotron.tiny_nemotron(*build.tiny(workload, config))
    shape = build_nemotron.nemotron_shape(workload, config)
    state, _, tokens = build.build_state(workload, config, jax.devices()[:1], 7)
    return state, shape, correct.first_micro_batch(state, tokens, workload)


def test_initial_check_passes_the_program(tiny_state):
    ok, numbers = check_nemotron.check_initial(*tiny_state)
    assert ok and numbers["held_overflow"] == 0.0
    for name in check_nemotron.TOLERANCE:
        assert numbers[f"{name}_err"] <= check_nemotron.TOLERANCE[name], name
    assert {f"held_rows_over_expected.layer{i}" for i in (1, 3, 6, 8)} <= set(numbers)
    assert {"ssd_out_err.layer0", "ssd_out_err.layer7", "global_out_err.layer5"} <= set(numbers)


@pytest.mark.parametrize("change, seen_by", [
    ({"gate_first": False}, "ssd_out"),
    ({"skip": False}, "ssd_out"),
    ({"conv_bias": False}, "ssd_out"),
    ({"group_shift": 1}, "ssd_grad"),
    ({"rotary": 10000.0}, "global_out"),
    ({"squared": False}, "moe_out"),
    ({"routed_scaling": 1.0}, "moe_out"),
    ({"shared": False}, "shared_out"),
    ({"held": (2, 3)}, "held_rows"),
], ids=["norm-then-gate", "no-skip", "no-conv-bias", "groups-one-off", "rotary", "relu",
        "no-scaling", "no-shared", "one-expert-fewer"])
def test_initial_check_refuses_a_wrong_reference(tiny_state, change, seen_by):
    state, shape, batch = tiny_state
    numbers = check_nemotron.check_initial_numbers(state, {**shape, **change}, batch)
    assert seen_by in check_nemotron.refused_by(numbers), numbers


def test_benchmark_entries_name_the_cell_and_its_metrics_in_this_order():
    benchmark = manifest.load_manifest()
    entry = [w for w in benchmark["workloads"] if w["name"] == CELL][0]
    assert (entry["config"], entry["traffic"], entry["chips"]) == (
        "nemotron-3-nano-30b-a3b", "share16-seq16384", 1)
    assert len(entry["why"]) <= 200 and "\n" not in entry["why"]
    names = [m["name"] for m in benchmark["per_layer"]]
    mine = [m["name"] for m in benchmark["per_layer"] if m.get("workloads") == [CELL]]
    at = names.index(NEW_METRICS[0])
    assert mine == NEW_METRICS and names[at:at + len(NEW_METRICS)] == NEW_METRICS
    listed = [m["name"] for m in benchmark["per_layer"]
              if CELL in m.get("workloads", []) and m["name"] not in NEW_METRICS]
    assert listed == ["recompute_time_pct", "moe_router_time_pct", "moe_dispatch_time_pct",
                      "expert_load_max_over_mean", "held_rows_over_expected"]
    for name in NEW_METRICS:
        module = __import__(f"perfbench.metrics.{name}", fromlist=["read"])
        declared = [m for m in benchmark["per_layer"] if m["name"] == name][0]
        assert (module.LAYER, module.UNIT, module.MOVES) == (
            declared["layer"], declared["unit"], declared["moves"])
    assert [c["name"] for c in benchmark["configs"]].count("nemotron-3-nano-30b-a3b") == 1
    assert len(benchmark["workloads"]) == 11 and len(benchmark["configs"]) == 9


def test_dry_run_of_the_cell_on_the_cpu():
    run = subprocess.run(
        [sys.executable, "perfbench/run.py", "--workload", CELL, "--seed", "5100000019",
         "--seconds", "1", "--trace", "1", "--allow-cpu"],
        cwd=manifest.ROOT, capture_output=True, text=True, timeout=900,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert run.returncode == 0, run.stderr[-2000:]
    last = json.loads(run.stdout.strip().splitlines()[-1])
    assert last["device"]["platform"] == "cpu" and last["failed"] == 0 and last["correct"] is True
    assert last["metrics"] == {}  # a dry run reports no metric
    assert "initial check ok=True" in run.stdout and "initial check, reading / limit:" in run.stdout
    for number in ("'held_overflow': 0.0", "'ssd_out_err'", "'ssd_grad_err'", "'global_out_err'",
                   "'global_grad_err'", "'moe_out_err'", "'shared_out_err'", "'expert_grad_err'",
                   "'held_rows_err'", "'per_position_err'", "'loss_err'"):
        assert number in run.stdout, number
    assert "layers ('ssd', 'mlp', 'ssd', 'mlp', 'ssd', 'global', 'mlp', 'ssd', 'mlp')" in run.stdout
    assert "('ssd_blocks', 4), ('mlp_blocks', 4), ('global_blocks', 1)" in run.stdout
    assert "held assignments that did not fit: 0" in run.stdout
    assert "perfbench: ssd_stats:" in run.stdout and "perfbench: attn_mask_stats:" in run.stdout
    for name in ("held_rows_over_expected", "expert_load_max_over_mean", "ssd_saved_state_mb"):
        assert f"not reported: {name}" in run.stdout, name
