"""What the SDAR-30B-A3B cell brings to the benchmark: its configuration file
against the catalog's entry, its FLOP and byte counts against hand
arithmetic, its generator's law, its reference's mask and blocked attention
against the whole matrix, the readers of the new scope, kernels and counters
on hand-made events, the cell's own initial check, and the cell's dry run."""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench.harness import bd_scopes, build_bd, flops, flops_bd, manifest, reference_bd
from perfbench.harness.trace_reduce import Event, Trace

CELL = "sdar-30b-a3b.share8-bd8192"
# The catalog's ``config`` for SDAR-30B-A3B-Chat (guides' architectures.jsonl).
CATALOG = {
    "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128, "hidden_act": "silu",
    "hidden_size": 2048, "intermediate_size": 6144, "max_position_embeddings": 32768,
    "max_window_layers": 48, "mlp_only_layers": [], "model_type": "sdar_moe",
    "moe_intermediate_size": 768, "norm_topk_prob": True, "num_attention_heads": 32,
    "num_experts": 128, "num_experts_per_tok": 8, "num_hidden_layers": 48,
    "num_key_value_heads": 4, "rms_norm_eps": 1e-06, "rope_scaling": None,
    "rope_theta": 1000000, "sliding_window": None, "tie_word_embeddings": False,
    "use_sliding_window": False, "vocab_size": 151936}
REDUCED = ["num_hidden_layers", "num_experts", "vocab_size"]
NEW_METRICS = ["bd_kernel_time_pct", "bd_kernel_roofline", "bd_live_fill_pct",
               "bd_noise_time_pct", "bd_masked_share", "bd_held_expert_matmul_roofline"]
PEAKS = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}


def cell_shape():
    _, workload, config = manifest.load_cell(CELL)
    return build_bd.bd_shape(workload, config), workload, config


def test_config_file_holds_every_catalog_key_and_cuts_three_counts():
    shape, workload, config = cell_shape()
    kept = {k: v for k, v in CATALOG.items() if k not in REDUCED}
    assert {k: config[k] for k in kept} == kept
    assert list(config["reduced"]) == REDUCED
    assert (config["num_hidden_layers"], config["num_experts"], config["vocab_size"]) == (
        6, 16, 18992)
    # both counts are stated, and the deployment: eight chips share each layer
    assert (config["num_hidden_layers_published"], config["num_experts_published"],
            config["vocab_size_published"]) == (48, 128, 151936)
    assert config["vocab_size_published"] // config["vocab_size"] == 8
    assert "eight chips sharing each layer" in config["deployment"]
    for assumed in ("block_length", "noise", "logit_shift", "mask_token_id", "qk_norm",
                    "qk_norm_scale_init", "router_aux_loss_coef", "document_length"):
        assert assumed in config["assumed"], assumed
    entry = [c for c in manifest.load_manifest()["configs"] if c["name"] == "sdar-30b-a3b"][0]
    assert entry["reduced"] == REDUCED and entry["source"] == config["source"]
    assert (workload["depth"], workload["seq_len"]) == (6, config["document_length"])
    assert (shape["experts"], shape["held"], shape["experts_per_token"]) == (128, (0, 16), 8)
    assert (shape["block"], shape["mask_id"], shape["vocab"]) == (4, 18991, 18992)
    assert shape["heads"] * shape["head_dim"] == 2 * shape["hidden"]  # 32 x 128 over 2048


def test_forward_flops_match_hand_arithmetic():
    """A document of 8192 in blocks of 4, 6 layers, 16 of 128 experts held,
    counted by the data token."""
    shape, _, _ = cell_shape()
    projections = 2 * 2048 * (4096 + 2 * 512) + 2 * 4096 * 2048
    router, routed = 2 * 2048 * 128, 1.0 * 6 * 2048 * 768
    scores = 4 * (8192 + 4) * 4096
    head = 2 * 2048 * 18992
    assert flops_bd.expected_routed_rows_per_stream_token(shape) == 1.0
    assert flops_bd.true_pairs(shape) == 8192 * 8192 + 8192 * 4
    total = 6 * (2 * (projections + router + routed) + scores) + head
    assert flops_bd.forward_flops_per_token(shape) == total
    assert round(total / 1e6) == 1456
    assert flops_bd.train_flops_per_token(shape) == 3 * total
    share = lambda x: round(100 * x / total)
    assert (share(6 * scores), share(12 * projections), share(head), share(12 * routed)) == (
        55, 31, 5, 8)
    assert round(8192 * total / 1e12, 2) == 11.93  # TF a step, forward


def test_kernel_cost_counts_the_true_pairs_and_is_compute_bound():
    shape, _, _ = cell_shape()
    operations, bytes_ = flops_bd.bd_kernel_cost(shape, 1)
    calls, pairs = 32 * 6, 8192 * 8192 + 8192 * 4
    assert operations == calls * 14 * pairs * 128
    assert bytes_ == calls * ((4 + 8) * 16384 * 128 * 2 + 3 * 16384 * 4)
    least, bound = flops.roofline_seconds(operations, bytes_, PEAKS)
    assert bound == "compute" and round(1e3 * least, 1) == 117.3
    # less than causal over the same stream would be, more than a document's causal pass
    causal_stream = calls * 14 * (16384 ** 2 / 2) * 128
    assert causal_stream / 2 < operations < causal_stream


def test_the_generator_draws_zipf_ids_below_the_mask_token():
    shape, workload, _ = cell_shape()
    table = build_bd.token_table(shape, {**workload, "dataset_rows": 8}, 2147483777)
    assert table.shape == (8, 8192) and table.dtype == np.int32
    assert table.min() == 0 and table.max() < shape["mask_id"]
    again = build_bd.token_table(shape, {**workload, "dataset_rows": 8}, 2147483777)
    other = build_bd.token_table(shape, {**workload, "dataset_rows": 8}, 2147483778)
    assert (again == table).all() and (other != table).mean() > 0.5
    counts = np.bincount(table.ravel(), minlength=shape["vocab"])
    harmonic = np.sum(1.0 / np.arange(1, shape["mask_id"] + 1))
    assert counts[0] / table.size == pytest.approx(1 / harmonic, rel=0.05)  # about 9.6 %
    assert counts[1] / counts[0] == pytest.approx(1 / 2, rel=0.1)
    entropy = -np.sum(counts[counts > 0] / table.size * np.log(counts[counts > 0] / table.size))
    assert 6.5 < entropy < np.log(shape["mask_id"])  # the law's own is 7.5 nats, under 9.85


TINY = {**build_bd.bd_shape(
    {"seq_len": 16, "held_rows_factor": None},
    {"hidden_size": 32, "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 8,
     "qk_norm": "head", "rope_theta": 10000, "rms_norm_eps": 1e-6, "moe_intermediate_size": 16,
     "num_experts_published": 6, "num_experts": 3, "experts_held_first": 2,
     "num_experts_per_tok": 2, "norm_topk_prob": True, "router_aux_loss_coef": 0.001,
     "vocab_size": 40, "num_hidden_layers": 1, "block_length": 4, "mask_token_id": 39,
     "noise": {"t_min": 0.001, "t_max": 1.0, "loss_weight": "1/t"}})}


def test_the_references_mask_is_the_three_part_rule():
    """L 8, blocks of 4: written out by hand, block by block of the 16 x 16."""
    pos = jnp.arange(16)
    got = np.asarray(reference_bd.allowed(TINY, pos, pos, 16)).astype(int)
    one, none = np.ones((4, 4), int), np.zeros((4, 4), int)
    want = np.block([
        [one, none, none, none],   # noisy block 0: itself, no clean past
        [none, one, one, none],    # noisy block 1: itself, clean block 0
        [none, none, one, none],   # clean block 0: itself
        [none, none, one, one],    # clean block 1: clean blocks 0 and 1
    ])
    np.testing.assert_array_equal(got, want)
    le = np.asarray(reference_bd.allowed({**TINY, "mask": "block_diffusion_le"}, pos, pos, 16))
    assert le[0, 8] and le[4, 12] and not got[0, 8]  # the own clean block seen: the control
    causal = np.asarray(reference_bd.allowed({**TINY, "mask": "causal"}, pos, pos, 16))
    np.testing.assert_array_equal(causal, np.tril(np.ones((16, 16), bool)))


def test_blocked_attention_matches_the_whole_matrix(monkeypatch):
    keys = jax.random.split(jax.random.key(1), 3)
    q = jax.random.normal(keys[0], (32, 4, 8))
    k, v = (jax.random.normal(key, (32, 2, 8)) for key in keys[1:])
    pos = jnp.arange(32)
    mask = reference_bd.allowed(TINY, pos, pos, 32)
    scores = jnp.einsum("qhd,khd->hqk", q, jnp.repeat(k, 2, axis=1)) * 8 ** -0.5
    scores = jnp.where(mask[None], scores, -jnp.inf)
    want = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, -1), jnp.repeat(v, 2, axis=1))
    monkeypatch.setattr(reference_bd, "QUERY_BLOCK", 8)
    np.testing.assert_allclose(
        reference_bd._attention(TINY, q, k, v), want.reshape(32, 32), rtol=1e-5, atol=1e-6)


def test_routed_mlp_matches_a_direct_per_token_loop():
    """Gates renormalised over a token's two choices before the held part is
    taken: a token with one choice held keeps that choice's renormalised gate."""
    keys = jax.random.split(jax.random.key(2), 4)
    h = np.asarray(jax.random.normal(keys[0], (12, 32)))
    w = {"router": np.asarray(jax.random.normal(keys[1], (32, 6))),
         "moe_wgu": 0.3 * np.asarray(jax.random.normal(keys[2], (3, 32, 32))),
         "moe_wd": 0.3 * np.asarray(jax.random.normal(keys[3], (3, 16, 32)))}
    got, statistics = reference_bd._routed_mlp(TINY, jnp.asarray(h), jax.tree.map(jnp.asarray, w))
    want, counts = np.zeros_like(h), np.zeros(6, int)
    silu = lambda x: x / (1 + np.exp(-x))
    for n in range(12):
        logits = h[n] @ w["router"]
        p = np.exp(logits - logits.max()) / np.exp(logits - logits.max()).sum()
        chosen = np.argsort(-p)[:2]
        counts[chosen] += 1
        for e in chosen:
            if 2 <= e < 5:
                gate_up, down = w["moe_wgu"][e - 2], w["moe_wd"][e - 2]
                out = (silu(h[n] @ gate_up[:, :16]) * (h[n] @ gate_up[:, 16:])) @ down
                want[n] += p[e] / p[chosen].sum() * out
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)
    np.testing.assert_array_equal(statistics["assignments"], counts)


STEP = "jit(train_step)"
HLO_TEXT = """HloModule jit_train_step

ENTRY %main.1 (p0: f32[8,128]) -> f32[8,128] {
  %p0 = f32[8,128]{1,0} parameter(0)
  %fusion.1 = f32[8,128]{1,0} fusion(%p0), kind=kLoop, calls=%f1, metadata={op_name="jit(train_step)/embed/noise/lt"}
  %flash_fwd.2 = f32[8,128]{1,0} custom-call(%fusion.1), custom_call_target="tpu_custom_call", metadata={op_name="jit(train_step)/jvp(attention)/jit(flash_attention)/pallas_call"}
  %flash_bwd_fused.3 = f32[8,128]{1,0} custom-call(%flash_fwd.2), custom_call_target="tpu_custom_call", metadata={op_name="jit(train_step)/transpose(jvp(attention))/pallas_call"}
  %gmm.4 = f32[8,128]{1,0} custom-call(%flash_bwd_fused.3), custom_call_target="tpu_custom_call", metadata={op_name="jit(train_step)/jvp(mlp)/experts/jit(gmm)/pallas_call"}
  %fusion.5 = f32[8,128]{1,0} fusion(%gmm.4), kind=kLoop, calls=%f5, metadata={op_name="jit(train_step)/jvp(mlp)/router/dot_general"}
  %fusion.6 = f32[8,128]{1,0} fusion(%fusion.5), kind=kLoop, calls=%f6, metadata={op_name="jit(train_step)/jvp(embed)/gather"}
  ROOT %fusion.7 = f32[8,128]{1,0} fusion(%fusion.6), kind=kLoop, calls=%f7, metadata={op_name="jit(train_step)/optimizer/mul"}
}
"""
TARGET = 'custom_call_target="tpu_custom_call"'
DURATIONS = [("fusion.1", 1.0), ("flash_fwd.2", 3.0), ("flash_bwd_fused.3", 5.0), ("gmm.4", 1.0),
             ("fusion.5", 4.0), ("fusion.6", 2.0), ("fusion.7", 4.0)]
STATS = {"true_pairs": 8192 * 8192 + 8192 * 4, "fwd_live_tiles": 80, "fwd_tiles": 256,
         "fwd_tile_pairs": 1024 * 1024, "bwd_live_tiles": 80, "bwd_tiles": 256,
         "bwd_tile_pairs": 1024 * 1024}


def hand_trace(durations):
    at, events = 0.0, []
    for name, seconds in durations:
        kind = f"custom-call(...), {TARGET}" if "fusion" not in name else "fusion(...)"
        events.append(Event(f"%{name} = f32[8,128]{{1,0}} {kind}", at, at + seconds))
        at += seconds
    return Trace({"/device:TPU:0": {"XLA Ops": events}})


@pytest.mark.parametrize("op_name, expected", [
    (f"{STEP}/embed/noise/lt", True),
    (f"{STEP}/jvp(embed)/noise/select_n", True),
    (f"{STEP}/jvp(mlp)/combine/add;{STEP}/embed/noise/concatenate", True),
    (f"{STEP}/jvp(embed)/gather", False),  # the embedding itself
    (f"{STEP}/noise/embed/lt", False),  # the scope lies under embed, not over it
    (f"{STEP}/jvp(mlp)/noise/mul", False),
    ("", False),
])
def test_under_noise(op_name, expected):
    assert bd_scopes.under_noise(op_name) is expected


def test_shares_of_the_new_scope_kernels_and_counters():
    trace = hand_trace(DURATIONS)
    run = {"hlo_text": HLO_TEXT, "peaks": None, "bd_mask_stats": STATS,
           "bd_masked_share_pct": 49.7}
    read = lambda name: manifest.metric_reader(name)(trace, run)
    assert read("bd_noise_time_pct") == pytest.approx(5.0)
    assert read("bd_kernel_time_pct") == pytest.approx(40.0)  # gmm is not one of them
    assert read("bd_live_fill_pct") == pytest.approx(80.0, abs=0.05)
    assert read("bd_masked_share") == 49.7
    assert read("bd_kernel_roofline") is None  # no peaks off the chip
    assert read("bd_held_expert_matmul_roofline") is None


def test_a_program_without_the_scope_or_the_counters_gives_the_readers_nothing():
    """Another model, or the parent of the PR that brought them: nothing, and
    no exception."""
    other = HLO_TEXT.replace("/embed/noise/", "/embed/")
    trace = hand_trace(DURATIONS)
    for name in NEW_METRICS:
        read = manifest.metric_reader(name)
        assert read(trace, {"hlo_text": other, "peaks": PEAKS}) is None, name
        assert read(Trace({}), {"hlo_text": other, "peaks": PEAKS}) is None, name
        assert read(trace, {}) is None, name


def test_the_roofline_reader_divides_the_least_time_by_the_time_taken():
    shape, workload, _ = cell_shape()
    trace = hand_trace([("fusion.1", 0.01), ("flash_fwd.2", 0.3), ("flash_bwd_fused.3", 0.7),
                        ("gmm.4", 0.05)])
    run = {"hlo_text": HLO_TEXT, "workload": workload, "shape": shape, "traced_steps": 5,
           "peaks": PEAKS}
    # 5 steps x 117.26 ms least, over 1000 ms in the two kernels
    assert manifest.metric_reader("bd_kernel_roofline")(trace, run) == pytest.approx(
        58.6, abs=0.05)


def test_the_held_experts_roofline_is_over_the_scope_experts():
    """``held_expert_matmul_roofline``'s arithmetic over the seconds ``bd_scopes``
    finds under ``mlp`` / ``experts`` (here the one ``gmm`` call)."""
    from perfbench.harness import flops_mla

    shape, workload, _ = cell_shape()
    trace = hand_trace([("fusion.1", 0.01), ("flash_fwd.2", 0.3), ("gmm.4", 0.05)])
    rows = 5 * 6 * 16384.0
    run = {"hlo_text": HLO_TEXT, "workload": workload, "shape": shape, "traced_steps": 5,
           "peaks": PEAKS, "held_rows_traced": rows}
    least = flops.roofline_seconds(*flops_mla.held_expert_matmul_cost(shape, rows, 30), PEAKS)[0]
    assert manifest.metric_reader("bd_held_expert_matmul_roofline")(trace, run) == pytest.approx(
        100 * least / 0.05)
    assert manifest.metric_reader("bd_held_expert_matmul_roofline")(
        trace, {**run, "held_rows_traced": 0.0}) is None


def test_the_accepted_readers_given_this_cell_read_its_trace():
    """``recompute_time_pct``, ``moe_router_time_pct`` and ``moe_dispatch_time_pct``
    find their scopes in this cell's step; the two counters are the driver's
    facts; ``attn_kernel_roofline``, which knows causal and no mask only, finds
    nothing to read in a cell whose file says ``flash_block_diffusion``."""
    _, workload, _ = cell_shape()
    text = HLO_TEXT.replace("jvp(embed)/gather", "jvp(mlp)/dispatch/gather").replace(
        "jvp(attention)/jit", "rematted_computation/attention/jit")
    trace = hand_trace(DURATIONS)
    run = {"hlo_text": text, "expert_load_max_over_mean": 4.2, "held_rows_over_expected": 1.1,
           "peaks": PEAKS, "workload": workload}
    read = lambda name: manifest.metric_reader(name)(trace, run)
    assert read("moe_router_time_pct") == pytest.approx(20.0)
    assert read("moe_dispatch_time_pct") == pytest.approx(10.0)
    assert read("recompute_time_pct") == pytest.approx(15.0)
    assert read("expert_load_max_over_mean") == 4.2
    assert read("held_rows_over_expected") == 1.1
    assert workload["attention"] == "flash_block_diffusion"
    assert read("attn_kernel_roofline") is None
    assert read("held_expert_matmul_roofline") is None  # its scopes are latent attention's


@pytest.fixture(scope="module")
def tiny_state():
    from perfbench.harness import bd_loop, build, correct

    _, workload, config = manifest.load_cell(CELL)
    workload, config = build.tiny(workload, config)
    config = build_bd.tiny_bd(config)
    jax.config.update("jax_default_prng_impl", "rbg")
    shape = build_bd.bd_shape(workload, config)
    state, _, tokens = bd_loop.build_state(workload, config, shape, jax.devices()[:1], 7)
    assert tokens.max() < shape["mask_id"]
    return state, shape, correct.first_micro_batch(state, tokens, workload)


def test_initial_check_passes_the_program(tiny_state):
    from perfbench.harness import bd_loop

    state, shape, batch = tiny_state
    assert float(state.params["blocks"]["q_norm"][0, 0]) == 4.0  # the cell's start
    assert state.model_config.held_rows_factor == 2.5  # the timed buffer
    ok, numbers = bd_loop.check_initial(state, shape, batch)
    assert ok, numbers
    for name, limit in bd_loop.TOLERANCE.items():
        assert numbers[f"{name}_err"] <= limit, name
    for name in ("attn_out", "first_blocks", "attn_grad", "moe_out", "expert_grad",
                 "per_position", "objective_grad"):
        assert numbers[f"{name}_err"] > 0, name  # bfloat16 against float32: never equal
    assert 0.0 < numbers["masked_share"] < 1.0
    assert 0.3 < numbers["clear_tokens_share_min"] < 1.0


@pytest.mark.parametrize("change, seen_by", [
    ({"mask": "causal"}, "attn_out"),
    ({"mask": "block_diffusion_le"}, "first_blocks"),
    ({"positions": "stream"}, "attn_grad"),
    ({"qk_norm": "whole"}, "attn_out"),
    ({"norm_topk_prob": False}, "moe_out"),
    ({"held": "one fewer"}, "held_rows"),
    ({"loss_weight": "one"}, "loss"),
    ({"loss_over": "masked"}, "loss"),
])
def test_initial_check_refuses_a_wrong_reference(tiny_state, change, seen_by):
    from perfbench.harness import bd_loop

    state, shape, batch = tiny_state
    if change.get("held"):
        change = {"held": (shape["held"][0], shape["held"][1] - 1)}
    ok, numbers = bd_loop.check_initial(state, {**shape, **change}, batch)
    assert not ok and numbers[f"{seen_by}_err"] > bd_loop.TOLERANCE[seen_by], numbers


def test_initial_check_refuses_a_buffer_that_drops_rows(tiny_state):
    """A fault of the program's bounded buffer, not of the reference: the rows
    it drops show in what the held experts add and in their gradients, beside
    the overflow count that alone fails a run."""
    import dataclasses

    from perfbench.harness import bd_loop

    state, shape, batch = tiny_state
    short = dataclasses.replace(state.model_config, held_rows_factor=0.5)
    numbers = bd_loop.check_layers(state, shape, batch, model_config=short)
    assert numbers["held_overflow"] > 0
    assert {"moe_out", "expert_grad"} <= set(bd_loop.refused_by(numbers))


def test_the_sublayers_chained_are_the_gradient_of_the_whole(tiny_state):
    """The check walks the reference a sublayer at a time, forward and then
    backward by ``jax.vjp``: the leaf gradients it holds the program to are
    ``jax.grad`` of the reference's training loss."""
    from perfbench.harness import bd_loop
    from distributed_llm_training_benchmark_framework_tpu.models import tinygpt

    state, shape, batch = tiny_state
    params = state.params
    t, masked = tinygpt.bd_noise(state.model_config, bd_loop.first_step_key(), batch.shape)
    reference = bd_loop.Reference(shape)
    x, inputs = reference.embed(params, batch, masked), []
    for i in range(shape["layers"]):
        h = reference.attention(bd_loop._layer(params, i), x)
        inputs.append((x, h))
        x = reference.routed(bd_loop._layer(params, i), h)[0]
    (_, cotangent) = reference.head(params, x, batch, t, masked)
    with jax.default_matmul_precision("highest"):
        want = jax.grad(lambda p: reference_bd.loss(shape, p, batch, t, masked))(params)["blocks"]
    for i in reversed(range(shape["layers"])):
        x, h = inputs[i]
        d_routed, cotangent = reference.routed_back(bd_loop._layer(params, i), h, cotangent)
        d_attention, cotangent = reference.attention_back(bd_loop._layer(params, i), x, cotangent)
        for leaves, got in ((bd_loop.ROUTED_LEAVES, d_routed), (bd_loop.ATTENTION_LEAVES, d_attention)):
            for k in leaves:
                np.testing.assert_allclose(got[k], want[k][i], rtol=2e-4, atol=1e-7, err_msg=k)


def test_the_fall_is_measured_against_what_a_line_leaves():
    from perfbench.harness import bd_loop

    noise = np.random.default_rng(0).normal(0, 0.1, 50)
    losses = 10 - 0.1 * np.arange(50) + 0.001 * np.arange(50) ** 2 + noise
    fall, spread = bd_loop.fall_and_spread(list(losses), 5)
    assert fall == pytest.approx(4.5 - 2.2, abs=0.2)  # the line, less the bend
    assert spread == pytest.approx(0.1 / 5 ** 0.5, rel=0.6)  # a window mean's own noise
    assert fall > 5 * spread


def test_benchmark_entries_name_the_cell_and_its_metrics():
    benchmark = manifest.load_manifest()
    entry = [w for w in benchmark["workloads"] if w["name"] == CELL][0]
    assert (entry["config"], entry["traffic"], entry["chips"]) == (
        "sdar-30b-a3b", "share8-bd8192", 1)
    assert len(entry["why"]) <= 200 and "data tokens" in entry["why"]
    mine = [m["name"] for m in benchmark["per_layer"] if m.get("workloads") == [CELL]]
    assert mine == NEW_METRICS
    # the accepted readers that serve this driver's facts unchanged list the cell; the one
    # that prices a causal S^2 / 2 over every Mosaic call lists the cells it had
    listed = [m["name"] for m in benchmark["per_layer"]
              if CELL in m.get("workloads", []) and m["name"] not in NEW_METRICS]
    assert listed == ["recompute_time_pct", "moe_router_time_pct", "moe_dispatch_time_pct",
                      "expert_load_max_over_mean", "held_rows_over_expected"]
    roofline = [m for m in benchmark["per_layer"] if m["name"] == "attn_kernel_roofline"][0]
    assert CELL not in roofline["workloads"] and len(roofline["workloads"]) == 6
    for name in NEW_METRICS:
        module = __import__(f"perfbench.metrics.{name}", fromlist=["read"])
        declared = [m for m in benchmark["per_layer"] if m["name"] == name][0]
        assert (module.LAYER, module.UNIT, module.MOVES) == (
            declared["layer"], declared["unit"], declared["moves"])


def test_dry_run_of_the_cell_on_the_cpu():
    run = subprocess.run(
        [sys.executable, "perfbench/run.py", "--workload", CELL, "--seed", "3000000019",
         "--seconds", "1", "--trace", "1", "--allow-cpu"],
        cwd=manifest.ROOT, capture_output=True, text=True, timeout=600,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert run.returncode == 0, run.stderr[-2000:]
    last = json.loads(run.stdout.strip().splitlines()[-1])
    assert last["device"]["platform"] == "cpu" and last["failed"] == 0
    assert last["metrics"] == {}  # a dry run reports no metric
    assert "initial check ok=True" in run.stdout
    for number in ("'held_overflow': 0.0", "'expert_grad_err'", "'attn_grad_err'", "'attn_out_err'",
                   "'moe_out_err'", "'first_blocks_err'", "'held_rows_err'", "'loss_err'",
                   "'masked_share'"):
        assert number in run.stdout, number
    assert "data tokens a step (a stream of" in run.stdout
    assert "held assignments that did not fit: 0" in run.stdout
    assert "window means fall by" in run.stdout
    for name in ("held_rows_over_expected", "expert_load_max_over_mean", "bd_live_fill_pct",
                 "bd_masked_share"):
        assert f"not reported: {name}" in run.stdout, name
