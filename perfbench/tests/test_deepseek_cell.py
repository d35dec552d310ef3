"""What the DeepSeek-V2-Lite cell brings to the benchmark: its configuration
file against the catalog's entry, its FLOP and byte counts against hand
arithmetic, its reference's routed layer against a direct per-token loop, the
readers of the new scopes and kernels on hand-made events, and the cell's dry
run."""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench.harness import build_mla, flops, flops_mla, manifest, mla_scopes, reference_mla
from perfbench.harness.trace_reduce import Event, Trace

CELL = "deepseek-v2-lite.share8-seq8192"
# The catalog's ``config`` for DeepSeek-V2-Lite (guides' architectures.jsonl).
CATALOG = {
    "attention_bias": False, "first_k_dense_replace": 1, "hidden_act": "silu",
    "hidden_size": 2048, "intermediate_size": 10944, "kv_lora_rank": 512,
    "max_position_embeddings": 163840, "model_type": "deepseek_v2",
    "moe_intermediate_size": 1408, "moe_layer_freq": 1, "n_group": 1, "n_routed_experts": 64,
    "n_shared_experts": 2, "norm_topk_prob": False, "num_attention_heads": 16,
    "num_experts_per_tok": 6, "num_hidden_layers": 27, "num_key_value_heads": 16,
    "q_lora_rank": None, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 40, "mscale": 0.707,
                     "mscale_all_dim": 0.707, "original_max_position_embeddings": 4096,
                     "type": "yarn"},
    "rope_theta": 10000, "routed_scaling_factor": 1, "scoring_func": "softmax", "seq_aux": True,
    "tie_word_embeddings": False, "topk_group": 1, "topk_method": "greedy", "v_head_dim": 128,
    "vocab_size": 102400}
REDUCED = ["num_hidden_layers", "n_routed_experts", "vocab_size"]
PEAKS = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}


def cell_shape():
    _, workload, config = manifest.load_cell(CELL)
    return build_mla.mla_shape(workload, config), workload, config


def test_config_file_holds_every_catalog_key_and_cuts_three_counts():
    shape, workload, config = cell_shape()
    kept = {k: v for k, v in CATALOG.items() if k not in REDUCED}
    assert {k: config[k] for k in kept} == kept
    assert list(config["reduced"]) == REDUCED
    assert (config["num_hidden_layers"], config["n_routed_experts"], config["vocab_size"]) == (
        6, 8, 12800)
    # both counts are stated, and the deployment: eight chips share each layer
    assert (config["num_hidden_layers_published"], config["n_routed_experts_published"],
            config["vocab_size_published"]) == (27, 64, 102400)
    assert config["vocab_size_published"] // config["vocab_size"] == 8
    assert "eight chips sharing each layer" in config["deployment"]
    assert "aux_loss_alpha" in config["assumed"]
    entry = [c for c in manifest.load_manifest()["configs"] if c["name"] == "deepseek-v2-lite"][0]
    assert entry["reduced"] == REDUCED and workload["depth"] == 6
    assert (shape["experts"], shape["held"], shape["experts_per_token"]) == (64, (0, 8), 6)
    assert round(shape["softmax_scale"], 6) == 0.114721
    assert shape["head_dim"] == 160  # 4 S^2 x 160 = 2 S^2 (192 + 128)


def test_forward_flops_match_hand_arithmetic():
    """S 8192 causal, 1 dense + 5 routed layers, 8 of 64 experts held."""
    shape, _, _ = cell_shape()
    projections = 2 * 2048 * (16 * 192 + 576 + 2048) + 2 * 512 * 16 * 256
    core = 2 * 4096 * 16 * (192 + 128)
    dense = 6 * 2048 * 10944
    shared = 6 * 2048 * 2816
    routed = 0.75 * 6 * 2048 * 1408
    router = 2 * 2048 * 64
    head = 2 * 2048 * 12800
    assert flops_mla.attention_projection_flops_per_token(shape) == projections
    assert flops_mla.attention_core_flops_per_token(shape) == core
    assert flops_mla.expected_routed_rows_per_token(shape) == 0.75
    total = 6 * (projections + core) + dense + 5 * (shared + routed + router) + head
    assert flops_mla.forward_flops_per_token(shape) == total
    assert round(total / 1e6) == 843
    assert flops_mla.train_flops_per_token(shape) == 3 * total
    share = lambda x: round(100 * x / total)
    assert (share(6 * core), share(6 * projections), share(5 * shared), share(dense),
            share(head), share(5 * routed)) == (30, 20, 21, 16, 6, 8)


def test_kernel_cost_counts_each_width_once_and_is_compute_bound():
    shape, _, _ = cell_shape()
    operations, bytes_ = flops_mla.mla_kernel_cost(shape, 2)
    calls = 2 * 16 * 6
    assert operations == calls * 0.5 * 2 * 8192 ** 2 * ((192 + 128) + (3 * 192 + 2 * 128))
    forward = 8192 * 2 * (2 * 192 + 2 * 128) + 8192 * 4
    backward = 8192 * 2 * (4 * 192 + 4 * 128) + 2 * 8192 * 4
    assert bytes_ == calls * (forward + backward)
    least, bound = flops.roofline_seconds(operations, bytes_, PEAKS)
    assert bound == "compute" and round(1e3 * least, 1) == 75.3
    # never more than the true work through the one-width reader at head_dim 160
    old, _ = flops.attention_pass_cost(shape, 2, ("fwd", "bwd"))
    assert old <= operations


def test_held_expert_cost_follows_the_counted_rows():
    shape, _, _ = cell_shape()
    rows, layer_steps = 5 * 12288, 5
    operations, bytes_ = flops_mla.held_expert_matmul_cost(shape, rows, layer_steps)
    assert operations == 3 * rows * 6 * 2048 * 1408
    moved = rows * (2048 + 2816 + 1408 + 2048) + layer_steps * 8 * 3 * 2048 * 1408
    assert bytes_ == 2 * 3 * moved
    twice, _ = flops_mla.held_expert_matmul_cost(shape, 2 * rows, layer_steps)
    assert twice == 2 * operations


TINY = {**build_mla.mla_shape(
    {"seq_len": 12, "held_rows_factor": None},
    {"hidden_size": 32, "num_attention_heads": 2, "num_key_value_heads": 2,
     "qk_nope_head_dim": 8, "qk_rope_head_dim": 4, "v_head_dim": 8, "kv_lora_rank": 16,
     "rope_theta": 10000, "rope_scaling": None, "rms_norm_eps": 1e-6,
     "first_k_dense_replace": 1, "intermediate_size": 48, "moe_intermediate_size": 16,
     "n_shared_experts": 2, "n_routed_experts_published": 6, "n_routed_experts": 3,
     "experts_held_first": 2, "num_experts_per_tok": 2, "norm_topk_prob": False,
     "routed_scaling_factor": 1, "aux_loss_alpha": 0.001, "seq_aux": True,
     "tie_word_embeddings": False, "causal": True, "vocab_size": 64, "num_hidden_layers": 2})}


def test_routed_mlp_matches_a_direct_per_token_loop():
    """Each token alone: its 2 largest of 6 router probabilities; of those, the
    experts this chip holds (2, 3, 4) run their SwiGLU, weighted by the
    probabilities as they are; the shared experts run for every token."""
    m = TINY
    D, F, Fs, E = m["hidden"], m["expert_width"], m["shared_width"], m["experts"]
    first, count = m["held"]
    keys = jax.random.split(jax.random.key(0), 6)
    w = {"router": 0.5 * jax.random.normal(keys[0], (D, E)),
         "moe_wgu": 0.3 * jax.random.normal(keys[1], (count, D, 2 * F)),
         "moe_wd": 0.3 * jax.random.normal(keys[2], (count, F, D)),
         "shared_wgu": 0.3 * jax.random.normal(keys[3], (D, 2 * Fs)),
         "shared_wd": 0.3 * jax.random.normal(keys[4], (Fs, D))}
    w64 = jax.tree.map(lambda t: np.asarray(t, np.float64), w)
    h = np.asarray(jax.random.normal(keys[5], (m["seq_len"], D)), np.float64)
    swiglu = lambda x, gu, d, f: ((x @ gu[:, :f]) / (1 + np.exp(-(x @ gu[:, :f])))
                                  * (x @ gu[:, f:])) @ d
    want, held_assignments = np.zeros_like(h), 0
    for t, x in enumerate(h):
        logits = x @ w64["router"]
        p = np.exp(logits - logits.max())
        p /= p.sum()
        want[t] = swiglu(x, w64["shared_wgu"], w64["shared_wd"], Fs)
        for e in np.argsort(-p)[: m["experts_per_token"]]:
            if first <= e < first + count:
                held_assignments += 1
                want[t] += p[e] * swiglu(x, w64["moe_wgu"][e - first], w64["moe_wd"][e - first], F)
    with jax.default_matmul_precision("highest"):
        got, statistics = reference_mla._routed_mlp(m, jnp.asarray(h, jnp.float32), w)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)
    assert int(statistics["assignments"].sum()) == m["seq_len"] * m["experts_per_token"]
    assert int(statistics["assignments"][first:first + count].sum()) == held_assignments
    assert 0 < held_assignments < m["seq_len"] * m["experts_per_token"]


def test_blocked_attention_matches_the_whole_matrix(monkeypatch):
    m = {**TINY, "yarn": None}
    keys = jax.random.split(jax.random.key(1), 3)
    q, k = (jax.random.normal(key, (12, 2, 12)) for key in keys[:2])
    v = jax.random.normal(keys[2], (12, 2, 8))
    scores = jnp.einsum("qhd,khd->hqk", q, k) * 12 ** -0.5
    scores = jnp.where(jnp.tril(jnp.ones((12, 12), bool)), scores, -jnp.inf)
    want = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, -1), v).reshape(12, 16)
    monkeypatch.setattr(reference_mla, "QUERY_BLOCK", 4)
    np.testing.assert_allclose(reference_mla._attention(m, q, k, v), want, rtol=1e-5, atol=1e-6)


STEP = "jit(train_step)"


@pytest.mark.parametrize("op_name, expected", [
    (f"{STEP}/jvp(attention)/mla_proj/dot_general", ("attention", "mla_proj")),
    (f"{STEP}/jvp(attention)/mla_core/jit(flash_attention)/pallas_call", ("attention", "mla_core")),
    (f"{STEP}/transpose(jvp(attention))/mla_out/dot_general", ("attention", "mla_out")),
    (f"{STEP}/jvp(mlp)/shared/dot_general", ("mlp", "shared")),
    (f"{STEP}/transpose(jvp(mlp))/experts/jit(tgmm)/pallas_call", ("mlp", "experts")),
    # under remat or a scan the wrapper sits above the scopes
    (f"{STEP}/transpose(jvp())/rematted_computation/attention/mla_core/mul",
     ("attention", "mla_core")),
    # the first path that names a module and a part
    (f"{STEP}/jvp(embed)/gather;{STEP}/jvp(mlp)/combine/add", ("mlp", "combine")),
    # a part outside its module, a module without a part, nothing
    (f"{STEP}/jvp(mlp)/mla_core/mul", None),
    (f"{STEP}/jvp(attention)/shared/mul", None),
    (f"{STEP}/jvp(attention)/mul", None),
    ("", None),
])
def test_part(op_name, expected):
    assert mla_scopes.part(op_name) == expected


HLO_TEXT = """HloModule jit_train_step

ENTRY %main.1 (p0: f32[8,128]) -> f32[8,128] {
  %p0 = f32[8,128]{1,0} parameter(0)
  %fusion.1 = f32[8,128]{1,0} fusion(%p0), kind=kLoop, calls=%f1, metadata={op_name="jit(train_step)/jvp(attention)/mla_proj/dot_general"}
  %flash_fwd.2 = f32[8,128]{1,0} custom-call(%fusion.1), custom_call_target="tpu_custom_call", metadata={op_name="jit(train_step)/jvp(attention)/mla_core/jit(flash_attention)/pallas_call"}
  %flash_bwd_fused.3 = f32[8,128]{1,0} custom-call(%flash_fwd.2), custom_call_target="tpu_custom_call", metadata={op_name="jit(train_step)/transpose(jvp(attention))/mla_core/pallas_call"}
  %gmm.4 = f32[8,128]{1,0} custom-call(%flash_bwd_fused.3), custom_call_target="tpu_custom_call", metadata={op_name="jit(train_step)/jvp(mlp)/experts/jit(gmm)/pallas_call"}
  %fusion.5 = f32[8,128]{1,0} fusion(%gmm.4), kind=kLoop, calls=%f5, metadata={op_name="jit(train_step)/jvp(mlp)/shared/dot_general"}
  %fusion.6 = f32[8,128]{1,0} fusion(%fusion.5), kind=kLoop, calls=%f6, metadata={op_name="jit(train_step)/jvp(mlp)/dispatch/gather"}
  ROOT %fusion.7 = f32[8,128]{1,0} fusion(%fusion.6), kind=kLoop, calls=%f7, metadata={op_name="jit(train_step)/optimizer/mul"}
}
"""
TARGET = 'custom_call_target="tpu_custom_call"'


def hand_trace(durations):
    at, events = 0.0, []
    for name, seconds in durations:
        kind = f"custom-call(...), {TARGET}" if "fusion" not in name else "fusion(...)"
        events.append(Event(f"%{name} = f32[8,128]{{1,0}} {kind}", at, at + seconds))
        at += seconds
    return Trace({"/device:TPU:0": {"XLA Ops": events}})


DURATIONS = [("fusion.1", 2.0), ("flash_fwd.2", 3.0), ("flash_bwd_fused.3", 5.0), ("gmm.4", 1.0),
             ("fusion.5", 4.0), ("fusion.6", 1.0), ("fusion.7", 4.0)]


def test_shares_of_the_new_parts_and_kernels():
    trace, run = hand_trace(DURATIONS), {"hlo_text": HLO_TEXT, "peaks": None}
    read = lambda name: manifest.metric_reader(name)(trace, run)
    assert read("mla_proj_time_pct") == pytest.approx(10.0)
    assert read("mla_kernel_time_pct") == pytest.approx(40.0)  # gmm is not one of them
    assert read("shared_expert_time_pct") == pytest.approx(20.0)
    assert read("routed_share_time_pct") == pytest.approx(10.0)  # experts + dispatch
    assert mla_scopes.seconds(trace, run, "mlp", ("experts",)) == pytest.approx(1.0)
    assert read("mla_kernel_roofline") is None  # no peaks off the chip


def test_a_program_without_the_scopes_gives_the_readers_nothing():
    """Another model, or the parent of the PR that brought the scopes."""
    other = HLO_TEXT
    for part in ("mla_proj", "mla_core", "mla_out", "shared"):
        other = other.replace(f"/{part}/", "/")
    trace = hand_trace(DURATIONS)
    for name in ("mla_proj_time_pct", "mla_kernel_time_pct", "mla_kernel_roofline",
                 "shared_expert_time_pct", "routed_share_time_pct",
                 "held_expert_matmul_roofline"):
        read = manifest.metric_reader(name)
        assert read(trace, {"hlo_text": other, "peaks": PEAKS}) is None, name
        assert read(Trace({}), {"hlo_text": other, "peaks": PEAKS}) is None, name
    assert manifest.metric_reader("held_rows_over_expected")(trace, {}) is None


def test_roofline_readers_divide_the_least_time_by_the_time_taken():
    shape, workload, _ = cell_shape()
    trace = hand_trace([("fusion.1", 0.1), ("flash_fwd.2", 0.3), ("flash_bwd_fused.3", 0.5),
                        ("gmm.4", 0.05), ("fusion.5", 0.05)])
    run = {"hlo_text": HLO_TEXT, "workload": workload, "shape": shape, "traced_steps": 5,
           "peaks": PEAKS, "held_rows_traced": 5 * 5 * 12288.0}
    # 5 steps x 75.35 ms least, over 800 ms in the two kernels
    assert manifest.metric_reader("mla_kernel_roofline")(trace, run) == pytest.approx(
        47.1, abs=0.05)
    operations, bytes_ = flops_mla.held_expert_matmul_cost(shape, 5 * 5 * 12288.0, 25)
    least, _ = flops.roofline_seconds(operations, bytes_, PEAKS)
    assert manifest.metric_reader("held_expert_matmul_roofline")(trace, run) == pytest.approx(
        100 * least / 0.05)


def test_the_accepted_readers_given_this_cell_read_its_trace():
    """``recompute_time_pct``, ``moe_router_time_pct`` and ``moe_dispatch_time_pct`` find
    their scopes in this cell's step; ``expert_load_max_over_mean`` is the driver's fact."""
    text = HLO_TEXT.replace("jvp(mlp)/shared", "jvp(mlp)/router").replace(
        "jvp(attention)/mla_proj", "rematted_computation/attention/mla_proj")
    trace, run = hand_trace(DURATIONS), {"hlo_text": text, "expert_load_max_over_mean": 1.3}
    read = lambda name: manifest.metric_reader(name)(trace, run)
    assert read("moe_router_time_pct") == pytest.approx(20.0)
    assert read("moe_dispatch_time_pct") == pytest.approx(5.0)
    assert read("recompute_time_pct") == pytest.approx(10.0)
    assert read("expert_load_max_over_mean") == 1.3


@pytest.fixture(scope="module")
def tiny_state():
    from perfbench.harness import build, correct

    _, workload, config = manifest.load_cell(CELL)
    workload, config = build.tiny(workload, config)
    config = build_mla.tiny_mla(config)
    jax.config.update("jax_default_prng_impl", "rbg")
    state, _, tokens = build.build_state(workload, config, jax.devices()[:1], 7)
    batch = correct.first_micro_batch(state, tokens, workload)
    return state, build_mla.mla_shape(workload, config), batch


def test_initial_check_passes_the_program(tiny_state):
    from perfbench.harness import mla_loop

    state, shape, batch = tiny_state
    ok, numbers = mla_loop.check_initial(state, shape, batch)
    assert ok, numbers
    assert 0 < numbers["expert_grad_err"] < mla_loop.TOLERANCE["expert_grad"]
    assert numbers["held_rows_err"] <= mla_loop.TOLERANCE["held_rows"]


def test_initial_check_refuses_one_held_expert_fewer(tiny_state):
    """The control the per-position limit cannot see (a held expert's term is a
    small gate on a few rows): the reference computes one held expert fewer
    than the program. Its gradient for that expert is nothing, and it counts
    that expert's assignments out."""
    from perfbench.harness import mla_loop

    state, shape, batch = tiny_state
    first, count = shape["held"]
    ok, numbers = mla_loop.check_initial(state, {**shape, "held": (first, count - 1)}, batch)
    assert not ok
    assert numbers["per_position_err"] <= mla_loop.TOLERANCE["per_position"]  # blind to it
    assert numbers["expert_grad_err"] == pytest.approx(1.0)
    assert numbers["held_rows_err"] > 5 * mla_loop.TOLERANCE["held_rows"]


def test_benchmark_entries_name_the_cell_and_its_metrics():
    benchmark = manifest.load_manifest()
    entry = [w for w in benchmark["workloads"] if w["name"] == CELL][0]
    assert (entry["config"], entry["traffic"], entry["chips"]) == (
        "deepseek-v2-lite", "share8-seq8192", 1)
    assert len(entry["why"]) <= 200
    mine = [m for m in benchmark["per_layer"] if m.get("workloads") == [CELL]]
    assert [m["name"] for m in mine] == [
        "mla_proj_time_pct", "mla_kernel_time_pct", "mla_kernel_roofline",
        "shared_expert_time_pct", "routed_share_time_pct", "held_expert_matmul_roofline",
        "held_rows_over_expected"]
    assert benchmark["per_layer"][-len(mine):] == mine  # appended, nothing moved
    # of the accepted readers with a list: remat's second run, the router, the row movement
    # and the router's imbalance read here what they read elsewhere; the two that price the
    # experts' matmuls from tokens x K rows over all E experts (8 times this chip's) do not
    others = [m["name"] for m in benchmark["per_layer"]
              if CELL in m.get("workloads", []) and m not in mine]
    assert others == ["recompute_time_pct", "moe_router_time_pct", "moe_dispatch_time_pct",
                      "expert_load_max_over_mean"]
    assert all(m["workloads"][-1] == CELL for m in benchmark["per_layer"]
               if m["name"] in others)  # appended to each list


def test_dry_run_of_the_cell_on_the_cpu():
    run = subprocess.run(
        [sys.executable, "perfbench/run.py", "--workload", CELL, "--seed", "3000000019",
         "--seconds", "1", "--trace", "1", "--allow-cpu"],
        cwd=manifest.ROOT, capture_output=True, text=True, timeout=600,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert run.returncode == 0, run.stderr[-2000:]
    last = json.loads(run.stdout.strip().splitlines()[-1])
    assert last["correct"] is True and last["device"]["platform"] == "cpu"
    assert last["metrics"] == {}  # a dry run reports no metric
    assert "'held_overflow': 0.0" in run.stdout and "'expert_grad_err'" in run.stdout
    assert "'held_rows_err'" in run.stdout
    assert "not reported: expert_load_max_over_mean" in run.stdout
    assert "held assignments that did not fit: 0" in run.stdout
    assert "not reported: held_rows_over_expected" in run.stdout
