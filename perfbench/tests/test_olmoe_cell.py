"""What the OLMoE cell brings to the benchmark: its FLOP count against hand
arithmetic, its reference against a direct per-token loop, the reader of the
routed layer's scopes on hand-made events, the configuration file against the
catalog's entry, and the cell's dry run."""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench.harness import build_moe, flops, flops_moe, manifest, moe_scopes, reference_moe
from perfbench.harness.trace_reduce import Event, Trace

CELL = "olmoe-1b-7b.d1"
# The catalog's ``config`` for OLMoE-1B-7B-0125-Instruct (guides' architectures.jsonl).
CATALOG = {"attention_bias": False, "clip_qkv": None, "hidden_act": "silu", "hidden_size": 2048,
           "intermediate_size": 1024, "max_position_embeddings": 4096, "model_type": "olmoe",
           "norm_topk_prob": False, "num_attention_heads": 16, "num_experts": 64,
           "num_experts_per_tok": 8, "num_hidden_layers": 16, "num_key_value_heads": 16,
           "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000,
           "tie_word_embeddings": False, "vocab_size": 50304}


def cell_shape():
    _, workload, config = manifest.load_cell(CELL)
    return build_moe.moe_shape(workload, config), workload, config


def test_config_file_holds_every_catalog_key_and_cuts_depth_only():
    shape, workload, config = cell_shape()
    assert {k: config[k] for k in CATALOG} == CATALOG
    assert list(config["reduced"]) == ["num_hidden_layers"] and workload["depth"] == 1
    assert {"intermediate_size", "router_aux_loss_coef", "router_z_loss_coef"} <= set(config["assumed"])
    entry = [c for c in manifest.load_manifest()["configs"] if c["name"] == "olmoe-1b-7b"][0]
    assert entry["reduced"] == ["num_hidden_layers"]
    assert (shape["experts"], shape["experts_per_token"], shape["mlp_hidden"]) == (64, 8, 1024)


def test_forward_flops_match_hand_arithmetic():
    """S 4096 causal, depth 1: head 206.0M, experts 100.7M (+ 0.26M router),
    attention 50.3M; 357.3M in all, of which the head is 58 %."""
    shape, _, _ = cell_shape()
    experts = 8 * 3 * 2 * 2048 * 1024
    router = 2 * 2048 * 64
    attention = 4 * 2 * 2048 * 2048 + 4 * (4096 // 2) * 2048
    head = 2 * 2048 * 50304
    assert flops_moe.expert_forward_flops_per_token(shape) == experts == 100_663_296
    assert flops_moe.forward_flops_per_token(shape) == experts + router + attention + head
    assert round(flops_moe.forward_flops_per_token(shape) / 1e6, 1) == 357.3
    assert flops_moe.train_flops_per_token(shape) == 3 * flops_moe.forward_flops_per_token(shape)
    assert (round(100 * head / 357.3e6), round(100 * experts / 357.3e6),
            round(100 * attention / 357.3e6)) == (58, 28, 14)
    # In the whole model (16 layers) the head is 8 %.
    whole = flops_moe.forward_flops_per_token({**shape, "layers": 16})
    assert round(100 * head / whole) == 8
    # The dense count would see one expert for the same sizes.
    assert flops.forward_flops_per_token(shape) < flops_moe.forward_flops_per_token(shape)


def test_expert_matmul_cost_is_compute_bound_on_a_v5e():
    shape, _, _ = cell_shape()
    operations, bytes_ = flops_moe.expert_matmul_cost(shape, 8192)
    assert operations == 3 * 8192 * 100_663_296  # 2.47 TFLOP a step
    rows = 8192 * 8
    moved = rows * (2048 + 2048 + 1024 + 2048) + 64 * 3 * 2048 * 1024
    assert bytes_ == 2 * 3 * moved  # 5.2 GB a step
    least, bound = flops.roofline_seconds(operations, bytes_,
                                          {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9})
    assert bound == "compute" and round(1e3 * least, 1) == 12.6


TINY = {"hidden": 32, "heads": 2, "kv_heads": 2, "head_dim": 16, "mlp_hidden": 16,
        "mlp": "swiglu", "norm": "rmsnorm", "norm_eps": 1e-5, "positions": "rope",
        "rope_theta": 10000.0, "tied_head": False, "causal": True, "vocab": 64, "layers": 2,
        "seq_len": 12, "experts": 4, "experts_per_token": 2, "norm_topk_prob": False,
        "qk_norm": True, "aux_coef": 0.01, "z_coef": 0.001}


def tiny_weights(m, key):
    D, F, E, L, V = m["hidden"], m["mlp_hidden"], m["experts"], m["layers"], m["vocab"]
    shapes = {"wte": (V, D), "lm_head": (V, D), "lnf_scale": (D,),
              "blocks": {"ln1_scale": (L, D), "ln2_scale": (L, D), "q_norm": (L, D),
                         "k_norm": (L, D), "wqkv": (L, D, 3, D), "wo": (L, D, D),
                         "router": (L, D, E), "moe_wgu": (L, E, D, 2 * F), "moe_wd": (L, E, F, D)}}
    leaves, tree = jax.tree.flatten(shapes, is_leaf=lambda x: isinstance(x, tuple))
    keys = jax.random.split(key, len(leaves))
    draw = lambda k, s: (1.0 + 0.1 * jax.random.normal(k, s) if len(s) <= 2 and s[-1] == D
                         and s != (V, D) else 0.3 * jax.random.normal(k, s))
    return jax.tree.unflatten(tree, [draw(k, s) for k, s in zip(keys, leaves)])


def test_routed_mlp_matches_a_direct_per_token_loop():
    """Each token alone: its 2 largest router probabilities, those experts'
    SwiGLU, summed with the probabilities as they are."""
    m = TINY
    w = jax.tree.map(lambda t: np.asarray(t[0], np.float64),
                     tiny_weights(m, jax.random.key(0))["blocks"])
    h = np.asarray(jax.random.normal(jax.random.key(1), (m["seq_len"], m["hidden"])), np.float64)
    want = np.zeros_like(h)
    for t, x in enumerate(h):
        logits = x @ w["router"]
        p = np.exp(logits - logits.max())
        p /= p.sum()
        for e in np.argsort(-p)[: m["experts_per_token"]]:
            F = m["mlp_hidden"]
            gate, up = x @ w["moe_wgu"][e][:, :F], x @ w["moe_wgu"][e][:, F:]
            want[t] += p[e] * ((gate / (1 + np.exp(-gate)) * up) @ w["moe_wd"][e])
    with jax.default_matmul_precision("highest"):
        got, statistics = reference_moe._routed_mlp(
            m, jnp.asarray(h, jnp.float32), jax.tree.map(lambda t: jnp.asarray(t, jnp.float32), w))
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)
    assert int(statistics["assignments"].sum()) == m["seq_len"] * m["experts_per_token"]


def test_training_loss_adds_both_router_terms_over_the_whole_batch():
    m = TINY
    params = tiny_weights(m, jax.random.key(2))
    batch = jax.random.randint(jax.random.key(3), (3, m["seq_len"]), 0, m["vocab"])
    with jax.default_matmul_precision("highest"):
        full = float(reference_moe.loss(m, params, batch))
        bare = float(reference_moe.loss({**m, "aux_coef": 0.0, "z_coef": 0.0}, params, batch))
        plain = float(jnp.mean(jax.vmap(
            lambda t: reference_moe.token_losses(m, params, t))(batch)))
        grads = jax.grad(lambda p: reference_moe.loss(m, p, batch))(params)
    assert bare == pytest.approx(plain, rel=1e-6)
    # Load-balance is at least 1 (even routing), the z-loss is positive.
    assert full - bare > m["aux_coef"] * 1.0
    assert all(bool(jnp.all(jnp.isfinite(g))) for g in jax.tree.leaves(grads))
    assert float(jnp.abs(grads["blocks"]["router"]).max()) > 0


STEP = "jit(train_step)"


@pytest.mark.parametrize("op_name, expected", [
    (f"{STEP}/jvp(mlp)/router/top_k", ("router", "forward")),
    (f"{STEP}/jvp(mlp)/dispatch/jit(argsort)/sort", ("dispatch", "forward")),
    (f"{STEP}/jvp(mlp)/experts/jit(gmm)/pallas_call", ("experts", "forward")),
    (f"{STEP}/transpose(jvp(mlp))/experts/jit(tgmm)/pallas_call", ("experts", "backward")),
    (f"{STEP}/transpose(jvp(mlp))/combine/mul", ("combine", "backward")),
    # inside a scan body or under remat the wrapper sits above the scope
    (f"{STEP}/transpose(jvp())/while/body/closed_call/mlp/experts/mul", ("experts", "backward")),
    # the first path that names mlp and a part
    (f"{STEP}/jvp(attention)/reshape;{STEP}/jvp(mlp)/combine/add", ("combine", "forward")),
    # a part's name outside mlp, mlp without a part, a jitted function of that name, nothing
    (f"{STEP}/jvp(attention)/experts/mul", None),
    (f"{STEP}/jvp(mlp)/mul", None),
    (f"{STEP}/jvp(mlp)/jit(router)/mul", None),
    ("", None),
])
def test_part(op_name, expected):
    assert moe_scopes.part(op_name) == expected


HLO_TEXT = """HloModule jit_train_step

ENTRY %main.1 (p0: f32[8,128]) -> f32[8,128] {
  %p0 = f32[8,128]{1,0} parameter(0)
  %fusion.1 = f32[8,128]{1,0} fusion(%p0), kind=kLoop, calls=%f1, metadata={op_name="jit(train_step)/jvp(mlp)/router/exp"}
  %sort.2 = f32[8,128]{1,0} sort(%fusion.1), metadata={op_name="jit(train_step)/jvp(mlp)/dispatch/jit(argsort)/sort"}
  %gmm.3 = f32[8,128]{1,0} custom-call(%sort.2), custom_call_target="tpu_custom_call", metadata={op_name="jit(train_step)/jvp(mlp)/experts/jit(gmm)/pallas_call"}
  %tgmm.4 = f32[8,128]{1,0} custom-call(%gmm.3), custom_call_target="tpu_custom_call", metadata={op_name="jit(train_step)/transpose(jvp(mlp))/experts/jit(tgmm)/pallas_call"}
  %fusion.5 = f32[8,128]{1,0} fusion(%tgmm.4), kind=kLoop, calls=%f5, metadata={op_name="jit(train_step)/jvp(mlp)/combine/mul"}
  ROOT %fusion.6 = f32[8,128]{1,0} fusion(%fusion.5), kind=kLoop, calls=%f6, metadata={op_name="jit(train_step)/jvp(attention)/mul"}
}
"""


def hand_trace(durations):
    at, events = 0.0, []
    for name, seconds in durations:
        events.append(Event(f"%{name} = f32[8,128]{{1,0}} fusion(...)", at, at + seconds))
        at += seconds
    return Trace({"/device:TPU:0": {"XLA Ops": events}})


def test_shares_and_seconds_of_the_routed_layers_parts():
    trace = hand_trace([("fusion.1", 1.0), ("sort.2", 2.0), ("gmm.3", 3.0), ("tgmm.4", 5.0),
                        ("fusion.5", 1.0), ("fusion.6", 8.0)])
    run = {"hlo_text": HLO_TEXT}
    assert moe_scopes.seconds(trace, run, ("experts",)) == pytest.approx(8.0)
    assert moe_scopes.share(trace, run, ("router",)) == pytest.approx(5.0)
    assert moe_scopes.share(trace, run, ("dispatch", "combine")) == pytest.approx(15.0)
    assert moe_scopes.share(trace, run, ("experts",)) == pytest.approx(40.0)


def test_a_program_without_the_scopes_gives_the_readers_nothing():
    """A dense step, or the parent of the PR that brought the scopes."""
    dense = HLO_TEXT.replace("/router/", "/").replace("/dispatch/", "/").replace(
        "/experts/", "/").replace("/combine/", "/")
    trace = hand_trace([("fusion.1", 1.0), ("gmm.3", 3.0)])
    for name in ("moe_router_time_pct", "moe_dispatch_time_pct", "expert_matmul_time_pct",
                 "expert_matmul_roofline"):
        read = manifest.metric_reader(name)
        assert read(trace, {"hlo_text": dense, "peaks": None}) is None
        assert read(Trace({}), {"hlo_text": dense, "peaks": None}) is None
    assert manifest.metric_reader("expert_load_max_over_mean")(trace, {}) is None


def test_roofline_reader_divides_the_least_time_by_the_time_under_experts():
    shape, workload, _ = cell_shape()
    trace = hand_trace([("gmm.3", 0.05), ("tgmm.4", 0.05), ("fusion.6", 0.4)])
    run = {"hlo_text": HLO_TEXT, "workload": workload, "shape": shape, "traced_steps": 5,
           "peaks": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}}
    # 5 steps x 12.56 ms least, over 100 ms under the scope
    assert manifest.metric_reader("expert_matmul_roofline")(trace, run) == pytest.approx(
        62.8, abs=0.05)


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
    assert "'dropped_assignments': 0.0" in run.stdout
    assert "not reported: expert_load_max_over_mean" in run.stdout
