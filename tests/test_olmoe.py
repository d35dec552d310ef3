"""OLMoE-class blocks (dropless top-k routing over SwiGLU experts, QK-norm, gates
not renormalised, load-balance + z-loss) against the plain float32 reference
the benchmark keeps (``perfbench/harness/reference_moe.py``), at a small size.

Both sides compute in float32 here, so they differ only by the order of
summation (rows sorted by expert through grouped matmuls against a dense sum
over experts; one fused qkv matmul against three): a few 1e-6 of the largest
value. The tolerances sit two orders above that and well under the smallest
wrong model below, one dropped assignment of 256, which moves the logits by
more than 1e-3 of their largest.
"""

import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_llm_training_benchmark_framework_tpu.models import moe, tinygpt
from distributed_llm_training_benchmark_framework_tpu.models.tinygpt import TinyGPTConfig
from distributed_llm_training_benchmark_framework_tpu.parallel import get_strategy, make_mesh
from distributed_llm_training_benchmark_framework_tpu.parallel.strategies import (
    make_optimizer,
    param_partition_specs,
)
from distributed_llm_training_benchmark_framework_tpu.train.step import (
    create_train_state,
    make_train_step,
)
from distributed_llm_training_benchmark_framework_tpu.utils.scopes import MOE_SCOPES, SCOPES
from perfbench.harness import reference_moe

TOLERANCE = {"logits": 1e-4, "loss": 1e-5, "grad_leaf": 1e-3}
SEQ, BATCH, EXPERTS, TOP_K = 64, 2, 8, 2
OLMOE = dict(vocab_size=512, n_embd=64, n_head=4, n_layer=2, block_size=SEQ, dropout=0.0,
             causal=True, norm="rmsnorm", pos_embed="rope", mlp_act="swiglu", mlp_hidden=32,
             bias=False, tie_embeddings=False, qk_norm=True, n_experts=EXPERTS,
             expert_top_k=TOP_K, capacity_factor=None, norm_topk_prob=False,
             router_aux_coef=0.01, router_z_coef=0.001)
CONFIG = TinyGPTConfig(**OLMOE, compute_dtype=jnp.float32, scan_layers=False)
SHAPE = {"hidden": 64, "heads": 4, "kv_heads": 4, "head_dim": 16, "mlp_hidden": 32,
         "mlp": "swiglu", "norm": "rmsnorm", "norm_eps": 1e-5, "positions": "rope",
         "rope_theta": 10000.0, "tied_head": False, "causal": True, "vocab": 512, "layers": 2,
         "seq_len": SEQ, "experts": EXPERTS, "experts_per_token": TOP_K,
         "norm_topk_prob": False, "qk_norm": True, "aux_coef": 0.01, "z_coef": 0.001}


@pytest.fixture(scope="module")
def weights():
    """Seeded weights large enough that every part shows in the logits: the
    program's initialization times five, norm scales drawn around one."""
    params = tinygpt.init_params(CONFIG, jax.random.key(0))
    leaves, tree = jax.tree.flatten(params)
    keys = jax.random.split(jax.random.key(1), len(leaves))
    redraw = lambda key, x: (1.0 + 0.1 * jax.random.normal(key, x.shape) if x.ndim <= 2
                             and bool(jnp.all(x == 1.0)) else 5.0 * x)
    return jax.tree.unflatten(tree, [redraw(k, x) for k, x in zip(keys, leaves)])


@pytest.fixture(scope="module")
def batch():
    return jax.random.randint(jax.random.key(2), (BATCH, SEQ), 0, OLMOE["vocab_size"])


def reference_logits(shape, params, batch):
    with jax.default_matmul_precision("highest"):
        return jax.vmap(lambda t: reference_moe.logits(shape, params, t))(batch)


def relative(got, want):
    return float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))


def test_logits_match_the_reference(weights, batch):
    got, _ = tinygpt.forward(CONFIG, weights, batch)
    assert relative(got, reference_logits(SHAPE, weights, batch)) <= TOLERANCE["logits"]


def test_full_loss_matches_the_reference_and_holds_both_router_terms(weights, batch):
    got = tinygpt.loss_fn(CONFIG, weights, batch, batch)
    with jax.default_matmul_precision("highest"):
        want = reference_moe.loss(SHAPE, weights, batch)
        bare = reference_moe.loss({**SHAPE, "aux_coef": 0.0, "z_coef": 0.0}, weights, batch)
        no_z = reference_moe.loss({**SHAPE, "z_coef": 0.0}, weights, batch)
    assert abs(float(got - want)) / float(want) <= TOLERANCE["loss"]
    # Each term is large enough that leaving it out would fail the line above.
    assert float(no_z - bare) / float(want) > 10 * TOLERANCE["loss"]
    assert float(want - no_z) / float(want) > 10 * TOLERANCE["loss"]


def test_gradient_of_every_leaf_matches_the_reference(weights, batch):
    got = jax.grad(lambda p: tinygpt.loss_fn(CONFIG, p, batch, batch))(weights)
    with jax.default_matmul_precision("highest"):
        want = jax.grad(lambda p: reference_moe.loss(SHAPE, p, batch))(weights)
    names = [jax.tree_util.keystr(path) for path, _ in jax.tree.leaves_with_path(want)]
    assert len(names) == 12  # wte, lm_head, lnf_scale and nine leaves a block
    for name, g, w in zip(names, jax.tree.leaves(got), jax.tree.leaves(want)):
        error = float(jnp.linalg.norm(g - w) / jnp.linalg.norm(w))
        assert error <= TOLERANCE["grad_leaf"], (name, error)


def drop_one_assignment(m, probs):
    """The first token loses its largest gate."""
    gates = GATE_WEIGHTS(m, probs)
    return gates.at[0, jnp.argmax(gates[0])].set(0.0)


GATE_WEIGHTS = reference_moe._gate_weights
WRONG = {
    "gates_renormalised": ({"norm_topk_prob": True}, None),
    "qk_norm_left_out": ({"qk_norm": False}, None),
    "one_expert_fewer_a_token": ({"experts_per_token": TOP_K - 1}, None),
    "an_assignment_dropped": ({}, drop_one_assignment),
}


@pytest.mark.parametrize("name", sorted(WRONG))
def test_a_wrong_model_fails_the_same_tolerance(weights, batch, name, monkeypatch):
    change, gate_weights = WRONG[name]
    if gate_weights:
        monkeypatch.setattr(reference_moe, "_gate_weights", gate_weights)
    got, _ = tinygpt.forward(CONFIG, weights, batch)
    wrong = reference_logits({**SHAPE, **change}, weights, batch)
    assert relative(got, wrong) > 10 * TOLERANCE["logits"]


def routed_layer(weights, x, router):
    """(program's output, its counts, the reference's output) of layer 0's
    routed MLP on ``x`` (B, S, D) under ``router``."""
    layer = {**jax.tree.map(lambda t: t[0], weights["blocks"]), "router": router}
    got, _ = moe.moe_mlp(CONFIG, layer, x, None, True)
    with jax.default_matmul_precision("highest"):
        want = jax.vmap(lambda h: reference_moe._routed_mlp(SHAPE, h, layer)[0])(x)
    return got, moe.expert_counts(CONFIG, layer, x), want


def test_a_skewed_router_drops_nothing(weights):
    """Every token's first choice is expert 0: half of all assignments."""
    D = OLMOE["n_embd"]
    direction = jnp.ones((D,)) / jnp.sqrt(D)
    x = direction + 0.3 * jax.random.normal(jax.random.key(3), (BATCH, SEQ, D))
    router = weights["blocks"]["router"][0].at[:, 0].set(20.0 * direction)
    got, counts, want = routed_layer(weights, x, router)
    tokens = BATCH * SEQ
    assert int(counts[0]) == tokens and int(counts.sum()) == tokens * TOP_K
    assert relative(got, want) <= TOLERANCE["logits"]


def test_an_expert_without_tokens_is_safe(weights):
    D = OLMOE["n_embd"]
    direction = jnp.ones((D,)) / jnp.sqrt(D)
    x = direction + 0.3 * jax.random.normal(jax.random.key(4), (BATCH, SEQ, D))
    router = weights["blocks"]["router"][0].at[:, 5].set(-20.0 * direction)
    layer = {**jax.tree.map(lambda t: t[0], weights["blocks"]), "router": router}
    assert int(moe.expert_counts(CONFIG, layer, x)[5]) == 0

    def total(experts):
        y, aux = moe.moe_mlp(CONFIG, {**layer, **experts}, x, None, True)
        return jnp.sum(jnp.square(y)) + aux

    grads = jax.grad(total)({k: layer[k] for k in ("moe_wgu", "moe_wd", "router")})
    assert all(bool(jnp.all(jnp.isfinite(g))) for g in jax.tree.leaves(grads))
    assert float(jnp.abs(grads["moe_wgu"][5]).max()) == 0.0
    assert float(jnp.abs(grads["moe_wd"][5]).max()) == 0.0
    assert float(jnp.abs(grads["moe_wgu"][0]).max()) > 0.0


def test_counts_of_a_batch_sum_to_every_assignment(weights, batch):
    counts = tinygpt.moe_expert_counts(CONFIG, weights, batch)
    assert counts.shape == (OLMOE["n_layer"], EXPERTS)
    assert counts.sum(-1).tolist() == [BATCH * SEQ * TOP_K] * OLMOE["n_layer"]
    assert float(tinygpt.moe_overflow_fraction(CONFIG, weights, batch)) == 0.0


def test_scan_and_unrolled_layer_loops_agree(weights, batch):
    scanned = dataclasses.replace(CONFIG, scan_layers=True)
    value = lambda c: jax.value_and_grad(lambda p: tinygpt.loss_fn(c, p, batch, batch))(weights)
    (loss_a, grad_a), (loss_b, grad_b) = value(CONFIG), value(scanned)
    assert abs(float(loss_a - loss_b)) <= 1e-6 * float(loss_a)
    for a, b in zip(jax.tree.leaves(grad_a), jax.tree.leaves(grad_b)):
        assert float(jnp.linalg.norm(a - b)) <= 1e-5 * float(jnp.linalg.norm(a))


@pytest.mark.parametrize("bad", [
    dict(capacity_factor=1.25),  # SwiGLU experts under a capacity
    dict(mlp_act="gelu", bias=True),  # GELU experts under dropless routing
    dict(bias=True),
    dict(router_aux_coef=0.0),  # the z-loss has no unit to ride in
], ids=["swiglu_capacity", "gelu_dropless", "biased_dropless", "z_without_aux"])
def test_config_refuses_what_no_path_computes(bad):
    with pytest.raises(ValueError):
        TinyGPTConfig(**{**OLMOE, **bad})


def test_capacity_path_refuses_the_dropless_facts():
    with pytest.raises(ValueError):
        TinyGPTConfig(n_experts=4, norm_topk_prob=False)
    with pytest.raises(ValueError):
        TinyGPTConfig(n_experts=4, router_z_coef=0.001)


def test_published_widths_build_and_count():
    """Hidden 2048, 16 heads of 128, 64 experts of width 1024, vocabulary 50304,
    depth 1: 625.6M parameters, 402.7M of them experts (shapes only)."""
    config = TinyGPTConfig(**{**OLMOE, "vocab_size": 50304, "n_embd": 2048, "n_head": 16,
                              "n_layer": 1, "block_size": 4096, "mlp_hidden": 1024,
                              "n_experts": 64, "expert_top_k": 8})
    shapes = jax.eval_shape(lambda: tinygpt.init_params(config, jax.random.key(0)))
    size = lambda tree: sum(int(np.prod(x.shape)) for x in jax.tree.leaves(tree))
    assert round(size(shapes) / 1e6, 1) == 625.6
    blocks = shapes["blocks"]
    assert blocks["moe_wgu"].shape == (1, 64, 2048, 2 * 1024)
    assert blocks["moe_wd"].shape == (1, 64, 1024, 2048)
    assert round(size([blocks["moe_wgu"], blocks["moe_wd"]]) / 1e6, 1) == 402.7
    assert blocks["q_norm"].shape == blocks["k_norm"].shape == (1, 2048)


def test_expert_axis_shards_the_stacked_expert_weights():
    if len(jax.devices()) < 2:
        pytest.skip("needs two devices")
    mesh = make_mesh((1, 1, 1, 1, 2), ("data", "seq", "model", "pipe", "expert"),
                     devices=jax.devices()[:2])
    shapes = jax.eval_shape(lambda: tinygpt.init_params(CONFIG, jax.random.key(0)))
    specs = param_partition_specs(shapes, mesh, shard=False)["blocks"]
    assert specs["moe_wgu"][1] == specs["moe_wd"][1] == "expert"
    assert "expert" not in tuple(specs["router"])


def compiled_paths(scan_layers):
    """Every ``/``-split path of every ``op_name`` of a compiled tiny step."""
    config = TinyGPTConfig(**OLMOE, attention_impl="flash", scan_layers=scan_layers)
    mesh = make_mesh((1, 1, 1, 1, 1), ("data", "seq", "model", "pipe", "expert"),
                     devices=jax.devices()[:1])
    strategy = get_strategy("zero2")
    shape = dict(grad_accum=1, from_table=True, global_micro=BATCH, seq_len=SEQ)
    state = create_train_state(config, strategy, mesh, seed=0, **shape)
    _, aot_compile = make_train_step(config, strategy, make_optimizer(strategy), mesh,
                                     state.param_specs, state.opt_specs, **shape)
    text = aot_compile(state.params, state.opt_state, jnp.zeros((8, SEQ), jnp.int32)).as_text()
    return [path.split("/") for op_name in re.findall(r'op_name="([^"]*)"', text)
            for path in op_name.split(";")]


@pytest.mark.parametrize("scan_layers", [False, True], ids=["unrolled", "scan"])
def test_the_routed_layers_scopes_are_in_the_compiled_step(scan_layers):
    """Forward and backward, nested in ``mlp``; the step's own scopes too."""
    wrapper = re.compile(r"^(?!jit\()\w+\((.*)\)$")

    def plain(component):
        while wrapped := wrapper.match(component):
            component = wrapped.group(1)
        return component

    paths = [[plain(c) for c in path] + [any(c.startswith("transpose(") for c in path)]
             for path in compiled_paths(scan_layers)]
    for scope in MOE_SCOPES:
        found = [p for p in paths if scope in p[:-1]]
        assert found and all("mlp" in p[: p.index(scope)] for p in found), scope
        assert any(p[-1] for p in found), f"no backward op under {scope}"
        assert any(not p[-1] for p in found), f"no forward op under {scope}"
    for scope in SCOPES:
        assert any(scope in p[:-1] for p in paths) == (scope != "dropout"), scope
