"""Kimi-Linear-class stacks (three Kimi-Delta-Attention layers to every NoPE
latent-attention one, a leading dense layer that is itself a KDA layer,
sigmoid routing with a selection bias over one chip's share of the experts, a
shared expert) against the plain float32 reference the benchmark keeps
(``perfbench/harness/reference_kda.py``: the recurrence position by position),
at a small size: the first five layers of the published pattern, 4 of 16
experts held.

Both sides compute in float32 here, so they differ only by the order of
summation: a few 1e-7 of the largest value. The tolerances sit two orders
above that and well under the smallest wrong model below.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from distributed_llm_training_benchmark_framework_tpu.models import mixers
from distributed_llm_training_benchmark_framework_tpu.models.mixers import (
    attention as attention_mixer,
    kda as kda_mixer,
)
from distributed_llm_training_benchmark_framework_tpu.models import moe, tinygpt
from distributed_llm_training_benchmark_framework_tpu.models.tinygpt import TinyGPTConfig
from distributed_llm_training_benchmark_framework_tpu.parallel import get_strategy, make_mesh
from distributed_llm_training_benchmark_framework_tpu.parallel import strategies
from distributed_llm_training_benchmark_framework_tpu.train.step import create_train_state
from distributed_llm_training_benchmark_framework_tpu.utils import flops, memory
from distributed_llm_training_benchmark_framework_tpu.utils.scopes import (
    GLOBAL, KDA, KDA_SCOPES, LAYER_KIND_SCOPES, WINDOW,
)
from perfbench.harness import build_kda, flops_kda, reference_kda

TOLERANCE = {"logits": 1e-4, "loss": 1e-5, "grad_leaf": 1e-3}
SEQ, BATCH, EXPERTS, HELD, TOP_K = 64, 2, 16, (4, 4), 3
MESH_AXES = ("data", "seq", "model", "pipe", "expert")
# The cell's two data files at a small size: what the builder and the
# reference's shape are made from, as the benchmark makes them.
FILE = dict(
    hidden_size=64, num_attention_heads=4, num_key_value_heads=4, head_dim=16,
    kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16, q_lora_rank=None,
    mla_use_nope=True, rope_scaling=None, rope_theta=10000, rms_norm_eps=1e-5,
    linear_attn_config=dict(
        full_attn_layers=[4, 8, 12, 16, 20, 24, 27], head_dim=16, num_heads=4,
        kda_layers=[1, 2, 3, 5, 6, 7, 9, 10, 11, 13, 14, 15, 17, 18, 19, 21, 22, 23, 25, 26],
        short_conv_kernel_size=4),
    kda_l2norm_eps=1e-6, first_k_dense_replace=1, intermediate_size=96, moe_intermediate_size=32,
    num_shared_experts=1, num_experts_published=EXPERTS, num_experts=HELD[1],
    experts_held_first=HELD[0], num_experts_per_token=TOP_K, moe_renormalize=True,
    moe_router_activation_func="sigmoid", routed_scaling_factor=2.446, use_grouped_topk=True,
    num_expert_group=1, topk_group=1, moe_layer_freq=1, num_nextn_predict_layers=0,
    hidden_act="silu", tie_word_embeddings=False, vocab_size=128, num_hidden_layers=5,
    dropout=0.0)
JOB = dict(seq_len=SEQ, held_rows_factor=4.0, attention="reference", layer_loop="unrolled",
           kda_chunk=16)
SHAPE = build_kda.kda_shape(JOB, FILE)
CONFIG = dataclasses.replace(build_kda.kimi_config(JOB, FILE), compute_dtype=jnp.float32)
KINDS = (KDA, KDA, KDA, GLOBAL, KDA)
WRONG = {
    "a_bfloat16_state": {"state_dtype": "bfloat16"},
    "no_rounding_guard_in_l2norm": {"l2_eps": 1e-2},
    "gates_not_renormalised": {"norm_topk_prob": False},
    "no_scaling_factor": {"routed_scaling": 1.0},
    "one_held_expert_fewer": {"held": (HELD[0], HELD[1] - 1)},
    "no_shared_expert": {"shared": False},
    "a_filter_of_three_taps": "conv3",
}


def seeded_weights(config, bias=0.0):
    """Seeded weights large enough that every part shows in the logits: the
    program's initialization times five for what it draws around zero, norm
    scales (the leaves that start from one constant) drawn around what they
    start from, the decay's own leaves as the program draws them, and the
    selection bias (a buffer the program starts at zero) drawn at ``bias``."""
    params = tinygpt.init_params(config, jax.random.key(0))
    keys = iter(jax.random.split(jax.random.key(1), 200))

    def redraw(path, x):
        name, key = path[-1].key, next(keys)
        if name == "router_bias":
            return bias * jax.random.normal(key, x.shape)
        if name in ("kda_a_log", "kda_dt_bias", "kda_conv"):
            return x
        if bool(jnp.all(x == x.ravel()[0])):
            return x * (1.0 + 0.1 * jax.random.normal(key, x.shape))
        return 5.0 * x

    return jax.tree_util.tree_map_with_path(redraw, params)


@pytest.fixture(scope="module")
def weights():
    return seeded_weights(CONFIG, bias=0.3)


@pytest.fixture(scope="module")
def batch():
    return jax.random.randint(jax.random.key(2), (BATCH, SEQ), 0, FILE["vocab_size"])


def reference_logits(shape, params, batch):
    if shape == "conv3":  # a wrong model of the weights: the filters' first tap dropped
        params = jax.tree_util.tree_map_with_path(
            lambda path, x: x.at[:, :, 0].set(0.0) if path[-1].key == "kda_conv" else x, params)
        shape = SHAPE
    with jax.default_matmul_precision("highest"):
        return jax.vmap(lambda t: reference_kda.logits(shape, params, t))(batch)


def relative(got, want):
    return float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))


def test_the_builder_gives_each_layer_its_mixer_and_each_stack_equal_leaves():
    assert CONFIG.layer_types == KINDS == SHAPE["kinds"] and mixers.own_leaves(CONFIG.layer_types)
    assert CONFIG.layer_groups == (
        ("kda_dense_blocks", (0,)), ("kda_blocks", (1, 2, 4)), ("blocks", (3,)))
    assert CONFIG.mla_nope and CONFIG.router_score == "sigmoid" and CONFIG.attn_scale is None
    assert CONFIG.routed_scaling_factor == 2.446 and not CONFIG.trains_routing
    params = tinygpt.init_params(CONFIG, jax.random.key(0))
    shapes = {k: jax.tree.map(jnp.shape, v) for k, v in params.items() if k.endswith("blocks")}
    mixer = {"kda_wqkv": (64, 3, 64), "kda_conv": (3, 4, 64), "kda_wfa": (64, 16),
             "kda_wfb": (16, 64), "kda_a_log": (4,), "kda_dt_bias": (64,), "kda_wb": (64, 4),
             "kda_wga": (64, 16), "kda_wgb": (16, 64), "kda_norm": (16,), "wo": (64, 64)}
    for name, layers in (("kda_dense_blocks", 1), ("kda_blocks", 3)):
        assert {k: v[1:] for k, v in shapes[name].items() if k in mixer} == mixer
        assert all(v[0] == layers for v in shapes[name].values())
    assert set(shapes["kda_dense_blocks"]) - set(mixer) == {"ln1_scale", "ln2_scale", "wgu", "wproj"}
    routed = {"router", "router_bias", "moe_wgu", "moe_wd", "shared_wgu", "shared_wd"}
    assert set(shapes["kda_blocks"]) - set(mixer) == routed | {"ln1_scale", "ln2_scale"}
    assert set(shapes["blocks"]) == routed | {
        "ln1_scale", "ln2_scale", "wq", "wkv_a", "kv_norm", "wkv_b", "wo"}
    assert shapes["blocks"]["router"] == (1, 64, EXPERTS) and shapes["blocks"]["moe_wd"][:2] == (1, 4)
    # the decay starts where the family's code starts it, the bias at zero
    rate, step = jnp.exp(params["kda_blocks"]["kda_a_log"]), jax.nn.softplus(
        params["kda_blocks"]["kda_dt_bias"])
    assert 1.0 <= float(rate.min()) and float(rate.max()) <= 16.0
    assert 0.000999 <= float(step.min()) and float(step.max()) <= 0.1001
    assert float(jnp.abs(params["blocks"]["router_bias"]).max()) == 0.0


def test_a_plain_stack_keeps_its_two_names_and_its_draws():
    """The stacks of the accepted configurations are 'blocks' and
    'dense_blocks' as before, and a seed draws what it drew: a leaf's first
    values, pinned."""
    plain = TinyGPTConfig(vocab_size=64, n_embd=32, n_head=2, n_layer=3, block_size=16)
    assert plain.layer_groups == (("blocks", (0, 1, 2)),) and not mixers.own_leaves(plain.layer_types)
    deepseek = dataclasses.replace(
        CONFIG, layer_types=None, mla_nope=False, kda_heads=0, kda_head_dim=0,
        router_score="softmax", routed_scaling_factor=1.0, router_aux_coef=0.001)
    assert deepseek.layer_groups == (("dense_blocks", (0,)), ("blocks", (1, 2, 3, 4)))
    params = tinygpt.init_params(deepseek, jax.random.key(0))
    assert set(params) == {"blocks", "dense_blocks", "wte", "lm_head", "lnf_scale"}
    assert "router_bias" not in params["blocks"]
    legacy = tinygpt.init_params(plain, jax.random.key(0))
    np.testing.assert_allclose(
        np.asarray(legacy["blocks"]["wqkv"][0, 0, 0, :3]),
        np.asarray(0.02 * jax.random.normal(jax.random.split(jax.random.key(0), 8)[0],
                                            (3, 32, 3, 32))[0, 0, 0, :3]))


@pytest.mark.parametrize("remat", ["none", "dots", "full_keep_kernels", "full"])
def test_logits_match_the_reference(weights, batch, remat):
    config = dataclasses.replace(CONFIG, remat=remat)
    got = tinygpt.forward(config, weights, batch)[0]
    assert relative(got, reference_logits(SHAPE, weights, batch)) < TOLERANCE["logits"]


def test_the_loss_matches_the_reference_and_has_no_router_term(weights, batch):
    got = float(tinygpt.loss_fn(CONFIG, weights, batch, batch))
    with jax.default_matmul_precision("highest"):
        want, (losses, counts) = reference_kda.loss_and_parts(SHAPE, weights, batch)
    assert abs(got - float(want)) / float(want) < TOLERANCE["loss"]
    assert float(want) == pytest.approx(float(jnp.mean(losses)))
    program = tinygpt.moe_expert_counts(CONFIG, weights, batch)
    np.testing.assert_array_equal(np.asarray(program), np.asarray(counts))
    assert counts.shape == (4, EXPERTS) and int(counts.sum()) == 4 * BATCH * SEQ * TOP_K


# Every expert on this chip, through the same held-experts path: the routing trains.
EVERY_EXPERT = {**FILE, "num_experts": EXPERTS, "experts_held_first": 0}


@pytest.mark.parametrize("file", [FILE, EVERY_EXPERT], ids=["a-part", "every-expert"])
def test_gradient_of_every_leaf_matches_the_reference(batch, file):
    """``jax.grad`` of the training loss through the chunked recurrence's own
    backward, the convolutions, the decay's and the gate's low-rank maps, NoPE
    latent attention, the sigmoid gates and the held experts, under remat as
    the timed cell runs them."""
    shape = build_kda.kda_shape(JOB, file)
    config = dataclasses.replace(
        build_kda.kimi_config(JOB, file), compute_dtype=jnp.float32, remat="dots")
    assert config.trains_routing == shape["routing_trained"] == (file is EVERY_EXPERT)
    weights = seeded_weights(config, bias=0.3)
    got = jax.grad(lambda p: tinygpt.loss_fn(config, p, batch, batch))(weights)
    with jax.default_matmul_precision("highest"):
        want = jax.grad(lambda p: reference_kda.loss(shape, p, batch))(weights)
    for stack in ("kda_blocks", "blocks"):
        # the bias moves the choice only: no gradient reaches it, on either side
        assert float(jnp.abs(got[stack].pop("router_bias")).max()) == 0.0
        assert float(jnp.abs(want[stack].pop("router_bias")).max()) == 0.0
        router = float(jnp.abs(got[stack]["router"]).max())
        if not config.trains_routing:
            assert router == float(jnp.abs(want[stack]["router"]).max()) == 0.0
            got[stack].pop("router"), want[stack].pop("router")
        else:
            assert router > 0.0
    errors = jax.tree.map(
        lambda g, w: float(jnp.linalg.norm(g - w) / jnp.linalg.norm(w)), got, want)
    for path, error in jax.tree_util.tree_leaves_with_path(errors):
        assert error < TOLERANCE["grad_leaf"], (jax.tree_util.keystr(path), error)
    assert set(got["kda_blocks"]) >= {"kda_wqkv", "kda_conv", "kda_a_log", "kda_dt_bias", "kda_wb"}


@pytest.mark.parametrize("name", sorted(WRONG))
def test_a_wrong_model_fails_the_same_tolerance(weights, batch, name):
    got = tinygpt.forward(CONFIG, weights, batch)[0]
    wrong = WRONG[name] if isinstance(WRONG[name], str) else {**SHAPE, **WRONG[name]}
    assert relative(got, reference_logits(wrong, weights, batch)) > 10 * TOLERANCE["logits"]


def test_float8_weights_fail_the_same_tolerance(weights, batch):
    got = tinygpt.forward(CONFIG, weights, batch)[0]
    fp8 = jax.tree.map(lambda t: t.astype(jnp.float8_e4m3fn).astype(t.dtype), weights)
    assert relative(got, reference_logits(SHAPE, fp8, batch)) > 10 * TOLERANCE["logits"]


def test_the_selection_bias_moves_the_choice_and_not_the_gate():
    """Sigmoid routing: a large bias on one expert puts it among every
    token's choices; a chosen expert's gate is its own score over the chosen
    scores' sum times the factor, whatever the bias."""
    c = dataclasses.replace(CONFIG, experts_held=None, held_rows_factor=None)
    x = jax.random.normal(jax.random.key(5), (SEQ, 64))
    router = jax.random.normal(jax.random.key(6), (64, EXPERTS))
    bias = jnp.zeros((EXPERTS,)).at[7].set(10.0)
    gates0, chosen0, counts0, aux0 = moe._route_dropless(c, x, router, bias=jnp.zeros((EXPERTS,)))
    gates, chosen, counts, aux = moe._route_dropless(c, x, router, bias=bias)
    assert int(counts[7]) == SEQ > int(counts0[7]) and float(aux) == float(aux0) == 0.0
    scores = jax.nn.sigmoid(jnp.dot(x, router, precision="highest"))
    picked = jnp.take_along_axis(scores, chosen, -1)
    np.testing.assert_allclose(gates, 2.446 * picked / picked.sum(-1, keepdims=True), rtol=1e-5)
    np.testing.assert_allclose(gates.sum(-1), 2.446, rtol=1e-5)
    # tokens that would have chosen expert 7 anyway keep their gates to the bit
    same = jnp.all(jnp.sort(chosen, -1) == jnp.sort(chosen0, -1), axis=-1)
    assert 0 < int(same.sum()) < SEQ
    np.testing.assert_array_equal(np.sort(gates[same], -1), np.sort(gates0[same], -1))
    # the softmax branch is the one the accepted cells take: no bias is read
    softmax = dataclasses.replace(c, router_score="softmax", routed_scaling_factor=1.0)
    gates_s, *_ = moe._route_dropless(softmax, x, router)
    np.testing.assert_allclose(gates_s.sum(-1), 1.0, rtol=1e-5)


def test_nope_latent_attention_is_the_reference_and_not_the_rotated_one(weights, batch):
    layer = tinygpt.layer_weights(CONFIG, weights, 3)
    x = jax.random.normal(jax.random.key(7), (BATCH, SEQ, 64))
    got = attention_mixer.sublayer(CONFIG, x, layer, None, True, GLOBAL)
    with jax.default_matmul_precision("highest"):
        want = jax.vmap(lambda x: reference_kda.latent_sublayer(SHAPE, x, layer))(x)
    assert relative(got - x, want - x) < TOLERANCE["logits"]
    rotated = attention_mixer.sublayer(
        dataclasses.replace(CONFIG, mla_nope=False), x, layer, None, True, GLOBAL)
    assert relative(rotated - x, want - x) > 10 * TOLERANCE["logits"]


def test_a_kda_layer_alone_is_the_reference(weights):
    layer = tinygpt.layer_weights(CONFIG, weights, 1)
    x = jax.random.normal(jax.random.key(8), (BATCH, SEQ, 64))
    with jax.default_matmul_precision("highest"):
        got = kda_mixer.sublayer(CONFIG, x, layer)
        want = jax.vmap(lambda x: reference_kda.kda_sublayer(SHAPE, x, layer))(x)
    assert relative(got - x, want - x) < TOLERANCE["logits"]
    # causal: a later token moves no earlier output (the convolutions too)
    moved = kda_mixer.sublayer(CONFIG, x.at[:, 40].add(1.0), layer)
    assert float(jnp.abs(moved[:, :40] - got[:, :40]).max()) < 1e-6
    assert float(jnp.abs(moved[:, 40:] - got[:, 40:]).max()) > 1e-3


def test_the_shares_add_up_to_the_uncut_layer(weights):
    """The share test: four chips hold 4 of the 16 experts each; what they
    compute of one routed layer (sigmoid scores, the choice by score + bias,
    gates renormalised over a token's chosen experts and scaled before each
    takes its held part), the shared expert counted once, adds up to the layer
    with every expert, and to the reference's layer given every expert."""
    share_of = HELD[1]
    whole = dataclasses.replace(CONFIG, experts_held=None, held_rows_factor=None)
    layer = tinygpt.layer_weights(CONFIG, weights, 1)
    key = jax.random.key(3)
    all_wgu = 0.1 * jax.random.normal(key, (EXPERTS, *layer["moe_wgu"].shape[1:]))
    all_wd = 0.1 * jax.random.normal(jax.random.fold_in(key, 1), (EXPERTS, *layer["moe_wd"].shape[1:]))
    x = jax.random.normal(jax.random.fold_in(key, 2), (BATCH, SEQ, CONFIG.n_embd))
    uncut, _ = moe.moe_mlp(whole, {**layer, "moe_wgu": all_wgu, "moe_wd": all_wd}, x, None, True)
    shared = moe._shared_experts(CONFIG, layer, x)
    total, parts = shared, []
    for first in range(0, EXPERTS, share_of):
        share = dataclasses.replace(CONFIG, experts_held=(first, share_of), held_rows_factor=None)
        held = {**layer, "moe_wgu": all_wgu[first:first + share_of],
                "moe_wd": all_wd[first:first + share_of]}
        y, _ = moe.moe_mlp(share, held, x, None, True)
        total = total + (y - shared)  # every chip computes the shared expert alike: once
        parts.append(relative(y - shared, uncut - shared))
    assert len(parts) == 4 and relative(total, uncut) < TOLERANCE["logits"]
    assert min(parts) > 0.1  # no share is all of it
    shape = {**SHAPE, "held": (0, EXPERTS)}
    w = {**layer, "moe_wgu": all_wgu, "moe_wd": all_wd}
    with jax.default_matmul_precision("highest"):
        want = jax.vmap(lambda h: reference_kda._routed_mlp(shape, h, w)[0])(x)
    assert relative(uncut, want) < TOLERANCE["logits"]


def test_the_train_step_runs_the_stack_and_reports_the_held_rows(batch):
    """Through ``create_train_state`` / ``state.step_fn``, as the cell runs it:
    the step's loss is the reference's at the state's weights, its report the
    held experts' rows and no overflow, and a later step's loss is lower."""
    mesh = make_mesh((1, 1, 1, 1, 1), MESH_AXES, devices=jax.devices()[:1])
    strategy = dataclasses.replace(get_strategy("zero2"), remat="dots")
    state = create_train_state(CONFIG, strategy, mesh, seed=5, from_table=True,
                               global_micro=1, seq_len=SEQ)
    table = jnp.asarray(batch[:1])
    with jax.default_matmul_precision("highest"):
        want = float(reference_kda.loss(SHAPE, state.params, table))
    params, opt_state, loss, report = state.step_fn(state.params, state.opt_state, table, 0)
    # at the seeded start every sigmoid score is 0.5 to two digits: near-ties take other
    # experts on the two sides, and the held experts' small part moves the loss in the 4th digit
    assert abs(float(loss) - want) / want < 50 * TOLERANCE["loss"]
    assert CONFIG.step_report == ("held_rows", "held_overflow")
    rows, overflow = np.asarray(report)
    assert overflow == 0.0 and 0.0 < rows <= 4 * SEQ * TOP_K  # four routed layers' rows
    assert float(jnp.abs(params["blocks"]["router_bias"]).max()) == 0.0  # a buffer stays put
    params, opt_state, *_ = state.step_fn(params, opt_state, table, 1)  # warm-up starts from 0
    *_, later, _ = state.step_fn(params, opt_state, table, 2)
    assert float(later) < float(loss)


@pytest.mark.parametrize("strategy", ["zero2", "fsdp"])
def test_the_new_leaves_have_specs_under_the_strategies(strategy):
    """Every leaf of every stack gets a spec of its rank; under fsdp the KDA
    mixer's matrices shard over 'data' inside the layer (never on the layers
    axis), under zero2 the parameters stay whole; the stacks' names all read as
    'blocks' to the rule table."""
    mesh = make_mesh((4, 1, 1, 1, 1), MESH_AXES, devices=jax.devices()[:1] * 4)
    shapes = jax.eval_shape(lambda k: tinygpt.init_params(CONFIG, k), jax.random.key(0))
    s = get_strategy(strategy)
    specs = strategies.param_partition_specs(shapes, mesh, shard=s.shard_params, scan_stacked=False)
    for path, leaf in jax.tree_util.tree_leaves_with_path(shapes):
        spec = specs
        for p in path:
            spec = spec[p.key]
        assert len(spec) == leaf.ndim, jax.tree_util.keystr(path)
        assert strategies._leaf_name(path).startswith("blocks/") == (len(path) == 2)
    kda = specs["kda_blocks"]
    if s.shard_params:
        assert kda["kda_wqkv"] == P(None, None, None, "data") or "data" in tuple(kda["kda_wqkv"])
        assert all(tuple(kda[k])[0] is None for k in ("kda_wqkv", "kda_wfb", "kda_wgb", "wo"))
        assert "data" in tuple(specs["blocks"]["wkv_b"])
    else:
        assert all(set(tuple(v)) == {None} for v in kda.values())


def test_each_kind_has_a_scope_under_attention_and_kda_its_three(weights, batch):
    assert LAYER_KIND_SCOPES[:3] == (WINDOW, GLOBAL, KDA) and tinygpt.LAYER_KINDS[:3] == (GLOBAL, WINDOW, KDA)
    text = jax.jit(lambda p, b: tinygpt.loss_fn(CONFIG, p, b, b)).lower(
        weights, batch).as_text(debug_info=True)
    assert f"attention/{GLOBAL}/mla_core" in text
    for scope in KDA_SCOPES:
        assert f"attention/{KDA}/{scope}" in text
    assert f"attention/{WINDOW}" not in text and f"mlp/shared" in text


@pytest.mark.parametrize("change, match", [
    (dict(scan_layers=True), "scanned\\s+loop is refused"),
    (dict(attention_impl="ring"), "attention_impl\\s+'flash' or 'reference'"),
    (dict(attention_impl="ulysses"), "attention_impl\\s+'flash' or 'reference'"),
    (dict(attention_impl="ring", kv_lora_rank=None, qk_nope_head_dim=0, qk_rope_head_dim=0,
          v_head_dim=0, mla_nope=False), "ring attention, Ulysses"),
    (dict(seq_manual_axis="seq"), "sequence-parallel"),
    (dict(kda_heads=0), "kda_heads"),
    (dict(kda_chunk=1), "kda_chunk"),
    (dict(dropout=0.1), "no dropout"),
    (dict(layer_types=(KDA, KDA, KDA, WINDOW, KDA), sliding_window=8), "no 'window' ones"),
    (dict(layer_types=(GLOBAL,) * 5), "go with 'kda' layers"),
    (dict(layer_types=(KDA, "mamba", KDA, GLOBAL, KDA)), "names one of"),
    (dict(router_score="tanh"), "router_score"),
    (dict(kv_lora_rank=None, qk_nope_head_dim=0, qk_rope_head_dim=0, v_head_dim=0), "mla_nope"),
])
def test_what_a_kda_stack_refuses_by_name(change, match):
    with pytest.raises(ValueError, match=match):
        dataclasses.replace(CONFIG, **change)


def test_the_pipeline_and_a_short_sequence_are_refused_by_name(weights):
    with pytest.raises(ValueError, match="first_k_dense|layer_types"):
        CONFIG.refuse_pipeline()
    plain = dataclasses.replace(CONFIG, kv_lora_rank=None, qk_nope_head_dim=0, qk_rope_head_dim=0,
                                v_head_dim=0, mla_nope=False, first_k_dense=0,
                                layer_types=(KDA,) * 5)
    with pytest.raises(ValueError, match="kda layers"):
        plain.refuse_pipeline()
    with pytest.raises(ValueError, match="not whole chunks of 16"):
        tinygpt.forward(CONFIG, weights, jnp.zeros((1, 40), jnp.int32))


def test_flops_and_memory_count_a_kda_layer():
    """The program's count and the benchmark's are written apart and agree to
    the convention's difference (the benchmark counts causal's exact pairs,
    the program S / 2 keys a token); a KDA layer's recurrence is the chunkwise
    form's work, 182,955 operations a token a head forward at chunk 64."""
    job = {**JOB, "kda_chunk": 64}
    shape = build_kda.kda_shape(job, FILE)
    config = build_kda.kimi_config(job, FILE)
    program, benchmark = flops.forward_flops_per_token(config), flops_kda.forward_flops_per_token(shape)
    exact_pairs = 2 * 0.5 * 4 * (16 + 8 + 16)  # (S + 1) / 2 against S / 2 keys, one global layer
    assert program + exact_pairs == pytest.approx(benchmark, rel=1e-9)
    published = {**shape, "kda_heads": 32, "kda_head_dim": 128}
    assert flops_kda.recurrence_forward_flops_per_token(published) / 32 == pytest.approx(182955, abs=1)
    by_hand = 4 * (5 * 2 * 64 * 16 + 3 * 2 * 16 * 16 + 2 * 64 * 64 / 3)
    assert flops_kda.recurrence_forward_flops_per_token(shape) == pytest.approx(by_hand)
    # memory: without remat a KDA layer keeps its operands, states and output; full keeps none,
    # full_keep_kernels the states and the output
    mesh = make_mesh((1, 1, 1, 1, 1), MESH_AXES, devices=jax.devices()[:1])
    strategy = get_strategy("zero2")
    kept = {remat: memory.estimate_hbm(dataclasses.replace(CONFIG, remat=remat), strategy, mesh, 1,
                                       SEQ).activations for remat in tinygpt.REMAT_POLICIES}
    plain = dataclasses.replace(CONFIG, layer_types=None, first_k_dense=1, kda_heads=0, kda_head_dim=0)
    assert kept["none"] > memory.estimate_hbm(plain, strategy, mesh, 1, SEQ).activations
    assert kept["none"] > kept["dots"] > kept["full_keep_kernels"] > kept["full"]


def test_kda_stats_count_chunks_calls_and_what_the_forward_keeps():
    stats = kda_mixer.kda_stats(CONFIG, SEQ)
    assert stats["layers"] == 4 and stats["chunk"] == 16 and stats["chunks"] == 4
    assert stats["kernel_calls"] == {"kda_fwd": 0, "kda_bwd": 0}  # the jnp path: no kernel here
    assert stats["prep_kernel_calls"] == {"kda_conv_fwd": 0, "kda_conv_bwd": 0}
    assert stats["saved_state_bytes"] == 4 * 4 * 16 * 16 * 4  # heads x chunks x d^2 x float32
    cell = dataclasses.replace(CONFIG, kda_heads=32, kda_head_dim=128, kda_chunk=128,
                               compute_dtype=jnp.bfloat16)
    assert kda_mixer.kda_stats(cell, 16384)["saved_state_bytes"] == 32 * 128 * 128 * 128 * 2
    assert set(attention_mixer.attn_mask_stats(CONFIG, SEQ)) == {GLOBAL}  # a kda layer has no mask


def test_kda_stats_count_the_prologues_calls_where_its_kernels_run(monkeypatch):
    """On a TPU at a 128-lane head the convolution, SiLU and the l2norms are
    ``kda_conv_fwd`` / ``kda_conv_bwd``, a call each for q, k and v a layer;
    at the test's 16-wide heads, or on another backend, the ``jnp`` chain."""
    cell = dataclasses.replace(CONFIG, kda_heads=32, kda_head_dim=128, kda_chunk=128)
    assert kda_mixer.kda_stats(cell, 16384)["prep_kernel_calls"] == {"kda_conv_fwd": 0, "kda_conv_bwd": 0}
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    stats = kda_mixer.kda_stats(cell, 16384)
    assert stats["kernel_calls"] == {"kda_fwd": 4, "kda_bwd": 4}
    assert stats["prep_kernel_calls"] == {"kda_conv_fwd": 12, "kda_conv_bwd": 12}
    assert kda_mixer.kda_stats(cell, 16380)["prep_kernel_calls"]["kda_conv_fwd"] == 0  # not whole 8-row tiles
    assert kda_mixer.kda_stats(CONFIG, SEQ)["prep_kernel_calls"] == {"kda_conv_fwd": 0, "kda_conv_bwd": 0}
