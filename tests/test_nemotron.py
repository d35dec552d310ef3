"""Nemotron-H-class stacks (each block one sublayer alone: Mamba-2 mixers, GQA
attention without positions, relu2 experts that are not gated with sigmoid
routing over one chip's share of them and a shared expert of its own width)
against the plain float32 reference the benchmark keeps
(``perfbench/harness/reference_nemotron.py``: the scan position by position),
at a small size: the cell's nine blocks ``MEMEM*EME``, which has ``M*`` and
``*E`` adjacent (a mixer with no feed-forward part behind it), 4 of 16 experts
held.

Both sides compute in float32 here, so they differ only by the order of
summation: a few 1e-7 of the largest value. The tolerances sit two orders
above that and well under the smallest wrong model below.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_llm_training_benchmark_framework_tpu.models.mixers import (
    attention as attention_mixer,
    ssd as ssd_mixer,
)
from distributed_llm_training_benchmark_framework_tpu.models import moe, tinygpt
from distributed_llm_training_benchmark_framework_tpu.models.tinygpt import TinyGPTConfig
from distributed_llm_training_benchmark_framework_tpu.parallel import get_strategy, make_mesh
from distributed_llm_training_benchmark_framework_tpu.parallel import strategies
from distributed_llm_training_benchmark_framework_tpu.train.step import create_train_state
from distributed_llm_training_benchmark_framework_tpu.utils import flops, memory
from distributed_llm_training_benchmark_framework_tpu.utils.scopes import (
    GLOBAL, KDA, LAYER_KIND_SCOPES, MLP, SSD, SSD_SCOPES, WINDOW,
)
from perfbench.harness import build_nemotron, flops_nemotron, reference_nemotron

TOLERANCE = {"logits": 1e-4, "loss": 1e-5, "grad_leaf": 1e-3}
SEQ, BATCH, EXPERTS, HELD, TOP_K = 32, 2, 16, (4, 4), 3
MESH_AXES = ("data", "seq", "model", "pipe", "expert")
PATTERN = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"  # the published 52 blocks
# The cell's two data files at a small size: what the builder and the
# reference's shape are made from, as the benchmark makes them.
FILE = dict(
    hidden_size=64, num_attention_heads=4, num_key_value_heads=2, head_dim=16,
    hybrid_override_pattern=PATTERN, num_hidden_layers=9, norm_eps=1e-5,
    mamba_num_heads=4, mamba_head_dim=16, n_groups=2, ssm_state_size=16, conv_kernel=4,
    chunk_size=16, mamba_hidden_act="silu", mamba_proj_bias=False, use_conv_bias=True,
    mlp_hidden_act="relu2", mlp_bias=False, use_bias=False, attention_bias=False,
    moe_intermediate_size=32, moe_shared_expert_intermediate_size=48, n_shared_experts=1,
    n_routed_experts_published=EXPERTS, n_routed_experts=HELD[1], experts_held_first=HELD[0],
    num_experts_per_tok=TOP_K, norm_topk_prob=True, routed_scaling_factor=2.5, n_group=1,
    topk_group=1, sliding_window=None, tie_word_embeddings=False, vocab_size=128, dropout=0.0)
JOB = dict(seq_len=SEQ, held_rows_factor=4.0, attention="reference", layer_loop="unrolled")
SHAPE = build_nemotron.nemotron_shape(JOB, FILE)
CONFIG = dataclasses.replace(build_nemotron.nemotron_config(JOB, FILE), compute_dtype=jnp.float32)
KINDS = (SSD, MLP, SSD, MLP, SSD, GLOBAL, MLP, SSD, MLP)
# (the kind of block it is wrong in, the change to the reference's shape)
WRONG = {
    "a_bfloat16_state": (SSD, {"state_dtype": "bfloat16"}),
    "the_norm_before_the_gate": (SSD, {"gate_first": False}),
    "no_skip": (SSD, {"skip": False}),
    "no_bias_on_the_convolution": (SSD, {"conv_bias": False}),
    "heads_reading_the_next_groups_b_and_c": (SSD, {"group_shift": 1}),
    "rotary_on_the_attention_block": (GLOBAL, {"rotary": 10000.0}),
    "relu_not_squared": (MLP, {"squared": False}),
    "gates_not_renormalised": (MLP, {"norm_topk_prob": False}),
    "no_scaling_factor": (MLP, {"routed_scaling": 1.0}),
    "the_held_experts_one_off": (MLP, {"held": (HELD[0] + 1, HELD[1])}),
    "no_shared_expert": (MLP, {"shared": False}),
}


def seeded_weights(config, bias=0.0):
    """Seeded weights large enough that every part shows in the logits: the
    program's initialization times five for what it draws around zero, the
    leaves that start from one constant (norm scales, the skip D) drawn around
    what they start from, the mixer's own leaves as the program draws them, and
    the selection bias (a buffer the program starts at zero) drawn at ``bias``."""
    params = tinygpt.init_params(config, jax.random.key(0))
    keys = iter(jax.random.split(jax.random.key(1), 200))

    def redraw(path, x):
        name, key = path[-1].key, next(keys)
        if name == "router_bias":
            return bias * jax.random.normal(key, x.shape)
        if name in ("ssd_a_log", "ssd_dt_bias", "ssd_conv", "ssd_conv_bias"):
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
    with jax.default_matmul_precision("highest"):
        return jax.vmap(lambda t: reference_nemotron.logits(shape, params, t))(batch)


def relative(got, want):
    return float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))


def test_the_builder_gives_each_block_its_one_half_and_each_kind_its_stack():
    assert CONFIG.layer_types == KINDS == SHAPE["kinds"] and CONFIG.block_halves
    assert CONFIG.layer_groups == (
        ("ssd_blocks", (0, 2, 4, 7)), ("mlp_blocks", (1, 3, 6, 8)), ("global_blocks", (5,)))
    assert (CONFIG.n_mlp_layers, CONFIG.n_moe_layers, SHAPE["moe_layers"]) == (4, 4, 4)
    assert CONFIG.pos_embed == "none" and CONFIG.mlp_act == "relu2" and CONFIG.shared_dim == 48
    assert CONFIG.routed_scaling_factor == 2.5 and not CONFIG.trains_routing
    assert [CONFIG.halves(kind) for kind in (SSD, GLOBAL, MLP)] == [
        (True, False), (True, False), (False, True)]
    params = tinygpt.init_params(CONFIG, jax.random.key(0))
    shapes = {k: jax.tree.map(jnp.shape, v) for k, v in params.items() if k.endswith("blocks")}
    assert set(params) == {"ssd_blocks", "mlp_blocks", "global_blocks", "wte", "lm_head", "lnf_scale"}
    assert {k: v[1:] for k, v in shapes["ssd_blocks"].items()} == {
        "ln1_scale": (64,), "ssd_win": (64, 64 + 128 + 4), "ssd_conv": (4, 128),
        "ssd_conv_bias": (128,), "ssd_dt_bias": (4,), "ssd_a_log": (4,), "ssd_d": (4,),
        "ssd_norm": (64,), "wo": (64, 64)}
    assert {k: v[1:] for k, v in shapes["global_blocks"].items()} == {
        "ln1_scale": (64,), "wq": (64, 64), "wkv": (64, 2, 32), "wo": (64, 64)}
    assert {k: v[1:] for k, v in shapes["mlp_blocks"].items()} == {
        "ln2_scale": (64,), "router": (64, EXPERTS), "router_bias": (EXPERTS,),
        "moe_wu": (4, 64, 32), "moe_wd": (4, 32, 64), "shared_wu": (64, 48), "shared_wd": (48, 64)}
    assert all(v[0] == 4 for v in shapes["ssd_blocks"].values())
    # the decay starts where the family's code starts it, the skip at one, the bias at zero
    rate = jnp.exp(params["ssd_blocks"]["ssd_a_log"])
    step = jax.nn.softplus(params["ssd_blocks"]["ssd_dt_bias"])
    assert 1.0 <= float(rate.min()) and float(rate.max()) <= 16.0
    assert 0.000999 <= float(step.min()) and float(step.max()) <= 0.1001
    assert float(jnp.abs(params["ssd_blocks"]["ssd_d"] - 1.0).max()) == 0.0
    assert float(jnp.abs(params["mlp_blocks"]["router_bias"]).max()) == 0.0


def test_the_accepted_configurations_keep_their_trees_and_their_draws():
    """The stacks of the accepted configurations are named and drawn as
    before: a plain stack, a leading dense one, a KDA stack and stacks by
    head count; a leaf's first values, pinned."""
    plain = TinyGPTConfig(vocab_size=64, n_embd=32, n_head=2, n_layer=3, block_size=16)
    assert plain.layer_groups == (("blocks", (0, 1, 2)),) and not plain.block_halves
    assert plain.halves(None) == (True, True) and plain.n_mlp_layers == 3
    legacy = tinygpt.init_params(plain, jax.random.key(0))
    assert set(legacy) == {"blocks", "wte", "wpe", "lnf_scale", "lnf_bias"}
    np.testing.assert_allclose(
        np.asarray(legacy["blocks"]["wqkv"][0, 0, 0, :3]),
        np.asarray(0.02 * jax.random.normal(jax.random.split(jax.random.key(0), 8)[0],
                                            (3, 32, 3, 32))[0, 0, 0, :3]))
    routed = dict(vocab_size=64, n_embd=32, n_head=2, n_kv_head=1, n_layer=4, block_size=16,
                  causal=True, dropout=0.0, norm="rmsnorm", pos_embed="rope", mlp_act="swiglu",
                  mlp_hidden=16, bias=False, tie_embeddings=False, n_experts=4, expert_top_k=2,
                  capacity_factor=None, n_shared_experts=1, scan_layers=False)
    deepseek = TinyGPTConfig(**routed, first_k_dense=1, dense_mlp_hidden=48)
    assert deepseek.layer_groups == (("dense_blocks", (0,)), ("blocks", (1, 2, 3)))
    tree = tinygpt.init_params(deepseek, jax.random.key(0))
    assert set(tree["blocks"]) == {"ln1_scale", "ln2_scale", "wq", "wkv", "wo", "router",
                                   "moe_wgu", "moe_wd", "shared_wgu", "shared_wd"}
    assert tree["blocks"]["shared_wgu"].shape == (3, 32, 32) and deepseek.n_moe_layers == 3
    np.testing.assert_allclose(  # 'blocks' draws first: wq from the first of 24 keys
        np.asarray(tree["blocks"]["wq"][0, 0, :3]),
        np.asarray(0.02 * jax.random.normal(jax.random.split(jax.random.key(0), 24)[0],
                                            (3, 32, 32))[0, 0, :3]))
    kimi = TinyGPTConfig(**routed, layer_types=(KDA, GLOBAL, KDA, KDA), kda_heads=2, kda_head_dim=16)
    assert kimi.layer_groups == (("kda_blocks", (0, 2, 3)), ("blocks", (1,)))
    laguna = TinyGPTConfig(**{**routed, "n_kv_head": 1}, layer_types=(GLOBAL, WINDOW) * 2,
                           sliding_window=8, layer_heads=((WINDOW, 4),), head_width=16)
    assert laguna.layer_groups == (("global_blocks", (0, 2)), ("window_blocks", (1, 3)))
    assert "ln2_scale" in tinygpt.init_params(laguna, jax.random.key(0))["global_blocks"]


@pytest.mark.parametrize("remat", ["none", "dots", "full_keep_kernels", "full"])
def test_logits_match_the_reference(weights, batch, remat):
    config = dataclasses.replace(CONFIG, remat=remat)
    got = tinygpt.forward(config, weights, batch)[0]
    assert relative(got, reference_logits(SHAPE, weights, batch)) < TOLERANCE["logits"]


def test_the_loss_matches_the_reference_and_has_no_router_term(weights, batch):
    got = float(tinygpt.loss_fn(CONFIG, weights, batch, batch))
    with jax.default_matmul_precision("highest"):
        want, (losses, counts) = reference_nemotron.loss_and_parts(SHAPE, weights, batch)
    assert abs(got - float(want)) / float(want) < TOLERANCE["loss"]
    assert float(want) == pytest.approx(float(jnp.mean(losses)))
    program = tinygpt.moe_expert_counts(CONFIG, weights, batch)
    np.testing.assert_array_equal(np.asarray(program), np.asarray(counts))
    assert counts.shape == (4, EXPERTS) and int(counts.sum()) == 4 * BATCH * SEQ * TOP_K


# Every expert on this chip, through the same held-experts path: the routing trains
# (the first pair of blocks, M E: what differs is the routed block's backward).
EVERY_EXPERT = {**FILE, "n_routed_experts": EXPERTS, "experts_held_first": 0,
                "num_hidden_layers": 2}


@pytest.mark.parametrize("file", [FILE, EVERY_EXPERT], ids=["a-part", "every-expert"])
def test_gradient_of_every_leaf_matches_the_reference(batch, file):
    """``jax.grad`` of the training loss through the chunked scan's own
    backward, the convolution with its bias, the gated grouped norm, attention
    without positions, the sigmoid gates and the held relu2 experts, under remat
    as the timed cell runs them."""
    shape = build_nemotron.nemotron_shape(JOB, file)
    config = dataclasses.replace(
        build_nemotron.nemotron_config(JOB, file), compute_dtype=jnp.float32,
        remat="full_keep_kernels")
    assert config.trains_routing == shape["routing_trained"] == (file is EVERY_EXPERT)
    weights = seeded_weights(config, bias=0.3)
    got = jax.grad(lambda p: tinygpt.loss_fn(config, p, batch, batch))(weights)
    with jax.default_matmul_precision("highest"):
        want = jax.grad(lambda p: reference_nemotron.loss(shape, p, batch))(weights)
    # the bias moves the choice only: no gradient reaches it, on either side
    assert float(jnp.abs(got["mlp_blocks"].pop("router_bias")).max()) == 0.0
    assert float(jnp.abs(want["mlp_blocks"].pop("router_bias")).max()) == 0.0
    if file is FILE:  # the gates are constants of this chip's backward: departure 2
        assert float(jnp.abs(got["mlp_blocks"]["router"]).max()) == 0.0
    errors = jax.tree_util.tree_map_with_path(
        lambda path, a, b: (jax.tree_util.keystr(path), float(
            jnp.linalg.norm(a - b) / jnp.maximum(jnp.linalg.norm(b), 1e-30))), got, want)
    worst = max(jax.tree.leaves(errors, is_leaf=lambda x: isinstance(x, tuple)), key=lambda e: e[1])
    assert worst[1] < TOLERANCE["grad_leaf"], worst
    for leaf in ("ssd_a_log", "ssd_dt_bias", "ssd_d", "ssd_conv_bias", "ssd_norm"):
        assert float(jnp.abs(got["ssd_blocks"][leaf]).max()) > 0.0, leaf


def reference_block(shape, kind, x, w):
    sublayer = {SSD: reference_nemotron.ssd_sublayer, GLOBAL: reference_nemotron.attention_sublayer,
                MLP: lambda m, x, w: reference_nemotron.routed_sublayer(m, x, w)[0]}[kind]
    with jax.default_matmul_precision("highest"):
        return jax.vmap(lambda x: sublayer(shape, x, w))(x)


@pytest.fixture(scope="module")
def blocks(weights):
    """{kind: (the block's weights, an input, what the program's block adds to it)}."""
    x = jax.random.normal(jax.random.key(4), (BATCH, SEQ, CONFIG.n_embd))
    out = {}
    for kind in (SSD, GLOBAL, MLP):
        layer = tinygpt.layer_weights(CONFIG, weights, KINDS.index(kind))
        out[kind] = (layer, x, tinygpt.apply_layer(CONFIG, layer, x, kind)[0] - x)
    return out


@pytest.mark.parametrize("name", sorted(WRONG))
def test_a_wrong_model_fails_the_same_tolerance(blocks, name):
    """What the program's block of the wrong model's kind adds to its input is
    the reference's, and not the wrong reference's."""
    kind, change = WRONG[name]
    layer, x, got = blocks[kind]
    assert relative(got, reference_block(SHAPE, kind, x, layer) - x) < TOLERANCE["logits"]
    assert relative(got, reference_block({**SHAPE, **change}, kind, x, layer) - x) > (
        2 * TOLERANCE["logits"]), name


def test_float8_weights_fail_the_same_tolerance(weights, batch):
    got = tinygpt.forward(CONFIG, weights, batch)[0]
    rounded = jax.tree.map(lambda t: t.astype(jnp.float8_e4m3fn).astype(t.dtype), weights)
    assert relative(got, reference_logits(SHAPE, rounded, batch)) > 10 * TOLERANCE["logits"]


@pytest.mark.parametrize("kind", [SSD, GLOBAL])
def test_a_mixer_block_alone_is_the_references_and_has_no_mlp_behind_it(blocks, kind):
    """``apply_layer`` on one block of a mixer's kind: x + mixer(RMSNorm(x)) and
    nothing else (its stack has no feed-forward leaves to run, and it reports
    no rows)."""
    layer, x, got = blocks[kind]
    assert not any(k.startswith(("moe_", "shared_", "router", "ln2")) for k in layer)
    assert relative(got, reference_block(SHAPE, kind, x, layer) - x) < TOLERANCE["logits"]
    _, aux = tinygpt.apply_layer(CONFIG, layer, x, kind)
    assert aux.shape == CONFIG.aux_shape and float(jnp.abs(aux).max()) == 0.0


def test_attention_without_positions_sees_no_order_but_the_masks(weights):
    """With ``pos_embed='none'`` nothing rotates q or k and there is no table:
    the last position's output is unchanged when the positions before it change
    places."""
    layer = tinygpt.layer_weights(CONFIG, weights, KINDS.index(GLOBAL))
    x = jax.random.normal(jax.random.key(6), (1, SEQ, CONFIG.n_embd))
    swapped = x.at[0, 3].set(x[0, 9]).at[0, 9].set(x[0, 3])
    a, b = (tinygpt.apply_layer(CONFIG, layer, t, GLOBAL)[0][0, -1] for t in (x, swapped))
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-5, atol=2e-6)
    assert "wpe" not in weights and tinygpt.embed_param_names(CONFIG) == ("wte",)


def test_the_sixteen_shares_of_eight_add_up_to_the_uncut_layer(weights):
    """The share test: sixteen chips hold 8 of 128 experts each; what they
    compute of one routed block (sigmoid scores over the 128, the choice by
    score + bias, gates renormalised over a token's 6 and times 2.5 before each
    takes its held part; experts not gated), the shared expert counted once,
    adds up to the block with every expert, and to the reference's block given
    every expert."""
    experts, share_of, top_k = 128, 8, 6
    base = dataclasses.replace(CONFIG, n_experts=experts, expert_top_k=top_k)
    whole = dataclasses.replace(base, experts_held=None, held_rows_factor=None)
    layer = tinygpt.layer_weights(CONFIG, weights, 1)
    key = jax.random.key(3)
    layer = {**layer, "router": jax.random.normal(key, (CONFIG.n_embd, experts)),
             "router_bias": 0.3 * jax.random.normal(jax.random.fold_in(key, 3), (experts,))}
    all_wu = 0.1 * jax.random.normal(key, (experts, *layer["moe_wu"].shape[1:]))
    all_wd = 0.1 * jax.random.normal(jax.random.fold_in(key, 1), (experts, *layer["moe_wd"].shape[1:]))
    x = jax.random.normal(jax.random.fold_in(key, 2), (1, 32, CONFIG.n_embd))
    uncut, _ = moe.moe_mlp(whole, {**layer, "moe_wu": all_wu, "moe_wd": all_wd}, x, None, True)
    shared = moe._shared_experts(base, layer, x)
    total, parts = shared, []
    for first in range(0, experts, share_of):
        share = dataclasses.replace(base, experts_held=(first, share_of), held_rows_factor=None)
        held = {**layer, "moe_wu": all_wu[first:first + share_of],
                "moe_wd": all_wd[first:first + share_of]}
        y, _ = moe.moe_mlp(share, held, x, None, True)
        total = total + (y - shared)  # every chip computes the shared expert alike: once
        parts.append(relative(y - shared, uncut - shared))
    assert len(parts) == 16 and relative(total, uncut) < TOLERANCE["logits"]
    assert min(parts) > 0.1  # no share is all of it
    shape = {**SHAPE, "experts": experts, "experts_per_token": top_k, "held": (0, experts)}
    w = {**layer, "moe_wu": all_wu, "moe_wd": all_wd}
    with jax.default_matmul_precision("highest"):
        want = jax.vmap(lambda h: reference_nemotron._routed_mlp(shape, h, w)[0])(x)
    assert relative(uncut, want) < TOLERANCE["logits"]


def test_the_train_step_runs_the_stacks_and_reports_the_held_rows(batch):
    """Through ``create_train_state`` / ``state.step_fn``, as the cell runs it:
    the step's loss is the reference's at the state's weights, its report the
    held experts' rows and no overflow, and a later step's loss is lower."""
    mesh = make_mesh((1, 1, 1, 1, 1), MESH_AXES, devices=jax.devices()[:1])
    strategy = dataclasses.replace(get_strategy("zero2"), remat="full_keep_kernels")
    state = create_train_state(CONFIG, strategy, mesh, seed=5, from_table=True,
                               global_micro=1, seq_len=SEQ)
    table = jnp.asarray(batch[:1])
    with jax.default_matmul_precision("highest"):
        want = float(reference_nemotron.loss(SHAPE, state.params, table))
    params, opt_state, loss, report = state.step_fn(state.params, state.opt_state, table, 0)
    # at the seeded start every sigmoid score is 0.5 to two digits: near-ties take other
    # experts on the two sides, and the held experts' small part moves the loss in the 4th digit
    assert abs(float(loss) - want) / want < 50 * TOLERANCE["loss"]
    assert CONFIG.step_report == ("held_rows", "held_overflow")
    rows, overflow = np.asarray(report)
    assert overflow == 0.0 and 0.0 < rows <= 4 * SEQ * TOP_K  # four routed blocks' rows
    assert float(jnp.abs(params["mlp_blocks"]["router_bias"]).max()) == 0.0  # a buffer stays put
    params, opt_state, *_ = state.step_fn(params, opt_state, table, 1)  # warm-up starts from 0
    *_, later, _ = state.step_fn(params, opt_state, table, 2)
    assert float(later) < float(loss)


@pytest.mark.parametrize("strategy", ["zero2", "fsdp"])
def test_the_new_leaves_have_specs_under_the_strategies(strategy):
    """Every leaf of every stack gets a spec of its rank; under fsdp the mixer's
    and the experts' matrices shard over 'data' inside the layer (never on the
    layers axis), under zero2 the parameters stay whole; the stacks' names all
    read as 'blocks' to the rule table, and every new leaf has a rule."""
    mesh = make_mesh((4, 1, 1, 1, 1), MESH_AXES, devices=jax.devices()[:1] * 4)
    shapes = jax.eval_shape(lambda k: tinygpt.init_params(CONFIG, k), jax.random.key(0))
    s = get_strategy(strategy)
    specs = strategies.param_partition_specs(shapes, mesh, shard=s.shard_params, scan_stacked=False)
    for path, leaf in jax.tree_util.tree_leaves_with_path(shapes):
        spec = specs
        for p in path:
            spec = spec[p.key]
        assert len(spec) == leaf.ndim, jax.tree_util.keystr(path)
        name = strategies._leaf_name(path)
        assert name.startswith("blocks/") == (len(path) == 2)
        assert name in tinygpt.PARAM_AXIS_RULES and len(tinygpt.PARAM_AXIS_RULES[name]) == (
            leaf.ndim + (len(path) == 2) - (len(path) == 2)), name
    ssd = specs["ssd_blocks"]
    if s.shard_params:
        assert all("data" in tuple(ssd[k]) and tuple(ssd[k])[0] is None for k in ("ssd_win", "wo"))
        assert "data" in tuple(specs["mlp_blocks"]["moe_wu"])
    else:
        assert all(set(tuple(v)) == {None} for v in ssd.values())


def test_each_kind_has_a_scope_and_ssd_its_three(weights, batch):
    assert LAYER_KIND_SCOPES[:4] == (WINDOW, GLOBAL, KDA, SSD)
    assert tinygpt.LAYER_KINDS[:5] == (GLOBAL, WINDOW, KDA, SSD, MLP)
    text = jax.jit(lambda p, b: tinygpt.loss_fn(CONFIG, p, b, b)).lower(
        weights, batch).as_text(debug_info=True)
    for scope in SSD_SCOPES:
        assert f"attention/{SSD}/{scope}" in text
    assert f"attention/{GLOBAL}" in text and f"attention/{WINDOW}" not in text
    for scope in ("router", "dispatch", "experts", "combine", "shared"):  # as the MoE cells have
        assert f"mlp/{scope}" in text
    assert "attention/mlp" not in text and "qk_prologue" not in text


@pytest.mark.parametrize("change, match", [
    (dict(scan_layers=True), "scanned\\s+loop is refused"),
    (dict(attention_impl="ring"), "attention_impl\\s+'flash' or 'reference'"),
    (dict(attention_impl="ulysses"), "attention_impl\\s+'flash' or 'reference'"),
    (dict(seq_manual_axis="seq"), "sequence-parallel pipeline"),
    (dict(block_halves=False), "block_halves"),
    (dict(layer_types=(SSD, GLOBAL) * 4 + (SSD,)), "name the 'mlp' blocks"),
    (dict(first_k_dense=1, dense_mlp_hidden=32), "first_k_dense leading layers are dense SwiGLU"),
    (dict(ssd_groups=3), "ssd_groups\\s+dividing ssd_heads"),
    (dict(norm="layernorm"), "norm='rmsnorm'"),
    (dict(dropout=0.1), "no dropout"),
    (dict(mlp_act="gelu"), "dropless"),
    (dict(bias=True), "relu2"),
    (dict(n_experts=0, n_shared_experts=0, experts_held=None, held_rows_factor=None,
          shared_expert_hidden=None, router_score="softmax", routed_scaling_factor=1.0),
     "a dense MLP of it is not built"),
    (dict(layer_types=None), "block_halves needs layer_types"),
])
def test_what_a_stack_of_halves_refuses_by_name(change, match):
    with pytest.raises(ValueError, match=match):
        dataclasses.replace(CONFIG, **change)


def test_no_positions_and_the_pipeline_are_refused_by_name():
    with pytest.raises(ValueError, match="pos_embed='none'"):
        TinyGPTConfig(n_embd=64, n_head=4, pos_embed="none", bias=False, kv_lora_rank=16,
                      qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16, norm="rmsnorm")
    with pytest.raises(ValueError, match="pos_embed must be"):
        dataclasses.replace(CONFIG, pos_embed="alibi")
    with pytest.raises(ValueError, match="shared_expert_hidden"):
        dataclasses.replace(CONFIG, n_shared_experts=0)
    with pytest.raises(ValueError, match="pipeline schedules slice one homogeneous stack"):
        CONFIG.refuse_pipeline()
    with pytest.raises(ValueError, match="not whole chunks"):
        tinygpt.forward(CONFIG, tinygpt.init_params(CONFIG, jax.random.key(0)),
                        jnp.zeros((1, SEQ - 8), jnp.int32))


def test_flops_and_memory_count_the_blocks_by_kind():
    """The program's count is the benchmark's (``flops_nemotron``) but for the
    causal pairs' half position; an ``ssd`` block's is the hand count; the
    memory estimate grows by the states a block keeps."""
    at_128 = dataclasses.replace(CONFIG, ssd_chunk=128)  # the benchmark's count fixes the chunk
    got, want = flops.forward_flops_per_token(at_128), flops_nemotron.forward_flops_per_token(SHAPE)
    half_position = 4 * 0.5 * 4 * 16  # (S + 1) / 2 against S / 2 keys, 4 heads of 16
    assert got == pytest.approx(want - half_position)
    D, inner, xbc, H, C, P, N = 64, 64, 128, 4, 16, 16, 16
    assert ssd_mixer.forward_flops_per_token(CONFIG) == (
        2 * D * (inner + xbc + H) + 2 * inner * D + 2 * 4 * xbc
        + H * (2 * C * P + 4 * N * P) + 2 * 2 * C * N)
    assert flops_nemotron.scan_forward_flops_per_token({**SHAPE, "ssd_state": 16}) == (
        H * (2 * 128 * P + 4 * N * P) + 2 * 2 * 128 * N)  # at the count's own chunk of 128
    mesh = make_mesh((1, 1, 1, 1, 1), MESH_AXES, devices=jax.devices()[:1])
    estimate = lambda config, remat: memory.estimate_hbm(
        dataclasses.replace(config, remat=remat), get_strategy("zero2"), mesh,
        per_device_batch=1, seq_len=SEQ).activations
    stats = ssd_mixer.ssd_stats(CONFIG, SEQ)
    assert stats["saved_state_bytes"] == (SEQ // 16) * 64 * 16 * 4
    kept = 4 * (stats["saved_state_bytes"] + SEQ * 64 * 4)
    assert estimate(CONFIG, "full_keep_kernels") - estimate(CONFIG, "full") >= kept


def test_ssd_stats_count_chunks_steps_calls_and_what_the_forward_keeps(monkeypatch):
    stats = ssd_mixer.ssd_stats(CONFIG, SEQ)
    assert (stats["layers"], stats["chunk"], stats["chunks"], stats["chunk_steps"]) == (4, 16, 2, 4)
    assert stats["kernel_calls"] == {"ssd_fwd": 0, "ssd_bwd": 0}  # the jnp path off a TPU
    cell = dataclasses.replace(CONFIG, ssd_heads=64, ssd_head_dim=64, ssd_groups=8, ssd_state=128,
                               ssd_chunk=128, compute_dtype=jnp.bfloat16)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    stats = ssd_mixer.ssd_stats(cell, 16384)
    assert stats["kernel_calls"] == {"ssd_fwd": 4, "ssd_bwd": 4}
    assert stats["conv_kernel_calls"] == {"kda_conv_fwd": 4, "kda_conv_bwd": 4}
    assert stats["chunk_steps"] == 128 * 8 and stats["saved_state_bytes"] == 128 * 4096 * 128 * 2
    assert attention_mixer.attn_mask_stats(cell, 16384).keys() == {"global"}
    assert attention_mixer.qk_prologue_stats(cell, 16384)["rotary_layers"] == 0
