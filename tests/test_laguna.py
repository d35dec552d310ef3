"""Laguna-class stacks (full layers of fewer query heads beside sliding-window
layers of more, so that a layer's kind decides the shapes of wq, wg and wo; a
per-head sigmoid gate on the attention's output; rotary over half of a full
layer's head under YaRN and over whole heads on the sliding layers; a leading
dense layer, then sigmoid routing with a shared expert over one chip's share of
the experts) against the plain float32 reference the benchmark keeps
(``perfbench/harness/reference_laguna.py``), at a small size: hidden 64, 6 / 8
heads of 16 over 2 KV heads, a window of 8 over 64 positions, 4 of 16 experts of
width 32 held, 4 a token, YaRN's original context half the sequence.

Both sides compute in float32 here, so they differ only by the order of
summation: a few 1e-7 of the largest value. The tolerances sit two orders
above that and well under the smallest wrong model below.
"""

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from distributed_llm_training_benchmark_framework_tpu.models import mixers
from distributed_llm_training_benchmark_framework_tpu.models.mixers import (
    attention as attention_mixer,
)
from distributed_llm_training_benchmark_framework_tpu.models import moe, tinygpt
from distributed_llm_training_benchmark_framework_tpu.models.tinygpt import Rotary, YarnScaling
from distributed_llm_training_benchmark_framework_tpu.ops import flash_attention as fa
from distributed_llm_training_benchmark_framework_tpu.ops import rotary as rotary_ops
from distributed_llm_training_benchmark_framework_tpu.parallel import (
    get_strategy, make_mesh, strategies,
)
from distributed_llm_training_benchmark_framework_tpu.train.step import create_train_state
from distributed_llm_training_benchmark_framework_tpu.utils import flops, scopes
from distributed_llm_training_benchmark_framework_tpu.utils.scopes import GLOBAL, WINDOW
from perfbench.harness import build_laguna, flops_laguna, manifest, reference_kda, reference_laguna

TOLERANCE = {"logits": 1e-4, "loss": 1e-5, "grad_leaf": 1e-3}
SEQ, BATCH, EXPERTS, HELD, TOP_K, WINDOW_KEYS = 64, 2, 16, (4, 4), 4, 8
MESH_AXES = ("data", "seq", "model", "pipe", "expert")
FACTOR, ORIGINAL = 4.0, 32
PERIOD = ["full_attention"] + ["sliding_attention"] * 3
# The cell's two data files at a small size: what the builder and the
# reference's shape are made from, as the benchmark makes them.
FILE = dict(
    hidden_size=64, num_attention_heads=6, num_key_value_heads=2, head_dim=16,
    num_attention_heads_per_layer=[6, 8, 8, 8] * 10, rms_norm_eps=1e-6, intermediate_size=128,
    moe_intermediate_size=32, shared_expert_intermediate_size=32, n_shared_experts=1,
    hidden_act="silu", num_experts_published=EXPERTS, num_experts=HELD[1],
    experts_held_first=HELD[0], num_experts_per_tok=TOP_K, norm_topk_prob=True,
    moe_routed_scaling_factor=2.5, moe_apply_router_weight_on_input=False,
    router_score="sigmoid", router_aux_loss_coef=0.0, gating=True, gating_granularity="per_head",
    qk_norm=False, rotary_lanes="leading", tie_word_embeddings=False, attention_bias=False,
    sliding_window=WINDOW_KEYS, layer_types=PERIOD * 10,
    mlp_layer_types=["dense"] + ["sparse"] * 39, rope_theta=10000,
    rope_parameters={
        "full_attention": dict(rope_type="yarn", rope_theta=500000, factor=FACTOR,
                               original_max_position_embeddings=ORIGINAL, beta_fast=64, beta_slow=1,
                               attention_factor=0.1 * math.log(FACTOR) + 1.0,
                               partial_rotary_factor=0.5),
        "sliding_attention": dict(rope_type="default", rope_theta=10000, partial_rotary_factor=1),
        "original_max_position_embeddings": ORIGINAL},
    vocab_size=128, num_hidden_layers=5, dropout=0.0)
JOB = dict(seq_len=SEQ, held_rows_factor=4.0, attention="flash", layer_loop="unrolled")
SHAPE = build_laguna.laguna_shape(JOB, FILE)
CONFIG = dataclasses.replace(build_laguna.laguna_config(JOB, FILE), compute_dtype=jnp.float32)
GLOBAL_TABLE, WINDOW_TABLE = dict(SHAPE["rotary"])["global"], dict(SHAPE["rotary"])["window"]
STACKS = ("global_dense_blocks", "window_blocks", "global_blocks")
WRONG = {
    "a_window_one_key_short": {"window": WINDOW_KEYS - 1},
    "a_window_one_key_long": {"window": WINDOW_KEYS + 1},
    "no_window_on_the_sliding_layers": {"mask_kinds": ("global",) * 5},
    "the_gate_left_out": {"gate": None},
    "the_gate_from_the_un_normed_input": {"gate": "raw"},
    "all_of_a_full_layers_head_rotated": {
        "rotary": (("global", (GLOBAL_TABLE[0], 16, GLOBAL_TABLE[2])), ("window", WINDOW_TABLE))},
    "lanes_paired_across_the_head_inside_the_half": {"pairing": "head"},
    "yarn_without_its_attention_factor": {
        "rotary": (("global", (*GLOBAL_TABLE[:2], GLOBAL_TABLE[2][:4] + (1.0,))),
                   ("window", WINDOW_TABLE))},
    "the_sliding_theta_on_the_full_layers": {
        "rotary": (("global", (WINDOW_TABLE[0], *GLOBAL_TABLE[1:])), ("window", WINDOW_TABLE))},
    "the_full_table_on_the_sliding_layers": {
        "rotary": (("global", GLOBAL_TABLE), ("window", (GLOBAL_TABLE[0], 16, GLOBAL_TABLE[2])))},
    "the_query_heads_grouped_one_off": {"kv_shift": 1},
    "gates_not_times_the_scaling_factor": {"routed_scaling": 1.0},
    "gates_not_renormalised": {"norm_topk_prob": False},
    "no_shared_expert": {"shared_width": 0},
    "one_held_expert_fewer": {"held": (HELD[0], HELD[1] - 1)},
}


def seeded_weights(config):
    """Seeded weights large enough that every part shows in the logits: the
    program's initialization times five, norm scales (the leaves that start
    from one constant) drawn around what they start from."""
    params = tinygpt.init_params(config, jax.random.key(0))
    leaves, tree = jax.tree.flatten(params)
    keys = jax.random.split(jax.random.key(1), len(leaves))
    redraw = lambda key, x: (x * (1.0 + 0.1 * jax.random.normal(key, x.shape))
                             if bool(jnp.all(x == x.ravel()[0])) and bool(x.ravel()[0] != 0)
                             else 5.0 * x)
    return jax.tree.unflatten(tree, [redraw(k, x) for k, x in zip(keys, leaves)])


@pytest.fixture(scope="module")
def weights():
    return seeded_weights(CONFIG)


@pytest.fixture(scope="module")
def batch():
    return jax.random.randint(jax.random.key(2), (BATCH, SEQ), 0, FILE["vocab_size"])


def reference_logits(shape, params, batch):
    with jax.default_matmul_precision("highest"):
        return jax.jit(jax.vmap(lambda t: reference_laguna.logits(shape, params, t)))(batch)


def relative(got, want):
    return float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))


def test_the_builder_gives_each_kind_its_heads_its_table_and_its_stack():
    assert CONFIG.layer_types == (GLOBAL, WINDOW, WINDOW, WINDOW, GLOBAL)
    assert (CONFIG.heads(GLOBAL), CONFIG.heads(WINDOW), CONFIG.kv_heads) == (6, 8, 2)
    assert CONFIG.heads_by_kind and CONFIG.stacks_unequal and not mixers.own_leaves(CONFIG.layer_types)
    assert CONFIG.layer_groups == (
        ("global_dense_blocks", (0,)), ("window_blocks", (1, 2, 3)), ("global_blocks", (4,)))
    assert CONFIG.attn_gate and CONFIG.first_k_dense == 1 and CONFIG.n_shared_experts == 1
    assert CONFIG.mask_rule(SEQ, WINDOW) == fa.SlidingWindow(WINDOW_KEYS)
    assert CONFIG.mask_rule(SEQ, GLOBAL) is True
    # a sliding layer rotates whole heads at its own theta; a full one half of each under YaRN
    assert CONFIG.rotary(WINDOW) == Rotary(10000.0) and CONFIG.layer_rotary == (
        (GLOBAL, Rotary(500000.0, YarnScaling(FACTOR, ORIGINAL, 64.0, 1.0, 1.0, 0.0), rotary_dim=8)),)
    params = tinygpt.init_params(CONFIG, jax.random.key(0))
    assert set(params) == {*STACKS, "wte", "lm_head", "lnf_scale"}
    shapes = jax.tree.map(jnp.shape, params)
    assert shapes["window_blocks"]["wq"] == (3, 64, 128) and shapes["window_blocks"]["wo"] == (3, 128, 64)
    assert shapes["global_blocks"]["wq"] == (1, 64, 96) and shapes["global_blocks"]["wo"] == (1, 96, 64)
    assert shapes["window_blocks"]["wg"] == (3, 64, 8) and shapes["global_dense_blocks"]["wg"] == (1, 64, 6)
    assert shapes["window_blocks"]["wkv"] == shapes["global_blocks"]["wkv"][:0] + (3, 64, 2, 32)
    assert "router" not in params["global_dense_blocks"] and shapes["global_dense_blocks"]["wgu"] == (1, 64, 2, 128)
    assert shapes["global_blocks"]["moe_wgu"] == (1, 4, 64, 64) and shapes["global_blocks"]["shared_wd"] == (1, 32, 64)
    for i in range(5):  # the program's slice of a stack is the reference's
        ours, theirs = tinygpt.layer_weights(CONFIG, params, i), reference_laguna.layer_weights(SHAPE, params, i)
        assert jax.tree.all(jax.tree.map(lambda a, b: bool(jnp.all(a == b)), ours, theirs))


def test_the_accepted_configurations_keep_their_stacks_and_draws():
    """A config without ``layer_heads`` names its stacks as before and draws
    its seeds' numbers as before: the Mellum builder's tree."""
    from perfbench.harness import build_mellum

    _, workload, file = manifest.load_cell("mellum2-12b-a2.5b.share4-seq16384")
    config = build_mellum.mellum_config({**workload, "seq_len": 64}, {
        **file, "hidden_size": 32, "head_dim": 8, "moe_intermediate_size": 16, "vocab_size": 64})
    assert config.layer_groups == (("blocks", (0, 1, 2, 3)),) and not config.stacks_unequal
    assert config.heads(WINDOW) == config.heads(GLOBAL) == config.heads() == config.n_head


@pytest.mark.parametrize("kind", [WINDOW, GLOBAL])
def test_a_layers_rotation_is_its_kinds_table_over_its_lanes(kind):
    x = jax.random.normal(jax.random.key(4), (1, SEQ, 2, 16))
    rotary = CONFIG.rotary(kind)
    got = attention_mixer._rope(x, jnp.arange(SEQ), rotary.theta, rotary.scaling, rotary.rotary_dim)[0]
    cos, sin, lanes = reference_laguna.rotary_table(SHAPE, kind, jnp.arange(SEQ))
    assert lanes == (8 if kind == GLOBAL else 16) and cos.shape == (SEQ, lanes // 2)
    np.testing.assert_allclose(got, reference_laguna._rotate(SHAPE, x[0], cos, sin, lanes), atol=1e-5)
    if kind == GLOBAL:  # the lanes past the half pass as they are; the half grows by the factor
        assert bool(jnp.all(got[..., 8:] == x[0, ..., 8:]))
        grows = float(jnp.linalg.norm(got[..., :8]) / jnp.linalg.norm(x[..., :8]))
        assert grows == pytest.approx(0.1 * math.log(FACTOR) + 1.0, rel=1e-5)
        # lane j meets lane j + 4, not j + 8: position 1's lane 0 holds x0 cos - x4 sin
        want = x[0, 1, 0, 0] * cos[1, 0] - x[0, 1, 0, 4] * sin[1, 0]
        assert float(got[1, 0, 0]) == pytest.approx(float(want), rel=1e-5)


@pytest.mark.parametrize("attention", ["flash", "reference"])
def test_logits_match_the_reference(weights, batch, attention):
    config = dataclasses.replace(CONFIG, attention_impl=attention)
    got = tinygpt.forward(config, weights, batch)[0]
    assert relative(got, reference_logits(SHAPE, weights, batch)) < TOLERANCE["logits"]


def test_the_loss_matches_the_reference(weights, batch):
    got = float(tinygpt.loss_fn(CONFIG, weights, batch, batch))
    with jax.default_matmul_precision("highest"):
        want = float(reference_laguna.loss(SHAPE, weights, batch))
    assert abs(got - want) / want < TOLERANCE["loss"]


def leaf_errors(got, want):
    return jax.tree.map(lambda g, w: float(jnp.linalg.norm(g - w) / jnp.linalg.norm(w)), got, want)


# Every expert on this chip, through the same held-experts path: the routing trains.
EVERY_EXPERT = {**FILE, "num_experts": EXPERTS, "experts_held_first": 0}


@pytest.mark.parametrize("file, remat", [
    (FILE, "dots"), (FILE, "full_keep_kernels"), (EVERY_EXPERT, "none")],
    ids=["a-part-dots", "a-part-full_keep_kernels", "every-expert-none"])
def test_gradient_of_every_leaf_matches_the_reference(batch, file, remat):
    """``jax.grad`` of the training loss through the flash kernels' einsum
    backward under each layer's rule at each kind's head count, each kind's
    rotary table over its lanes, the gate, the dense layer, the held experts
    and the shared one, the stacks unrolled in the published order under the
    cell's remat policies."""
    shape = build_laguna.laguna_shape(JOB, file)
    config = dataclasses.replace(
        build_laguna.laguna_config(JOB, file), compute_dtype=jnp.float32, remat=remat)
    assert config.trains_routing == shape["routing_trained"] == (file is EVERY_EXPERT)
    weights = seeded_weights(config)
    got = jax.jit(jax.grad(lambda p: tinygpt.loss_fn(config, p, batch, batch)))(weights)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(jax.grad(lambda p: reference_laguna.loss(shape, p, batch)))(weights)
    for stack in STACKS[1:]:
        got[stack].pop("router_bias"), want[stack].pop("router_bias")  # a buffer: no gradient
        router = float(jnp.abs(got[stack]["router"]).max())
        if not config.trains_routing:
            assert router == float(jnp.abs(want[stack]["router"]).max()) == 0.0
            got[stack].pop("router"), want[stack].pop("router")
        else:
            assert router > 0.0
    for path, error in jax.tree_util.tree_leaves_with_path(leaf_errors(got, want)):
        assert error < TOLERANCE["grad_leaf"], (jax.tree_util.keystr(path), error)
    for stack in STACKS:
        assert set(got[stack]) >= {"wq", "wkv", "wg", "wo", "ln1_scale"}
        assert float(jnp.abs(got[stack]["wg"]).max()) > 0.0  # the gate's leaf trains


def test_in_bfloat16_the_gradients_stay_near_the_reference(batch):
    """The cell's compute type. bfloat16 keeps 8 bits of a product's operands,
    so a leaf's gradient lies some per cent from the float32 reference's (up to
    6 % here, on a dense layer's gate): the reason for the wider limit, which
    the reference at float8 weights still fails (0.1 to 0.3 on these leaves)."""
    config = build_laguna.laguna_config(JOB, FILE)
    assert config.compute_dtype == jnp.bfloat16
    weights = seeded_weights(config)
    got = jax.jit(jax.grad(lambda p: tinygpt.loss_fn(config, p, batch, batch)))(weights)
    fp8 = jax.tree.map(lambda t: t.astype(jnp.float8_e4m3fn).astype(t.dtype), weights)
    with jax.default_matmul_precision("highest"):
        reference = jax.jit(jax.grad(lambda p: reference_laguna.loss(SHAPE, p, batch)))
        want, rounded = reference(weights), reference(fp8)
    for stack in STACKS:
        for leaf in ("wq", "wkv", "wg", "wo"):
            ours = float(leaf_errors(got[stack][leaf], want[stack][leaf]))
            theirs = float(leaf_errors(rounded[stack][leaf], want[stack][leaf]))
            assert ours < 0.1 < theirs, (stack, leaf, ours, theirs)


def test_a_gate_shut_removes_what_attention_adds(weights):
    """wg -> -inf makes every gate 0 (on an input whose normed lanes are all
    positive): a layer then adds its MLP's part alone, as with ``wo`` zeroed."""
    layer = tinygpt.layer_weights(CONFIG, weights, 1)
    layer = {**layer, "ln1_scale": jnp.abs(layer["ln1_scale"])}
    x = jnp.abs(jax.random.normal(jax.random.key(5), (BATCH, SEQ, 64)))
    run = lambda w: tinygpt.apply_layer(CONFIG, w, x, WINDOW)[0]
    shut = run({**layer, "wg": jnp.full_like(layer["wg"], -1e30)})
    without = run({**layer, "wo": jnp.zeros_like(layer["wo"])})
    assert bool(jnp.all(shut == without)) and relative(run(layer), without) > 1e-3


@pytest.mark.parametrize("name", sorted(WRONG))
def test_a_wrong_model_fails_the_same_tolerance(weights, batch, name):
    got = tinygpt.forward(CONFIG, weights, batch)[0]
    wrong = reference_logits({**SHAPE, **WRONG[name]}, weights, batch)
    assert relative(got, wrong) > 10 * TOLERANCE["logits"]


def test_float8_weights_fail_the_same_tolerance(weights, batch):
    got = tinygpt.forward(CONFIG, weights, batch)[0]
    fp8 = jax.tree.map(lambda t: t.astype(jnp.float8_e4m3fn).astype(t.dtype), weights)
    assert relative(got, reference_logits(SHAPE, fp8, batch)) > 10 * TOLERANCE["logits"]


def test_the_sixteen_shares_add_up_to_the_uncut_layer(weights):
    """The share test at the published counts: sixteen chips hold 16 of the
    256 experts each; the held experts' partial sums of all sixteen, gates
    renormalised over a token's 8 chosen experts and times 2.5 before each
    takes its held part, plus the shared expert **once**, add up to the layer
    with every expert, and to the reference's layer given every expert."""
    experts, top_k, share_of = 256, 8, 16
    config = dataclasses.replace(CONFIG, n_experts=experts, expert_top_k=top_k)
    whole = dataclasses.replace(config, experts_held=None, held_rows_factor=None)
    layer = jax.tree.map(lambda t: t[0], weights["window_blocks"])
    key = jax.random.key(3)
    layer["router"] = jax.random.normal(jax.random.fold_in(key, 3), (CONFIG.n_embd, experts))
    layer["router_bias"] = jnp.zeros((experts,))
    all_wgu = 0.1 * jax.random.normal(key, (experts, *layer["moe_wgu"].shape[1:]))
    all_wd = 0.1 * jax.random.normal(jax.random.fold_in(key, 1), (experts, *layer["moe_wd"].shape[1:]))
    x = jax.random.normal(jax.random.fold_in(key, 2), (BATCH, SEQ, CONFIG.n_embd))
    uncut, _ = moe.moe_mlp(whole, {**layer, "moe_wgu": all_wgu, "moe_wd": all_wd}, x, None, True)
    no_shared = {**layer, "shared_wd": jnp.zeros_like(layer["shared_wd"])}
    shared, _ = moe.moe_mlp(dataclasses.replace(config, experts_held=(0, 1), held_rows_factor=None), {
        **layer, "moe_wgu": all_wgu[:1], "moe_wd": jnp.zeros_like(all_wd[:1])}, x, None, True)
    routed, parts = 0.0, []
    for first in range(0, experts, share_of):
        share = dataclasses.replace(config, experts_held=(first, share_of), held_rows_factor=None)
        held = {**no_shared, "moe_wgu": all_wgu[first:first + share_of],
                "moe_wd": all_wd[first:first + share_of]}
        y, _ = moe.moe_mlp(share, held, x, None, True)
        routed = routed + y
        parts.append(relative(y, uncut))
    assert len(parts) == 16 and relative(routed + shared, uncut) < TOLERANCE["logits"]
    assert relative(routed, uncut) > 0.01 and min(parts) > 0.1  # the shared expert counts, once
    shape = {**SHAPE, "experts": experts, "experts_per_token": top_k, "held": (0, experts)}
    w = {**layer, "moe_wgu": all_wgu, "moe_wd": all_wd}
    with jax.default_matmul_precision("highest"):
        want = jax.vmap(lambda h: reference_kda._routed_mlp(shape, h, w)[0])(x)
    assert relative(uncut, want) < TOLERANCE["logits"]


@pytest.mark.parametrize("heads, kv_heads, part", [(6, 2, 64), (4, 4, 32), (2, 1, 128), (2, 1, None)])
def test_the_pass_over_a_part_of_a_head_is_the_chain(heads, kv_heads, part):
    """``ops/rotary.py``'s pass in interpret mode where the leading ``part``
    lanes of a 128-lane head rotate, against the ``jnp`` chain (``_rope``),
    forward and backward; a part of the whole head is the whole-head pass."""
    S, D, theta = 64, 128, 500000.0
    scaling = YarnScaling(FACTOR, ORIGINAL, 64.0, 1.0, 1.0, 0.0)
    keys = jax.random.split(jax.random.key(6), 4)
    q = jax.random.normal(keys[0], (BATCH, S, heads * D))
    k = jax.random.normal(keys[1], (BATCH, S, kv_heads * D))
    wq = jax.random.normal(keys[2], (BATCH, S, heads, D))
    wk = jax.random.normal(keys[3], (BATCH, S, kv_heads, D))
    pos = jnp.arange(S, dtype=jnp.int32)
    assert rotary_ops.fits(D, S, part) and not rotary_ops.fits(D, S, 63) and not rotary_ops.fits(D, S, 192)

    def the_pass(q, k):
        table = rotary_ops.table(pos, D, theta, scaling, part)
        return rotary_ops.qk_prologue(q, k, None, None, table, 1e-6, interpret=True, rotary_dim=part)

    def the_chain(q, k):
        return (attention_mixer._rope(q.reshape(BATCH, S, heads, D), pos, theta, scaling, part),
                attention_mixer._rope(k.reshape(BATCH, S, kv_heads, D), pos, theta, scaling, part))

    loss = lambda fn: lambda q, k: sum(jnp.sum(y * w) for y, w in zip(fn(q, k), (wq, wk)))
    for got, want in zip(the_pass(q, k), the_chain(q, k)):
        assert got.shape == want.shape and relative(got, want) < 1e-6
        if part not in (None, D):
            assert bool(jnp.all(got[..., part:] == want[..., part:]))  # the rest passes untouched
    for got, want in zip(jax.grad(loss(the_pass), (0, 1))(q, k), jax.grad(loss(the_chain), (0, 1))(q, k)):
        assert relative(got, want) < 1e-6
    if part in (None, D):  # the whole head's table and kernel, as before
        assert bool(jnp.all(rotary_ops.table(pos, D, theta, scaling, part)
                            == rotary_ops.table(pos, D, theta, scaling)))
        assert rotary_ops._part(D, part) is None


def test_a_layer_takes_the_pass_by_its_kind(monkeypatch):
    """On a chip a kind's layers take the pass where its operand fits: at heads
    of 128 both kinds do, the full layers with their 64 lanes; the counter says
    so a kind, with the heads and the bytes of each."""
    file = {**FILE, "head_dim": 128}
    config = build_laguna.laguna_config(JOB, file)
    assert attention_mixer.qk_prologue_stats(config, SEQ)["pass_layers"] == 0  # the CPU: the chain
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    stats = attention_mixer.qk_prologue_stats(config, SEQ)
    assert (stats["rotary_layers"], stats["pass_layers"], stats["norm_stage_layers"]) == (5, 5, 0)
    full, sliding = stats["by_kind"][GLOBAL], stats["by_kind"][WINDOW]
    assert (full["heads"], full["rotary_lanes"], full["rotary_layers"], full["pass_layers"]) == (6, 64, 2, 2)
    assert (sliding["heads"], sliding["rotary_lanes"], sliding["pass_layers"]) == (8, 128, 3)
    assert full["forward_bytes"] == full["backward_bytes"] == 2 * SEQ * (6 + 2) * 128 * 2
    assert sliding["forward_bytes"] == 2 * SEQ * (8 + 2) * 128 * 2
    tables = attention_mixer.qk_prologue_tables(config, SEQ)
    assert set(tables) == {GLOBAL, WINDOW} and tables[GLOBAL].shape == (SEQ, 128)
    assert bool(jnp.all(tables[GLOBAL][:, 64:] == 0.0)) and bool(jnp.any(tables[WINDOW][:, 64:] != 0.0))
    # heads of 16 are not the pass's: every layer keeps the chain, and says so
    small = attention_mixer.qk_prologue_stats(build_laguna.laguna_config(JOB, FILE), SEQ)
    assert small["pass_layers"] == 0 and small["by_kind"][GLOBAL]["rotary_lanes"] == 8


def test_through_the_pass_the_loss_and_gradients_are_the_chains(batch, monkeypatch):
    """Both kinds at heads of 128 with the kernels interpreted, against the
    ``jnp`` chain the cases above hold to the reference."""
    file = {**FILE, "head_dim": 128, "num_hidden_layers": 5}
    config = dataclasses.replace(build_laguna.laguna_config(JOB, file), compute_dtype=jnp.float32,
                                 remat="dots", attention_impl="reference")
    weights = seeded_weights(config)
    run = lambda: jax.jit(jax.value_and_grad(
        lambda p: tinygpt.loss_fn(config, p, batch, batch)))(weights)
    want_loss, want = run()
    monkeypatch.setattr(rotary_ops, "kernel_mode", lambda: True)  # as a chip, interpreted
    assert attention_mixer.qk_prologue_stats(config, SEQ)["pass_layers"] == 5
    got_loss, got = run()
    assert abs(float(got_loss) - float(want_loss)) / float(want_loss) < TOLERANCE["loss"]
    for stack in STACKS:
        for leaf in ("wq", "wkv"):
            assert float(leaf_errors(got[stack][leaf], want[stack][leaf])) < TOLERANCE["grad_leaf"]


@pytest.mark.parametrize("window, tiles", [
    (512, (512, 512, 512)), (1024, (1024, 1024, 1024)), (513, (512, 512, 512)),
    (300, (256, 256, 256)), (64, (256, 256, 256)), (4096, (1024, 1024, 1024))])
def test_the_tiles_follow_a_window_narrower_than_the_default_tile(window, tiles):
    rule = fa.SlidingWindow(window)
    assert fa.pick_tiles(16384, 128, jnp.bfloat16, causal=rule)[:3] == tiles
    assert fa.pick_tiles(16384, 128, jnp.bfloat16, causal=True)[:3] == (1024, 1024, 1024)
    # the caller's tiles still win, and a short sequence is cut as before
    assert fa.pick_tiles(16384, 128, jnp.bfloat16, causal=rule, block_q=128, block_k=128,
                         block_k_bwd=128)[:3] == (128, 128, 128)
    assert fa.pick_tiles(64, 16, jnp.float32, causal=rule)[:3] == (64, 64, 64)


def test_the_published_cells_counters_follow_the_tiles_taken():
    """``attn_mask_stats`` at the cell's config: 64 heads on the band at tiles
    of 512 (two key tiles a query tile: a whole trailing one and the diagonal's
    lower pieces), 48 under causal at 1024; the Mellum cell's window of 1024
    keeps its tiles and its counts."""
    _, workload, file = manifest.load_cell("laguna-xs.2.share16-seq16384")
    config = manifest.resolve(file["builder"])(workload, file)
    shape = build_laguna.laguna_shape(workload, file)
    stats = attention_mixer.attn_mask_stats(config, 16384)
    window, whole = stats[WINDOW], stats[GLOBAL]
    assert (window["layers"], window["heads"], whole["layers"], whole["heads"]) == (3, 64, 2, 48)
    assert window["fwd_tile"] == window["bwd_tile"] == (512, 512)
    assert whole["fwd_tile"] == whole["bwd_tile"] == (1024, 1024)
    assert window["true_pairs"] == flops_laguna.true_pairs(shape, "window") == 8257792
    assert whole["true_pairs"] == flops_laguna.true_pairs(shape, "global")
    assert (window["fwd_live_tiles"], window["fwd_grid_steps"]) == (63, 64)
    assert window["fwd_pairs_multiplied"] == 31 * 512 ** 2 + 32 * 10 * 128 ** 2
    assert window["bwd_pairs_multiplied"] == 31 * 512 ** 2 + 32 * 3 * 256 ** 2
    fill = 2 * window["true_pairs"] / (window["fwd_pairs_multiplied"] + window["bwd_pairs_multiplied"])
    assert round(100 * fill, 1) == 59.4
    _, workload, file = manifest.load_cell("mellum2-12b-a2.5b.share4-seq16384")
    mellum = attention_mixer.attn_mask_stats(manifest.resolve(file["builder"])(workload, file), 16384)[WINDOW]
    assert mellum["fwd_tile"] == mellum["bwd_tile"] == (1024, 1024) and mellum["heads"] == 32
    assert mellum["fwd_pairs_multiplied"] == 15 * 1024 ** 2 + 16 * 36 * 128 ** 2


def test_the_programs_count_is_the_benchmarks():
    _, workload, file = manifest.load_cell("laguna-xs.2.share16-seq16384")
    config = manifest.resolve(file["builder"])(workload, file)
    shape = build_laguna.laguna_shape(workload, file)
    ours = flops_laguna.forward_flops_per_token(shape)
    # the program counts a global layer's keys as S / 2 a token, the benchmark (S + 1) / 2
    convention = 2 * 4 * 0.5 * 48 * 128
    assert flops.forward_flops_per_token(config) == pytest.approx(ours - convention, rel=1e-12)
    assert ours * 3 * 16384 == pytest.approx(48.7e12, rel=2e-3)  # the issue's arithmetic
    params = jax.eval_shape(lambda: tinygpt.init_params(config, jax.random.key(0)))
    assert tinygpt.count_params(params) == pytest.approx(490.3e6, rel=1e-3)


def test_the_train_step_runs_the_stacks_and_reports_the_held_rows(batch):
    """Through ``create_train_state`` / ``state.step_fn``, as the cell runs it:
    the step's loss is the reference's at the state's weights, its report the
    held experts' rows and no overflow, and a later step's loss is lower."""
    mesh = make_mesh((1, 1, 1, 1, 1), MESH_AXES, devices=jax.devices()[:1])
    strategy = dataclasses.replace(get_strategy("zero2"), remat="dots")
    config = dataclasses.replace(build_laguna.laguna_config(JOB, FILE), compute_dtype=jnp.float32)
    state = create_train_state(config, strategy, mesh, seed=5, from_table=True,
                               global_micro=1, seq_len=SEQ)
    table = jnp.asarray(batch[:1])
    with jax.default_matmul_precision("highest"):
        want = float(reference_laguna.loss(SHAPE, state.params, table))
    params, opt_state, loss, report = state.step_fn(state.params, state.opt_state, table, 0)
    assert abs(float(loss) - want) / want < 10 * TOLERANCE["loss"]  # jitted whole, summed otherwise
    assert config.step_report == ("held_rows", "held_overflow")
    rows, overflow = np.asarray(report)
    assert overflow == 0.0 and 0.0 < rows <= 4 * SEQ * TOP_K  # four routed layers' rows
    params, opt_state, *_ = state.step_fn(params, opt_state, table, 1)  # warm-up starts from 0
    *_, later, _ = state.step_fn(params, opt_state, table, 2)
    assert float(later) < float(loss)


def test_the_gate_has_a_scope_under_each_kind(weights, batch):
    text = jax.jit(lambda p, b: tinygpt.loss_fn(CONFIG, p, b, b)).lower(
        weights, batch).as_text(debug_info=True)
    for kind in (WINDOW, GLOBAL):
        assert f"attention/{kind}/{scopes.ATTN_GATE}" in text
    _, workload, file = manifest.load_cell("mistral-7b.d2")
    from perfbench.harness import build

    plain = build.tinygpt_config(*build.tiny(workload, file))
    params = tinygpt.init_params(plain, jax.random.key(0))
    text = jax.jit(lambda p, b: tinygpt.loss_fn(plain, p, b, b)).lower(
        params, batch % 512).as_text(debug_info=True)
    assert scopes.ATTN_GATE not in text and "wg" not in params["blocks"]


def test_the_gates_columns_split_over_model_as_wqs_do():
    """Specs for ``wg`` under fsdp and under a 'model' axis: heads on 'model'
    (as wq's columns), 'data' on the embedding axis under fsdp."""
    devices = np.array(jax.devices()[:1] * 4).reshape(2, 1, 2, 1, 1)
    mesh = jax.sharding.Mesh(devices, MESH_AXES)
    params = jax.eval_shape(lambda: tinygpt.init_params(CONFIG, jax.random.key(0)))
    specs = strategies.param_partition_specs(params, mesh, shard=True, kv_heads=CONFIG.kv_heads)
    for stack, heads in (("window_blocks", 8), ("global_blocks", 6), ("global_dense_blocks", 6)):
        assert params[stack]["wg"].shape[-1] == heads
        assert specs[stack]["wg"] == P(None, "data", "model")
        assert specs[stack]["wq"][2] == "model" and specs[stack]["wo"][1] == "model"
    whole = strategies.param_partition_specs(params, mesh, shard=False, kv_heads=CONFIG.kv_heads)
    assert whole["window_blocks"]["wg"] == P(None, None, "model")
    data_only = jax.sharding.Mesh(np.array(jax.devices()[:1] * 2).reshape(2, 1, 1, 1, 1), MESH_AXES)
    fsdp = strategies.param_partition_specs(params, data_only, shard=True, kv_heads=CONFIG.kv_heads)
    assert "model" not in fsdp["window_blocks"]["wg"] and "data" in fsdp["window_blocks"]["wq"]


@pytest.mark.parametrize("change, match", [
    (dict(scan_layers=True), "stacks of unequal leaves run unrolled"),
    (dict(attention_impl="ring"), "ring attention, Ulysses"),
    (dict(attention_impl="ulysses"), "ring attention, Ulysses"),
    (dict(seq_manual_axis="seq"), "sequence-parallel"),
    (dict(layer_heads=(("linear", 8),)), "layer_heads"),
    (dict(layer_heads=((WINDOW, 7),)), "multiple of n_kv_head"),
    (dict(layer_heads=((WINDOW, 8),), layer_types=None, sliding_window=None, layer_rotary=None,
          first_k_dense=0), "layer_heads"),
    (dict(tp_collective_matmul=True), "tp_collective_matmul"),
    (dict(layer_heads=None), "first_k_dense leading layers"),
    (dict(layer_rotary=((GLOBAL, Rotary(1e4, rotary_dim=7)),)), "rotary_dim"),
    (dict(layer_rotary=((GLOBAL, Rotary(1e4, rotary_dim=32)),)), "rotary_dim"),
])
def test_what_stacks_by_kind_refuse_by_name(change, match):
    with pytest.raises(ValueError, match=match):
        dataclasses.replace(CONFIG, **change)


def test_the_pipeline_is_refused_by_name():
    with pytest.raises(ValueError, match="one homogeneous stack"):
        CONFIG.refuse_pipeline()
