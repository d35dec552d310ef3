"""LFM2-MoE-class stacks (gated short-convolution mixers beside grouped-query
attention under per-head QK-norm and rotary, a leading dense layer of the
convolution's kind, then experts chosen by sigmoid score + an expert bias over
one chip's share of them, no shared expert, a tied head) against the plain
float32 reference the benchmark keeps (``perfbench/harness/reference_lfm2.py``:
the convolution as its shifted products written out), at a small size: the
cell's five layers, conv + dense, attention, conv x 3, 4 of 16 experts held.

Both sides compute in float32 here, so they differ only by the order of
summation: a few 1e-7 of the largest value. The tolerances sit two orders above
that and well under the smallest wrong model below.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_llm_training_benchmark_framework_tpu.models import mixers
from distributed_llm_training_benchmark_framework_tpu.models.mixers import (
    attention as attention_mixer,
    conv as conv_mixer,
)
from distributed_llm_training_benchmark_framework_tpu.models import moe, tinygpt
from distributed_llm_training_benchmark_framework_tpu.models.tinygpt import TinyGPTConfig
from distributed_llm_training_benchmark_framework_tpu.ops import short_conv
from distributed_llm_training_benchmark_framework_tpu.parallel import get_strategy, make_mesh
from distributed_llm_training_benchmark_framework_tpu.parallel import strategies
from distributed_llm_training_benchmark_framework_tpu.train.step import create_train_state
from distributed_llm_training_benchmark_framework_tpu.utils import flops, memory
from distributed_llm_training_benchmark_framework_tpu.utils.scopes import (
    CONV, GLOBAL, LAYER_KIND_SCOPES, NAMES, SCONV_SCOPES, WINDOW,
)
from perfbench.harness import build_lfm2, flops_lfm2, manifest, reference_lfm2

TOLERANCE = {"logits": 1e-4, "loss": 1e-5, "grad_leaf": 1e-3}
SEQ, BATCH, EXPERTS, HELD, TOP_K = 32, 2, 16, (4, 4), 3
MESH_AXES = ("data", "seq", "model", "pipe", "expert")
PUBLISHED = ["conv", "conv", "full_attention"] + ["conv", "conv", "conv", "full_attention"] * 4 + [
    "conv", "conv", "full_attention", "conv", "conv"]
# The cell's two data files at a small size: what the builder and the
# reference's shape are made from, as the benchmark makes them.
FILE = dict(
    hidden_size=64, num_attention_heads=4, num_key_value_heads=2, layer_types=PUBLISHED,
    num_hidden_layers=5, first_layer_kept=1, num_dense_layers=2, norm_eps=1e-5, rope_theta=1e6,
    conv_L_cache=3, conv_bias=False, intermediate_size=96, moe_intermediate_size=32,
    num_experts_published=EXPERTS, num_experts=HELD[1], experts_held_first=HELD[0],
    num_experts_per_tok=TOP_K, norm_topk_prob=True, routed_scaling_factor=1, use_expert_bias=True,
    tie_word_embeddings=True, qk_norm="head_before_rotary", conv_columns="B,C,x",
    router_aux_loss=None, vocab_size=128, dropout=0.0)
JOB = dict(seq_len=SEQ, held_rows_factor=4.0, attention="reference", layer_loop="unrolled")
SHAPE = build_lfm2.lfm2_shape(JOB, FILE)
CONFIG = dataclasses.replace(build_lfm2.lfm2_config(JOB, FILE), compute_dtype=jnp.float32)
KINDS = (CONV, GLOBAL, CONV, CONV, CONV)
DENSE, ROUTED = "dense", "routed"  # the feed-forward parts, beside the mixers' kinds
# (the part it is wrong in, the change to the reference's shape)
WRONG = {
    "a_bfloat16_convolution": (CONV, {"conv_dtype": "bfloat16"}),
    "no_b_gate": (CONV, {"gate_b": False}),
    "no_c_gate": (CONV, {"gate_c": False}),
    "two_taps": (CONV, {"taps_used": 2}),
    "four_taps": (CONV, {"taps_used": 4}),
    "taps_one_position_later": (CONV, {"tap_shift": 1}),
    "no_qk_norm": (GLOBAL, {"qk_norm": None}),
    "the_norm_after_rotary": (GLOBAL, {"qk_norm": "after"}),
    "no_rotary": (GLOBAL, {"rotary": False}),
    "selection_by_the_unbiased_score": (ROUTED, {"select_by": "score"}),
    "gates_from_the_biased_score": (ROUTED, {"gates_from": "biased"}),
    "gates_not_renormalised": (ROUTED, {"norm_topk_prob": False}),
    "the_held_experts_one_off": (ROUTED, {"held": (HELD[0] + 1, HELD[1])}),
}


def seeded_weights(config, bias=0.0):
    """Seeded weights large enough that every part shows in the logits: the
    program's initialization times five for what it draws around zero, the
    leaves that start from one constant (norm scales) drawn around what they
    start from, the taps as the program draws them, and the expert bias (a
    buffer the program starts at zero) drawn at ``bias``."""
    params = tinygpt.init_params(config, jax.random.key(0))
    keys = iter(jax.random.split(jax.random.key(1), 200))

    def redraw(path, x):
        name, key = path[-1].key, next(keys)
        if name == "router_bias":
            return bias * jax.random.normal(key, x.shape)
        if name == "sconv_taps":
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
        return jax.vmap(lambda t: reference_lfm2.logits(shape, params, t))(batch)


def relative(got, want):
    return float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))


def test_the_builder_gives_each_kind_its_stack_and_the_dense_layer_the_convolutions_kind():
    assert CONFIG.layer_types == KINDS == SHAPE["kinds"] and mixers.own_leaves(CONFIG.layer_types)
    assert CONFIG.layer_groups == (
        ("conv_dense_blocks", (0,)), ("blocks", (1,)), ("conv_blocks", (2, 3, 4)))
    assert (CONFIG.first_k_dense, CONFIG.n_moe_layers, SHAPE["moe_layers"]) == (1, 4, 4)
    assert CONFIG.stacks_unequal and not CONFIG.heads_by_kind and not CONFIG.block_halves
    assert CONFIG.qk_norm == "head" and CONFIG.pos_embed == "rope" and CONFIG.rope_theta == 1e6
    assert CONFIG.tie_embeddings and CONFIG.n_shared_experts == 0 and CONFIG.conv_taps == 3
    assert CONFIG.router_score == "sigmoid" and not CONFIG.trains_routing
    params = tinygpt.init_params(CONFIG, jax.random.key(0))
    shapes = {k: jax.tree.map(jnp.shape, v) for k, v in params.items() if k.endswith("blocks")}
    assert set(params) == {"conv_dense_blocks", "blocks", "conv_blocks", "wte", "lnf_scale"}
    mixer = {"ln1_scale": (64,), "ln2_scale": (64,), "sconv_win": (64, 192),
             "sconv_taps": (3, 64), "wo": (64, 64)}
    routed = {"router": (64, EXPERTS), "router_bias": (EXPERTS,), "moe_wgu": (4, 64, 64),
              "moe_wd": (4, 32, 64)}
    assert {k: v[1:] for k, v in shapes["conv_dense_blocks"].items()} == {
        **mixer, "wgu": (64, 2, 96), "wproj": (96, 64)}
    assert {k: v[1:] for k, v in shapes["conv_blocks"].items()} == {**mixer, **routed}
    assert {k: v[1:] for k, v in shapes["blocks"].items()} == {
        "ln1_scale": (64,), "ln2_scale": (64,), "wq": (64, 64), "wkv": (64, 2, 32),
        "q_norm": (16,), "k_norm": (16,), "wo": (64, 64), **routed}
    assert all(v[0] == 3 for v in shapes["conv_blocks"].values())
    # the taps start as a depthwise Conv1d's, the expert bias at zero
    taps = params["conv_blocks"]["sconv_taps"]
    assert float(jnp.abs(taps).max()) <= 3 ** -0.5 and float(jnp.abs(taps).max()) > 0.5
    assert float(jnp.abs(params["conv_blocks"]["router_bias"]).max()) == 0.0


def test_the_accepted_configurations_keep_their_stacks_and_their_draws():
    """A new kind changes no accepted configuration's tree: the stacks' names
    and a leaf's first values, pinned."""
    routed = dict(vocab_size=64, n_embd=32, n_head=2, n_kv_head=1, n_layer=4, block_size=16,
                  causal=True, dropout=0.0, norm="rmsnorm", pos_embed="rope", mlp_act="swiglu",
                  mlp_hidden=16, bias=False, tie_embeddings=False, n_experts=4, expert_top_k=2,
                  capacity_factor=None, n_shared_experts=1, scan_layers=False)
    deepseek = TinyGPTConfig(**routed, first_k_dense=1, dense_mlp_hidden=48)
    assert deepseek.layer_groups == (("dense_blocks", (0,)), ("blocks", (1, 2, 3)))
    kimi = TinyGPTConfig(**routed, first_k_dense=1, dense_mlp_hidden=48,
                         layer_types=("kda", GLOBAL, "kda", "kda"), kda_heads=2, kda_head_dim=16)
    assert kimi.layer_groups == (
        ("kda_dense_blocks", (0,)), ("blocks", (1,)), ("kda_blocks", (2, 3)))
    tree = tinygpt.init_params(kimi, jax.random.key(0))
    np.testing.assert_allclose(  # 'blocks' draws first: wq from the first of 64 keys
        np.asarray(tree["blocks"]["wq"][0, 0, :3]),
        np.asarray(0.02 * jax.random.normal(jax.random.split(jax.random.key(0), 64)[0],
                                            (1, 32, 32))[0, 0, :3]))


# -- the gated convolution alone ---------------------------------------------

def _operands(B, S, C, K, dtype=jnp.float32):
    keys = jax.random.split(jax.random.key(7), 3)
    bcx = jax.random.normal(keys[0], (B, S, 3 * C), jnp.float32).astype(dtype)
    taps = jax.random.uniform(keys[1], (K, C), jnp.float32, -K ** -0.5, K ** -0.5)
    cotangent = jax.random.normal(keys[2], (B, S, C), jnp.float32)
    return bcx, taps, cotangent


def _three_shifted_products(bcx, taps):
    C = bcx.shape[-1] // 3
    b, c, x = (bcx[..., i * C:(i + 1) * C].astype(jnp.float32) for i in range(3))
    return jax.vmap(lambda b, c, x: c * reference_lfm2.short_conv({}, b * x, taps))(b, c, x)


@pytest.mark.parametrize("mode, shape", [
    (None, (2, 40, 24, 3)), (None, (1, 16, 8, 4)), (True, (2, 256, 128, 3)),
    (True, (1, 136, 256, 4))], ids=["jnp-3", "jnp-4", "kernel-3-b2", "kernel-4"])
def test_gated_conv_is_the_three_shifted_products_forward_and_every_gradient(mode, shape):
    """``ops.short_conv.gated_conv`` in its ``jnp`` form and in its kernels' interpreted
    form against the reference's convolution written out: the output and the
    gradients by b, c, x (the three thirds of the operand) and the taps."""
    bcx, taps, cotangent = _operands(*shape)
    C = shape[2]
    run = lambda f: jax.value_and_grad(
        lambda bcx, taps: jnp.sum(f(bcx, taps) * cotangent), argnums=(0, 1))(bcx, taps)
    (got, (got_dbcx, got_dtaps)) = run(lambda a, t: short_conv.gated_conv(a, t, interpret=mode))
    (want, (want_dbcx, want_dtaps)) = run(_three_shifted_products)
    assert abs(float(got - want)) <= 1e-5 * abs(float(want)) + 1e-3
    np.testing.assert_allclose(np.asarray(short_conv.gated_conv(bcx, taps, interpret=mode)),
                               np.asarray(_three_shifted_products(bcx, taps)), rtol=1e-5, atol=1e-5)
    for third, name in enumerate("bcx"):
        cut = slice(third * C, (third + 1) * C)
        np.testing.assert_allclose(np.asarray(got_dbcx[..., cut]), np.asarray(want_dbcx[..., cut]),
                                   rtol=1e-4, atol=1e-4, err_msg=f"d{name}")
    np.testing.assert_allclose(np.asarray(got_dtaps), np.asarray(want_dtaps), rtol=1e-4, atol=1e-3)
    assert float(jnp.abs(got_dtaps).min()) > 0.0


def test_gated_conv_in_bfloat16_rounds_once_and_its_kernels_agree_with_the_chain():
    bcx, taps, _ = _operands(2, 128, 128, 3, jnp.bfloat16)
    kernel = short_conv.gated_conv(bcx, taps, interpret=True)
    chain = short_conv.gated_conv(bcx, taps, interpret=None)
    assert kernel.dtype == chain.dtype == jnp.bfloat16
    exact = _three_shifted_products(bcx, taps)
    assert relative(kernel.astype(jnp.float32), exact) < 2 ** -7
    # one rounding each, of sums taken in another order: an ulp of bfloat16 apart at the most
    np.testing.assert_allclose(np.asarray(kernel, np.float32), np.asarray(chain, np.float32),
                               rtol=2 ** -7, atol=1e-6)
    assert not short_conv.conv_fits(128, 3, 64)  # such an operand takes the chain, whatever the mode
    narrow, taps, _ = _operands(1, 16, 64, 3)
    np.testing.assert_array_equal(np.asarray(short_conv.gated_conv(narrow, taps, interpret=True)),
                                  np.asarray(short_conv.gated_conv(narrow, taps, interpret=None)))


@pytest.mark.parametrize("mode, shape", [(None, (1, 24, 8, 3)), (True, (1, 256, 128, 3))],
                         ids=["jnp", "kernel"])
def test_gated_conv_is_causal_and_starts_from_zeros(mode, shape):
    """Perturbing position t leaves every output before t bit-equal, and moves
    t .. t + K - 1 only; the first position sees its own tap alone."""
    bcx, taps, _ = _operands(*shape)
    B, S, C, K = shape
    t = S // 2 + 3  # inside a tile, past its first rows
    got = short_conv.gated_conv(bcx, taps, interpret=mode)
    moved = short_conv.gated_conv(bcx.at[:, t, 2 * C:].add(1.0), taps, interpret=mode)
    np.testing.assert_array_equal(np.asarray(got[:, :t]), np.asarray(moved[:, :t]))
    np.testing.assert_array_equal(np.asarray(got[:, t + K:]), np.asarray(moved[:, t + K:]))
    assert float(jnp.abs(got[:, t:t + K] - moved[:, t:t + K]).min(-1).min()) > 0.0
    b, c, x = (bcx[:, 0, i * C:(i + 1) * C] for i in range(3))
    np.testing.assert_allclose(np.asarray(got[:, 0]), np.asarray(c * taps[K - 1] * b * x),
                               rtol=1e-6, atol=1e-6)


# -- a sublayer at a time -----------------------------------------------------

def reference_part(shape, part, x, w):
    sublayer = {CONV: reference_lfm2.conv_sublayer, GLOBAL: reference_lfm2.attention_sublayer,
                DENSE: reference_lfm2.dense_sublayer,
                ROUTED: lambda m, x, w: reference_lfm2.routed_sublayer(m, x, w)[0]}[part]
    with jax.default_matmul_precision("highest"):
        return jax.vmap(lambda x: sublayer(shape, x, w))(x)


def program_part(config, part, layer, x):
    if part == CONV:
        return conv_mixer.sublayer(config, x, layer)
    if part == GLOBAL:
        return attention_mixer.sublayer(config, x, layer, None, True, GLOBAL)
    return tinygpt._mlp_sublayer(config, x, layer, None, True)[0]


@pytest.fixture(scope="module")
def parts(weights):
    """{part: (the layer's weights, an input, what the program's sublayer adds to it)}."""
    x = jax.random.normal(jax.random.key(4), (BATCH, SEQ, CONFIG.n_embd))
    at = {CONV: 3, GLOBAL: 1, DENSE: 0, ROUTED: 2}  # layer 2: its held experts all take rows
    out = {}
    for part, i in at.items():
        layer = tinygpt.layer_weights(CONFIG, weights, i)
        out[part] = (layer, x, program_part(CONFIG, part, layer, x) - x)
    return out


@pytest.mark.parametrize("part", [CONV, GLOBAL, DENSE, ROUTED])
def test_each_sublayer_is_the_references(parts, part):
    layer, x, got = parts[part]
    assert relative(got, reference_part(SHAPE, part, x, layer) - x) < TOLERANCE["logits"]


@pytest.mark.parametrize("name", sorted(WRONG))
def test_a_wrong_model_fails_the_same_tolerance(parts, name):
    """What the program's sublayer of the wrong model's part adds to its input is
    the reference's, and not the wrong reference's."""
    part, change = WRONG[name]
    layer, x, got = parts[part]
    assert relative(got, reference_part({**SHAPE, **change}, part, x, layer) - x) > (
        2 * TOLERANCE["logits"]), name


@pytest.mark.parametrize("impl", ["flash", "reference"])
def test_attention_at_head_width_64_with_qk_norm_rotary_and_four_query_heads_a_kv_head(impl):
    """The attention layer as the cell has it but for the head count: heads of
    64 lanes under per-head QK-norm (before rotary) and rotary at theta 1e6, 4
    query heads a KV head, causal, through the flash kernels (interpreted, k and
    v at their own head count) and through the ``jnp`` path."""
    file = {**FILE, "hidden_size": 256, "num_attention_heads": 4, "num_key_value_heads": 1}
    job = {**JOB, "seq_len": 128, "attention": impl}
    shape = build_lfm2.lfm2_shape(job, file)
    config = dataclasses.replace(build_lfm2.lfm2_config(job, file), compute_dtype=jnp.float32)
    assert config.head_dim == shape["head_dim"] == 64 and config.kv_heads == 1
    assert not attention_mixer._takes_qk_prologue(config, 128, GLOBAL)  # heads narrower than a vreg
    layer = tinygpt.layer_weights(config, seeded_weights(config), 1)
    x = jax.random.normal(jax.random.key(5), (2, 128, 256))
    got = jax.jit(lambda l, x: program_part(config, GLOBAL, l, x))(layer, x) - x
    assert relative(got, reference_part(shape, GLOBAL, x, layer) - x) < TOLERANCE["logits"]
    for change in ({"qk_norm": "after"}, {"rotary": False}):
        assert relative(got, reference_part({**shape, **change}, GLOBAL, x, layer) - x) > 1e-2


# -- the whole model ---------------------------------------------------------

@pytest.fixture(scope="module")
def reference_loss_and_gradients(weights, batch):
    with jax.default_matmul_precision("highest"):
        return jax.jit(jax.value_and_grad(lambda p: reference_lfm2.loss(SHAPE, p, batch)))(weights)


@pytest.mark.parametrize("remat", ["none", "dots", "full_keep_kernels", "full"])
def test_loss_and_gradients_match_the_reference_under_each_remat_policy(
        weights, batch, reference_loss_and_gradients, remat):
    """``jax.grad`` of the training loss through the gated convolution, attention
    under QK-norm and rotary, the dense MLP, the sigmoid gates and the held
    experts, and the tied head, under every policy."""
    config = dataclasses.replace(CONFIG, remat=remat)
    got_loss, got = jax.jit(jax.value_and_grad(
        lambda p: tinygpt.loss_fn(config, p, batch, batch)))(weights)
    want_loss, want = reference_loss_and_gradients
    got, want = jax.tree.map(lambda t: t, (got, want))  # the pops below are this test's own
    assert abs(float(got_loss) - float(want_loss)) / float(want_loss) < TOLERANCE["loss"]
    # the bias moves the choice only: no gradient reaches it; the gates are constants
    # of this chip's backward (departure 2)
    for stack in ("blocks", "conv_blocks"):
        assert float(jnp.abs(got[stack].pop("router_bias")).max()) == 0.0
        assert float(jnp.abs(want[stack].pop("router_bias")).max()) == 0.0
        assert float(jnp.abs(got[stack]["router"]).max()) == 0.0
    errors = jax.tree_util.tree_map_with_path(
        lambda path, a, b: (jax.tree_util.keystr(path), float(
            jnp.linalg.norm(a - b) / jnp.maximum(jnp.linalg.norm(b), 1e-30))), got, want)
    worst = max(jax.tree.leaves(errors, is_leaf=lambda x: isinstance(x, tuple)), key=lambda e: e[1])
    assert worst[1] < TOLERANCE["grad_leaf"], worst
    for leaf in ("sconv_win", "sconv_taps", "wo"):
        assert float(jnp.abs(got["conv_blocks"][leaf]).max()) > 0.0, leaf


def test_logits_and_counts_match_the_reference_and_float8_weights_do_not(weights, batch):
    got = tinygpt.forward(CONFIG, weights, batch)[0]
    assert relative(got, reference_logits(SHAPE, weights, batch)) < TOLERANCE["logits"]
    rounded = jax.tree.map(lambda t: t.astype(jnp.float8_e4m3fn).astype(t.dtype), weights)
    assert relative(got, reference_logits(SHAPE, rounded, batch)) > 10 * TOLERANCE["logits"]
    untied = {**SHAPE, "tied": False}
    other = {**weights, "lm_head": weights["wte"][::-1]}
    assert relative(got, reference_logits(untied, other, batch)) > 10 * TOLERANCE["logits"]
    with jax.default_matmul_precision("highest"):
        _, (_, counts) = reference_lfm2.loss_and_parts(SHAPE, weights, batch)
    program = tinygpt.moe_expert_counts(CONFIG, weights, batch)
    np.testing.assert_array_equal(np.asarray(program), np.asarray(counts))
    assert counts.shape == (4, EXPERTS) and int(counts.sum()) == 4 * BATCH * SEQ * TOP_K


def test_every_expert_on_the_chip_trains_its_routing_as_the_reference_does(batch):
    file = {**FILE, "num_experts": EXPERTS, "experts_held_first": 0, "num_hidden_layers": 2}
    shape = build_lfm2.lfm2_shape(JOB, file)
    config = dataclasses.replace(build_lfm2.lfm2_config(JOB, file), compute_dtype=jnp.float32,
                                 remat="full_keep_kernels")
    assert config.trains_routing and shape["routing_trained"] and shape["kinds"] == (CONV, GLOBAL)
    weights = seeded_weights(config, bias=0.3)
    got = jax.grad(lambda p: tinygpt.loss_fn(config, p, batch, batch))(weights)
    with jax.default_matmul_precision("highest"):
        want = jax.grad(lambda p: reference_lfm2.loss(shape, p, batch))(weights)
    a, b = got["blocks"]["router"], want["blocks"]["router"]
    assert float(jnp.abs(b).max()) > 0.0
    assert float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b)) < TOLERANCE["grad_leaf"]


def test_the_four_shares_of_eight_add_up_to_the_uncut_layer(weights):
    """The share test: four chips hold 8 of 32 experts each (0-7, 8-15, 16-23,
    24-31); what they compute of one routed layer (sigmoid scores over the 32,
    the choice by score + bias, gates renormalised over a token's 4 before each
    takes its held part; no shared expert) adds up to the layer with every
    expert, and to the reference's layer given every expert."""
    experts, share_of, top_k = 32, 8, 4
    base = dataclasses.replace(CONFIG, n_experts=experts, expert_top_k=top_k)
    whole = dataclasses.replace(base, experts_held=None, held_rows_factor=None)
    layer = tinygpt.layer_weights(CONFIG, weights, 3)
    key = jax.random.key(3)
    layer = {**layer, "router": jax.random.normal(key, (CONFIG.n_embd, experts)),
             "router_bias": 0.3 * jax.random.normal(jax.random.fold_in(key, 3), (experts,))}
    all_wgu = 0.1 * jax.random.normal(key, (experts, *layer["moe_wgu"].shape[1:]))
    all_wd = 0.1 * jax.random.normal(jax.random.fold_in(key, 1), (experts, *layer["moe_wd"].shape[1:]))
    x = jax.random.normal(jax.random.fold_in(key, 2), (1, 32, CONFIG.n_embd))
    uncut, _ = moe.moe_mlp(whole, {**layer, "moe_wgu": all_wgu, "moe_wd": all_wd}, x, None, True)
    total, parts = jnp.zeros_like(uncut), []
    for first in range(0, experts, share_of):
        share = dataclasses.replace(base, experts_held=(first, share_of), held_rows_factor=None)
        held = {**layer, "moe_wgu": all_wgu[first:first + share_of],
                "moe_wd": all_wd[first:first + share_of]}
        y, _ = moe.moe_mlp(share, held, x, None, True)
        total = total + y
        parts.append(relative(y, uncut))
        shape = {**SHAPE, "experts": experts, "experts_per_token": top_k, "held": (first, share_of)}
        with jax.default_matmul_precision("highest"):
            want = jax.vmap(lambda h: reference_lfm2._routed_mlp(shape, h, held)[0])(x)
        assert relative(y, want) < TOLERANCE["logits"]
    assert len(parts) == 4 and relative(total, uncut) < TOLERANCE["logits"]
    assert min(parts) > 0.1  # no share is all of it
    shape = {**SHAPE, "experts": experts, "experts_per_token": top_k, "held": (0, experts)}
    w = {**layer, "moe_wgu": all_wgu, "moe_wd": all_wd}
    with jax.default_matmul_precision("highest"):
        want = jax.vmap(lambda h: reference_lfm2._routed_mlp(shape, h, w)[0])(x)
    assert relative(uncut, want) < TOLERANCE["logits"]


def test_the_train_step_runs_the_stacks_and_its_loss_falls(batch):
    """Through ``create_train_state`` / ``state.step_fn`` under zero2, as the
    cell runs it: the step's loss is the reference's at the state's weights, its
    report the held experts' rows and no overflow, and a later step's loss is
    lower."""
    mesh = make_mesh((1, 1, 1, 1, 1), MESH_AXES, devices=jax.devices()[:1])
    strategy = dataclasses.replace(get_strategy("zero2"), remat="full_keep_kernels")
    state = create_train_state(CONFIG, strategy, mesh, seed=5, from_table=True,
                               global_micro=2, seq_len=SEQ)
    table = jnp.asarray(batch)
    with jax.default_matmul_precision("highest"):
        want = float(reference_lfm2.loss(SHAPE, state.params, table))
    params, opt_state, loss, report = state.step_fn(state.params, state.opt_state, table, 0)
    # at the seeded start every sigmoid score is 0.5 to two digits: near-ties take other
    # experts on the two sides, and the held experts' small part moves the loss in the 4th digit
    assert abs(float(loss) - want) / want < 50 * TOLERANCE["loss"]
    assert CONFIG.step_report == ("held_rows", "held_overflow")
    rows, overflow = np.asarray(report)
    assert overflow == 0.0 and 0.0 < rows <= 4 * BATCH * SEQ * TOP_K  # four routed layers' rows
    assert float(jnp.abs(params["conv_blocks"]["router_bias"]).max()) == 0.0  # a buffer stays put
    params, opt_state, *_ = state.step_fn(params, opt_state, table, 1)  # warm-up starts from 0
    *_, later, _ = state.step_fn(params, opt_state, table, 2)
    assert float(later) < float(loss)


# -- the published widths, by shape alone -----------------------------------

def _published(**changes):
    _, workload, config = manifest.load_cell("lfm2-8b-a1b.share4-seq16384")
    config = {**config, **changes}
    model = build_lfm2.lfm2_config({**workload, "depth": config["num_hidden_layers"]}, config)
    return config, jax.eval_shape(lambda k: tinygpt.init_params(model, k), jax.random.key(0))


def test_count_params_at_the_published_widths_is_the_files_arithmetic():
    """The cell's cut, leaf by leaf: 507.8M parameters (with the four routed
    layers' 32 bias entries, which the arithmetic of the matrices leaves out)."""
    config, shapes = _published()
    conv_mixer = 2048 * 6144 + 2048 * 2048 + 3 * 2048
    attention = 2 * 2048 * 2048 + 2 * 2048 * 512 + 2 * 64
    held = 8 * 3 * 2048 * 1792
    router, norms, dense = 2048 * 32 + 32, 2 * 2048, 3 * 2048 * 7168
    assert (conv_mixer, attention, dense) == (16_783_360, 10_485_888, 44_040_192)
    layers = [conv_mixer + dense + norms, attention + held + router + norms] + [
        conv_mixer + held + router + norms] * 3
    assert layers == [60_827_648, 98_635_936, 104_933_408, 104_933_408, 104_933_408]
    want = sum(layers) + 16384 * 2048 + 2048
    assert want == 507_820_288 == config["parameter_count"]
    assert tinygpt.count_params(shapes) == want
    assert {k: tinygpt.count_params(v) for k, v in shapes.items() if k.endswith("blocks")} == {
        "conv_dense_blocks": layers[0], "blocks": layers[1], "conv_blocks": 3 * layers[2]}
    assert shapes["conv_blocks"]["sconv_win"].shape == (3, 2048, 6144)
    assert shapes["blocks"]["wkv"].shape == (1, 2048, 2, 512) and "lm_head" not in shapes


def test_count_params_uncut_is_the_published_models():
    """All 24 layers, 32 experts and 65,536 ids: 8.34B parameters."""
    config, shapes = _published(
        num_hidden_layers=24, first_layer_kept=0, num_experts=32, vocab_size=65536)
    conv_mixer, attention = 16_783_360, 10_485_888
    routed = 32 * 3 * 2048 * 1792 + 2048 * 32 + 32
    want = (2 * (conv_mixer + 44_040_192) + 16 * (conv_mixer + routed) + 6 * (attention + routed)
            + 24 * 4096 + 65536 * 2048 + 2048)
    assert tinygpt.count_params(shapes) == want == config["parameter_count_published"]
    assert 8.33e9 < want < 8.35e9


# -- names, rules, counts -----------------------------------------------------

@pytest.mark.parametrize("strategy", ["zero2", "fsdp"])
def test_the_new_leaves_have_specs_under_the_strategies(strategy):
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
        assert name in tinygpt.PARAM_AXIS_RULES and len(tinygpt.PARAM_AXIS_RULES[name]) == leaf.ndim
    conv = specs["conv_blocks"]
    if s.shard_params:
        assert all("data" in tuple(conv[k]) and tuple(conv[k])[0] is None
                   for k in ("sconv_win", "wo"))
    else:
        assert all(set(tuple(v)) == {None} for v in conv.values())


def test_the_kind_has_a_scope_and_its_three_parts(weights, batch):
    assert LAYER_KIND_SCOPES[-1] == CONV == tinygpt.LAYER_KINDS[-1] and set(SCONV_SCOPES) <= NAMES
    text = jax.jit(lambda p, b: tinygpt.loss_fn(CONFIG, p, b, b)).lower(
        weights, batch).as_text(debug_info=True)
    for scope in SCONV_SCOPES:
        assert f"attention/{CONV}/{scope}" in text
    assert f"attention/{GLOBAL}" in text and f"attention/{WINDOW}" not in text
    for scope in ("router", "dispatch", "experts", "combine"):
        assert f"mlp/{scope}" in text
    assert "mlp/shared" not in text and f"{GLOBAL}/qk_prologue" not in text  # heads of 64: the chain
    # the projection has a name, which full_keep_kernels keeps (_under_remat's rule; PR 55)
    jaxpr = str(jax.make_jaxpr(lambda p, b: tinygpt.loss_fn(CONFIG, p, b, b))(weights, batch))
    assert "name=sconv_bcx" in jaxpr and conv_mixer.SCONV_BCX in tinygpt.remat_kept_names()


@pytest.mark.parametrize("change, match", [
    (dict(scan_layers=True), "scanned loop is refused"),
    (dict(attention_impl="ring"), "attention_impl 'flash' or 'reference'"),
    (dict(attention_impl="ulysses"), "attention_impl 'flash' or 'reference'"),
    (dict(seq_manual_axis="seq"), "sequence-parallel pipeline"),
    (dict(causal=False), "causal=True"),
    (dict(norm="layernorm"), "a 'conv' layer"),
    (dict(dropout=0.1), "a 'conv' layer"),
    (dict(conv_taps=0), "conv_taps >= 1"),
    (dict(tp_collective_matmul=True), "tp_collective_matmul"),
    (dict(layer_types=(CONV, "mlp", CONV, "mlp", CONV)), "block_halves"),
    (dict(layer_types=(CONV,) * 4), "layer_types names one of"),
    (dict(first_k_dense=5), "0 < first_k_dense < n_layer"),
])
def test_what_the_kind_refuses_by_name(change, match):
    with pytest.raises(ValueError, match=match):
        dataclasses.replace(CONFIG, **change)


def test_the_pipeline_schedules_refuse_the_stack():
    with pytest.raises(ValueError, match="pipeline schedules"):
        CONFIG.refuse_pipeline()
    with pytest.raises(ValueError, match="conv or ssd layers"):
        dataclasses.replace(CONFIG, first_k_dense=0, dense_mlp_hidden=None).refuse_pipeline()


def test_flops_and_memory_count_the_layers_by_kind():
    """The program's count is the benchmark's (``flops_lfm2``) but for the causal
    pairs' half position; a ``conv`` layer's is the hand count; without remat
    the memory estimate grows by what a conv layer keeps, under
    ``full_keep_kernels`` by the projection it keeps by name."""
    got, want = flops.forward_flops_per_token(CONFIG), flops_lfm2.forward_flops_per_token(SHAPE)
    half_position = 4 * 0.5 * 4 * 16  # (S + 1) / 2 against S / 2 keys, 4 heads of 16
    assert got == pytest.approx(want - half_position)
    D = 64
    assert conv_mixer.forward_flops_per_token(CONFIG) == 2 * D * 3 * D + 2 * 3 * D + 2 * D * D
    mesh = make_mesh((1, 1, 1, 1, 1), MESH_AXES, devices=jax.devices()[:1])
    estimate = lambda config, remat: memory.estimate_hbm(
        dataclasses.replace(config, remat=remat), get_strategy("zero2"), mesh,
        per_device_batch=1, seq_len=SEQ).activations
    fewer = dataclasses.replace(CONFIG, layer_types=(CONV, GLOBAL, GLOBAL, GLOBAL, CONV))
    kept = 2 * SEQ * 4 * D * 4  # two conv layers' B | C | x~ and gated result (float32 here)
    assert estimate(CONFIG, "none") - estimate(fewer, "none") == kept
    named = 2 * SEQ * 3 * D * 4  # by name under full_keep_kernels: B | C | x~ alone
    assert estimate(CONFIG, "full_keep_kernels") - estimate(fewer, "full_keep_kernels") == named


def test_sconv_stats_count_layers_calls_and_bytes(monkeypatch):
    stats = conv_mixer.sconv_stats(CONFIG, SEQ)
    assert (stats["layers"], stats["taps"], stats["layers_in_kernel"]) == (4, 3, 0)
    assert stats["kernel_calls"] == {"sconv_fwd": 0, "sconv_bwd": 0}  # the jnp chain off a TPU
    cell = dataclasses.replace(CONFIG, n_embd=2048, n_head=32, n_kv_head=8,
                               compute_dtype=jnp.bfloat16)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    stats = conv_mixer.sconv_stats(cell, 16384)
    assert stats["layers_in_kernel"] == 4
    assert stats["kernel_calls"] == {"sconv_fwd": 4, "sconv_bwd": 4}
    assert stats["forward_bytes"] == 4 * 16384 * 2048 * 2
    assert stats["backward_bytes"] == 7 * 16384 * 2048 * 2
    assert conv_mixer.sconv_stats(dataclasses.replace(cell, n_embd=2112, n_head=33, n_kv_head=11),
                               16384)["layers_in_kernel"] == 0  # 2112 is no whole 128-lane tiles
    assert attention_mixer.attn_mask_stats(cell, 16384).keys() == {"global"}
    assert attention_mixer.attn_mask_stats(cell, 16384)["global"]["kv_heads_in_kernel"] in (8, 32)
    assert attention_mixer.qk_prologue_stats(cell, 16384)["pass_layers"] == 0  # heads of 64: the jnp chain
