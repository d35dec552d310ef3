"""DeepSeek-V2-class blocks (latent attention under YaRN through the flash
kernels' two widths, a leading dense layer, shared experts, one chip's share
of the routed experts) against the plain float32 reference the benchmark keeps
(``perfbench/harness/reference_mla.py``), at a small size.

Both sides compute in float32 here, so they differ only by the order of
summation: a few 1e-7 of the largest value. The tolerances sit two orders
above that and well under the smallest wrong model below.
"""

import dataclasses
import math
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_llm_training_benchmark_framework_tpu.models import moe, tinygpt
from distributed_llm_training_benchmark_framework_tpu.models.tinygpt import (
    TinyGPTConfig,
    YarnScaling,
)
from distributed_llm_training_benchmark_framework_tpu.parallel import get_strategy, make_mesh
from distributed_llm_training_benchmark_framework_tpu.parallel.strategies import make_optimizer
from distributed_llm_training_benchmark_framework_tpu.train.step import (
    create_train_state,
    make_train_step,
)
from distributed_llm_training_benchmark_framework_tpu.utils import flops
from distributed_llm_training_benchmark_framework_tpu.utils.scopes import (
    MLA_SCOPES,
    MOE_SCOPES,
    SCOPES,
    SHARED,
)
from perfbench.harness import build_mla, reference_mla

TOLERANCE = {"logits": 1e-4, "loss": 1e-5, "grad_leaf": 1e-3}
SEQ, BATCH, EXPERTS, HELD, TOP_K = 64, 2, 8, (2, 4), 3
MESH_AXES = ("data", "seq", "model", "pipe", "expert")
# The cell's two data files at a small size: what the builder and the
# reference's shape are made from, as the benchmark makes them.
FILE = dict(
    hidden_size=64, num_attention_heads=4, num_key_value_heads=4, qk_nope_head_dim=16,
    qk_rope_head_dim=8, v_head_dim=16, kv_lora_rank=32, rope_theta=10000,
    rope_scaling=dict(beta_fast=32, beta_slow=1, factor=40, mscale=0.707, mscale_all_dim=0.707,
                      original_max_position_embeddings=32, type="yarn"),
    rms_norm_eps=1e-6, first_k_dense_replace=1, intermediate_size=96, moe_intermediate_size=32,
    n_shared_experts=2, n_routed_experts_published=EXPERTS, n_routed_experts=HELD[1],
    experts_held_first=HELD[0], num_experts_per_tok=TOP_K, norm_topk_prob=False,
    routed_scaling_factor=1, aux_loss_alpha=0.001, seq_aux=True, tie_word_embeddings=False,
    causal=True, vocab_size=128, num_hidden_layers=3, q_lora_rank=None, scoring_func="softmax",
    topk_method="greedy", dropout=0.0)
JOB = dict(seq_len=SEQ, held_rows_factor=2.0, attention="flash", layer_loop="unrolled")
SHAPE = build_mla.mla_shape(JOB, FILE)
CONFIG = dataclasses.replace(build_mla.deepseek_config(JOB, FILE), compute_dtype=jnp.float32)
WRONG = {
    "yarn_off": {"yarn": None},
    "scale_without_m2": {"softmax_scale": 24 ** -0.5},
    "latent_norm_left_out": {"latent_norm": False},
    "rotary_over_the_whole_head": {"rope_whole_head": True},
    "shared_experts_left_out": {"shared_width": 0},
    "one_held_expert_fewer": {"held": (HELD[0], HELD[1] - 1)},
}


def seeded_weights(config):
    """Seeded weights large enough that every part shows in the logits: the
    program's initialization times five, norm scales drawn around one."""
    params = tinygpt.init_params(config, jax.random.key(0))
    leaves, tree = jax.tree.flatten(params)
    keys = jax.random.split(jax.random.key(1), len(leaves))
    redraw = lambda key, x: (1.0 + 0.1 * jax.random.normal(key, x.shape)
                             if bool(jnp.all(x == 1.0)) else 5.0 * x)
    return jax.tree.unflatten(tree, [redraw(k, x) for k, x in zip(keys, leaves)])


@pytest.fixture(scope="module")
def weights():
    return seeded_weights(CONFIG)


@pytest.fixture(scope="module")
def batch():
    return jax.random.randint(jax.random.key(2), (BATCH, SEQ), 0, FILE["vocab_size"])


def reference_logits(shape, params, batch):
    with jax.default_matmul_precision("highest"):
        return jax.vmap(lambda t: reference_mla.logits(shape, params, t))(batch)


def relative(got, want):
    return float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))


def test_yarn_numbers():
    """The published rope_scaling at dim 64, base 10000: the ramp runs from
    frequency 10 to 23, m = 0.1 * 0.707 * ln 40 + 1, scale = 192^-0.5 * m^2."""
    yarn = YarnScaling(factor=40, original_max_position_embeddings=4096, beta_fast=32,
                       beta_slow=1, mscale=0.707, mscale_all_dim=0.707)
    assert yarn.correction_range(64, 10000.0) == (10, 23)
    assert round(math.sqrt(yarn.softmax_factor), 5) == 1.26080
    assert yarn.cos_sin_factor == 1.0
    published = TinyGPTConfig(
        n_embd=2048, n_head=16, pos_embed="rope", bias=False, attention_impl="flash",
        kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
        rope_scaling=yarn)
    assert (published.qk_dim, published.v_dim) == (192, 128)
    assert round(published.attn_scale, 6) == 0.114721
    inv_freq = yarn.inv_freq(64, 10000.0)
    plain = 10000.0 ** (-2.0 * np.arange(32) / 64)
    np.testing.assert_allclose(inv_freq[:11], plain[:11], rtol=1e-6)  # fast: kept
    np.testing.assert_allclose(inv_freq[23:], plain[23:] / 40, rtol=1e-6)  # slow: / factor
    ramp = (16 - 10) / (23 - 10)
    np.testing.assert_allclose(inv_freq[16], plain[16] / 40 * ramp + plain[16] * (1 - ramp),
                               rtol=1e-6)
    # the reference computes the same numbers on its own
    shape = {"rope_theta": 10000, "yarn": dict(factor=40, original_max_position_embeddings=4096,
                                               beta_fast=32, beta_slow=1)}
    assert reference_mla.yarn_ramp_ends(shape, 64) == (10, 23)
    np.testing.assert_allclose(reference_mla.yarn_inv_freq(shape, 64), inv_freq, rtol=1e-6)


def test_logits_match_the_reference(weights, batch):
    got = tinygpt.forward(CONFIG, weights, batch)[0]
    assert relative(got, reference_logits(SHAPE, weights, batch)) < TOLERANCE["logits"]


def test_reference_attention_impl_matches_too(weights, batch):
    config = dataclasses.replace(CONFIG, attention_impl="reference")
    got = tinygpt.forward(config, weights, batch)[0]
    assert relative(got, reference_logits(SHAPE, weights, batch)) < TOLERANCE["logits"]


def test_full_loss_matches_the_reference_and_holds_the_router_term(weights, batch):
    got = tinygpt.loss_fn(CONFIG, weights, batch, batch)
    with jax.default_matmul_precision("highest"):
        want = reference_mla.loss(SHAPE, weights, batch)
        bare = reference_mla.loss({**SHAPE, "aux_coef": 0.0}, weights, batch)
    assert abs(float(got - want)) / float(want) < TOLERANCE["loss"]
    assert float(want - bare) > 10 * TOLERANCE["loss"] * float(want)


# Every expert on this chip, through the same held-experts path: the routing trains.
EVERY_EXPERT = {**FILE, "n_routed_experts": EXPERTS, "experts_held_first": 0}


@pytest.mark.parametrize("file", [FILE, EVERY_EXPERT], ids=["a-part", "every-expert"])
def test_gradient_of_every_leaf_matches_the_reference(batch, file):
    """A part of the experts does not train its routing, in the program and in
    the reference alike (the router's leaf gets exactly nothing); with every
    expert held the gates' and the load-balance term's gradients are compared
    too, through the gate weighting in ``_moe_mlp_held``."""
    shape = build_mla.mla_shape(JOB, file)
    config = dataclasses.replace(build_mla.deepseek_config(JOB, file), compute_dtype=jnp.float32)
    assert config.trains_routing == shape["routing_trained"] == (file is EVERY_EXPERT)
    weights = seeded_weights(config)
    got = jax.grad(lambda p: tinygpt.loss_fn(config, p, batch, batch))(weights)
    with jax.default_matmul_precision("highest"):
        want = jax.grad(lambda p: reference_mla.loss(shape, p, batch))(weights)
    router = float(jnp.abs(got["blocks"]["router"]).max())
    if not config.trains_routing:
        assert router == float(jnp.abs(want["blocks"]["router"]).max()) == 0.0
        got["blocks"].pop("router"), want["blocks"].pop("router")
    else:
        assert router > 0.0
    errors = jax.tree.map(
        lambda g, w: float(jnp.linalg.norm(g - w) / jnp.linalg.norm(w)), got, want)
    for path, error in jax.tree_util.tree_leaves_with_path(errors):
        assert error < TOLERANCE["grad_leaf"], (jax.tree_util.keystr(path), error)
    assert set(got["dense_blocks"]) >= {"wq", "wkv_a", "kv_norm", "wkv_b", "wo", "wgu", "wproj"}
    assert set(got["blocks"]) >= {"moe_wgu", "moe_wd", "shared_wgu", "shared_wd"}


@pytest.mark.parametrize("name", sorted(WRONG))
def test_a_wrong_model_fails_the_same_tolerance(weights, batch, name):
    got = tinygpt.forward(CONFIG, weights, batch)[0]
    wrong = reference_logits({**SHAPE, **WRONG[name]}, weights, batch)
    assert relative(got, wrong) > 10 * TOLERANCE["logits"]


def test_float8_weights_fail_the_same_tolerance(weights, batch):
    got = tinygpt.forward(CONFIG, weights, batch)[0]
    fp8 = jax.tree.map(lambda t: t.astype(jnp.float8_e4m3fn).astype(t.dtype), weights)
    assert relative(got, reference_logits(SHAPE, fp8, batch)) > 10 * TOLERANCE["logits"]


def test_the_shares_add_up_to_the_uncut_layer(weights, batch):
    """The share test: what the chips of a deployment compute of one routed
    layer, their own experts' parts and the shared experts counted once, add up
    to the layer with every expert. Two chips hold 4 of the 8 experts each."""
    whole = dataclasses.replace(CONFIG, experts_held=None, held_rows_factor=None)
    layer = jax.tree.map(lambda t: t[0], weights["blocks"])
    key = jax.random.key(3)
    all_wgu = 0.1 * jax.random.normal(key, (EXPERTS, *layer["moe_wgu"].shape[1:]))
    all_wd = 0.1 * jax.random.normal(jax.random.fold_in(key, 1),
                                     (EXPERTS, *layer["moe_wd"].shape[1:]))
    x = jax.random.normal(jax.random.fold_in(key, 2), (BATCH, SEQ, CONFIG.n_embd))
    uncut, _ = moe.moe_mlp(whole, {**layer, "moe_wgu": all_wgu, "moe_wd": all_wd}, x, None, True)
    shared = moe._shared_experts(whole, layer, x)
    routed = 0.0
    for first in (0, 4):
        share = dataclasses.replace(CONFIG, experts_held=(first, 4), held_rows_factor=None)
        held = {**layer, "moe_wgu": all_wgu[first:first + 4], "moe_wd": all_wd[first:first + 4]}
        y, _ = moe.moe_mlp(share, held, x, None, True)
        routed = routed + (y - shared)  # every chip computes the shared experts alike
    assert relative(routed + shared, uncut) < TOLERANCE["logits"]
    assert relative(routed, uncut) > 0.01  # the shared part is not nothing
    # and the reference, given every expert, agrees with the uncut layer
    shape = {**SHAPE, "held": (0, EXPERTS)}
    w = {**layer, "moe_wgu": all_wgu, "moe_wd": all_wd}
    with jax.default_matmul_precision("highest"):
        want = jax.vmap(lambda h: reference_mla._routed_mlp(shape, h, w)[0])(x)
    assert relative(uncut, want) < TOLERANCE["logits"]


def routing_trained(config):
    """``config`` with its gates and load-balance term differentiated through,
    whatever it holds: ``trains_routing`` is a property, so a subclass."""
    through = type("Trained", (TinyGPTConfig,), {"trains_routing": property(lambda self: True)})
    return through(**{f.name: getattr(config, f.name) for f in dataclasses.fields(config)})


def layer_and_gradients(config, layer, x, cotangent):
    """-> (y, aux, the gradient of sum(y * cotangent) by (``layer``, ``x``))."""
    def program(layer, x):
        y, aux = moe.moe_mlp(config, layer, x, None, True)
        return jnp.sum(y * cotangent), (y, aux)

    (_, (y, aux)), grads = jax.value_and_grad(program, argnums=(0, 1), has_aux=True)(layer, x)
    return y, aux, grads


def steered_layer(weights, chosen):
    """A routed layer and its input whose router sends token n to the experts
    ``chosen[n % len(chosen)]`` (the router reads the input's first E columns
    alone, a margin of 2 over everything else and distinct steps between the
    choices, so no top-k is a near-tie); ``chosen`` None keeps the seeded
    router."""
    layer = jax.tree.map(lambda t: t[0], weights["blocks"])
    x = jax.random.normal(jax.random.key(5), (BATCH, SEQ, CONFIG.n_embd))
    if chosen is None:
        return layer, x
    wanted = np.zeros((BATCH * SEQ, EXPERTS), np.float32)
    for n in range(BATCH * SEQ):
        wanted[n, list(chosen[n % len(chosen)])] = 2.0 + 0.25 * np.arange(TOP_K)
    x = x.at[..., :EXPERTS].set(wanted.reshape(BATCH, SEQ, EXPERTS))
    router = jnp.zeros_like(layer["router"]).at[:EXPERTS].set(jnp.eye(EXPERTS))
    return {**layer, "router": router}, x


@jax.custom_vjp
def poison(rows, filled):
    """NaN in the rows that are not ``filled``, and in their cotangent."""
    return jnp.where(filled[:, None], rows, jnp.nan)


poison.defvjp(lambda rows, filled: (poison(rows, filled), filled),
              lambda filled, g: (poison(g, filled), None))


def poisoned_padding(monkeypatch):
    """The grouped matmuls neither read nor write the buffer's rows past the
    last group: make them NaN on the way in and out, forward and backward, so a
    pass that reads one shows."""
    experts = moe._experts_dropless

    def poisoned(c, layer, rows, counts):
        filled = jnp.arange(rows.shape[0]) < jnp.sum(counts)
        return poison(experts(c, layer, poison(rows, filled), counts), filled)

    monkeypatch.setattr(moe, "_experts_dropless", poisoned)


# Experts 2..5 are held, 3 choices a token. Which of a token's choices are held:
STEERED = {
    "seeded-router": None,
    "none-one-all-held": [(0, 1, 6), (0, 2, 7), (2, 3, 4), (5, 3, 1), (7, 6, 0)],
    "a-held-expert-without-rows": [(2, 4, 0), (5, 1, 7), (4, 5, 2), (0, 6, 7)],  # 3 gets none
    "the-first-held-expert-without-rows": [(3, 4, 5), (0, 1, 5), (6, 3, 7)],
    "only-the-last-held-expert": [(5, 0, 1), (6, 7, 0)],
    "nothing-held": [(0, 1, 6), (7, 6, 1)],
}


@pytest.mark.parametrize("trained", [False, True], ids=["routing-constant", "routing-trained"])
@pytest.mark.parametrize("case", sorted(STEERED))
def test_the_held_rows_go_out_and_come_back_as_the_reference_says(
        weights, monkeypatch, case, trained):
    """The rows' two passes (tokens -> buffer rows -> tokens, each the other's
    transpose) against the plain reference: the layer's output and the gradient
    of every leaf that feeds it, the input's too, with the gates constants of
    the backward pass (a part of the experts) and differentiated through."""
    poisoned_padding(monkeypatch)
    layer, x = steered_layer(weights, STEERED[case])
    config = routing_trained(CONFIG) if trained else CONFIG
    shape = {**SHAPE, "routing_trained": trained}
    cotangent = jax.random.normal(jax.random.key(6), x.shape)
    counts, held = moe.routing_rows(config, layer, x)
    if STEERED[case] is not None:
        per_expert = np.zeros(EXPERTS, int)
        for n in range(BATCH * SEQ):
            per_expert[list(STEERED[case][n % len(STEERED[case])])] += 1
        np.testing.assert_array_equal(counts, per_expert)
    assert (int(held[0]), int(held[1])) == (int(counts[HELD[0]:HELD[0] + HELD[1]].sum()), 0)

    def reference(layer, x):
        with jax.default_matmul_precision("highest"):
            y = jax.vmap(lambda h: reference_mla._routed_mlp(shape, h, layer)[0])(x)
        return jnp.sum(y * cotangent), y

    y, aux, got = layer_and_gradients(config, layer, x, cotangent)
    (_, want_y), want = jax.value_and_grad(reference, argnums=(0, 1), has_aux=True)(layer, x)
    assert (int(aux[1]), int(aux[2])) == (int(held[0]), 0)
    assert relative(y, want_y) < TOLERANCE["logits"]
    used = {"moe_wgu", "moe_wd", "shared_wgu", "shared_wd"} | ({"router"} if trained else set())
    for name in sorted(used):
        scale = float(jnp.max(jnp.abs(want[0][name]))) or 1.0
        assert float(jnp.max(jnp.abs(got[0][name] - want[0][name]))) < 1e-4 * scale, name
    assert float(jnp.abs(got[0]["router"]).max()) > 0.0 or not trained or "nothing" in case
    assert relative(got[1], want[1]) < TOLERANCE["logits"]


@pytest.mark.parametrize("trained", [False, True], ids=["routing-constant", "routing-trained"])
def test_an_overflowing_buffer_computes_what_fits_and_reads_no_padding(
        weights, monkeypatch, trained):
    """Every token on the held experts 2 and 4, every other one on 3 too, and a
    buffer of 256 rows for those 320: the layer computes the first 256
    assignments in buffer order (expert by expert, token order inside one),
    counts the rest, and its gradients are those of that sum."""
    poisoned_padding(monkeypatch)
    layer, x = steered_layer(weights, [(2, 3, 4), (2, 4, 0)])
    N = BATCH * SEQ
    config = dataclasses.replace(CONFIG, held_rows_factor=1.0)
    config = routing_trained(config) if trained else config
    M = moe.held_buffer_rows(config, N)
    assert M == 2 * N  # the expected 3 N / 2 rows, up to a whole row tile
    fits = np.zeros((N, EXPERTS), bool)  # experts 2 and 3 whole, expert 4's first N / 2 tokens
    fits[:, 2], fits[:, 3], fits[:N // 2, 4] = True, True, True
    cotangent = jax.random.normal(jax.random.key(6), x.shape)

    def what_fits(layer, x):
        h = x.reshape(N, -1)
        probs = jax.nn.softmax(h @ layer["router"], -1)
        gates = reference_mla._gate_weights(SHAPE, probs) * fits
        if not trained:
            gates = jax.lax.stop_gradient(gates)
        F, y = SHAPE["expert_width"], 0.0
        with jax.default_matmul_precision("highest"):
            for e in range(HELD[1]):
                wgu, wd = layer["moe_wgu"][e], layer["moe_wd"][e]
                y = y + gates[:, HELD[0] + e, None] * reference_mla._swiglu(
                    h, wgu[:, :F], wgu[:, F:], wd)
        return jnp.sum((y.reshape(x.shape) + moe._shared_experts(config, layer, x)) * cotangent), y

    y, aux, got = layer_and_gradients(config, layer, x, cotangent)
    (_, want_y), want = jax.value_and_grad(what_fits, argnums=(0, 1), has_aux=True)(layer, x)
    assert (int(aux[1]), int(aux[2])) == (M, 5 * N // 2 - M)
    routed = y - moe._shared_experts(config, layer, x)
    assert relative(routed.reshape(N, -1), want_y) < TOLERANCE["logits"]
    for name in ("moe_wgu", "moe_wd") + (("router",) if trained else ()):
        scale = float(jnp.max(jnp.abs(want[0][name])))
        assert float(jnp.max(jnp.abs(got[0][name] - want[0][name]))) < 1e-4 * scale, name
    assert relative(got[1], want[1]) < TOLERANCE["logits"]


def test_a_bounded_buffer_counts_what_does_not_fit(weights, batch):
    """A router that sends every token to the held experts overflows a buffer
    of twice the expected rows; the layer says by how much, stays finite, and
    the unbounded buffer computes them all."""
    layer = jax.tree.map(lambda t: t[0], weights["blocks"])
    skew = jnp.zeros_like(layer["router"]).at[:, HELD[0]:HELD[0] + TOP_K].set(1.0)
    x = jnp.abs(jax.random.normal(jax.random.key(4), (BATCH, SEQ, CONFIG.n_embd)))
    N = BATCH * SEQ
    bound = moe.held_buffer_rows(CONFIG, N)
    assert bound == 2 * N * TOP_K * HELD[1] // EXPERTS
    y, aux = moe.moe_mlp(CONFIG, {**layer, "router": skew}, x, None, True)
    assert aux.shape == (3,) and bool(jnp.all(jnp.isfinite(y)))
    assert (int(aux[1]), int(aux[2])) == (bound, N * TOP_K - bound)
    unbounded = dataclasses.replace(CONFIG, held_rows_factor=None)
    assert moe.held_buffer_rows(unbounded, N) == N * TOP_K
    _, scalar = moe.moe_mlp(unbounded, {**layer, "router": skew}, x, None, True)
    assert scalar.shape == ()


def test_held_rows_equal_the_routers_count(weights, batch):
    counts = tinygpt.moe_expert_counts(CONFIG, weights, batch)
    held = tinygpt.moe_held_rows(CONFIG, weights, batch)
    assert counts.shape == (2, EXPERTS) and held.shape == (2, 2)
    assert np.all(np.asarray(counts.sum(-1)) == BATCH * SEQ * TOP_K)
    np.testing.assert_array_equal(held[:, 0], counts[:, HELD[0]:HELD[0] + HELD[1]].sum(-1))
    assert int(held[:, 1].sum()) == 0


@pytest.mark.parametrize("change", [
    dict(scan_layers=True), dict(scan_layers=True, remat="dots"), dict(remat="full"),
    dict(remat="dots")], ids=["scan", "scan-dots", "unrolled-full", "unrolled-dots"])
def test_layer_loops_and_remat_policies_agree(weights, batch, change):
    want = jax.value_and_grad(lambda p: tinygpt.loss_fn(CONFIG, p, batch, batch))(weights)
    config = dataclasses.replace(CONFIG, **change)
    got = jax.value_and_grad(lambda p: tinygpt.loss_fn(config, p, batch, batch))(weights)
    assert abs(float(got[0] - want[0])) < 1e-5
    for g, w in zip(jax.tree.leaves(got[1]), jax.tree.leaves(want[1])):
        np.testing.assert_allclose(g, w, atol=1e-5 * float(jnp.max(jnp.abs(w))) + 1e-9, rtol=1e-3)


BAD = [
    dict(kv_lora_rank=32),  # no head widths
    dict(kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
         attention_impl="ring"),
    dict(rope_scaling=YarnScaling(40, 4096)),  # YaRN without latent attention
    dict(first_k_dense=1),  # no routed layers behind it
    dict(first_k_dense=3, n_experts=8, capacity_factor=None, dense_mlp_hidden=96),
    dict(n_experts=8, capacity_factor=None, experts_held=(6, 4)),  # past the 8th expert
    dict(n_experts=8, capacity_factor=None, held_rows_factor=2.0),  # nothing held
    dict(n_shared_experts=2),  # no routed layer to stand beside
    dict(n_experts=8, capacity_factor=None, experts_held=(2, 0)),  # holds none
]


@pytest.mark.parametrize("bad", BAD, ids=[",".join(b) for b in BAD])
def test_config_refuses_what_no_path_computes(bad):
    base = dict(n_embd=64, n_head=4, n_layer=3, pos_embed="rope", mlp_act="swiglu", bias=False,
                norm="rmsnorm", attention_impl="flash")
    with pytest.raises(ValueError):
        TinyGPTConfig(**{**base, **bad})


def test_the_pipeline_schedules_refuse_it_by_name():
    if len(jax.devices()) < 2:
        pytest.skip("needs two devices")
    mesh = make_mesh((1, 1, 1, 2, 1), MESH_AXES, devices=jax.devices()[:2])
    with pytest.raises(ValueError, match="first_k_dense=1.*kv_lora_rank=32"):
        create_train_state(CONFIG, get_strategy("ddp"), mesh, seed=0, global_micro=2,
                           seq_len=SEQ, from_table=True)


def test_published_widths_build_and_count():
    """The cell's config at its real sizes, shapes only: 635.5M parameters."""
    import json, os

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "perfbench/configs/deepseek-v2-lite.json")) as f:
        file = json.load(f)
    config = build_mla.deepseek_config(
        dict(seq_len=8192, held_rows_factor=3.0, attention="flash", layer_loop="unrolled",
             depth=6), file)
    shapes = jax.eval_shape(lambda: tinygpt.init_params(config, jax.random.key(0)))
    size = lambda tree: sum(int(np.prod(x.shape)) for x in jax.tree.leaves(tree))
    assert round(size(shapes) / 1e6, 1) == 635.5
    blocks, dense = shapes["blocks"], shapes["dense_blocks"]
    assert blocks["wq"].shape == (5, 2048, 16 * 192)
    assert blocks["wkv_a"].shape == (5, 2048, 576) and blocks["kv_norm"].shape == (5, 512)
    assert blocks["wkv_b"].shape == (5, 512, 16 * 256) and blocks["wo"].shape == (5, 2048, 2048)
    assert blocks["router"].shape == (5, 2048, 64)
    assert blocks["moe_wgu"].shape == (5, 8, 2048, 2 * 1408)
    assert blocks["shared_wgu"].shape == (5, 2048, 2 * 2816)
    assert dense["wgu"].shape == (1, 2048, 2, 10944)
    assert shapes["wte"].shape == shapes["lm_head"].shape == (12800, 2048)
    attention = size([blocks[k] for k in ("wq", "wkv_a", "kv_norm", "wkv_b", "wo")]) / 5
    assert round(attention / 1e6, 2) == 13.76
    assert moe.held_buffer_rows(config, 16384) == 3 * 12288
    # the program's own MFU print counts this model, not a dense one
    per_token = flops.forward_flops_per_token(config)
    assert round(per_token / 1e6) == 843


@pytest.mark.parametrize("strategy", ["ddp", "zero2", "zero3", "fsdp"])
def test_every_strategy_builds_on_four_devices(strategy):
    """Specs for every new leaf, both stacks; the step compiles and runs."""
    if len(jax.devices()) < 4:
        pytest.skip("needs four devices")
    mesh = make_mesh((4, 1, 1, 1, 1), MESH_AXES, devices=jax.devices()[:4])
    config = dataclasses.replace(CONFIG, attention_impl="reference", scan_layers=True)
    plan = get_strategy(strategy)
    shape = dict(grad_accum=1, from_table=True, global_micro=4, seq_len=SEQ)
    state = create_train_state(config, plan, mesh, seed=0, **shape)
    for stack in ("blocks", "dense_blocks"):
        specs = state.param_specs[stack]
        assert set(specs) == set(state.params[stack])
        sharded = {name for name, spec in specs.items() if "data" in jax.tree.leaves(tuple(spec))}
        if plan.shard_params:
            assert {"wq", "wkv_a", "wkv_b", "wo"} <= sharded, (stack, sharded)
        else:
            assert not sharded
    assert ("data" in tuple(state.param_specs["blocks"]["shared_wgu"])) == plan.shard_params
    table = jax.random.randint(jax.random.key(5), (8, SEQ), 0, FILE["vocab_size"])
    out = state.step_fn(state.params, state.opt_state, table, 0)
    assert len(out) == 4 and out[3].shape == (2,)  # loss, then (held rows, overflow)
    assert math.isfinite(float(out[2])) and float(out[3][1]) == 0.0


def compiled_paths(scan_layers):
    """Every ``/``-split path of every ``op_name`` of a compiled tiny step."""
    config = dataclasses.replace(CONFIG, scan_layers=scan_layers, compute_dtype=jnp.bfloat16)
    mesh = make_mesh((1, 1, 1, 1, 1), MESH_AXES, devices=jax.devices()[:1])
    strategy = get_strategy("zero2")
    shape = dict(grad_accum=1, from_table=True, global_micro=BATCH, seq_len=SEQ)
    state = create_train_state(config, strategy, mesh, seed=0, **shape)
    _, aot_compile = make_train_step(config, strategy, make_optimizer(strategy), mesh,
                                     state.param_specs, state.opt_specs, **shape)
    text = aot_compile(state.params, state.opt_state, jnp.zeros((8, SEQ), jnp.int32)).as_text()
    return [path.split("/") for op_name in re.findall(r'op_name="([^"]*)"', text)
            for path in op_name.split(";")]


@pytest.mark.parametrize("scan_layers", [False, True], ids=["unrolled", "scan"])
def test_the_new_scopes_are_in_the_compiled_step(scan_layers):
    """``mla_*`` nested in ``attention``, ``shared`` and the routed layer's four
    in ``mlp``, forward and backward; the step's own scopes too."""
    wrapper = re.compile(r"^(?!jit\()\w+\((.*)\)$")

    def plain(component):
        while wrapped := wrapper.match(component):
            component = wrapped.group(1)
        return component

    paths = [[plain(c) for c in path] + [any(c.startswith("transpose(") for c in path)]
             for path in compiled_paths(scan_layers)]
    for module, scopes in (("attention", MLA_SCOPES), ("mlp", MOE_SCOPES + (SHARED,))):
        for scope in scopes:
            found = [p for p in paths if scope in p[:-1]]
            assert found and all(module in p[: p.index(scope)] for p in found), scope
            # a part of the experts does not train its routing: no backward there
            assert any(p[-1] for p in found) == (scope != "router"), f"backward ops: {scope}"
            assert any(not p[-1] for p in found), f"no forward op under {scope}"
    for scope in SCOPES:
        assert any(scope in p[:-1] for p in paths) == (scope != "dropout"), scope


def test_a_part_of_the_experts_does_not_train_its_routing(weights, batch):
    """``trains_routing`` is read off ``experts_held``, not set: a part of the
    experts makes the gates and the load-balance term constants of the backward
    pass. Against the same layer differentiated through them: the loss is the
    same number, the router's leaf gets no gradient, and what no gate lies
    behind (the last routed layer's experts, the head) gets the gradient it
    always got."""
    assert not CONFIG.trains_routing
    assert dataclasses.replace(CONFIG, experts_held=(0, EXPERTS)).trains_routing
    assert dataclasses.replace(CONFIG, experts_held=None, held_rows_factor=None).trains_routing
    through = routing_trained(CONFIG)
    got = jax.value_and_grad(lambda p: tinygpt.loss_fn(CONFIG, p, batch, batch))(weights)
    want = jax.value_and_grad(lambda p: tinygpt.loss_fn(through, p, batch, batch))(weights)
    assert float(got[0]) == float(want[0])
    assert float(jnp.abs(got[1]["blocks"]["router"]).max()) == 0.0
    assert float(jnp.abs(want[1]["blocks"]["router"]).max()) > 0.0
    for name in ("moe_wgu", "moe_wd", "shared_wd"):
        np.testing.assert_allclose(got[1]["blocks"][name][-1], want[1]["blocks"][name][-1],
                                   rtol=1e-5, atol=1e-9)
    np.testing.assert_allclose(got[1]["lm_head"], want[1]["lm_head"], rtol=1e-5, atol=1e-9)
    # the gates' path into the hidden state is what went: earlier layers differ
    assert float(jnp.abs(got[1]["wte"] - want[1]["wte"]).max()) > 0.0
