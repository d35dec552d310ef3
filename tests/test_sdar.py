"""SDAR-class blocks (GQA, per-head QK-norm, renormalised top-k gates over one
chip's share of the experts) trained by block diffusion (a noisy and a clean
copy of every document in one stream, the three-part block mask, rotary
positions inside each copy, the 1 / t-weighted loss on the masked tokens of the
noisy copy) against the plain float32 reference the benchmark keeps
(``perfbench/harness/reference_bd.py``), at a small size.

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
)
from distributed_llm_training_benchmark_framework_tpu.models import moe, tinygpt
from distributed_llm_training_benchmark_framework_tpu.models.tinygpt import (
    BlockDiffusionObjective,
    TinyGPTConfig,
)
from distributed_llm_training_benchmark_framework_tpu.parallel import get_strategy, make_mesh
from distributed_llm_training_benchmark_framework_tpu.parallel import strategies
from distributed_llm_training_benchmark_framework_tpu.train.step import create_train_state
from distributed_llm_training_benchmark_framework_tpu.utils import flops, memory
from distributed_llm_training_benchmark_framework_tpu.utils.scopes import NOISE
from perfbench.harness import build_bd, flops_bd, manifest, reference_bd

TOLERANCE = {"logits": 1e-4, "loss": 1e-5, "grad_leaf": 1e-3}
SEQ, BATCH, EXPERTS, HELD, TOP_K, BLOCK = 64, 2, 16, (4, 2), 3, 4
MESH_AXES = ("data", "seq", "model", "pipe", "expert")
# The cell's two data files at a small size: what the builder and the
# reference's shape are made from, as the benchmark makes them.
FILE = dict(
    hidden_size=64, num_attention_heads=4, num_key_value_heads=2, head_dim=32, qk_norm="head",
    qk_norm_scale_init=4.0,
    rope_theta=1000000, rope_scaling=None, rms_norm_eps=1e-6, moe_intermediate_size=32,
    num_experts_published=EXPERTS, num_experts=HELD[1], experts_held_first=HELD[0],
    num_experts_per_tok=TOP_K, norm_topk_prob=True, router_aux_loss_coef=0.001,
    tie_word_embeddings=False, attention_bias=False, mlp_only_layers=[], decoder_sparse_step=1,
    use_sliding_window=False, vocab_size=128, num_hidden_layers=2, dropout=0.0,
    block_length=BLOCK, mask_token_id=127, noise=dict(t_min=1e-3, t_max=1.0, loss_weight="1/t"))
JOB = dict(seq_len=SEQ, held_rows_factor=4.0, attention="flash_block_diffusion",
           layer_loop="unrolled")
SHAPE = build_bd.bd_shape(JOB, FILE)
CONFIG = dataclasses.replace(build_bd.sdar_config(JOB, FILE), compute_dtype=jnp.float32)
KEY = jax.random.key(11)  # forward's key: the noise is drawn from it
WRONG = {
    "a_causal_mask": {"mask": "causal"},
    "the_own_clean_block_seen": {"mask": "block_diffusion_le"},  # <= for < in noisy -> clean
    "positions_along_the_stream": {"positions": "stream"},
    "qk_norm_over_the_whole_vector": {"qk_norm": "whole"},
    "gates_not_renormalised": {"norm_topk_prob": False},
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
                             if bool(jnp.all(x == x.ravel()[0])) else 5.0 * x)
    return jax.tree.unflatten(tree, [redraw(k, x) for k, x in zip(keys, leaves)])


@pytest.fixture(scope="module")
def weights():
    return seeded_weights(CONFIG)


@pytest.fixture(scope="module")
def batch():  # data ids: everything but the mask token
    return jax.random.randint(jax.random.key(2), (BATCH, SEQ), 0, FILE["mask_token_id"])


@pytest.fixture(scope="module")
def noise(batch):
    return tinygpt.bd_noise(CONFIG, KEY, batch.shape)


def reference_logits(shape, params, batch, masked):
    with jax.default_matmul_precision("highest"):
        return jax.vmap(lambda t, m: reference_bd.logits(shape, params, t, m))(batch, masked)


def relative(got, want):
    return float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))


def test_the_layout_is_the_published_block(weights):
    """(head_dim,) norm scales that every head shares, GQA's split
    projections, the held experts' leaves, an untied head."""
    shapes = jax.tree.map(jnp.shape, weights["blocks"])
    assert shapes["q_norm"] == shapes["k_norm"] == (2, 32)  # heads of 32 over a hidden 64
    assert shapes["wq"] == (2, 64, 128) and shapes["wkv"] == (2, 64, 2, 64)
    assert shapes["wo"] == (2, 128, 64)
    assert shapes["router"] == (2, 64, EXPERTS) and shapes["moe_wgu"] == (2, HELD[1], 64, 64)
    assert weights["lm_head"].shape == (128, 64) and "wpe" not in weights
    assert CONFIG.step_report == ("held_rows", "held_overflow", "masked_tokens")
    assert not CONFIG.trains_routing and not SHAPE["routing_trained"]


def test_the_noise_is_a_level_a_block_and_masks_at_that_rate():
    config = dataclasses.replace(CONFIG, block_size=4096)
    t, masked = tinygpt.bd_noise(config, KEY, (8, 4096))
    assert t.shape == (8, 1024) and masked.shape == (8, 4096)
    assert 1e-3 <= float(t.min()) and float(t.max()) <= 1.0
    assert abs(float(t.mean()) - 0.5) < 0.02 and abs(float(masked.mean()) - 0.5) < 0.02
    by_block = masked.reshape(8, 1024, BLOCK).mean(-1)
    assert float(jnp.corrcoef(by_block.ravel(), t.ravel())[0, 1]) > 0.7
    again, _ = tinygpt.bd_noise(config, KEY, (8, 4096))
    other, _ = tinygpt.bd_noise(config, jax.random.key(12), (8, 4096))
    assert bool(jnp.all(again == t)) and not bool(jnp.all(other == t))


def test_the_stream_is_the_noisy_copy_then_the_clean_one(batch, noise):
    t, masked = noise
    stream, weight, masked_again = tinygpt.bd_stream(CONFIG, batch, KEY)
    assert stream.shape == (BATCH, 2 * SEQ) and bool(jnp.all(masked_again == masked))
    np.testing.assert_array_equal(stream[:, SEQ:], batch)
    np.testing.assert_array_equal(stream[:, :SEQ], np.where(masked, 127, batch))
    np.testing.assert_allclose(weight, np.where(masked, 1 / np.repeat(t, BLOCK, 1), 0), rtol=1e-6)


@pytest.mark.parametrize("attention", ["flash", "reference"])
def test_logits_match_the_reference(weights, batch, noise, attention):
    """The flash kernels (interpreted) and the in-model dense mask alike; the
    logits are the noisy copy's, one a position of the document."""
    config = dataclasses.replace(CONFIG, attention_impl=attention)
    got = tinygpt.forward(config, weights, batch, dropout_key=KEY)[0]
    assert got.shape == (BATCH, SEQ, FILE["vocab_size"])
    assert relative(got, reference_logits(SHAPE, weights, batch, noise[1])) < TOLERANCE["logits"]


def test_full_loss_matches_the_reference_and_holds_the_router_term(weights, batch, noise):
    got = tinygpt.loss_fn(CONFIG, weights, batch, batch, dropout_key=KEY)
    with jax.default_matmul_precision("highest"):
        want = reference_bd.loss(SHAPE, weights, batch, *noise)
        bare = reference_bd.loss({**SHAPE, "aux_coef": 0.0}, weights, batch, *noise)
    assert abs(float(got - want)) / float(want) < TOLERANCE["loss"]
    assert float(want - bare) > 10 * TOLERANCE["loss"] * float(want)
    # the weights matter: the plain mean over the masked tokens is another number
    losses = reference_bd.weighted_loss(jnp.ones((BATCH, SEQ)), noise[0], noise[1], BLOCK)
    assert abs(float(losses) - float(noise[1].mean())) > 0.1


def test_the_report_counts_the_masked_tokens(weights, batch, noise):
    _, report = tinygpt.loss_and_report_fn(CONFIG, weights, batch, batch, dropout_key=KEY)
    assert report.shape == (3,) and float(report[1]) == 0.0
    assert float(report[2]) == float(noise[1].sum())
    with pytest.raises(ValueError, match="draws its noise"):
        tinygpt.forward(CONFIG, weights, batch)


# Every expert on this chip, through the same held-experts path: the routing trains.
EVERY_EXPERT = {**FILE, "num_experts": EXPERTS, "experts_held_first": 0}


@pytest.mark.parametrize("file", [FILE, EVERY_EXPERT], ids=["a-part", "every-expert"])
def test_gradient_of_every_leaf_matches_the_reference(batch, noise, file):
    """``jax.grad`` of the training loss through the flash kernels' einsum
    backward under the rule, the rotary positions inside each copy, the
    per-head norms' scales, the held experts and the weighted loss."""
    shape = build_bd.bd_shape(JOB, file)
    config = dataclasses.replace(build_bd.sdar_config(JOB, file), compute_dtype=jnp.float32)
    assert config.trains_routing == shape["routing_trained"] == (file is EVERY_EXPERT)
    weights = seeded_weights(config)
    got = jax.grad(lambda p: tinygpt.loss_fn(config, p, batch, batch, dropout_key=KEY))(weights)
    with jax.default_matmul_precision("highest"):
        want = jax.grad(lambda p: reference_bd.loss(shape, p, batch, *noise))(weights)
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
    assert set(got["blocks"]) >= {"wq", "wkv", "q_norm", "k_norm", "wo", "moe_wgu", "moe_wd"}
    # the mask token's row of the head is never a target: it is only pushed down
    assert float(jnp.abs(got["wte"][127]).max()) > 0.0


def test_gradients_through_the_fused_backward_kernel_match_too(weights, batch, noise, monkeypatch):
    """The chip's backward is the fused kernel; interpret mode picks the einsum
    path. Forced onto the kernel, one layer's attention leaves still agree."""
    from distributed_llm_training_benchmark_framework_tpu.ops import flash_attention as fa

    picked = fa.pick_tiles
    monkeypatch.setattr(fa, "pick_tiles", lambda S, D, dtype, interpret, pallas, *tiles: picked(
        S, D, dtype, interpret, True, *tiles))
    leaves = ("wq", "wkv", "q_norm", "k_norm")

    def split(p, part):
        return {**p, "blocks": {**p["blocks"], **part}}

    part = {k: weights["blocks"][k] for k in leaves}
    program = jax.grad(lambda part: tinygpt.loss_fn(
        CONFIG, split(weights, part), batch, batch, dropout_key=KEY))
    fa.flash_attention.clear_cache()  # the call is jitted: traced before, it is not traced again
    try:
        assert "flash_bwd_fused" in str(jax.make_jaxpr(program)(part))
        got = program(part)
    finally:
        fa.flash_attention.clear_cache()
    with jax.default_matmul_precision("highest"):
        want = jax.grad(lambda part: reference_bd.loss(
            SHAPE, split(weights, part), batch, *noise))(part)
    for name in leaves:
        error = float(jnp.linalg.norm(got[name] - want[name]) / jnp.linalg.norm(want[name]))
        assert error < TOLERANCE["grad_leaf"], (name, error)


@pytest.mark.parametrize("name", sorted(WRONG))
def test_a_wrong_model_fails_the_same_tolerance(weights, batch, noise, name):
    got = tinygpt.forward(CONFIG, weights, batch, dropout_key=KEY)[0]
    wrong = reference_logits({**SHAPE, **WRONG[name]}, weights, batch, noise[1])
    assert relative(got, wrong) > 10 * TOLERANCE["logits"]


def test_float8_weights_fail_the_same_tolerance(weights, batch, noise):
    got = tinygpt.forward(CONFIG, weights, batch, dropout_key=KEY)[0]
    fp8 = jax.tree.map(lambda t: t.astype(jnp.float8_e4m3fn).astype(t.dtype), weights)
    assert relative(got, reference_logits(SHAPE, fp8, batch, noise[1])) > 10 * TOLERANCE["logits"]


def test_the_eight_shares_add_up_to_the_uncut_layer(weights):
    """The share test: eight chips hold 2 of the 16 experts each; what they
    compute of one routed layer, gates renormalised over a token's chosen
    experts before each takes its held part, adds up to the layer with every
    expert, and to the reference's layer given every expert."""
    whole = dataclasses.replace(CONFIG, experts_held=None, held_rows_factor=None)
    layer = jax.tree.map(lambda t: t[0], weights["blocks"])
    key = jax.random.key(3)
    all_wgu = 0.1 * jax.random.normal(key, (EXPERTS, *layer["moe_wgu"].shape[1:]))
    all_wd = 0.1 * jax.random.normal(jax.random.fold_in(key, 1),
                                     (EXPERTS, *layer["moe_wd"].shape[1:]))
    x = jax.random.normal(jax.random.fold_in(key, 2), (BATCH, 2 * SEQ, CONFIG.n_embd))
    uncut, _ = moe.moe_mlp(whole, {**layer, "moe_wgu": all_wgu, "moe_wd": all_wd}, x, None, True)
    routed, parts = 0.0, []
    for first in range(0, EXPERTS, 2):
        share = dataclasses.replace(CONFIG, experts_held=(first, 2), held_rows_factor=None)
        held = {**layer, "moe_wgu": all_wgu[first:first + 2], "moe_wd": all_wd[first:first + 2]}
        y, _ = moe.moe_mlp(share, held, x, None, True)
        routed = routed + y
        parts.append(float(jnp.max(jnp.abs(y))))
    assert relative(routed, uncut) < TOLERANCE["logits"]
    assert min(parts) > 0.0 and max(parts) < float(jnp.max(jnp.abs(uncut)))  # no share is all
    shape = {**SHAPE, "held": (0, EXPERTS)}
    w = {**layer, "moe_wgu": all_wgu, "moe_wd": all_wd}
    with jax.default_matmul_precision("highest"):
        want = jax.vmap(lambda h: reference_bd._routed_mlp(shape, h, w)[0])(x)
    assert relative(uncut, want) < TOLERANCE["logits"]


def test_the_step_draws_its_noise_from_seed_step_and_micro_batch(batch):
    """Equal noise from equal (seed, step), different from different: the
    step's fourth output counts the masked tokens, and the key is the one
    dropout's would be: fold_in(fold_in(key(seed), step), micro-batch 0)."""
    mesh = make_mesh((1, 1, 1, 1, 1), MESH_AXES, devices=jax.devices()[:1])
    strategy = dataclasses.replace(get_strategy("zero2"), remat="dots")
    config = build_bd.sdar_config(JOB, FILE)
    table = jnp.asarray(batch[:1])

    def build(seed):
        return create_train_state(config, strategy, mesh, seed=seed, from_table=True,
                                  global_micro=1, seq_len=SEQ)

    def run(seed, step):
        state = build(seed)
        *_, loss, report = state.step_fn(state.params, state.opt_state, table, step)
        return float(loss), np.asarray(report)

    loss_a, report_a = run(5, 3)
    loss_b, report_b = run(5, 3)
    loss_c, report_c = run(5, 4)
    assert loss_a == loss_b and (report_a == report_b).all()
    assert loss_c != loss_a and report_c[2] != report_a[2]
    key = jax.random.fold_in(jax.random.fold_in(jax.random.key(5), 3), 0)
    assert report_a[2] == float(tinygpt.bd_noise(config, key, (1, SEQ))[1].sum())
    assert report_a[1] == 0.0 and 0.0 < report_a[0] <= 2 * SEQ * TOP_K * 2  # two layers' rows


def test_the_noise_has_a_scope_under_embed(weights, batch):
    text = jax.jit(lambda p, b: tinygpt.loss_fn(CONFIG, p, b, b, dropout_key=KEY)).lower(
        weights, batch).as_text(debug_info=True)
    assert f"embed/{NOISE}" in text


@pytest.mark.parametrize("change, match", [
    (dict(attention_impl="ring"), "ring attention, Ulysses"),
    (dict(attention_impl="ulysses"), "ring attention, Ulysses"),
    (dict(seq_manual_axis="seq"), "sequence-parallel"),
    (dict(causal=True), "its own mask rule"),
    (dict(pos_embed="learned"), "pos_embed='rope'"),
    (dict(block_diffusion=BlockDiffusionObjective(block=4, mask_id=128)), "inside the vocabulary"),
    (dict(qk_norm="vector"), "qk_norm must be"),
])
def test_what_the_objective_refuses_by_name(change, match):
    with pytest.raises(ValueError, match=match):
        dataclasses.replace(CONFIG, **change)


def test_the_pipeline_is_refused_by_name():
    with pytest.raises(ValueError, match="block diffusion"):
        CONFIG.refuse_pipeline()


def test_flops_and_memory_count_the_objective():
    """The program's count against the benchmark's independent one at the
    published widths: two copies through every layer, the true pairs, the
    noisy copy alone through the head."""
    published = dict(
        FILE, hidden_size=2048, num_attention_heads=32, num_key_value_heads=4, head_dim=128,
        moe_intermediate_size=768, num_experts_published=128, num_experts=16,
        experts_held_first=0, num_experts_per_tok=8, vocab_size=18992, mask_token_id=18991)
    job = dict(JOB, seq_len=8192, depth=6, held_rows_factor=1.5)
    config, shape = build_bd.sdar_config(job, published), build_bd.bd_shape(job, published)
    want = flops_bd.train_flops_per_token(shape)
    assert flops.train_flops_per_token(config) == pytest.approx(want, rel=1e-12)
    assert round(want / 3e6) == 1456  # M a data token, forward
    assert flops_bd.true_pairs(shape) == 8192 * 8192 + 8192 * 4
    stats = attention_mixer.bd_mask_stats(config, 8192)
    assert stats["true_pairs"] == flops_bd.true_pairs(shape)
    # the unit is the piece a kernel skips by: 69.5 of 256 tiles' worth of forward
    # pieces of 128 x 128, 71 of 256 of backward pieces of 256 x 256
    assert (stats["fwd_live_tiles"], stats["fwd_tiles"], stats["fwd_tile_pairs"]) == (
        56 * 64 + 24 * 36, 256 * 64, 128 * 128)
    assert (stats["bwd_live_tiles"], stats["bwd_tiles"], stats["bwd_tile_pairs"]) == (
        71 * 16, 256 * 16, 256 * 256)
    visited = (stats["fwd_live_tiles"] * stats["fwd_tile_pairs"]
               + stats["bwd_live_tiles"] * stats["bwd_tile_pairs"])
    assert round(100 * 2 * stats["true_pairs"] / visited, 2) == 91.15  # bd_live_fill_pct's arithmetic
    # 80 live tiles a head in 144 forward steps (9 a query tile) and the square's
    # 256 backward: the dead ones bring nothing
    assert (stats["fwd_grid_steps"], stats["fwd_tile_fetches"]) == (144, 79)
    assert (stats["bwd_grid_steps"], stats["bwd_tile_fetches"]) == (256, 80)
    # the causal next-token model of the same widths: one copy, half the pairs
    plain = dataclasses.replace(config, block_diffusion=None, causal=True)
    D, H, Dh = 2048, 32, 128
    assert flops.forward_flops_per_token(config) - 2 * D * 18992 == pytest.approx(
        2 * (flops.forward_flops_per_token(plain) - 2 * D * 18992)
        + 6 * 4 * (8192 + 4 - 8192) * H * Dh, rel=1e-12)
    mesh = make_mesh((1, 1, 1, 1, 1), MESH_AXES, devices=jax.devices()[:1])
    strategy = dataclasses.replace(get_strategy("zero2"), remat="dots")
    both = memory.estimate_hbm(dataclasses.replace(config, remat="dots"), strategy, mesh, 1, 8192)
    one = memory.estimate_hbm(dataclasses.replace(plain, remat="dots"), strategy, mesh, 1, 8192)
    assert both.params == one.params and both.logits == one.logits
    assert both.activations == 2 * one.activations


def test_the_norm_scales_are_replicated_over_the_model_axis():
    """Every head needs the whole (head_dim,) scale: no tensor-parallel rule
    names q_norm / k_norm, whatever their length."""
    mesh = make_mesh((1, 1, 2, 1, 1), MESH_AXES, devices=jax.devices()[:2])
    shapes = jax.eval_shape(lambda k: tinygpt.init_params(CONFIG, k), jax.random.key(0))
    specs = strategies.param_partition_specs(shapes, mesh, shard=False, kv_heads=2)
    assert tuple(specs["blocks"]["q_norm"]) == tuple(specs["blocks"]["k_norm"]) == (None, None)
    assert "model" in tuple(specs["blocks"]["wq"])


def test_through_the_one_pass_prologue_the_loss_and_gradients_are_the_chains(batch, monkeypatch):
    """On a chip the per-head QK-norm and the rotation at i mod L are
    ``ops/rotary.py``'s one pass. One layer at the cell's head width with the
    kernels interpreted, under the cell's remat, against the ``jnp`` chain the
    cases above hold to the reference: the same loss, every leaf's gradient."""
    from distributed_llm_training_benchmark_framework_tpu.ops import rotary

    file = {**FILE, "head_dim": 128, "num_hidden_layers": 1}
    config = dataclasses.replace(
        build_bd.sdar_config(JOB, file), compute_dtype=jnp.float32, remat="dots",
        attention_impl="reference")  # the pass does not care which attention follows it
    weights = seeded_weights(config)
    run = lambda: jax.jit(jax.value_and_grad(
        lambda p: tinygpt.loss_fn(config, p, batch, batch, dropout_key=KEY)))(weights)
    assert attention_mixer.qk_prologue_stats(config, SEQ)["pass_layers"] == 0
    want_loss, want = run()
    monkeypatch.setattr(rotary, "kernel_mode", lambda: True)  # as a chip, interpreted
    stats = attention_mixer.qk_prologue_stats(config, SEQ)
    assert (stats["rotary_layers"], stats["pass_layers"], stats["norm_stage_layers"]) == (1, 1, 1)
    got_loss, got = run()
    assert abs(float(got_loss) - float(want_loss)) / float(want_loss) < TOLERANCE["loss"]
    got["blocks"].pop("router"), want["blocks"].pop("router")  # not trained: zero on both
    errors = jax.tree.map(
        lambda g, w: float(jnp.linalg.norm(g - w) / jnp.linalg.norm(w)), got, want)
    for path, error in jax.tree_util.tree_leaves_with_path(errors):
        assert error < TOLERANCE["grad_leaf"], (jax.tree_util.keystr(path), error)


@pytest.mark.parametrize("cell,kind,steps,live,fetches", [
    ("sdar-30b-a3b.share8-bd8192", None, (144, 256), 80, (79, 80)),
    ("mellum2-12b-a2.5b.share4-seq16384", "global", (256, 256), 136, (135, 135)),
    ("mellum2-12b-a2.5b.share4-seq16384", "window", (32, 32), 31, (16, 16)),
    ("laguna-xs.2.share16-seq16384", "global", (256, 256), 136, (135, 135)),
    ("laguna-xs.2.share16-seq16384", "window", (64, 64), 63, (32, 32)),
], ids=["block-diffusion", "causal-mellum", "window-1024", "causal-laguna", "window-512"])
def test_a_cells_walks_fetch_their_live_tiles_and_no_dead_one(cell, kind, steps, live, fetches):
    """``bd_mask_stats`` / ``attn_mask_stats`` at the cells' own configs:
    ``*_tile_fetches`` (the copies a head's walk issues for the operand its
    inner axis walks) read the live tiles or fewer where ``*_grid_steps``
    read the square's or the band's steps: 144 / 256 against 79 / 80 under
    block diffusion, 256 against 135 under causal; a window's rows share a
    tile with the next."""
    from distributed_llm_training_benchmark_framework_tpu.ops import flash_attention as fa

    _, workload, file = manifest.load_cell(cell)
    config = manifest.resolve(file["builder"])(workload, file)
    S = workload["seq_len"]
    if kind is None:
        stats, S = attention_mixer.bd_mask_stats(config, S), 2 * S
        rule = config.mask_rule(S)
    else:
        stats, rule = attention_mixer.attn_mask_stats(config, S)[kind], config.mask_rule(S, kind)
        assert (stats["fwd_live_tiles"], stats["bwd_live_tiles"]) == (live, live)
    assert (stats["fwd_grid_steps"], stats["bwd_grid_steps"]) == steps
    assert (stats["fwd_tile_fetches"], stats["bwd_tile_fetches"]) == fetches
    bq, bk, bk_bwd, _ = fa.pick_tiles(S, config.qk_dim, config.compute_dtype, causal=rule)
    tiles = fa.tiles_by_shape(rule, S, bq, bk, fa._fwd_sub_k(bk))
    assert sum(int(t.sum()) for t in tiles.values()) == live
    assert all(live - S // bq < n <= live for n in fetches)
