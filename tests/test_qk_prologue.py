"""``ops/rotary.py``: QK-norm and rotary in one Pallas pass, forward and
backward, against the ``jnp`` chain it stands in for (``models/tinygpt.py``:
``_rms_norm`` -> ``_rope``), with the kernels in Pallas interpret mode at the
cells' head widths and a few rows.

In float32 the two differ by the order of summation (the pass's mean of
squares is a matrix product of three bf16 terms, exact in f32). In bfloat16
the chain rounds the norm's result before the rotation and the pass does not,
so they differ by one bf16 rounding of the result.
"""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from distributed_llm_training_benchmark_framework_tpu.models import common
from distributed_llm_training_benchmark_framework_tpu.models.mixers import (
    attention as attention_mixer,
)
from distributed_llm_training_benchmark_framework_tpu.models import tinygpt
from distributed_llm_training_benchmark_framework_tpu.models.tinygpt import (
    Rotary,
    TinyGPTConfig,
    YarnScaling,
)
from distributed_llm_training_benchmark_framework_tpu.ops import rotary
from distributed_llm_training_benchmark_framework_tpu.parallel import make_mesh
from perfbench.harness import manifest

D, S, EPS = 128, 64, 1e-6
#: relative to the largest entry of the chain's: (values and gradients of q
#: and k, the scales' gradients, which are sums over every row and head)
TOLERANCE = {jnp.float32: (1e-5, 1e-5), jnp.bfloat16: (1.6e-2, 1.6e-2)}
HEADS = {"32/4": (32, 4), "32/8": (32, 8), "16/16": (16, 16)}
YARN = YarnScaling(16.0, 8192, mscale=1.0, mscale_all_dim=0.0)  # Mellum's: cos, sin x 1.277
#: name -> (positions of the S rows, the kind's rotary)
POSITIONS = {
    "plain_theta": (jnp.arange(S), Rotary(1e6)),
    "yarn_with_cos_sin_factor": (jnp.arange(S) + 9000, Rotary(5e5, YARN)),
    "block_diffusion_i_mod_L": (jnp.arange(S) % (S // 2), Rotary(1e6)),
    "sequence_manual_offset": (jnp.arange(S) + 3 * S, Rotary(1e4)),
}


def operands(heads, kv, dtype, batch=1, seed=0):
    keys = jax.random.split(jax.random.key(seed), 6)
    normal = lambda key, shape: jax.random.normal(key, shape, jnp.float32)
    return dict(
        q=normal(keys[0], (batch, S, heads * D)).astype(dtype),
        k=normal(keys[1], (batch, S, kv * D)).astype(dtype),
        q_scale=1.0 + 0.3 * normal(keys[2], (D,)),
        k_scale=1.0 + 0.3 * normal(keys[3], (D,)),
        # what the loss weighs the results by: every entry has its own gradient
        wq=normal(keys[4], (batch, S, heads * D)),
        wk=normal(keys[5], (batch, S, kv * D)),
    )


def weighed(q, k, x):
    return (jnp.sum(q.astype(jnp.float32) * x["wq"])
            + jnp.sum(k.astype(jnp.float32) * x["wk"]))


def chain(x, norm, positions, rot):
    """The ``jnp`` composition ``_attention_sublayer`` runs off the chip."""
    def one(t, scale):
        t = t.reshape(*t.shape[:2], -1, D)
        if norm:
            t = common._rms_norm(t, scale, EPS)
        t = attention_mixer._rope(t, positions.astype(jnp.int32), rot.theta, rot.scaling)
        return t.reshape(*t.shape[:2], -1)

    return one(x["q"], x["q_scale"]), one(x["k"], x["k_scale"])


def the_pass(x, norm, positions, rot):
    return _pass(x, norm, rotary.table(positions.astype(jnp.int32), D, rot.theta, rot.scaling))


def _pass(x, norm, table):
    q, k = rotary.qk_prologue(
        x["q"], x["k"], x["q_scale"] if norm else None, x["k_scale"] if norm else None,
        table, EPS, interpret=True)
    assert q.shape[2:] == (x["q"].shape[-1] // D, D) and k.shape[2:] == (x["k"].shape[-1] // D, D)
    return q.reshape(x["q"].shape), k.reshape(x["k"].shape)


def _values_and_grads(fn, x, *args):
    def loss(leaves):
        q, k = fn({**x, **leaves}, *args)
        return weighed(q, k, x), (q, k)

    leaves = {name: x[name] for name in ("q", "k", "q_scale", "k_scale")}
    (_, out), grads = jax.value_and_grad(loss, has_aux=True)(leaves)
    return out, grads


@functools.partial(jax.jit, static_argnames=("fn", "norm", "rot"))
def values_and_grads(fn, x, norm, positions, rot):
    """((q, k), their and the scales' gradients) of ``fn``: the pass or the chain."""
    return _values_and_grads(fn, x, norm, positions, rot)


@functools.partial(jax.jit, static_argnames=("norm",))
def pass_values_and_grads(x, norm, table):
    """The same of the pass with its table as an argument, so that one trace
    and one compilation of the interpreted kernels serve every table."""
    return _values_and_grads(_pass, x, norm, table)


def kernel_calls(jaxpr, name):
    """How many ``pallas_call``s of that name a jaxpr holds, at any depth."""
    found = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            found += eqn.params["name"] == name
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else (value,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    found += kernel_calls(sub, name)
    return found


def assert_close(got, want, tolerance, what):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    assert got.shape == want.shape, what
    err = np.max(np.abs(got - want)) / np.max(np.abs(want))
    assert err <= tolerance, f"{what}: {err:.3g} > {tolerance}"


@pytest.mark.parametrize("positions", POSITIONS)
@pytest.mark.parametrize("heads", HEADS)
@pytest.mark.parametrize("norm", [True, False], ids=["head_norm_and_rotary", "rotary_alone"])
def test_the_pass_is_the_chain_forward_and_backward(norm, heads, positions):
    """Values and every gradient (q, k, both scales), in f32 and in bf16."""
    pos, rot = POSITIONS[positions]
    for dtype, (rows, sums) in TOLERANCE.items():
        x = operands(*HEADS[heads], dtype)
        table = rotary.table(pos.astype(jnp.int32), D, rot.theta, rot.scaling)
        (q, k), got = pass_values_and_grads(x, norm, table)
        (q_ref, k_ref), want = values_and_grads(chain, x, norm, pos, rot)
        assert q.dtype == dtype and got["q"].dtype == dtype
        assert_close(q, q_ref, rows, "q")
        assert_close(k, k_ref, rows, "k")
        assert_close(got["q"], want["q"], rows, "dq")
        assert_close(got["k"], want["k"], rows, "dk")
        if norm:
            assert_close(got["q_scale"], want["q_scale"], sums, "dq_scale")
            assert_close(got["k_scale"], want["k_scale"], sums, "dk_scale")
        else:
            assert not np.any(np.asarray(got["q_scale"]))


def test_the_table_holds_ropes_cos_and_sin_side_by_side():
    pos, rot = POSITIONS["yarn_with_cos_sin_factor"]
    table = rotary.table(pos.astype(jnp.int32), D, rot.theta, rot.scaling)
    freqs = np.asarray(pos, np.float32)[:, None] * YARN.inv_freq(D, rot.theta)[None, :]
    factor = YARN.cos_sin_factor
    assert factor == pytest.approx(0.1 * np.log(16.0) + 1.0)
    assert table.shape == (S, D) and table.dtype == jnp.float32
    np.testing.assert_allclose(table[:, :64], factor * np.cos(freqs), rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(table[:, 64:], factor * np.sin(freqs), rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("norm", [True, False], ids=["head_norm_and_rotary", "rotary_alone"])
def test_remat_dots_gives_the_same_loss_and_gradients(norm):
    """Under ``jax.checkpoint`` with the layer loop's ``dots`` policy the
    pass's result is not a residual: the backward runs the forward kernel
    again and comes to the same numbers."""
    pos, rot = POSITIONS["plain_theta"]
    x = operands(4, 2, jnp.bfloat16)
    w = jax.random.normal(jax.random.key(7), (x["q"].shape[-1], 4 * D), jnp.float32)

    def loss(leaves):
        # a projection before the pass, as in the layer: its result is a
        # dot's, kept; the pass's is not
        q = jnp.einsum("bse,ef->bsf", leaves["q"], w.astype(jnp.bfloat16),
                       preferred_element_type=jnp.float32).astype(jnp.bfloat16)
        return weighed(*the_pass({**x, **leaves, "q": q}, norm, pos, rot), x)

    leaves = {name: x[name] for name in ("q", "k", "q_scale", "k_scale")}
    plain = jax.jit(jax.value_and_grad(loss))(leaves)
    under = jax.jit(jax.value_and_grad(tinygpt._under_remat("dots", loss)))(leaves)
    from jax._src.ad_checkpoint import saved_residuals

    kept = saved_residuals(tinygpt._under_remat("dots", loss), leaves)
    # a dot's result leaves the policy through jax's reduce_precision; nothing
    # the pass makes is kept
    from_where = ("argument", "constant", "dot_general", "reduce_precision")
    assert kept and all(any(w in why for w in from_where) for _, why in kept), kept
    np.testing.assert_array_equal(plain[0], under[0])
    for name in leaves:
        np.testing.assert_array_equal(
            np.asarray(plain[1][name], np.float32), np.asarray(under[1][name], np.float32))


def test_under_a_mesh_the_batch_is_split_over_four(eight_devices):
    pos, rot = POSITIONS["plain_theta"]
    x = operands(4, 2, jnp.bfloat16, batch=4)
    (q, k), one = values_and_grads(the_pass, x, True, pos, rot)
    mesh = make_mesh((4,), ("data",), devices=eight_devices[:4])
    with jax.set_mesh(mesh):
        rows = NamedSharding(mesh, P("data"))
        split = {name: jax.device_put(x[name], rows) for name in ("q", "k", "wq", "wk")}
        (q4, k4), four = values_and_grads(the_pass, {**x, **split}, True, pos, rot)
    assert q4.sharding.spec == P("data") and four["q"].sharding.spec == P("data")
    np.testing.assert_array_equal(np.asarray(q4, np.float32), np.asarray(q, np.float32))
    np.testing.assert_array_equal(np.asarray(k4, np.float32), np.asarray(k, np.float32))
    for name in ("q", "k"):
        np.testing.assert_array_equal(
            np.asarray(four[name], np.float32), np.asarray(one[name], np.float32))
    for name in ("q_scale", "k_scale"):  # four shards' partial sums, added
        assert_close(four[name], one[name], 1e-5, name)


def test_interpret_mode_is_refused_where_the_backend_is_a_tpu(monkeypatch):
    pos, rot = POSITIONS["plain_theta"]
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert rotary.kernel_mode() is False
    with pytest.raises(ValueError, match="interpret=True on a TPU backend"):
        the_pass(operands(4, 2, jnp.bfloat16), True, pos, rot)


def test_a_partly_manual_region_keeps_the_chain(eight_devices, monkeypatch):
    """Inside a shard_map that is manual over one axis while another still
    spans devices (the pipeline schedules) a Mosaic call can be neither
    partitioned nor wrapped: the pass is not taken there. A region manual
    over every axis takes it bare."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    mesh = make_mesh((2, 2), ("data", "pipe"), devices=eight_devices[:4])
    seen = {}

    def region(name):
        def body(x):
            seen[name] = rotary.kernel_mode()
            return x
        return body

    x = jnp.zeros((4, 4))
    with jax.set_mesh(mesh):
        jax.jit(jax.shard_map(region("partly"), in_specs=P("pipe"), out_specs=P("pipe"),
                              axis_names={"pipe"}))(x)
        jax.jit(jax.shard_map(region("wholly"), in_specs=P("pipe", "data"),
                              out_specs=P("pipe", "data")))(x)
        seen["outside"] = rotary.kernel_mode()
    assert seen == {"partly": None, "wholly": False, "outside": False}


# --- the choice rule, from what the code sees ------------------------------

def llama_like(**keys):
    base = dict(
        vocab_size=64, block_size=S, n_layer=2, n_head=4, n_kv_head=2, n_embd=512,
        pos_embed="rope", norm="rmsnorm", mlp_act="swiglu", bias=False, tie_embeddings=False,
        causal=True, dropout=0.0, attention_impl="flash", compute_dtype=jnp.bfloat16)
    return TinyGPTConfig(**{**base, **keys})


CHOICES = {
    # name: (config, layers that rotate, take the pass on a chip, with the norm stage)
    "heads_of_128_rotary_alone": (llama_like(), 2, 2, 0),
    "heads_of_128_under_a_head_norm": (llama_like(qk_norm="head"), 2, 2, 2),
    "a_norm_over_all_features_stays_in_jnp": (llama_like(qk_norm=True), 2, 2, 0),
    "heads_of_64_stay_on_rope": (llama_like(n_embd=256), 2, 0, 0),
    "learned_positions_have_nothing_to_rotate": (
        llama_like(pos_embed="learned", n_kv_head=None), 0, 0, 0),
}


@pytest.mark.parametrize("name", CHOICES)
def test_the_choice_is_made_from_the_shapes_and_the_backend(name, monkeypatch):
    config, rotate, taken, normed = CHOICES[name]
    # a CPU backend keeps the jnp chain whatever the shapes are
    assert rotary.kernel_mode() is None
    assert attention_mixer.qk_prologue_tables(config, S) == {}
    assert attention_mixer.qk_prologue_stats(config, S)["pass_layers"] == 0
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    stats = attention_mixer.qk_prologue_stats(config, S)
    assert (stats["rotary_layers"], stats["pass_layers"], stats["norm_stage_layers"]) == (
        rotate, taken, normed)
    assert set(attention_mixer.qk_prologue_tables(config, S)) == ({None} if taken else set())


def test_the_latent_operand_stays_on_rope(monkeypatch):
    """64 of 192 lanes of q and a one-head 64-wide key: not the pass's."""
    _, workload, file = manifest.load_cell("deepseek-v2-lite.share8-seq8192")
    config = manifest.resolve(file["builder"])(workload, file)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert config.latent_attention and config.qk_rope_head_dim == 64
    assert attention_mixer.qk_prologue_tables(config, workload["seq_len"]) == {}


#: cell: (layers that rotate, that take the pass, with the norm stage, q + k lanes)
CELLS = {
    "tinygpt-a.seq8192": (0, 0, 0, 0),
    "mistral-7b.d2": (2, 2, 0, 4096 + 1024),
    "olmoe-1b-7b.d1": (1, 1, 0, 2048 + 2048),
    "deepseek-v2-lite.share8-seq8192": (6, 0, 0, 0),
    "sdar-30b-a3b.share8-bd8192": (6, 6, 6, 4096 + 512),
    "mellum2-12b-a2.5b.share4-seq16384": (4, 4, 4, 4096 + 512),
    # head counts by kind: the bytes are the full layers' (48 + 8 heads; ``by_kind`` has both)
    "laguna-xs.2.share16-seq16384": (5, 5, 0, 6144 + 1024),
}


@pytest.mark.parametrize("cell", CELLS)
def test_the_counter_at_the_six_configurations(cell, monkeypatch):
    """What ``qk_prologue_stats`` says of each configuration's timed config on
    a chip: which layers take the pass, and the bytes a layer's pass moves."""
    _, workload, file = manifest.load_cell(cell)
    builder = manifest.resolve(file.get("builder", "perfbench.harness.build:tinygpt_config"))
    config = builder(workload, file)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    stats = attention_mixer.qk_prologue_stats(config, workload["seq_len"])
    rotate, taken, normed, lanes = CELLS[cell]
    assert (stats["rotary_layers"], stats["pass_layers"], stats["norm_stage_layers"]) == (
        rotate, taken, normed)
    rows = workload["seq_len"] * (2 if config.block_diffusion is not None else 1)
    assert stats["forward_bytes"] == 2 * rows * lanes * 2
    assert stats["backward_bytes"] == (3 if normed else 2) * rows * lanes * 2
    if config.layer_types and taken:  # one pair of tables a kind of layer
        assert set(attention_mixer.qk_prologue_tables(config, rows)) == set(config.layer_types)


def test_a_layer_hands_the_pass_its_kinds_tables(monkeypatch):
    """The layer loop makes the tables once and each layer rotates by its
    kind's: a stack of two kinds with two thetas equals the chain's."""
    config = dataclasses.replace(
        llama_like(qk_norm="head", n_layer=2), compute_dtype=jnp.float32,
        layer_types=("window", "global"), sliding_window=16,
        layer_rotary=(("window", Rotary(1e4)), ("global", Rotary(5e5, YARN))))
    params = tinygpt.init_params(config, jax.random.key(0))
    x = jax.random.normal(jax.random.key(1), (1, S, config.n_embd), jnp.float32)
    run = lambda: jax.jit(lambda p: tinygpt.apply_blocks(config, p, x)[0])(params["blocks"])
    want = run()
    monkeypatch.setattr(rotary, "kernel_mode", lambda: True)
    jaxpr = jax.make_jaxpr(lambda p: tinygpt.apply_blocks(config, p, x)[0])(params["blocks"])
    assert kernel_calls(jaxpr.jaxpr, "qk_prologue_fwd") == 2
    assert_close(run(), want, 1e-5, "two kinds of layer")


def test_the_pass_has_a_scope_of_its_own_under_attention(monkeypatch):
    """``qk_prologue`` below ``attention`` (below the kind's scope in a stack of
    kinds), forward, remat's re-run and backward; a layer on the chain has none."""
    from distributed_llm_training_benchmark_framework_tpu.utils.scopes import QK_PROLOGUE

    config = dataclasses.replace(
        llama_like(qk_norm="head", n_layer=2), remat="dots", scan_layers=False,
        layer_types=("window", "global"), sliding_window=16)
    params = tinygpt.init_params(config, jax.random.key(0))
    idx = jnp.zeros((1, S), jnp.int32)
    lowered = lambda: jax.jit(jax.grad(lambda p: tinygpt.loss_fn(config, p, idx, idx))).lower(
        params).as_text(debug_info=True)
    assert f"attention/window/{QK_PROLOGUE}/" not in lowered()
    monkeypatch.setattr(rotary, "kernel_mode", lambda: True)
    text = lowered()
    for path in (f"jvp(attention)/window/{QK_PROLOGUE}/qk_prologue_fwd",
                 f"jvp(attention)/global/{QK_PROLOGUE}/qk_prologue_fwd",
                 f"rematted_computation/attention/window/{QK_PROLOGUE}/qk_prologue_fwd",
                 f"attention/global/{QK_PROLOGUE}/qk_prologue_bwd"):
        assert path in text, path
