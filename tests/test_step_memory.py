"""The step's memory account (``utils/scopes.py::step_memory``, written by
``train/step.py::aot_compile``; docs/OBSERVABILITY.md, "The step's memory"):
the compiled step's classes, the devices' limit, and what a micro-batch's
forward keeps for its backward, by scope and name, under the step's remat
policy (``kept``) and under none (``all``).

Every step here is tiny and compiled from shapes (``abstract_compile_step``)
unless a case runs it; each case reads the record right after the
``aot_compile`` it is about, since the record is the process's newest.
"""

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
import pytest
from jax.ad_checkpoint import checkpoint_name

from distributed_llm_training_benchmark_framework_tpu.models import common
from distributed_llm_training_benchmark_framework_tpu.models.mixers import (
    conv as conv_mixer,
    kda as kda_mixer,
    ssd as ssd_mixer,
)
from distributed_llm_training_benchmark_framework_tpu.analysis import memory_anatomy
from distributed_llm_training_benchmark_framework_tpu.models import moe, tinygpt
from distributed_llm_training_benchmark_framework_tpu.models.tinygpt import TinyGPTConfig
from distributed_llm_training_benchmark_framework_tpu.ops.flash_attention import (
    FLASH_RESIDUAL_NAMES,
)
from distributed_llm_training_benchmark_framework_tpu.ops.kda import KDA_RESIDUAL_NAMES
from distributed_llm_training_benchmark_framework_tpu.parallel import get_strategy, make_mesh
from distributed_llm_training_benchmark_framework_tpu.train.step import (
    abstract_compile_step, create_train_state,
)
from distributed_llm_training_benchmark_framework_tpu.utils import memory, residuals, scopes
from perfbench.harness import build_kda, build_lfm2, build_nemotron
from tests.test_kimi_linear import FILE as KIMI_FILE
from tests.test_lfm2 import FILE as LFM2_FILE
from tests.test_nemotron import FILE as NEMOTRON_FILE

SEQ, BATCH = 128, 4
AXES = ("data", "seq", "model", "pipe", "expert")
POLICIES = ("none", "dots", "full_keep_kernels", "full")
# tinygpt-a's shape (LayerNorm, learned positions, GELU, tied head, dropout) at a small size
DENSE = TinyGPTConfig(vocab_size=512, n_embd=128, n_head=4, n_layer=3, block_size=SEQ,
                      attention_impl="flash", dropout=0.1)
ROUTED = TinyGPTConfig(
    vocab_size=512, n_embd=64, n_head=4, n_layer=2, block_size=SEQ, mlp_hidden=32,
    n_experts=8, expert_top_k=2, capacity_factor=None, norm_topk_prob=False, qk_norm=True,
    norm="rmsnorm", mlp_act="swiglu", pos_embed="rope", tie_embeddings=False, bias=False,
    dropout=0.0, router_aux_coef=0.01, router_z_coef=0.001, attention_impl="flash",
    scan_layers=False)
KIMI_JOB = dict(seq_len=SEQ, held_rows_factor=4.0, attention="flash", layer_loop="unrolled",
                kda_chunk=16)
KIMI = build_kda.kimi_config(KIMI_JOB, KIMI_FILE)
NEMOTRON = build_nemotron.nemotron_config(
    dict(seq_len=SEQ, held_rows_factor=4.0, attention="flash", layer_loop="unrolled"),
    NEMOTRON_FILE)
LFM2 = build_lfm2.lfm2_config(
    dict(seq_len=SEQ, held_rows_factor=4.0, attention="flash", layer_loop="unrolled"), LFM2_FILE)


def mesh_of(**sizes):
    shape = tuple(sizes.get(axis, 1) for axis in AXES)
    return make_mesh(shape, AXES, devices=jax.devices()[:math.prod(shape)])


def compiled_step(config, remat, mesh=None, strategy="zero2", batch=BATCH):
    """Compile the tiny step from shapes -> (the record it left, the executable)."""
    strategy = dataclasses.replace(get_strategy(strategy), remat=remat)
    compiled = abstract_compile_step(config, strategy, mesh or mesh_of(), from_table=True,
                                     global_micro=batch, seq_len=SEQ)
    return scopes.step_memory(), compiled


def total(entries):
    return sum(e[4] for e in entries)


def named(entries, name):
    return [e for e in entries if e[1] == name]


@pytest.fixture(scope="module")
def dense():
    """{policy: saved()} of the dense config's step under each policy."""
    return {policy: compiled_step(DENSE, policy)[0]["saved"]() for policy in POLICIES}


def test_the_policies_order_and_all_is_one_list(dense):
    kept = [total(dense[policy]["kept"]) for policy in POLICIES]
    assert kept == sorted(kept, reverse=True) and kept[0] > kept[1] > kept[2] > kept[3] > 0
    for policy in POLICIES:
        assert dense[policy]["all"] == dense["none"]["kept"]
        assert total(dense[policy]["all"]) >= total(dense[policy]["kept"])


@pytest.mark.parametrize("policy", POLICIES)
def test_the_flash_kernels_results_by_name(dense, policy):
    """``out`` (B x H, S, D at the compute dtype) and ``lse`` (B x H, S, float32),
    a layer each in the scanned stack: kept by name where the policy names
    them or keeps everything, run again under ``full``."""
    out, lse = (named(dense[policy]["kept"], name) for name in FLASH_RESIDUAL_NAMES)
    if policy == "full":
        assert not out and not lse
        return
    heads, head_dim = DENSE.n_head, DENSE.n_embd // DENSE.n_head
    itemsize = jnp.dtype(DENSE.compute_dtype).itemsize
    assert total(out) == DENSE.n_layer * BATCH * heads * SEQ * head_dim * itemsize
    assert total(lse) == DENSE.n_layer * BATCH * heads * SEQ * 4
    assert {e[0] for e in out + lse} == {(scopes.ATTENTION,)}
    assert {e[3] for e in out} == {str(jnp.dtype(DENSE.compute_dtype))}


@pytest.mark.parametrize("policy", POLICIES)
def test_every_scope_path_is_the_programs(dense, policy):
    for which in ("kept", "all"):
        for path, name, shape, dtype, nbytes in dense[policy][which]:
            assert path == residuals.UNSCOPED or path[0] in scopes.SCOPES
            assert all(component in scopes.NAMES for component in path[1:])
            assert isinstance(name, str) and nbytes > 0
            assert dtype.startswith("key<") or \
                nbytes == math.prod(shape) * jnp.dtype(dtype).itemsize
    scoped = sum(e[4] for e in dense[policy]["all"] if e[0] != residuals.UNSCOPED)
    assert scoped > 0.99 * total(dense[policy]["all"])


@pytest.mark.parametrize("policy", ("dots", "none"))
def test_no_parameter_leaf_is_listed(dense, policy):
    """The parameters are state: a residual that is one is left out, and what
    is computed from them alone (a cast) is summed apart."""
    shapes = jax.eval_shape(lambda: tinygpt.init_params(DENSE, jax.random.key(0)))
    leaves = {(tuple(x.shape), str(x.dtype)) for x in jax.tree.leaves(shapes)}
    for which in ("kept", "all"):
        assert not [e for e in dense[policy][which] if (e[2], e[3]) in leaves]
        assert dense[policy]["left_out"][which]["weights"] > 0
        assert dense[policy]["left_out"][which]["constants"] >= 0


def test_a_routed_config_shows_the_experts():
    saved = compiled_step(ROUTED, "dots")[0]["saved"]()
    paths = {e[0] for e in saved["all"]}
    assert (scopes.MLP, scopes.EXPERTS) in paths and (scopes.MLP, scopes.ROUTER) in paths
    # a grouped matmul is no ``dot_general``: ``dots`` keeps the router's and drops the experts'
    assert (scopes.MLP, scopes.ROUTER) in {e[0] for e in saved["kept"]}
    assert total(saved["all"]) > total(saved["kept"])


@functools.lru_cache(maxsize=None)
def saved_of(which, policy):
    """``saved()`` of the routed, the Kimi- or the LFM2-shaped config's step under ``policy``."""
    config, batch = {"routed": (ROUTED, BATCH), "kimi": (KIMI, 1), "lfm2": (LFM2, 2)}[which]
    return compiled_step(config, policy, batch=batch)[0]["saved"]()


@pytest.mark.parametrize("policy", ("full_keep_kernels", "none"))
def test_the_kda_states_are_the_counters_bytes(policy):
    """Under the recurrence's second name the account holds what
    ``kda_mixer.kda_stats`` counts a layer, times the KDA layers."""
    saved = saved_of("kimi", policy)
    stats = kda_mixer.kda_stats(dataclasses.replace(KIMI, compute_dtype=jnp.bfloat16), SEQ)
    states = named(saved["kept"], KDA_RESIDUAL_NAMES[1])
    assert len(states) == stats["layers"] == 4
    assert total(states) == stats["saved_state_bytes"] * stats["layers"]
    assert {e[0] for e in states} == {(scopes.ATTENTION, scopes.KDA, scopes.KDA_CORE)}
    paths = {e[0] for e in saved["all"]}
    assert (scopes.ATTENTION, scopes.KDA, scopes.KDA_PREP) in paths
    assert (scopes.ATTENTION, scopes.GLOBAL, scopes.MLA_CORE) in paths


@pytest.mark.parametrize("policy", ("dots", "full_keep_kernels", "full"))
def test_the_routed_layers_named_values_by_scope_and_bytes(policy):
    """The experts' gate+up (N x K rows of 2F at the compute dtype), the router's
    float32 logits and its choice, the two permutations of the plan: a layer
    each under the policies that keep names, none of them under ``full``."""
    kept = saved_of("routed", policy)["kept"]
    by_name = {name: named(kept, name) for name in moe.MOE_RESIDUAL_NAMES}
    if policy == "full":
        assert not any(by_name.values())
        return
    layers, tokens, K = ROUTED.n_layer, BATCH * SEQ, ROUTED.expert_top_k
    assert total(by_name[moe.MOE_GU]) == layers * tokens * K * 2 * ROUTED.mlp_dim * 2
    assert {e[3] for e in by_name[moe.MOE_GU]} == {"bfloat16"}
    assert total(by_name[moe.ROUTER_LOGITS]) == layers * tokens * ROUTED.n_experts * 4
    assert total(by_name[moe.MOE_PLAN]) == layers * 2 * tokens * K * 4
    assert by_name[moe.ROUTER_CHOICE]
    for name, path in ((moe.MOE_GU, scopes.EXPERTS), (moe.ROUTER_LOGITS, scopes.ROUTER),
                       (moe.ROUTER_CHOICE, scopes.ROUTER), (moe.MOE_PLAN, scopes.DISPATCH)):
        assert {e[0] for e in by_name[name]} == {(scopes.MLP, path)}


@pytest.mark.parametrize("policy", ("dots", "full_keep_kernels", "full"))
def test_the_kimi_layers_named_values_by_scope_and_bytes(policy):
    """A KDA layer's q, k, v projection and the dense layer's gate+up, in their
    compute-dtype form: by name under ``full_keep_kernels``; under ``dots`` as
    their ``dot_general``'s float32 result and under no name; nowhere under
    ``full``. The held experts' gate+up by name under both."""
    kept = saved_of("kimi", policy)["kept"]
    kda_layers = KIMI.layer_types.count(scopes.KDA)
    width = 3 * KIMI.kda_heads * KIMI.kda_head_dim
    qkv, dense, gu = (named(kept, name) for name in (kda_mixer.KDA_QKV, tinygpt.MLP_GU, moe.MOE_GU))
    prep = (scopes.ATTENTION, scopes.KDA, scopes.KDA_PREP)
    products = [e for e in kept if e[:3] == (prep, "dot_general", (1, SEQ, width))]
    if policy == "full_keep_kernels":
        assert total(qkv) == kda_layers * SEQ * width * 2 and {e[0] for e in qkv} == {prep}
        assert total(dense) == KIMI.first_k_dense * SEQ * 2 * KIMI.dense_mlp_hidden * 2
        assert {e[0] for e in dense} == {(scopes.MLP,)}
        assert {e[3] for e in qkv + dense} == {"bfloat16"} and not products
    else:
        assert not qkv and not dense
        assert len(products) == (kda_layers if policy == "dots" else 0)
    rows = moe.held_buffer_rows(KIMI, SEQ)
    assert total(gu) == (0 if policy == "full" else KIMI.n_moe_layers * rows * 2 * KIMI.mlp_dim * 2)
    assert {e[0] for e in gu} <= {(scopes.MLP, scopes.EXPERTS)}


@pytest.mark.parametrize("policy", POLICIES)
def test_a_conv_layers_projection_by_scope_and_bytes(policy):
    """A ``conv`` layer's B | C | x~ projection, 3 D columns a token in the compute
    dtype: by name under ``full_keep_kernels``; under ``dots`` as its ``dot_general``'s
    float32 result and under no name; nowhere under ``full``. The gated convolution's
    own residuals (off a TPU the ``jnp`` chain's: the thirds and their products, at
    least the 4 D columns a token the estimate counts) are kept without remat alone."""
    kept = saved_of("lfm2", policy)["kept"]
    layers, tokens, D = LFM2.layer_types.count(scopes.CONV), 2 * SEQ, LFM2.n_embd
    bcx = named(kept, conv_mixer.SCONV_BCX)
    into = (scopes.ATTENTION, scopes.CONV, scopes.SCONV_IN)
    products = [e for e in kept if e[:3] == (into, "dot_general", (2, SEQ, 3 * D))]
    core = [e for e in kept if e[0] == (scopes.ATTENTION, scopes.CONV, scopes.SCONV_CORE)]
    if policy == "full_keep_kernels":
        assert total(bcx) == layers * tokens * 3 * D * 2 and len(bcx) == layers == 4
        assert {e[0] for e in bcx} == {into} and {e[3] for e in bcx} == {"bfloat16"}
    else:
        assert not bcx
    assert len(products) == (layers if policy == "dots" else 0)
    if policy == "none":
        assert total(core) >= layers * tokens * 4 * D * 2
    else:
        assert not core
    assert saved_of("lfm2", policy)["all"] == saved_of("lfm2", "none")["kept"]


def _jaxprs(jaxpr):
    """``jaxpr`` and every jaxpr inside it."""
    yield jaxpr
    for eqn in jaxpr.eqns:
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _jaxprs(sub)


def _names_in_front_of_a_cast(jaxpr, names):
    """The ``checkpoint_name``s of ``names`` in ``jaxpr`` (and every jaxpr inside
    it) given to a float32 value that a cast to a narrower float follows."""
    found = []
    for inner in _jaxprs(jaxpr):
        for eqn in inner.eqns:
            if eqn.primitive.name != "name" or eqn.params["name"] not in names:
                continue
            value = eqn.outvars[0]
            if any(value in e.invars and e.primitive.name == "convert_element_type"
                   and jnp.issubdtype(e.params["new_dtype"], jnp.floating)
                   and jnp.dtype(e.params["new_dtype"]).itemsize < value.aval.dtype.itemsize
                   for e in inner.eqns):
                found.append(eqn.params["name"])
    return found


@pytest.mark.parametrize("which", ("routed", "kimi", "nemotron", "lfm2",
                                   "a name in front of its cast"))
def test_no_name_is_given_to_a_float32_in_front_of_its_cast(which):
    """A name keeps the value it is given: on the float32 product in front of
    ``.astype(bfloat16)`` it would hold twice the bytes the backward reads."""
    names = tinygpt.remat_kept_names()
    if which == "a name in front of its cast":  # what the walk is there to catch
        wrong = lambda x, w: checkpoint_name(x @ w, names[-1]).astype(jnp.bfloat16).sum()
        x = jnp.ones((4, 4), jnp.float32)
        assert _names_in_front_of_a_cast(jax.make_jaxpr(wrong)(x, x).jaxpr, names) == [names[-1]]
        return
    config = dataclasses.replace(
        {"routed": ROUTED, "kimi": KIMI, "nemotron": NEMOTRON, "lfm2": LFM2}[which],
        compute_dtype=jnp.bfloat16, remat="none")
    params = jax.eval_shape(lambda: tinygpt.init_params(config, jax.random.key(0)))
    batch = jax.ShapeDtypeStruct((1, SEQ), jnp.int32)
    jaxpr = jax.make_jaxpr(lambda p, b: tinygpt.loss_fn(config, p, b, b))(params, batch).jaxpr
    seen = {e.params["name"] for inner in _jaxprs(jaxpr) for e in inner.eqns
            if e.primitive.name == "name"}
    assert seen >= ({moe.MOE_GU, moe.ROUTER_LOGITS} | {
        "kimi": {kda_mixer.KDA_QKV, tinygpt.MLP_GU},
        "nemotron": {ssd_mixer.SSD_XBC, ssd_mixer.SSD_Z, common.SHARED_U},
        "lfm2": {conv_mixer.SCONV_BCX, tinygpt.MLP_GU}}.get(which, set()))
    assert _names_in_front_of_a_cast(jaxpr, names) == []


def test_it_works_from_shapes_and_once():
    """No array is made for the account, it traces when asked and not before,
    and a second call is the first one's result."""
    record, _ = compiled_step(DENSE, "dots")
    before = scopes.compile_events()["sums"]
    live = len(jax.live_arrays())
    with jax.transfer_guard("disallow"):
        first = record["saved"]()
    assert len(jax.live_arrays()) == live
    traced = scopes.compile_events()["sums"]
    assert sum(c for (e, _), (c, _) in traced.items() if e == scopes.TRACE_EVENT) > \
        sum(c for (e, _), (c, _) in before.items() if e == scopes.TRACE_EVENT)
    assert record["saved"]() is first
    assert scopes.compile_events()["sums"] == traced


def test_nothing_traces_unless_asked():
    """``create_train_state`` + ``aot_compile`` + three steps: jax traces what
    the step needs (the initialisers and the step) as often as it did before
    the account was there, and nothing for the account."""
    before = scopes.compile_events()["sums"]
    state = create_train_state(
        dataclasses.replace(DENSE, n_layer=2), dataclasses.replace(get_strategy("zero2"),
                                                                   remat="dots"),
        mesh_of(), seed=0, grad_accum=2, from_table=True, global_micro=BATCH, seq_len=SEQ)
    table = jnp.zeros((8, SEQ), jnp.int32)
    state.aot_compile(state.params, state.opt_state, table)
    params, opt_state = state.params, state.opt_state
    for step in range(3):
        params, opt_state, _ = state.step_fn(params, opt_state, table, step)
    after = scopes.compile_events()["sums"]
    new = {function: count - before.get((event, function), (0, 0))[0]
           for (event, function), (count, _) in after.items() if event == scopes.TRACE_EVENT}
    new = {function: count for function, count in new.items() if count}
    # the parent commit's counts (the same script on both trees, 1137 events each:
    # ``lower`` traces the step once and the first call once more); what else
    # is in ``new`` are jax's own helpers and the model's inner jits
    assert (new["train_step"], new["init_fn"], new["init_params"]) == (2, 4, 1)
    assert "pullback" not in new  # the account's one traced function
    record = scopes.step_memory()
    record["saved"]()
    asked = scopes.compile_events()["sums"]
    assert asked[scopes.TRACE_EVENT, "pullback"][0] == \
        after.get((scopes.TRACE_EVENT, "pullback"), (0, 0))[0] + 2  # kept and all


def test_the_compiled_classes_follow_the_newest_compile():
    record, compiled = compiled_step(DENSE, "dots")
    assert record["compiled"] == memory_anatomy.compile_memory_fields(compiled)
    assert set(record["compiled"]) == set(memory_anatomy.COMPILE_FIELDS)
    assert record["bytes_limit"] is None  # the CPU's allocator keeps no statistics
    newer, other = compiled_step(dataclasses.replace(DENSE, n_layer=1), "none")
    assert newer["compiled"] == memory_anatomy.compile_memory_fields(other)
    assert newer["compiled"] != record["compiled"]
    assert scopes.step_memory()["compiled"] == newer["compiled"]


def test_a_model_axis_reads_none(eight_devices):
    record, compiled = compiled_step(DENSE, "dots", mesh=mesh_of(data=2, model=2), strategy="fsdp")
    assert record["saved"] is None
    assert record["compiled"] == memory_anatomy.compile_memory_fields(compiled)


def test_four_chips_under_fsdp_read_a_quarter(eight_devices):
    """A data-only mesh of four under fsdp against one device at the same
    global micro-batch: entry by entry a quarter of the bytes at the same
    traced shape; what is computed from the weights alone is whole on both."""
    one = compiled_step(DENSE, "dots", strategy="fsdp")[0]["saved"]()
    four = compiled_step(DENSE, "dots", mesh=mesh_of(data=4), strategy="fsdp")[0]["saved"]()
    for which in ("kept", "all"):
        assert [e[:4] for e in four[which]] == [e[:4] for e in one[which]]
        assert [e[4] for e in four[which]] == [e[4] // 4 for e in one[which]]
        assert four["left_out"][which] == one["left_out"][which]
    assert abs(total(four["kept"]) - total(one["kept"]) / 4) <= len(one["kept"])


# What ``estimate_hbm``'s ``activations`` class (with its ``logits``: memory
# anatomy folds them in) reads over the account's ``kept`` at the tiny
# tinygpt-a shape, found by this test's first run and held since. The
# estimator states +-20 %: the policies outside it are named in
# docs/OBSERVABILITY.md ("The step's memory"); the estimator is not tuned here.
ESTIMATE_OVER_KEPT = {"none": 0.474, "dots": 0.903, "full_keep_kernels": 1.791, "full": 1.937}


@pytest.mark.parametrize("policy", POLICIES)
def test_the_analytic_activations_against_the_account(dense, policy):
    strategy = dataclasses.replace(get_strategy("zero2"), remat=policy)
    config = dataclasses.replace(DENSE, remat=policy, compute_dtype=jnp.bfloat16)
    est = memory.estimate_hbm(config, strategy, mesh_of(), BATCH, SEQ)
    ratio = (est.activations + est.logits) / total(dense[policy]["kept"])
    assert ratio == pytest.approx(ESTIMATE_OVER_KEPT[policy], rel=0.02)
    assert (abs(ratio - 1) <= 0.2) == (policy == "dots")
