"""Remat ``dots`` and ``full_keep_kernels`` keep what is dear to make again and
cheap to hold, by name.

A Mosaic call is no ``dot_general``: under the plain dots-class policy the
kernel's ``out`` and ``lse`` (residuals of ``ops.flash_attention
._flash_fwd_rule``) were dropped and the whole O(S^2) kernel ran a second time
in the backward pass. They carry ``checkpoint_name``s
(``FLASH_RESIDUAL_NAMES``) and ``models.tinygpt._under_remat`` saves those
names beside the matmul results; since PR 50 the same list
(``tinygpt.remat_kept_names``) holds the routed experts' gate+up grouped
matmul's result, the router's logits, choice and plan, a KDA layer's q, k, v
projection and a dense SwiGLU layer's gate+up, since PR 52 an SSD layer's
x | B | C and z products and the up product of a shared expert that is not
gated, and since PR 55 a ``conv`` layer's B | C | x~ projection. These tests
count calls in the gradient's jaxpr (walking it: shared sub-jaxprs print once
in its text), hold both policies to ``none``'s loss and gradients, and hold the
list to its rule.
"""

import dataclasses
import functools
import re

import jax
import jax.ad_checkpoint
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from distributed_llm_training_benchmark_framework_tpu.models import common
from distributed_llm_training_benchmark_framework_tpu.models.mixers import (
    conv as conv_mixer,
    kda as kda_mixer,
    ssd as ssd_mixer,
)
from distributed_llm_training_benchmark_framework_tpu.models import (
    get_llama_config,
    get_model_config,
)
from distributed_llm_training_benchmark_framework_tpu.models import moe, tinygpt
from distributed_llm_training_benchmark_framework_tpu.models.tinygpt import (
    TinyGPTConfig,
    init_params,
    loss_fn,
)
from distributed_llm_training_benchmark_framework_tpu.ops.flash_attention import (
    FLASH_RESIDUAL_NAMES,
)
from distributed_llm_training_benchmark_framework_tpu.ops.kda import KDA_RESIDUAL_NAMES
from distributed_llm_training_benchmark_framework_tpu.ops.ssd import SSD_RESIDUAL_NAMES
from distributed_llm_training_benchmark_framework_tpu.parallel import (
    get_strategy,
    make_mesh,
)
from distributed_llm_training_benchmark_framework_tpu.train import create_train_state
from distributed_llm_training_benchmark_framework_tpu.utils import scopes
from perfbench.harness import build_kda, build_lfm2, build_nemotron
from test_deepseek import CONFIG as MLA_CONFIG
from test_kimi_linear import FILE as KIMI_FILE
from test_lfm2 import FILE as LFM2_FILE
from test_nemotron import FILE as NEMOTRON_FILE

SEQ, BATCH = 64, 2


CONFIGS = {
    # tier S: head dim 64, not causal, attention-probability dropout inside
    # the kernel.
    "tier-s-dropout": get_model_config("S", SEQ, dropout=0.1, attention_impl="flash"),
    # llama family: 2 query heads of 128 over 1 K/V head, causal.
    "gqa-d128-causal": get_llama_config(
        "S", SEQ, n_embd=256, n_head=2, n_kv_head=1, attention_impl="flash"
    ),
    # latent attention: keys 16 + 8 rotary over values of 16 (the cell's
    # 192 / 128 in small), a leading dense layer, held routed experts.
    "mla": MLA_CONFIG,
    # three KDA layers to a latent-attention one, a leading dense SwiGLU layer,
    # sigmoid routing over held experts: unequal stacks, run unrolled whatever
    # ``scan_layers`` says.
    "kda": dataclasses.replace(
        build_kda.kimi_config(dict(seq_len=SEQ, held_rows_factor=4.0, attention="flash",
                                   layer_loop="unrolled", kda_chunk=16), KIMI_FILE),
        compute_dtype=jnp.float32),
    # the Nemotron cell's nine blocks, each one sublayer alone: four Mamba-2 mixers,
    # four routed blocks of relu2 experts with a shared expert that is not gated,
    # one attention block; unrolled as ``kda``. Six scan heads (``tiny_nemotron``
    # has four), so that no two weight blocks of a mixer share a shape: z's is
    # (64, 96), x | B | C's (64, 160), out_proj's (96, 64).
    "ssd": dataclasses.replace(
        build_nemotron.nemotron_config(
            dict(seq_len=SEQ, held_rows_factor=4.0, attention="flash", layer_loop="unrolled"),
            {**NEMOTRON_FILE, "mamba_num_heads": 6}),
        compute_dtype=jnp.float32),
    # the LFM2 cell's five layers: a gated short convolution in front of a dense
    # SwiGLU layer, an attention layer at heads of 16 under per-head QK-norm and
    # rotary, three more convolutions, the last four in front of held experts
    # chosen by sigmoid score; unrolled as ``kda``.
    "conv": dataclasses.replace(
        build_lfm2.lfm2_config(
            dict(seq_len=SEQ, held_rows_factor=4.0, attention="flash", layer_loop="unrolled"),
            LFM2_FILE),
        compute_dtype=jnp.float32),
    # every expert on the chip, routing trained: the sort by expert and back.
    "dropless": TinyGPTConfig(
        vocab_size=128, n_embd=64, n_head=4, n_layer=2, block_size=SEQ, mlp_hidden=32,
        n_experts=8, expert_top_k=2, capacity_factor=None, norm_topk_prob=False,
        norm="rmsnorm", mlp_act="swiglu", pos_embed="rope", tie_embeddings=False, bias=False,
        dropout=0.0, router_aux_coef=0.01, router_z_coef=0.001, attention_impl="flash",
        compute_dtype=jnp.float32),
}
CASES = sorted(CONFIGS)
LOOPS = {"unrolled": False, "scan": True}
# (case, loop) a test runs: the ``kda``, ``ssd`` and ``conv`` stacks have one loop
RUNS = [(case, loop) for case in CASES for loop in sorted(LOOPS)
        if (case, loop) not in (("kda", "scan"), ("ssd", "scan"), ("conv", "scan"))]
KEEPING = ("dots", "full_keep_kernels")


def _config(case, loop, remat):
    return dataclasses.replace(CONFIGS[case], scan_layers=LOOPS[loop], remat=remat)


def _loss(config):
    """The training loss as the step differentiates it (live dropout keys
    where the config drops anything)."""
    if config.dropout > 0:
        key = jax.random.key(3)
        return lambda p, b: loss_fn(config, p, b, b, dropout_key=key, deterministic=False)
    return lambda p, b: loss_fn(config, p, b, b)


def _operands(config):
    params = init_params(config, jax.random.key(0))
    batch = jax.random.randint(jax.random.key(1), (BATCH, SEQ), 0, config.vocab_size)
    return params, batch


def _equations(jaxpr, rematted=False, times=1):
    """(equation, whether a ``jax.checkpoint``'s second run holds it, how often it
    runs: a scan's body ``length`` times) of ``jaxpr`` and every jaxpr inside it."""
    for eqn in jaxpr.eqns:
        yield eqn, rematted, times
        inner = times * eqn.params["length"] if eqn.primitive.name == "scan" else times
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _equations(sub, rematted or eqn.primitive.name == "remat2", inner)


def _kernel_runs(jaxpr, name):
    """How often the ``pallas_call`` called ``name`` runs in ``jaxpr``."""
    return sum(times for eqn, _, times in _equations(jaxpr)
               if eqn.primitive.name == "pallas_call" and eqn.params["name"] == name)


def _flash_layers(config):
    kinds = config.layer_types or ()
    return sum(config.halves(kind)[0] and kind not in (scopes.KDA, scopes.SSD, scopes.CONV)
               for kind in kinds) if kinds else config.n_layer


@pytest.mark.parametrize("remat, runs_a_layer", [
    ("none", 1), ("dots", 1), ("full_keep_kernels", 1), ("full", 2)])
@pytest.mark.parametrize("case, loop", RUNS)
def test_flash_forward_runs_a_layer_in_the_gradient(case, loop, remat, runs_a_layer):
    """``dots`` runs the forward kernel once a layer, as no remat does: its
    two results are saved by name; ``full_keep_kernels`` saves them and
    nothing else. ``full`` has no policy, so nothing is kept by name and the
    kernel still runs again in the backward pass."""
    config = _config(case, loop, remat)
    params, batch = _operands(config)
    jaxpr = jax.make_jaxpr(jax.grad(_loss(config)))(params, batch).jaxpr
    assert _kernel_runs(jaxpr, "flash_fwd") == runs_a_layer * _flash_layers(config)


@functools.lru_cache(maxsize=None)
def _loss_and_gradients(case, loop, remat):
    config = _config(case, loop, remat)
    return jax.value_and_grad(_loss(config))(*_operands(config))


@pytest.mark.parametrize("remat", KEEPING)
@pytest.mark.parametrize("case, loop", RUNS)
def test_a_keeping_policy_matches_no_remat_through_the_saved_results(case, loop, remat):
    """Same limits as ``test_model.py::test_remat_matches_no_remat``: the
    backward reads the named values where it used to make them again."""
    l_plain, g_plain = _loss_and_gradients(case, loop, "none")
    l_kept, g_kept = _loss_and_gradients(case, loop, remat)
    assert np.allclose(float(l_plain), float(l_kept), rtol=1e-5)
    assert jax.tree.structure(g_plain) == jax.tree.structure(g_kept)
    for a, b in zip(jax.tree.leaves(g_plain), jax.tree.leaves(g_kept)):
        a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
        assert np.linalg.norm(a - b) / (np.linalg.norm(a) + 1e-12) < 1e-2


@functools.lru_cache(maxsize=None)  # three products of one case read one trace
def _gradient_equations(case, loop, remat):
    config = _config(case, loop, remat)
    jaxpr = jax.make_jaxpr(jax.grad(_loss(config)))(*_operands(config)).jaxpr
    return config, list(_equations(jaxpr))


@functools.lru_cache(maxsize=None)
def _routed_counts(case, loop, remat):
    """(megablox's ``gmm`` jits in the gradient, forward and backward alike; the
    ``sort``s and the ``top_k``s a ``jax.checkpoint``'s second run holds)."""
    _, equations = _gradient_equations(case, loop, remat)
    gmms = sum(times for eqn, _, times in equations
               if eqn.primitive.name == "jit" and eqn.params["name"] == "gmm")
    again = {name: sum(times for eqn, rematted, times in equations
                       if rematted and eqn.primitive.name == name) for name in ("sort", "top_k")}
    return gmms, again


# grouped matmuls a routed layer's second run adds to what no remat runs (until PR 50
# gate+up's, one more): where the routing trains, the gates' gradient reads the experts'
# output, which stays dropped, so the down matmul runs again
GMMS_AGAIN = {"mla": 0, "kda": 0, "ssd": 0, "conv": 0, "dropless": 1}


@pytest.mark.parametrize("remat", KEEPING + ("full",))
@pytest.mark.parametrize("case, loop", [run for run in RUNS if run[0] in GMMS_AGAIN])
def test_the_routed_layers_second_run_holds_no_gate_up_matmul_and_no_sort(case, loop, remat):
    """``gu`` by name: the gradient runs gate+up's ``gmm`` once, as no remat does.
    The router's choice and the plan by name: no ``sort`` and no ``top_k`` runs
    twice (but the one ``top_k`` whose own JVP reads its indices, where the gates
    are its values and the routing trains: no cell's). ``full`` names nothing
    and shows what the names took away."""
    layers = _config(case, loop, remat).n_moe_layers
    gmms, again = _routed_counts(case, loop, remat)
    kept = _routed_counts(case, loop, "none")[0] + GMMS_AGAIN[case] * layers
    if remat == "full":
        assert gmms > kept and again["sort"] > 0 and again["top_k"] > 0
    else:
        assert gmms == kept
        assert again == {"sort": 0, "top_k": layers if case == "dropless" else 0}


# a named product: (the case that makes it, the kind of layer that multiplies by its
# weight block, the block's shape from the case's config)
PRODUCTS = {
    "kda_q_k_v": ("kda", scopes.KDA, lambda c: (c.n_embd, 3 * c.kda_heads * c.kda_head_dim)),
    "ssd_x_b_c": ("ssd", scopes.SSD, lambda c: (c.n_embd, c.ssd_xbc)),
    "ssd_z": ("ssd", scopes.SSD, lambda c: (c.n_embd, c.ssd_inner)),
    "shared_up": ("ssd", scopes.MLP, lambda c: (c.n_embd, c.shared_dim)),
    "sconv_b_c_x": ("conv", scopes.CONV, lambda c: (c.n_embd, 3 * c.n_embd)),
}


@pytest.mark.parametrize("remat, reads", [("dots", 1), ("full_keep_kernels", 1), ("full", 2)])
@pytest.mark.parametrize("product", sorted(PRODUCTS))
def test_a_named_product_runs_once(product, remat, reads):
    """A layer's second run multiplies by a named product's weight block once, for
    its input's gradient: the product itself is kept (``dots``: as the
    ``dot_general``'s result; ``full_keep_kernels``: its cast by name:
    ``tinygpt.MATMUL_CAST_NAMES``). ``full`` keeps nothing and multiplies twice."""
    case, kind, block = PRODUCTS[product]
    config, equations = _gradient_equations(case, "unrolled", remat)
    weight = block(config)
    products = sum(times for eqn, rematted, times in equations
                   if rematted and eqn.primitive.name == "dot_general"
                   and weight in [tuple(v.aval.shape) for v in eqn.invars])
    assert products == reads * config.layer_types.count(kind)


def test_the_list_is_one_and_names_what_the_rule_allows():
    """``_under_remat``'s rule: a value is named only if its second run costs at
    least 5 ms a step per GB it holds in the benchmark cell where it is largest.
    The readings are PERF.md's (section 5, "Memory by scope", my chip runs, PRs
    50, 52 and 54): a new name comes with its own, and one that reads under 5 goes.
    The rule's second clause (1.5 GB of HBM free in that cell with the name kept) is
    read on the compiled step, ``hbm_headroom_gb``: no test here can hold it."""
    ms_a_gb = {moe.MOE_GU: 6.6,  # sdar-30b-a3b.share8-bd8192: 4.95 ms for 0.755 GB
               moe.ROUTER_LOGITS: 1000.0, moe.ROUTER_CHOICE: 1000.0,  # kimi: 8.78 ms, 17 MB
               moe.MOE_PLAN: 1000.0,  # mellum2: 7.5 ms with combine's backward, 3 MB
               kda_mixer.KDA_QKV: 12.3,  # kimi: 19.86 ms for 1.611 GB
               tinygpt.MLP_GU: 11.1,  # kimi: 6.73 ms for 0.604 GB
               # nemotron-3-nano-30b-a3b.share16-seq16384, the parent traced (my chip run, PR
               # 52), four blocks each:
               ssd_mixer.SSD_XBC: 14.9,  # 12.02 ms (4 x 3.005) for 0.805 GB
               ssd_mixer.SSD_Z: 15.4,  # 8.28 ms (in_proj's 20.33 less x | B | C's and dt's) for 0.537 GB
               common.SHARED_U: 14.5,  # 7.05 ms (4 x 1.763) for 0.487 GB
               # lfm2-8b-a1b.share4-seq16384, the parent traced (my chip run, PR 54, seed
               # 5400000101: scope ``sconv_in`` under remat), four layers:
               conv_mixer.SCONV_BCX: 10.9}  # 17.6 ms (4 x 4.4) for 1.611 GB
    names = tinygpt.remat_kept_names()
    assert len(set(names)) == len(names)
    assert set(names) == {*FLASH_RESIDUAL_NAMES, *KDA_RESIDUAL_NAMES, *SSD_RESIDUAL_NAMES, *ms_a_gb}
    assert min(ms_a_gb.values()) >= 5.0


def test_dots_saves_the_shard_mapped_calls_results_on_their_shards(eight_devices, capsys):
    """fsdp over ``data`` = 4: the kernel's call is inside a shard_map inside
    the checkpointed block. The policy reaches the two names in there, and
    they leave it as per-shard outputs: one ``out`` and one ``lse`` a layer
    among the saved residuals, each the shards' (B / 4 x H, S, ...) side by
    side, and no collective of the compiled gradient moves an array of
    ``out``'s shape."""
    n_dev, batch_size = 4, 4
    config = dataclasses.replace(
        get_model_config("S", SEQ, dropout=0.0, attention_impl="flash"),
        remat="dots", scan_layers=False,
    )
    mesh = make_mesh((n_dev,), ("data",), devices=eight_devices[:n_dev])
    state = create_train_state(config, get_strategy("fsdp"), mesh, seed=0)
    batch = jax.device_put(
        jnp.zeros((batch_size, SEQ), jnp.int32), NamedSharding(mesh, P("data"))
    )
    heads, width = config.n_head, config.n_embd // config.n_head
    rows = batch_size * heads

    with jax.sharding.set_mesh(mesh):
        jax.ad_checkpoint.print_saved_residuals(_loss(config), state.params, batch)
        hlo = jax.jit(jax.grad(_loss(config))).lower(state.params, batch).compile().as_text()

    saved = capsys.readouterr().out.splitlines()
    from_flash = sorted(
        re.search(r"\[([\d,]+)\]", line).group(1) for line in saved if "flash_attention" in line
    )
    assert from_flash == sorted(
        [f"{rows},{SEQ},{width}", f"{rows},{SEQ}"] * config.n_layer
    ), saved

    # (BH, S, D) inside the call, (B, S, H, D) around it; whole and a shard's.
    out_like = {
        dims
        for b in (batch_size, batch_size // n_dev)
        for dims in ((b * heads, SEQ, width), (b, SEQ, heads, width), (b, heads, SEQ, width))
    }
    collectives = re.findall(
        r"= (.*?) (?:all-gather|all-reduce|all-to-all|collective-permute|reduce-scatter)"
        r"(?:-start)?\(", hlo,
    )
    assert collectives  # fsdp gathers its weights and reduces their gradients
    for result in collectives:
        for dims in re.findall(r"\[([\d,]+)\]", result):
            assert tuple(map(int, dims.split(","))) not in out_like, result
