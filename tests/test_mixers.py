"""``models/mixers/``: the table from a kind of layer to the module that mixes
it, and what was pinned on the parent commit before anything moved there (PR
56): the forward FLOPs a token, ``estimate_hbm``'s numbers under each remat
policy, ``remat_kept_names`` and a digest of ``init_params``' leaves (the seeds'
contract: the compiled step does not contain the init, so no hash of a lowered
step guards it), for a tiny config of each of the ten configurations' layer
patterns. Cheap cases only: no step is compiled and nothing is at cell size.
"""

import dataclasses
import hashlib

import jax
import numpy as np
import pytest

from distributed_llm_training_benchmark_framework_tpu.models import mixers, tinygpt
from distributed_llm_training_benchmark_framework_tpu.models.tinygpt import (
    REMAT_POLICIES,
    BlockDiffusionObjective,
    Rotary,
    TinyGPTConfig,
    YarnScaling,
)
from distributed_llm_training_benchmark_framework_tpu.parallel import get_strategy, make_mesh
from distributed_llm_training_benchmark_framework_tpu.utils import flops, memory
from distributed_llm_training_benchmark_framework_tpu.utils.scopes import (
    CONV,
    GLOBAL,
    KDA,
    MLP,
    SSD,
    WINDOW,
)

_SMALL = dict(vocab_size=128, n_embd=64, n_head=4, block_size=64, dropout=0.0)
_LLAMA = dict(_SMALL, norm="rmsnorm", pos_embed="rope", mlp_act="swiglu", mlp_hidden=32, bias=False,
              tie_embeddings=False, scan_layers=False)
_ROUTED = dict(_LLAMA, causal=True, n_experts=16, expert_top_k=3, capacity_factor=None,
               experts_held=(4, 4), held_rows_factor=4.0)
_LATENT = dict(kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16)

# A tiny config of each of the ten configurations' layer patterns (BENCHMARK.json's ``configs``).
TEN = {
    "tinygpt-a": TinyGPTConfig(vocab_size=128, n_embd=64, n_head=4, n_layer=2, block_size=64),
    "mistral-7b": TinyGPTConfig(**{**_LLAMA, "scan_layers": True}, n_layer=2, causal=True, n_kv_head=2),
    "olmoe-1b-7b": TinyGPTConfig(**{**_LLAMA, "scan_layers": True}, n_layer=2, causal=True, n_experts=8,
                                 expert_top_k=2, capacity_factor=None, qk_norm=True,
                                 norm_topk_prob=False, router_z_coef=0.001),
    "deepseek-v2-lite": TinyGPTConfig(
        **{**_ROUTED, "experts_held": (2, 4), "n_experts": 8}, n_layer=3, **_LATENT,
        rope_scaling=YarnScaling(40, 32, 32, 1, 0.707, 0.707), first_k_dense=1,
        dense_mlp_hidden=96, n_shared_experts=2, seq_aux=True, norm_topk_prob=False),
    "sdar-30b-a3b": TinyGPTConfig(
        **{**_ROUTED, "causal": False, "experts_held": (4, 2)}, n_layer=2, n_kv_head=2, head_width=32,
        qk_norm="head", block_diffusion=BlockDiffusionObjective(block=4, mask_id=127)),
    "mellum2-12b-a2.5b": TinyGPTConfig(
        **{**_ROUTED, "experts_held": (4, 8)}, n_layer=4, n_kv_head=2, head_width=32, qk_norm="head",
        layer_types=(WINDOW, WINDOW, WINDOW, GLOBAL), sliding_window=16,
        layer_rotary=((GLOBAL, Rotary(10000.0, YarnScaling(4.0, 32))),)),
    "kimi-linear-48b-a3b": TinyGPTConfig(
        **_ROUTED, n_layer=5, **_LATENT, mla_nope=True, first_k_dense=1, dense_mlp_hidden=96,
        n_shared_experts=1, layer_types=(KDA, KDA, KDA, GLOBAL, KDA), kda_heads=4, kda_head_dim=16,
        kda_chunk=16, router_score="sigmoid", routed_scaling_factor=2.446),
    "laguna-xs.2": TinyGPTConfig(
        **{**_ROUTED, "n_head": 6, "expert_top_k": 4}, n_layer=5, n_kv_head=2, head_width=16,
        first_k_dense=1, dense_mlp_hidden=128, n_shared_experts=1,
        layer_types=(GLOBAL, WINDOW, WINDOW, WINDOW, GLOBAL), sliding_window=8,
        layer_rotary=((GLOBAL, Rotary(500000.0, YarnScaling(4.0, 32, 64.0), rotary_dim=8)),),
        layer_heads=((WINDOW, 8),), attn_gate=True, router_score="sigmoid",
        routed_scaling_factor=2.5),
    "nemotron-3-nano-30b-a3b": TinyGPTConfig(
        **{**_ROUTED, "pos_embed": "none", "mlp_act": "relu2", "block_size": 32}, n_layer=9,
        n_kv_head=2, head_width=16, n_shared_experts=1, shared_expert_hidden=48,
        layer_types=(SSD, MLP, SSD, MLP, SSD, GLOBAL, MLP, SSD, MLP), ssd_heads=4, ssd_head_dim=16,
        ssd_groups=2, ssd_state=16, ssd_chunk=16, block_halves=True, router_score="sigmoid",
        routed_scaling_factor=2.5),
    "lfm2-8b-a1b": TinyGPTConfig(
        **{**_ROUTED, "tie_embeddings": True, "block_size": 32}, n_layer=5, n_kv_head=2,
        qk_norm="head", first_k_dense=1, dense_mlp_hidden=96,
        layer_types=(CONV, GLOBAL, CONV, CONV, CONV), router_score="sigmoid"),
}

# Taken on the parent commit (b410d93, PR 55's tree) with the configs above, before any code moved:
# ``utils.flops.forward_flops_per_token(config)``
FLOPS = {
    "tinygpt-a": 245760.0, "mistral-7b": 106496.0, "olmoe-1b-7b": 149504.0,
    "deepseek-v2-lite": 273408.0, "sdar-30b-a3b": 309248.0, "mellum2-12b-a2.5b": 332992.0,
    "kimi-linear-48b-a3b": 431786.6666666667, "laguna-xs.2": 401248.0,
    "nemotron-3-nano-30b-a3b": 292864.0, "lfm2-8b-a1b": 259584.0,
}
# ``estimate_hbm(replace(config, remat=policy), zero2, one device, per_device_batch=2,
# seq_len=config.block_size)``: ``activations`` by policy in ``REMAT_POLICIES``' order, then
# (params, grads, opt_state, logits), which no policy moves
ACTIVATIONS = {
    "tinygpt-a": (983040, 851968, 589824, 557056), "mistral-7b": (884736, 802816, 573440, 507904),
    "olmoe-1b-7b": (884736, 876544, 614400, 507904),
    "deepseek-v2-lite": (1327104, 1081344, 737280, 540672),
    "sdar-30b-a3b": (2818048, 2260992, 1736704, 1540096),
    "mellum2-12b-a2.5b": (1769472, 1359872, 835584, 573440),
    "kimi-linear-48b-a3b": (2678784, 1671168, 1261568, 606208),
    "laguna-xs.2": (3522560, 1867776, 1277952, 868352),
    "nemotron-3-nano-30b-a3b": (1601536, 1077248, 610304, 299008),
    "lfm2-8b-a1b": (909312, 704512, 499712, 237568),
}
STATE = {
    "tinygpt-a": (449536, 449536, 899080, 131072), "mistral-7b": (214272, 214272, 428552, 131072),
    "olmoe-1b-7b": (596224, 596224, 1192456, 131072),
    "deepseek-v2-lite": (643200, 643200, 1286408, 131072),
    "sdar-30b-a3b": (370432, 370432, 740872, 131072),
    "mellum2-12b-a2.5b": (1264896, 1264896, 2529800, 131072),
    "kimi-linear-48b-a3b": (1063360, 1063360, 2126728, 131072),
    "laguna-xs.2": (1060864, 1060864, 2121736, 131072),
    "nemotron-3-nano-30b-a3b": (772032, 772032, 1544072, 65536),
    "lfm2-8b-a1b": (833664, 833664, 1667336, 65536),
}
# ``init_digest(config)``: (sha256 over the leaves by path, their count)
INIT = {
    "tinygpt-a": ("aed943d70762b3b2", 16), "mistral-7b": ("7477e84371659f54", 10),
    "olmoe-1b-7b": ("75a956106ec04f38", 12), "deepseek-v2-lite": ("c9d9ce7ad851ac89", 24),
    "sdar-30b-a3b": ("87e9127dd8024d15", 13), "mellum2-12b-a2.5b": ("711c2f464f7205cc", 13),
    "kimi-linear-48b-a3b": ("95eeafe3cae2c63d", 50), "laguna-xs.2": ("d867c3d156fcd255", 35),
    "nemotron-3-nano-30b-a3b": ("a713e4d1e82f93a8", 23), "lfm2-8b-a1b": ("2615349bbe085769", 29),
}
KEPT = ("flash_out", "flash_lse", "kda_out", "kda_states", "ssd_out", "ssd_states", "moe_gu",
        "router_logits", "router_choice", "moe_plan", "kda_qkv", "mlp_gu", "ssd_xbc", "ssd_z",
        "shared_u", "sconv_bcx")


def init_digest(config):
    """(the first 16 hex digits of sha256 over every leaf of ``init_params(config,
    PRNGKey(0))``: its path, dtype, shape and bytes, in the tree's order; the leaves' count)."""
    leaves = jax.tree_util.tree_leaves_with_path(
        tinygpt.init_params(config, jax.random.PRNGKey(0)))
    digest = hashlib.sha256()
    for path, leaf in leaves:
        array = np.asarray(leaf)
        for part in (jax.tree_util.keystr(path), str(array.dtype), str(array.shape)):
            digest.update(part.encode())
        digest.update(array.tobytes())
    return digest.hexdigest()[:16], len(leaves)


def test_the_table_covers_the_layer_kinds_less_mlp():
    assert set(mixers.MIXERS) == set(tinygpt.LAYER_KINDS) - {MLP}
    assert mixers.of(None) is mixers.attention is mixers.of(GLOBAL) is mixers.of(WINDOW)
    assert set(mixers.attention.KINDS) == {k for k, m in mixers.MIXERS.items() if m is mixers.attention}
    assert mixers.MODULES == (mixers.attention, mixers.kda, mixers.ssd, mixers.conv)
    # the stacks by a mixer's name, in the order ``init_params`` draws them
    assert mixers.STACKS == ("blocks", "dense_blocks", "kda_blocks", "kda_dense_blocks",
                             "conv_dense_blocks", "conv_blocks")
    assert [mixers.stack_name(kind, dense) for kind in (None, KDA, CONV) for dense in (False, True)] == [
        "blocks", "dense_blocks", "kda_blocks", "kda_dense_blocks", "conv_blocks", "conv_dense_blocks"]
    assert not mixers.own_leaves(None) and not mixers.own_leaves((GLOBAL, WINDOW, MLP))
    assert all(mixers.own_leaves((GLOBAL, kind)) for kind in (KDA, SSD, CONV))
    for module in mixers.MODULES:  # the same few names in every module
        for name in ("NEEDS", "check", "STACKS", "leaves", "AXIS_RULES", "sublayer", "RESIDUAL_NAMES",
                     "CAST_NAMES", "forward_flops_per_token", "kept_bytes"):
            assert hasattr(module, name), (module.__name__, name)


# For each mixer, (config, kind) pairs whose stacks make, between them, every leaf it can make.
_ATTENTION_VARIANTS = (
    (TEN["tinygpt-a"], None),  # fused qkv with biases, layernorm
    (dataclasses.replace(TEN["mistral-7b"], bias=True, mlp_act="gelu"), None),  # split q | kv with biases
    (TEN["olmoe-1b-7b"], None), (TEN["deepseek-v2-lite"], None), (TEN["laguna-xs.2"], WINDOW),
    (TEN["nemotron-3-nano-30b-a3b"], GLOBAL),
)
MAKES = {
    "attention": _ATTENTION_VARIANTS,
    "kda": ((TEN["kimi-linear-48b-a3b"], KDA),),
    "ssd": ((TEN["nemotron-3-nano-30b-a3b"], SSD),),
    "conv": ((TEN["lfm2-8b-a1b"], CONV),),
}
NORMS = {"ln1_scale", "ln1_bias", "ln2_scale", "ln2_bias"}  # tinygpt's rules: every stack has them


@pytest.mark.parametrize("name", sorted(MAKES))
def test_every_leaf_a_mixer_makes_has_a_rule_and_every_rule_a_leaf(name):
    module = getattr(mixers, name)
    made = set()
    for config, kind in MAKES[name]:
        assert mixers.of(kind) is module
        leaves = jax.eval_shape(  # shapes only: nothing is drawn
            lambda key: module.leaves(config, iter(jax.random.split(key, 16)), 2, kind),
            jax.random.PRNGKey(0))
        assert all(leaf.shape[0] == 2 for leaf in leaves.values())
        made |= set(leaves) - NORMS
    assert {f"blocks/{leaf}" for leaf in made} == set(module.AXIS_RULES)
    for leaf, axes in module.AXIS_RULES.items():
        assert tinygpt.PARAM_AXIS_RULES[leaf] == axes and axes[0] == "layers"


_PLAIN = dict(_LLAMA, causal=True, n_layer=2)
REFUSES = {
    KDA: TinyGPTConfig(**_PLAIN, layer_types=(KDA, GLOBAL), kda_heads=2, kda_head_dim=16, kda_chunk=16),
    SSD: TinyGPTConfig(**_PLAIN, layer_types=(SSD, MLP), ssd_heads=4, ssd_head_dim=16, ssd_groups=2,
                       ssd_state=16, ssd_chunk=16, block_halves=True),
    CONV: TinyGPTConfig(**_PLAIN, layer_types=(CONV, GLOBAL)),
}


@pytest.mark.parametrize("kind, change, match", [
    (KDA, dict(kda_heads=0), "a 'kda' layer needs kda_heads"),
    (KDA, dict(kda_head_dim=0), "kda_head_dim"),
    (KDA, dict(kda_conv=0), "kda_conv >= 1"),
    (KDA, dict(kda_chunk=1), "kda_chunk >= 2"),
    (SSD, dict(ssd_heads=0), "an 'ssd' layer needs ssd_heads"),
    (SSD, dict(ssd_head_dim=0), "ssd_head_dim"),
    (SSD, dict(ssd_state=0), "ssd_state"),
    (SSD, dict(ssd_groups=3), "ssd_groups dividing ssd_heads"),
    (SSD, dict(ssd_conv=0), "ssd_conv >= 1"),
    (SSD, dict(ssd_chunk=0), "ssd_chunk >= 1"),
    (SSD, dict(block_halves=False), "block_halves=True"),
    (CONV, dict(conv_taps=0), "a 'conv' layer .* needs conv_taps >= 1"),
    (CONV, dict(layer_types=(CONV, MLP), block_halves=True), "no block_halves"),
])
def test_a_mixer_refuses_what_its_own_fields_do_not_give(kind, change, match):
    assert mixers.of(kind).check(REFUSES[kind])
    with pytest.raises(ValueError, match=match):
        dataclasses.replace(REFUSES[kind], **change)


@pytest.mark.parametrize("kind", sorted(REFUSES))
def test_every_mixer_but_attention_refuses_by_the_one_shared_clause(kind):
    lead = mixers.of(kind).NEEDS[:12]  # "a 'kda' layer", ...
    for change, clause in ((dict(norm="layernorm"), "norm='rmsnorm'"), (dict(bias=True), "bias=False"),
                           (dict(dropout=0.1), "no dropout"),
                           (dict(tp_collective_matmul=True), "no tp_collective_matmul"),
                           (dict(scan_layers=True), "scanned loop is refused")):
        with pytest.raises(ValueError, match=f"{lead}.*{clause}"):
            dataclasses.replace(REFUSES[kind], **change)
    # attention's kinds have no such clause: the same fields pass
    plain = dataclasses.replace(REFUSES[KDA], layer_types=(GLOBAL, GLOBAL), kda_heads=0)
    assert dataclasses.replace(plain, scan_layers=True, dropout=0.1, bias=True, norm="layernorm")


@pytest.mark.parametrize("name", sorted(TEN))
def test_forward_flops_are_the_parents_to_the_last_digit(name):
    assert flops.forward_flops_per_token(TEN[name]) == FLOPS[name]


@pytest.mark.parametrize("remat", REMAT_POLICIES)
@pytest.mark.parametrize("name", sorted(TEN))
def test_the_memory_estimate_is_the_parents_under_every_policy(name, remat):
    config = dataclasses.replace(TEN[name], remat=remat)
    mesh = make_mesh((1,), ("data",), devices=jax.devices()[:1])
    got = memory.estimate_hbm(config, get_strategy("zero2"), mesh, 2, config.block_size)
    assert got.activations == ACTIVATIONS[name][REMAT_POLICIES.index(remat)]
    assert (got.params, got.grads, got.opt_state, got.logits) == STATE[name]


def test_the_kept_names_are_the_parents_tuple_in_order():
    assert tinygpt.remat_kept_names() == KEPT
    assert tinygpt.MATMUL_CAST_NAMES == KEPT[-6:] and mixers.RESIDUAL_NAMES == KEPT[:6]
    for module in mixers.MODULES:
        assert set(module.CAST_NAMES) <= set(tinygpt.MATMUL_CAST_NAMES)


@pytest.mark.parametrize("name", sorted(TEN))
def test_init_params_draws_what_the_parent_drew(name):
    """The seeds' contract: the same leaves, by path, from the same key splits in the same order."""
    assert init_digest(TEN[name]) == INIT[name]
