"""Remat ``dots`` keeps the flash forward kernel's two results.

A Mosaic call is no ``dot_general``: under the plain dots-class policy the
kernel's ``out`` and ``lse`` (residuals of ``ops.flash_attention
._flash_fwd_rule``) were dropped and the whole O(S^2) kernel ran a second time
in the backward pass. They carry ``checkpoint_name``s now
(``FLASH_RESIDUAL_NAMES``) and ``models.tinygpt.apply_blocks`` saves those
names beside the matmul results. These tests count the kernel's calls in the
gradient's jaxpr (walking it: shared sub-jaxprs print once in its text) and
hold ``dots`` to ``none``'s loss and gradients.
"""

import dataclasses
import re

import jax
import jax.ad_checkpoint
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from distributed_llm_training_benchmark_framework_tpu.models import (
    get_llama_config,
    get_model_config,
)
from distributed_llm_training_benchmark_framework_tpu.models.tinygpt import (
    init_params,
    loss_fn,
)
from distributed_llm_training_benchmark_framework_tpu.parallel import (
    get_strategy,
    make_mesh,
)
from distributed_llm_training_benchmark_framework_tpu.train import create_train_state
from test_deepseek import CONFIG as MLA_CONFIG

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
}
CASES = sorted(CONFIGS)
LOOPS = {"unrolled": False, "scan": True}


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


def _kernel_runs(jaxpr, name, times=1):
    """How often the ``pallas_call`` called ``name`` runs in ``jaxpr``: every
    sub-jaxpr is walked, a scan's body counted ``length`` times."""
    runs = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call" and eqn.params["name"] == name:
            runs += times
        inner = times * eqn.params["length"] if eqn.primitive.name == "scan" else times
        for sub in jax.core.jaxprs_in_params(eqn.params):
            runs += _kernel_runs(sub, name, inner)
    return runs


@pytest.mark.parametrize("remat, runs_a_layer", [
    ("none", 1), ("dots", 1), ("full_keep_kernels", 1), ("full", 2)])
@pytest.mark.parametrize("loop", sorted(LOOPS))
@pytest.mark.parametrize("case", CASES)
def test_flash_forward_runs_a_layer_in_the_gradient(case, loop, remat, runs_a_layer):
    """``dots`` runs the forward kernel once a layer, as no remat does: its
    two results are saved by name; ``full_keep_kernels`` saves them and
    nothing else. ``full`` has no policy, so nothing is kept by name and the
    kernel still runs again in the backward pass."""
    config = _config(case, loop, remat)
    params, batch = _operands(config)
    jaxpr = jax.make_jaxpr(jax.grad(_loss(config)))(params, batch).jaxpr
    assert _kernel_runs(jaxpr, "flash_fwd") == runs_a_layer * config.n_layer


@pytest.mark.parametrize("loop", sorted(LOOPS))
@pytest.mark.parametrize("case", CASES)
def test_dots_matches_no_remat_through_the_saved_results(case, loop):
    """Same limits as ``test_model.py::test_remat_matches_no_remat``: the
    backward reads the kernel's results where it used to recompute them."""
    plain, dots = _config(case, loop, "none"), _config(case, loop, "dots")
    params, batch = _operands(plain)
    l_plain, g_plain = jax.value_and_grad(_loss(plain))(params, batch)
    l_dots, g_dots = jax.value_and_grad(_loss(dots))(params, batch)
    assert np.allclose(float(l_plain), float(l_dots), rtol=1e-5)
    for a, b in zip(jax.tree.leaves(g_plain), jax.tree.leaves(g_dots)):
        a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
        assert np.linalg.norm(a - b) / (np.linalg.norm(a) + 1e-12) < 1e-2


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
