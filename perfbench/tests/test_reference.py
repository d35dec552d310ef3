"""The plain reference against the program's own forward and gradient, at a
tiny width on the CPU, for both knob sets. The program runs in float32 with
its ``jnp`` attention here, so the two independent implementations of the same
mathematics have to agree to rounding."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench.harness import build, manifest, reference


@pytest.mark.parametrize("cell", ["tinygpt-a.seq2048", "mistral-7b.d2"])
def test_reference_agrees_with_the_program(cell):
    from distributed_llm_training_benchmark_framework_tpu.models import tinygpt

    import dataclasses

    _, workload, config = manifest.load_cell(cell)
    workload, config = build.tiny(workload, config)
    shape = build.model_shape(workload, config)
    cfg = dataclasses.replace(
        build.tinygpt_config(workload, config), attention_impl="reference",
        compute_dtype=jnp.float32,
    )
    params = tinygpt.init_params(cfg, jax.random.key(0))
    # Zero biases and unit scales would hide a misplaced bias or scale.
    leaves, tree = jax.tree.flatten(params)
    keys = jax.random.split(jax.random.key(1), len(leaves))
    params = jax.tree.unflatten(tree, [
        leaf + 0.02 * jax.random.normal(k, leaf.shape) for leaf, k in zip(leaves, keys)
    ])
    batch = jax.random.randint(jax.random.key(2), (2, workload["seq_len"]), 0, cfg.vocab_size)

    with jax.default_matmul_precision("highest"):
        want, g_want = jax.value_and_grad(lambda p: reference.loss(shape, p, batch))(params)
        got, g_got = jax.value_and_grad(lambda p: tinygpt.loss_fn(cfg, p, batch, batch))(params)
    assert float(got) == pytest.approx(float(want), rel=1e-5)
    for (path, a), b in zip(jax.tree.leaves_with_path(g_got), jax.tree.leaves(g_want)):
        np.testing.assert_allclose(a, b, rtol=2e-3, atol=2e-6, err_msg=str(path))


def test_reference_sees_a_dropped_mask():
    _, workload, config = manifest.load_cell("mistral-7b.d2")
    workload, config = build.tiny(workload, config)
    shape = build.model_shape(workload, config)
    from distributed_llm_training_benchmark_framework_tpu.models import tinygpt

    params = tinygpt.init_params(build.tinygpt_config(workload, config), jax.random.key(0))
    tokens = jax.random.randint(jax.random.key(2), (workload["seq_len"],), 0, 512)
    causal = reference.token_losses(shape, params, tokens)
    full = reference.token_losses({**shape, "causal": False}, params, tokens)
    # the last position of a causal model already sees everything
    assert float(jnp.std(causal - full)) > 1e-4
