"""The comparison that decides ``correct``.

Outside the timed window, at the seeded initial parameters and the step's
first micro-batch:

(a) the program's own forward (``tinygpt.forward``: dropout off, the cell's
    attention kernel, the cell's sharding, bfloat16 matmuls) against the
    plain float32 reference, position by position: the root-mean-square
    difference of the per-position losses, over the spread (standard
    deviation) of the reference's per-position losses. Dividing by the spread
    and not by the mean (about ln(vocab) for every position at
    initialization) is what makes the check see a dropped mask: the mean loss
    hides it (see TOLERANCE). The mean losses are compared as well;
(b) where the cell's file says the reference's gradient fits beside the
    state (``check_grads``), the global gradient norm and a seeded sample of
    leaves of ``jax.grad`` of both.

And over the window: every loss finite, the last sync window's mean loss
below the first's, and no compilation.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np

from . import reference

# Measured on the v5e at the published widths (tools/calibrate_correct.py and
# every run's "initial check" line; PERF.md section 6, PR 22). per_position:
# the program's bfloat16 matmuls put it 0.011-0.012 spreads from the reference
# in tinygpt-a (16 layers), 0.017 in mistral-7b at depth 2 and 0.033 at depth
# 8 (rounding adds up with depth; the reference itself run in bfloat16 passes
# is 0.007-0.015 away); a dropped or added causal mask is 0.57-1.04 away, a
# dropped rotary embedding 1.34. mean_loss: 8e-7 to 3e-5 measured; a wrong
# mask moves it by 4e-4 to 1e-3. Gradients: norm 3e-4 to 3e-3, leaves 1.3e-2
# to 2e-2 measured (bfloat16 backward against float32).
TOLERANCE = {"per_position": 0.1, "mean_loss": 2e-4, "grad_norm": 2e-2, "grad_leaf": 6e-2}
GRAD_LEAVES = 3


def token_losses(model_config, shape):
    """(params, batch) -> the program's and the reference's (B, S) per-position
    losses. The parameters are an argument: closed over, 3 GB of weights
    become constants of the program and set-up takes minutes."""
    from distributed_llm_training_benchmark_framework_tpu.models import tinygpt

    def both(params, batch):
        logits, _ = tinygpt.forward(model_config, params, batch)
        logp = jax.nn.log_softmax(logits, -1)
        got = -jnp.take_along_axis(logp, batch[..., None], -1)[..., 0]
        with jax.default_matmul_precision("highest"):
            want = jax.vmap(lambda t: reference.token_losses(shape, params, t))(batch)
        return got, want

    return both


def gradients(model_config, shape):
    """(params, batch) -> relative error of the program's global gradient norm
    against the reference's ``jax.grad``, and of every leaf (a vector: which
    leaves are held to the tolerance is drawn from the seed on the host, so
    that the program is the same in every run). The program's side runs its
    layers as a rematerialized scan here: same block, same kernels, one body
    to compile, and the activations of a long sequence fit beside the state."""
    import dataclasses

    from distributed_llm_training_benchmark_framework_tpu.models import tinygpt

    scanned = dataclasses.replace(model_config, scan_layers=True, remat="full")

    def compare(params, batch):
        got = jax.grad(lambda p: tinygpt.loss_fn(scanned, p, batch, batch))(params)
        with jax.default_matmul_precision("highest"):
            want = jax.grad(lambda p: reference.loss(shape, p, batch))(params)
        square = lambda tree: [jnp.sum(jnp.square(x.astype(jnp.float32)))
                               for x in jax.tree.leaves(tree)]
        norm_got, norm_want = jnp.sqrt(sum(square(got))), jnp.sqrt(sum(square(want)))
        difference = jax.tree.map(jnp.subtract, got, want)
        leaf_err = jnp.sqrt(jnp.stack(square(difference)) / jnp.stack(square(want)))
        return jnp.abs(norm_got - norm_want) / norm_want, leaf_err

    return compare


def first_micro_batch(state, tokens, workload):
    """Rows the step gathers for step 0, micro-batch 0, in the step's layout."""
    rows = workload["micro_batch_per_chip"] * workload["mesh"]["data"]
    sharding = jax.sharding.NamedSharding(
        state.mesh, jax.sharding.PartitionSpec(*state.batch_sharding.spec[1:])
    )
    return jax.device_put(tokens[:rows], sharding)


def check_initial(state, shape, batch, check_grads, seed):
    """-> (ok, numbers) for (a) and (b) above; two compiled programs at most."""
    import dataclasses

    from distributed_llm_training_benchmark_framework_tpu.train.step import (
        fsdp_block_param_spec,
    )

    # As the step does under fsdp: each block's weights gathered at their use.
    cfg = dataclasses.replace(state.model_config, block_param_spec=fsdp_block_param_spec(
        state.strategy, state.param_specs, pipelined=False))
    params = state.params
    with jax.set_mesh(state.mesh):
        got, want = jax.jit(token_losses(cfg, shape))(params, batch)
    want, got = np.asarray(want, np.float64), np.asarray(got, np.float64)
    numbers = {
        "loss_program": got.mean(),
        "loss_reference": want.mean(),
        "mean_loss_rel_err": abs(got.mean() - want.mean()) / abs(want.mean()),
        "per_position_err": math.sqrt(np.mean((got - want) ** 2)) / want.std(),
    }
    ok = (numbers["per_position_err"] <= TOLERANCE["per_position"]
          and numbers["mean_loss_rel_err"] <= TOLERANCE["mean_loss"])
    if check_grads:
        with jax.set_mesh(state.mesh):
            norm_err, leaf_err = jax.jit(gradients(cfg, shape))(params, batch)
        leaf_err = np.asarray(leaf_err)
        picks = np.random.default_rng(seed).choice(
            len(leaf_err), min(GRAD_LEAVES, len(leaf_err)), replace=False)
        numbers["grad_norm_rel_err"] = norm_err
        numbers["grad_leaf_rel_err"] = leaf_err[picks].max()
        ok = (ok and numbers["grad_norm_rel_err"] <= TOLERANCE["grad_norm"]
              and numbers["grad_leaf_rel_err"] <= TOLERANCE["grad_leaf"])
    return bool(ok), {k: float(v) for k, v in numbers.items()}


def check_window(losses, sync_every, compiles):
    """-> (ok, failed steps) for the losses of the timed window, in order."""
    failed = sum(not math.isfinite(x) for x in losses)
    first, last = losses[:sync_every], losses[-sync_every:]
    falling = sum(last) / len(last) < sum(first) / len(first)
    return failed == 0 and falling and compiles == 0, failed
