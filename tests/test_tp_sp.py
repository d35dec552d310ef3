"""Tensor-parallel and sequence-parallel composition tests (8-dev CPU mesh).

Neither exists in the reference (SURVEY §2.3: TP/PP/SP all listed as future
work there); here they are first-class mesh axes that compose with the four
ZeRO-style arms. Correctness bar: the same seed/data must produce the same
loss trajectory whatever the mesh factorization — parallelism changes where
arrays live, never what is computed.
"""

import jax
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from distributed_llm_training_benchmark_framework_tpu.models.mixers import (
    attention as attention_mixer,
)
from distributed_llm_training_benchmark_framework_tpu.models import get_model_config
from distributed_llm_training_benchmark_framework_tpu.parallel import (
    make_mesh,
    get_strategy,
    param_partition_specs,
)
from distributed_llm_training_benchmark_framework_tpu.train import create_train_state
from distributed_llm_training_benchmark_framework_tpu.data import SyntheticDataset


def make_state(strategy, mesh_shape, attention="reference", grad_accum=1):
    cfg = get_model_config("S", 64, dropout=0.0, attention_impl=attention)
    mesh = make_mesh(mesh_shape, ("data", "seq", "model"), devices=jax.devices()[: int(np.prod(mesh_shape))])
    return create_train_state(cfg, get_strategy(strategy), mesh, seed=42, grad_accum=grad_accum)


def run_steps(state, n_steps, dp, grad_accum=1, seq=64):
    ds = SyntheticDataset(vocab_size=512, seq_len=seq, size=64)
    losses = []
    params, opt = state.params, state.opt_state
    for step in range(n_steps):
        batch = ds.batch_for_step(step, dp * 2 * grad_accum).reshape(grad_accum, dp * 2, seq)
        batch = jax.device_put(batch, state.batch_sharding)
        params, opt, loss = state.step_fn(params, opt, batch, step)
        losses.append(float(loss))
    return losses


def test_tp_param_layout(eight_devices):
    """Megatron layout: qkv column-parallel, wo row-parallel, vocab sharded."""
    state = make_state("ddp", (1, 1, 8))
    specs = state.param_specs
    assert tuple(specs["blocks"]["wqkv"]) == (None, None, None, "model")
    assert tuple(specs["blocks"]["wo"]) == (None, "model", None)
    assert tuple(specs["blocks"]["wfc"]) == (None, None, "model")
    assert tuple(specs["blocks"]["wproj"]) == (None, "model", None)
    assert tuple(specs["wte"]) == ("model", None)
    # LayerNorms replicated
    assert tuple(specs["blocks"]["ln1_scale"]) == (None, None)
    # Shards are real: each device holds 1/8 of wqkv.
    w = state.params["blocks"]["wqkv"]
    assert np.prod(w.sharding.shard_shape(w.shape)) == np.prod(w.shape) // 8


@pytest.mark.slow
def test_tp_matches_ddp_trajectory(eight_devices):
    base = run_steps(make_state("ddp", (4, 1, 1)), 3, dp=4)
    tp = run_steps(make_state("ddp", (4, 1, 2)), 3, dp=4)
    np.testing.assert_allclose(tp, base, rtol=2e-3)


@pytest.mark.slow
def test_fsdp_composes_with_tp(eight_devices):
    """2-D mesh: 'data' sharding lands on a different axis than 'model'."""
    state = make_state("fsdp", (4, 1, 2))
    specs = state.param_specs
    wfc = tuple(specs["blocks"]["wfc"])
    assert "model" in wfc and "data" in wfc and wfc.index("model") != wfc.index("data")
    base = run_steps(make_state("ddp", (4, 1, 1)), 3, dp=4)
    mixed = run_steps(state, 3, dp=4)
    np.testing.assert_allclose(mixed, base, rtol=2e-3)


@pytest.mark.slow
def test_sp_ring_matches_ddp_trajectory(eight_devices):
    base = run_steps(make_state("ddp", (2, 1, 1)), 3, dp=2)
    sp = run_steps(make_state("ddp", (2, 4, 1), attention="ring"), 3, dp=2)
    np.testing.assert_allclose(sp, base, rtol=5e-3)


@pytest.mark.slow
def test_dp_sp_tp_all_at_once(eight_devices):
    """The full 3-D mesh: 2-way data x 2-way sequence x 2-way tensor."""
    base = run_steps(make_state("zero2", (2, 1, 1)), 3, dp=2)
    full = run_steps(make_state("zero2", (2, 2, 2), attention="ring"), 3, dp=2)
    np.testing.assert_allclose(full, base, rtol=5e-3)


@pytest.mark.slow
def test_sp_ulysses_matches_ddp_trajectory(eight_devices):
    """All-to-all (Ulysses) sequence parallelism walks the same trajectory as
    plain ddp — same bar as the ring variant, different comm pattern."""
    base = run_steps(make_state("ddp", (2, 1, 1)), 3, dp=2)
    sp = run_steps(make_state("ddp", (2, 4, 1), attention="ulysses"), 3, dp=2)
    np.testing.assert_allclose(sp, base, rtol=5e-3)


@pytest.mark.slow
def test_dp_sp_ulysses_tp(eight_devices):
    """Ulysses composes with data + tensor parallelism (local heads H/tp
    must still divide the seq axis: tier S has 4 heads, tp=2 -> 2, sp=2 ok)."""
    base = run_steps(make_state("zero2", (2, 1, 1)), 3, dp=2)
    full = run_steps(make_state("zero2", (2, 2, 2), attention="ulysses"), 3, dp=2)
    np.testing.assert_allclose(full, base, rtol=5e-3)


@pytest.mark.parametrize("degree,kv_in_kernel", [(2, 2), (4, 8)], ids=["divides-kv-heads", "cuts-a-kv-heads-group"])
def test_flash_under_a_heads_axis_takes_kv_heads_whole_or_repeats_them(eight_devices, degree, kv_in_kernel):
    """8 query heads over 2 under 'model' = 2 and 4: where the degree divides
    the kv heads k and v ride the heads axis at their own count and a shard's
    kernels read its own kv head; where it does not they are repeated in front
    of the ``shard_map``, as the model repeated them. Either way the loss and
    the gradients are one device's."""
    import jax.numpy as jnp
    from jax.sharding import NamedSharding

    from distributed_llm_training_benchmark_framework_tpu.models import tinygpt

    cfg = tinygpt.TinyGPTConfig(
        vocab_size=64, n_embd=128, n_head=8, n_kv_head=2, n_layer=1, block_size=64, dropout=0.0,
        causal=True, norm="rmsnorm", pos_embed="rope", mlp_act="swiglu", mlp_hidden=96, bias=False,
        tie_embeddings=False, param_dtype=jnp.float32, compute_dtype=jnp.float32, attention_impl="flash")
    params = tinygpt.init_params(cfg, jax.random.key(0))
    idx = jax.random.randint(jax.random.key(1), (2, 64), 0, cfg.vocab_size)
    step = jax.jit(jax.value_and_grad(lambda p: tinygpt.loss_fn(cfg, p, idx, idx)))
    assert attention_mixer.attn_mask_stats(cfg, 64)["global"]["kv_heads_in_kernel"] == 2
    want_loss, want = step(params)
    mesh = make_mesh((1, 1, degree), ("data", "seq", "model"), devices=eight_devices[:degree])
    specs = param_partition_specs(params, mesh, shard=False, kv_heads=cfg.kv_heads)
    assert ("model" in tuple(specs["blocks"]["wkv"])) == (degree == 2)
    placed = jax.tree.map(lambda leaf, spec: jax.device_put(leaf, NamedSharding(mesh, spec)), params, specs)
    with jax.set_mesh(mesh):
        assert attention_mixer.attn_mask_stats(cfg, 64)["global"]["kv_heads_in_kernel"] == kv_in_kernel
        got_loss, got = step(placed)
    np.testing.assert_allclose(float(got_loss), float(want_loss), rtol=1e-5)
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(
            np.asarray(g), np.asarray(w), atol=1e-4 * float(jnp.abs(w).max()), rtol=1e-3,
            err_msg=jax.tree_util.keystr(path))


def test_world_size_not_divisible_raises():
    from distributed_llm_training_benchmark_framework_tpu.train.loop import run_benchmark
    from distributed_llm_training_benchmark_framework_tpu.parallel import get_strategy

    with pytest.raises(ValueError, match="not divisible"):
        run_benchmark(
            strategy=get_strategy("ddp"), tier="S", seq_len=64, steps=1,
            warmup_steps=0, per_device_batch=1, grad_accum=1, world_size=6,
            tensor_parallel=4,
        )


def test_sp_requires_ring():
    from distributed_llm_training_benchmark_framework_tpu.train.loop import run_benchmark
    from distributed_llm_training_benchmark_framework_tpu.parallel import get_strategy

    with pytest.raises(ValueError, match="ring"):
        run_benchmark(
            strategy=get_strategy("ddp"), tier="S", seq_len=64, steps=1,
            warmup_steps=0, per_device_batch=1, grad_accum=1, world_size=8,
            sequence_parallel=2,
        )
