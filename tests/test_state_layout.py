"""The optimizer runs in the layout the training state lives in
(``train/step.py::_in_the_layouts_the_state_lives_in``: every gradient of two
or more axes is pinned to the default device layout of its leaf's shard, as
the device's client gives it), so the step copies no new parameter or moment
back through a relayout at its boundary.

On the CPU the default layout is the plain one and the pin moves nothing, so
the case that needs a gradient to *cross* layouts pins a concrete other one
(minor axis first): the CPU backend computes in such a layout too. A layout is
not a value: the variants agree to float32's rounding (the compiler fuses
differently round a pin, so not to the bit). What the pin does on a chip
is ``tests/test_tpu_compile.py``'s (described compiles at Mistral's widths).

The last case pins the fault of jax 0.9.0 that keeps the state itself in the
default layouts (``Format(Layout.AUTO, sharding)`` on the donated arguments
would remove the gradient's crossing too).
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.layout import Format, Layout, with_layout_constraint

from distributed_llm_training_benchmark_framework_tpu.models.tinygpt import TinyGPTConfig
from distributed_llm_training_benchmark_framework_tpu.parallel import get_strategy, make_mesh
from distributed_llm_training_benchmark_framework_tpu.train import step as step_mod
from distributed_llm_training_benchmark_framework_tpu.utils import scopes

SEQ = 32


CONFIG = TinyGPTConfig(
    vocab_size=128, n_embd=64, n_head=4, n_kv_head=2, n_layer=2, block_size=SEQ,
    dropout=0.0, causal=True, norm="rmsnorm", pos_embed="rope", mlp_act="swiglu",
    mlp_hidden=96, bias=False, tie_embeddings=False, scan_layers=False,
)


def unpinned(grads, shardings):
    """The step as it was: the gradients' layouts left to the compiler."""
    return grads


def minor_axis_first(grads, shardings):
    """Every matrix gradient in a layout that is not the default one."""
    return jax.tree.map(
        lambda g: with_layout_constraint(g, Layout(tuple(range(g.ndim))[::-1]))
        if g.ndim > 1 else g, grads)


def build(monkeypatch, strategy, pin=None, devices=4, sentinel=False):
    """A tiny SwiGLU / GQA step over ``devices`` CPU devices; ``pin`` stands in
    for ``step._in_the_layouts_the_state_lives_in`` (None: the program's own)."""
    mesh = make_mesh((devices,), ("data",), devices=jax.devices()[:devices])
    strategy = dataclasses.replace(get_strategy(strategy), remat="none")
    with monkeypatch.context() as patch:
        if pin is not None:
            patch.setattr(step_mod, "_in_the_layouts_the_state_lives_in", pin)
        state = step_mod.create_train_state(
            CONFIG, strategy, mesh, seed=0, grad_accum=1, from_table=True,
            global_micro=devices, seq_len=SEQ, sentinel=sentinel,
        )
        table = jnp.asarray(np.random.default_rng(0).integers(0, 128, (16, SEQ)), jnp.int32)
        state.aot_compile(state.params, state.opt_state, table, 0)  # traced under the patch
    return state, table


def run(state, table, steps=2):
    params, opt_state, losses = state.params, state.opt_state, []
    for step in range(steps):
        params, opt_state, loss = state.step_fn(params, opt_state, table, step)[:3]
        losses.append(loss)
    return params, opt_state, [float(x) for x in jax.device_get(losses)]


def host(tree):
    return [np.asarray(x) for x in jax.tree.leaves(tree)]


def assert_equal_to_the_bit(a, b):
    for x, y in zip(host(a), host(b), strict=True):
        np.testing.assert_array_equal(x, y)


def state_copy(tree):
    """Fresh arrays with the same values (the step donates what it is given)."""
    return jax.tree.map(lambda x: jax.device_put(np.asarray(x), x.sharding), tree)


@pytest.mark.parametrize("strategy", ["zero2", "fsdp"])
def test_two_steps_equal_an_unpinned_step_to_rounding(monkeypatch, strategy):
    """The program's step against the same body with the gradients' layouts
    left to the compiler: parameters, optimizer state and loss. The program's
    arithmetic is the same; the compiler's is not to the bit, because the pin
    stands between the gradient's matmul and the update and the CPU backend
    fuses (and contracts multiply-adds) on either side of it: 41 of 24,576
    elements of ``wgu`` differ after two steps, by 1e-6 of their value."""
    ours, plain = (run(*build(monkeypatch, strategy, pin)) for pin in (None, unpinned))
    np.testing.assert_allclose(ours[2], plain[2], rtol=1e-6)
    for x, y in zip(host(ours[:2]), host(plain[:2]), strict=True):
        np.testing.assert_allclose(x, y, rtol=2e-5, atol=1e-8)


@pytest.mark.parametrize("strategy", ["zero2", "fsdp"])
def test_gradients_that_cross_layouts_train_the_same(monkeypatch, strategy):
    """Gradients that really reach AdamW in another layout than their matmul
    wrote. The sums inside a partitioned matmul may be taken in another order
    for it, so the two agree to float32's rounding, not to the bit."""
    crossed, plain = (run(*build(monkeypatch, strategy, pin))
                      for pin in (minor_axis_first, unpinned))
    np.testing.assert_allclose(crossed[2], plain[2], rtol=1e-6)
    for x, y in zip(host(crossed[:2]), host(plain[:2]), strict=True):
        np.testing.assert_allclose(x, y, rtol=1e-4, atol=1e-7)


@pytest.mark.parametrize("strategy", ["zero2", "fsdp"])
def test_every_matrix_gradient_is_pinned_to_its_shards_default_layout(monkeypatch, strategy):
    """What the pin asks for: one constraint a leaf of two or more axes, the
    layout the device's client gives for the shard of the gradient as the
    optimizer meets it (sharded under zero2 and fsdp alike); vectors are left."""
    pinned = []

    def record(grad, layout):
        pinned.append((grad.shape, grad.dtype, layout))
        return with_layout_constraint(grad, layout)

    monkeypatch.setattr(step_mod, "with_layout_constraint", record)
    state, _ = build(monkeypatch, strategy)
    leaves = jax.tree.leaves(state.params)
    matrices = [x for x in leaves if x.ndim > 1]
    assert len(pinned) == len(matrices) < len(leaves)
    device = state.mesh.devices.flat[0]
    assert sorted(shape for shape, _, _ in pinned) == sorted(x.shape for x in matrices)
    for shape, dtype, layout in pinned:  # (on the CPU a shard's default is the whole's)
        assert layout == Layout.from_pjrt_layout(
            device.client.get_default_layout(dtype, shape, device))


def test_a_fresh_tree_after_steps_and_first_use_without_aot_compile(monkeypatch):
    """The state never leaves the default layouts: a tree that is not the
    step's own results (init's, a restore's) trains as they do, and a step
    that was never ``aot_compile``d compiles on first use."""
    mesh = make_mesh((4,), ("data",), devices=jax.devices()[:4])
    state = step_mod.create_train_state(
        CONFIG, dataclasses.replace(get_strategy("zero2"), remat="none"), mesh, seed=0,
        grad_accum=1, from_table=True, global_micro=4, seq_len=SEQ)
    _, table = build(monkeypatch, "zero2")
    first = state.step_fn(state_copy(state.params), state_copy(state.opt_state), table, 0)
    kept = (host(first[:2]), float(first[2]))  # the next call donates them
    params, opt_state = first[:2]
    for step in (1, 2):
        params, opt_state, _ = state.step_fn(params, opt_state, table, step)
    again = state.step_fn(state.params, state.opt_state, table, 0)
    assert float(again[2]) == kept[1]
    assert_equal_to_the_bit(again[:2], kept[0])
    for leaf in jax.tree.leaves(again[:2]):
        assert leaf.format.layout.major_to_minor == tuple(range(leaf.ndim))


def test_a_checkpoint_of_trained_state_restores_equal(monkeypatch, tmp_path):
    from distributed_llm_training_benchmark_framework_tpu.runtime.checkpoint import (
        BenchmarkCheckpointer,
    )

    state, table = build(monkeypatch, "fsdp")
    params, opt_state, _ = run(state, table)
    checkpointer = BenchmarkCheckpointer(str(tmp_path / "ck"))
    assert checkpointer.save(1, params, opt_state)
    restored_params, restored_opt, step = checkpointer.restore(params, opt_state)
    checkpointer.close()
    assert step == 1
    assert_equal_to_the_bit(restored_params, params)
    assert_equal_to_the_bit(restored_opt, opt_state)
    # what came back trains on as what was saved does
    resumed = state.step_fn(restored_params, restored_opt, table, 2)
    straight = state.step_fn(params, opt_state, table, 2)
    assert float(resumed[2]) == float(straight[2])
    assert_equal_to_the_bit(resumed[:2], straight[:2])


def test_aot_compile_then_steps_compile_once(monkeypatch):
    """``aot_compile`` makes the executable ``step_fn`` calls: one compilation
    of ``train_step``; the steps after it and a second ``aot_compile`` make
    none."""
    def compiles():
        return [c for c in scopes.compile_events()["backend_compiles"] if "train_step" in c[0]]

    before = len(compiles())
    state, table = build(monkeypatch, "zero2")
    assert len(compiles()) == before + 1
    params, opt_state = state.params, state.opt_state
    for step in range(4):
        params, opt_state, _ = state.step_fn(params, opt_state, table, step)
    state.aot_compile(params, opt_state, table, 0)
    assert len(compiles()) == before + 1
    everything = len(scopes.compile_events()["backend_compiles"])
    for step in range(4, 8):
        params, opt_state, _ = state.step_fn(params, opt_state, table, step)
    assert len(scopes.compile_events()["backend_compiles"]) == everything


def test_the_sentinel_step_reads_the_gradients_before_the_pin(monkeypatch):
    """The sentinel's gradient norm and the parameter checksum of a pinned
    step are those of an unpinned one."""
    ours, _ = build(monkeypatch, "zero2", sentinel=True)
    plain, table = build(monkeypatch, "zero2", unpinned, sentinel=True)
    params, _, _, gnorm = ours.step_fn(ours.params, ours.opt_state, table, 0)
    p_params, _, _, p_gnorm = plain.step_fn(plain.params, plain.opt_state, table, 0)
    assert float(gnorm) == float(p_gnorm)
    checksum = step_mod.make_param_norm_fn(ours.mesh)
    assert float(checksum(params)) == float(checksum(p_params))
    assert_equal_to_the_bit(jax.device_get(params), jax.device_get(p_params))


@pytest.fixture
def persistent_cache(tmp_path):
    """jax's persistent compilation cache on, in a directory of this test's,
    every program kept; as it was afterwards."""
    from jax.experimental.compilation_cache import compilation_cache

    keys = ("jax_enable_compilation_cache", "jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    before = {key: getattr(jax.config, key) for key in keys}
    for key, value in zip(keys, (True, str(tmp_path / "cache"), 0.0, -1)):
        jax.config.update(key, value)
    compilation_cache.reset_cache()
    yield
    for key, value in before.items():
        jax.config.update(key, value)
    compilation_cache.reset_cache()


def test_an_executable_read_back_from_the_cache_mislabels_its_result(persistent_cache):
    """Why the state itself stays in the default layouts and only the
    gradients are pinned: compiled, a program with a result layout of its own
    hands its result over in that layout; read back from the persistent cache
    (which every run places) it still says so (``output_formats``), writes it
    so (the values come back right), and labels the array with the default
    layout, which the next executable's layout check refuses. A step whose
    donated state had ``Format(Layout.AUTO, sharding)`` (measured: 3.5 %
    faster in ``mistral-7b.d2`` where the pin gives 2.7, PERF.md section 6, PR
    35) dies at its second call. When this case fails, jax keeps the label and
    that step can be built."""
    sharding = jax.sharding.SingleDeviceSharding(jax.devices()[0])
    custom = Format(Layout((0, 2, 1, 3)), sharding)
    host = np.arange(2 * 4 * 2 * 8, dtype=np.float32).reshape(2, 4, 2, 8)
    aval = jax.ShapeDtypeStruct(host.shape, host.dtype, sharding=sharding)

    def load():  # a new jit object: the first compiles and writes, the second reads
        return jax.jit(lambda x: x * 2, out_shardings=custom).lower(aval).compile()

    compiled, read_back = load(), load()
    for executable in (compiled, read_back):
        assert executable.output_formats.layout.major_to_minor == (0, 2, 1, 3)
    first, second = compiled(jnp.asarray(host)), read_back(jnp.asarray(host))
    np.testing.assert_array_equal(np.asarray(first), 2 * host)
    np.testing.assert_array_equal(np.asarray(second), 2 * host)
    assert first.format.layout.major_to_minor == (0, 2, 1, 3)
    assert second.format.layout.major_to_minor == (0, 1, 2, 3)  # the bug
