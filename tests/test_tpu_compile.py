"""Compile the main path's kernels for a described (not attached) TPU v5e.

The TPU compiler ships with jax here, and it compiles for a chip that is
described and not present (``/opt/skills/guides/on-chip-measurement`` §2):
what Mosaic or XLA:TPU would refuse on the chip — a mis-tiled block, too
much VMEM, a kernel GSPMD cannot partition — it refuses here, at no chip
time. Interpret-mode tests cannot see any of that. Nothing runs, so these
say nothing about results or speed.

Skipped where the topology cannot be described (no libtpu). The persistent
compilation cache is off (``tests/conftest.py``): an entry written for a
described chip cannot be read back without one. One process at a time —
libtpu's lock file refuses a second.
"""

import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else libtpu logs under /tmp

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from distributed_llm_training_benchmark_framework_tpu.ops import (
    flash_attention as fa,
    ring_attention as ra,
)

# (heads, head_dim, causal, dropout): TinyGPT tier A and the llama tier A.
TIER_A = (16, 64, False, 0.1)
LLAMA_A = (8, 128, True, 0.0)


@pytest.fixture(scope="module")
def v5e_devices():
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no libtpu / topology unknown to this build
        pytest.skip(f"cannot describe a v5e:2x2 topology here: {e}")
    assert not jax.config.jax_enable_compilation_cache  # tests/conftest.py
    return list(topo.devices)


def _compile(fn, *avals):
    text = jax.jit(fn).lower(*avals).compile().as_text()
    assert "tpu_custom_call" in text, "no Mosaic kernel in the compiled HLO"
    return text


def _qkv(sharding, replicated, batch, seq, heads, head_dim):
    x = jax.ShapeDtypeStruct(
        (batch, seq, heads, head_dim), jnp.bfloat16, sharding=sharding
    )
    seed = jax.ShapeDtypeStruct((), jnp.uint32, sharding=replicated)
    return x, x, x, seed


def _flash_loss(causal, rate, q, k, v, seed):
    out = fa.flash_attention(
        q, k, v, causal=causal, interpret=False, dropout_rate=rate,
        dropout_seed=seed if rate else None,
    )
    return jnp.sum(out.astype(jnp.float32))


@pytest.mark.parametrize("width", [TIER_A, LLAMA_A], ids=["16x64", "8x128"])
def test_flash_forward_compiles(v5e_devices, width):
    heads, head_dim, causal, rate = width
    one = SingleDeviceSharding(v5e_devices[0])
    _compile(
        lambda *a: _flash_loss(causal, rate, *a),
        *_qkv(one, one, 1, 2048, heads, head_dim),
    )


@pytest.mark.parametrize("width", [TIER_A, LLAMA_A], ids=["16x64", "8x128"])
@pytest.mark.parametrize(
    "seq,n_kernels", [(2048, 1), (4096, 2), (8192, 2)],
    ids=["einsum-bwd", "pallas-bwd", "pallas-bwd-8k"],
)
def test_flash_backward_compiles(v5e_devices, width, seq, n_kernels):
    """fwd + the backward the S crossover picks: the XLA einsum backward at
    2048 (one kernel: the forward), the one fused kernel from 4096 (8192 at
    16x64 with dropout is what ``tinygpt-a.seq8192`` runs)."""
    heads, head_dim, causal, rate = width
    one = SingleDeviceSharding(v5e_devices[0])
    text = _compile(
        jax.grad(lambda *a: _flash_loss(causal, rate, *a), argnums=(0, 1, 2)),
        *_qkv(one, one, 1, seq, heads, head_dim),
    )
    assert text.count("custom_call_target=\"tpu_custom_call\"") == n_kernels
    assert ("flash_bwd_fused" in text) == (n_kernels == 2)


def test_fused_backward_compiles_at_its_vmem_cap(v5e_devices):
    """The longest sequence ``_fused_fits`` lets through (the resident dq row
    is what grows with S) compiles under the limit the call asks for."""
    one = SingleDeviceSharding(v5e_devices[0])
    S, D = 65536, 128
    assert fa._fused_fits(S, D, jnp.bfloat16)
    assert not fa._fused_fits(2 * S, D, jnp.bfloat16)  # so this is the cap

    def aval(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    x, stat = aval((1, S, D), jnp.bfloat16), aval((1, 8, S), jnp.float32)
    text = _compile(
        lambda q, k, v, do, lse, d, seed, bh: fa._fused_backward(
            q, k, v, do, lse, d, seed, bh, True, 0.0, 1024, 1024, False
        ),
        x, x, x, x, stat, stat, aval((1,), jnp.uint32), aval((1,), jnp.int32),
    )
    assert "flash_bwd_fused" in text


def test_ring_block_kernels_compile(v5e_devices):
    """The per-hop ring kernels at the sp=4 x seq-8192 chunk (2048), tier-A
    widths: forward stats, then the shared dq / dk+dv backward kernels."""
    heads, head_dim, _, rate = TIER_A
    chunk = 2048
    one = SingleDeviceSharding(v5e_devices[0])

    def aval(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    x = aval((heads, chunk, head_dim), jnp.bfloat16)
    stat = aval((heads, 8, chunk), jnp.float32)
    seed = aval((1,), jnp.uint32)
    bh = aval((heads,), jnp.int32)
    q_tiles = aval((chunk // 1024,), jnp.int32)
    k_tiles = aval((chunk // 512,), jnp.int32)

    _compile(
        lambda q, k, v, s, qo, ko, b: ra._block_stats_kernel(
            q, k, v, s, qo, ko, b, False, rate, 1024, 1024
        ),
        x, x, x, seed, q_tiles, q_tiles, bh,
    )
    _compile(
        lambda q, k, v, do, lse, d, s, qo, ko, b: fa._pair_backward(
            q, k, v, do, lse, d, s, b, False, rate, 1024, 512, False,
            q_tile_offsets=qo, k_tile_offsets=ko, out_dtype=jnp.float32,
        ),
        x, x, x, x, stat, stat, seed, q_tiles, k_tiles, bh,
    )


def test_dropless_expert_matmuls_compile_at_olmoe_widths(v5e_devices, monkeypatch):
    """The routed MLP's expert stack at OLMoE-1B-7B's sizes (8192 tokens x 8
    experts a token = 65536 rows, hidden 2048, 64 experts of width 1024),
    forward and backward: six Pallas grouped matmuls (gate+up and down; the
    rows' gradient of each is one more ``gmm``, the weights' a ``tgmm``) at
    the tile ``models/moe.py`` picked on the chip."""
    from distributed_llm_training_benchmark_framework_tpu.models import moe
    from distributed_llm_training_benchmark_framework_tpu.models.tinygpt import TinyGPTConfig

    # The program asks the backend whether to run its kernels or interpret
    # them; the target here is the described chip.
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    config = TinyGPTConfig(n_embd=2048, n_head=16, mlp_act="swiglu", mlp_hidden=1024,
                           bias=False, n_experts=64, expert_top_k=8, capacity_factor=None)
    one = SingleDeviceSharding(v5e_devices[0])
    aval = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    def loss(rows, wgu, wd, counts):
        out = moe._experts_dropless(config, {"moe_wgu": wgu, "moe_wd": wd}, rows, counts)
        return jnp.sum(jnp.square(out.astype(jnp.float32)))  # its gradient needs ``out``

    text = _compile(
        jax.grad(loss, argnums=(0, 1, 2)),
        aval((65536, 2048), jnp.bfloat16), aval((64, 2048, 2048), jnp.float32),
        aval((64, 1024, 2048), jnp.float32), aval((64,), jnp.int32),
    )
    assert text.count('custom_call_target="tpu_custom_call"') == 6
    for scope in ("jit(gmm)", "jit(tgmm)"):  # how a trace tells them from the flash kernels
        assert scope in text


def test_flash_partitions_over_a_four_device_data_mesh(v5e_devices):
    """The case GSPMD refuses bare ("Mosaic kernels cannot be automatically
    partitioned"): batch sharded over a 4-chip 'data' axis. flash_attention
    shard_maps itself over the axis, so each chip runs the kernel on its
    own examples and no collective touches q/k/v."""
    import numpy as np

    heads, head_dim, causal, rate = TIER_A
    mesh = Mesh(np.asarray(v5e_devices).reshape(4), ("data",))
    avals = _qkv(
        NamedSharding(mesh, P("data")), NamedSharding(mesh, P()),
        4, 2048, heads, head_dim,
    )
    with jax.set_mesh(mesh):
        text = _compile(
            jax.grad(
                lambda *a: _flash_loss(causal, rate, *a), argnums=(0, 1, 2)
            ),
            *avals,
        )
    assert "all-gather" not in text and "all-to-all" not in text


# --- whole train steps at tier-A size (48-76 s each: slow set) -------------

WHOLE_STEPS = {
    # the one-chip parity cell: 16 flash kernels, no collective
    "zero2-1chip-flash": dict(
        strategy="zero2", mesh=(1, 1), attention="flash", seq=2048,
        micro=1, accum=4, kernels=16, collectives=False,
    ),
    # the headline multi-chip arm that could not compile before the
    # shard_map: fsdp dp=4 with flash
    "fsdp-dp4-flash": dict(
        strategy="fsdp", mesh=(4, 1), attention="flash", seq=2048,
        micro=4, accum=1, kernels=16, collectives=True,
    ),
    "zero2-sp4-ring-8k": dict(
        strategy="zero2", mesh=(1, 4), attention="ring", seq=8192,
        micro=1, accum=1, kernels=64, collectives=True,
    ),
}


@pytest.mark.slow
@pytest.mark.parametrize("name", sorted(WHOLE_STEPS))
def test_whole_step_compiles_at_tier_a(v5e_devices, monkeypatch, name):
    from distributed_llm_training_benchmark_framework_tpu.models import (
        get_model_config,
    )
    from distributed_llm_training_benchmark_framework_tpu.parallel import (
        get_strategy,
        make_mesh,
    )
    from distributed_llm_training_benchmark_framework_tpu.train.step import (
        abstract_compile_step,
    )

    case = WHOLE_STEPS[name]
    # The program asks jax.default_backend() to choose kernel vs interpret
    # mode; the compile target is the described chip, so answer for it.
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    dp, sp = case["mesh"]
    mesh = make_mesh(
        (dp, sp), ("data", "seq"), devices=v5e_devices[: dp * sp]
    )
    config = get_model_config(
        "A", case["seq"], attention_impl=case["attention"], scan_layers=False
    )
    compiled = abstract_compile_step(
        config, get_strategy(case["strategy"]), mesh,
        grad_accum=case["accum"], global_micro=case["micro"],
        seq_len=case["seq"], dataset_size=1000,
    )
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == case["kernels"]
    has_collective = any(
        op in text for op in ("all-gather", "reduce-scatter", "all-reduce",
                              "collective-permute")
    )
    assert has_collective == case["collectives"]
    peak = compiled.memory_analysis().peak_memory_in_bytes
    assert 0 < peak < 16 * 1024**3
    print(f"{name}: peak {peak / 1e9:.2f} GB/chip")
