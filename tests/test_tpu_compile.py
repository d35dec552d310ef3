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

import functools
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


@pytest.mark.parametrize("block", [4, 24], ids=["shift", "divide"])
def test_block_diffusion_kernels_compile(v5e_devices, block):
    """Forward and fused backward under the block-diffusion rule at the SDAR
    cell's shape (a stream of 2 x 8192, head width 128): the rule's integer
    work on the narrow operands, a block length that is a power of two (a
    shift) and one that is not (a divide), and the unsigned compare a score."""
    one = SingleDeviceSharding(v5e_devices[0])
    rule = fa.BlockDiffusion(8192 if block == 4 else 6144, block)

    def loss(q, k, v):
        out = fa.flash_attention(q, k, v, causal=rule, interpret=False)
        return jnp.sum(out.astype(jnp.float32))

    q, k, v, _ = _qkv(one, one, 1, 2 * rule.seq_len, 4, 128)
    text = _compile(jax.grad(loss, argnums=(0, 1, 2)), q, k, v)
    assert "flash_fwd" in text and "flash_bwd_fused" in text


@pytest.mark.parametrize("window", [1024, 1536, 300], ids=["a-tile", "not-a-multiple", "inside-a-tile"])
def test_sliding_window_kernels_compile_on_their_band(v5e_devices, window):
    """Forward and fused backward under the window rule at the Mellum cell's
    shape (S 16,384, head width 128): the band is the grid, so the operands'
    block indices are computed from the step (clamped in the clipped corner),
    and a q tile's slice of the resident dq row is zeroed and written out by
    conditions on scalars of the grid."""
    one = SingleDeviceSharding(v5e_devices[0])
    rule = fa.SlidingWindow(window)

    def loss(q, k, v):
        out = fa.flash_attention(q, k, v, causal=rule, interpret=False)
        return jnp.sum(out.astype(jnp.float32))

    q, k, v, _ = _qkv(one, one, 1, 16384, 4, 128)
    text = _compile(jax.grad(loss, argnums=(0, 1, 2)), q, k, v)
    assert "flash_fwd" in text and "flash_bwd_fused" in text
    assert rule.band_steps(16384, 1024, 1024, True) == -(-(window - 1) // 1024) + 1


GROUPED_CALLS = {
    # (query heads, kv heads, S, rule) of the cells whose query heads share kv heads
    "mistral-32-over-8": (32, 8, 4096, True),
    "sdar-32-over-4": (32, 4, 16384, fa.BlockDiffusion(8192, 4)),
    "mellum-window-32-over-4": (32, 4, 16384, fa.SlidingWindow(1024)),
    "laguna-global-48-over-8": (48, 8, 16384, True),
    "laguna-window-64-over-8": (64, 8, 16384, fa.SlidingWindow(512)),
}


@pytest.mark.parametrize("call", sorted(GROUPED_CALLS))
def test_grouped_query_kernels_compile_at_the_cells_head_counts(v5e_devices, call):
    """k and v at the model's kv heads: the forward's and the fused backward's
    index maps divide the grid's row by the group (a scalar op in the index
    computation, in the square's grid and in the band's), and dk / dv leave
    at the kv heads' shape."""
    heads, kv_heads, seq, rule = GROUPED_CALLS[call]
    one = SingleDeviceSharding(v5e_devices[0])

    def loss(q, k, v):
        out = fa.flash_attention(q, k, v, causal=rule, interpret=False)
        return jnp.sum(out.astype(jnp.float32))

    q, _, _, _ = _qkv(one, one, 1, seq, heads, 128)
    kv, _, _, _ = _qkv(one, one, 1, seq, kv_heads, 128)
    lowered = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(q, kv, kv)
    assert [tuple(leaf.shape) for leaf in jax.tree.leaves(lowered.out_info)] == [q.shape, kv.shape, kv.shape]
    text = lowered.compile().as_text()
    assert "flash_fwd" in text and "flash_bwd_fused" in text


@pytest.mark.parametrize("kernel", ["fwd", "bwd"])
def test_the_bodies_change_the_mosaic_module_and_a_retrace_does_not(v5e_devices, kernel, monkeypatch):
    """``scripts/flash_mosaic_modules.py``: what a kernel hands the compiler,
    printed without source locations, is how two checkouts are shown to run
    the same kernel with no chip (PR 37: the bodies' chains from ``jnp`` to
    ``lax``). The text names no file, a kernel built and traced again gives
    it again, and the causal kernel with its tiles' shapes turned off, the
    whole-tile walk, gives another."""
    import importlib.util
    import os

    path = os.path.join(os.path.dirname(os.path.dirname(__file__)), "scripts", "flash_mosaic_modules.py")
    spec = importlib.util.spec_from_file_location("flash_mosaic_modules", path)
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)

    def module():
        fa.forget_kernel_calls()
        (found,) = [tool.mosaic_modules(call.lower(*operands).as_text())
                    for name, call, operands in tool.kernels(v5e_devices[0])
                    if name == f"{kernel}.causal.0.0.128.128"]
        return found[0]

    text = module()
    assert "flash_attention.py" not in text and "loc(" not in text
    assert {"fwd": "flash_fwd", "bwd": "flash_bwd_fused"}[kernel] in text
    assert module() == text
    monkeypatch.setattr(fa, "_tile_shape", lambda *a: False)
    assert module() != text
    fa.forget_kernel_calls()


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


# (rows a layer's grouped matmuls see, hidden, expert width, experts on the chip)
# of the benchmark's routed cells: olmoe-1b-7b.d1 and the held experts' buffers
# of the DeepSeek, SDAR and Mellum cells.
EXPERT_STACKS = {"olmoe": (65536, 2048, 1024, 64), "deepseek": (18432, 2048, 1408, 8),
                 "sdar": (40960, 2048, 768, 16), "mellum": (49152, 2304, 896, 16),
                 "kimi": (6144, 2304, 1024, 8)}


@pytest.mark.parametrize("cell", sorted(EXPERT_STACKS))
def test_dropless_expert_matmuls_compile_at_the_cells_widths(v5e_devices, monkeypatch, cell):
    """The routed MLP's expert stack at the sizes of each routed cell (OLMoE-1B-7B:
    8192 tokens x 8 experts a token = 65536 rows, hidden 2048, 64 experts of
    width 1024), forward and backward: six Pallas grouped matmuls (gate+up and
    down; the rows' gradient of each is one more ``gmm``, the weights' a
    ``tgmm``), each at the tiles ``moe.gmm_tiling`` makes of its own widths:
    what the rule says fits the kernel's VMEM, the chip's compiler takes."""
    from distributed_llm_training_benchmark_framework_tpu.models import moe
    from distributed_llm_training_benchmark_framework_tpu.models.tinygpt import TinyGPTConfig

    # The program asks the backend whether to run its kernels or interpret
    # them; the target here is the described chip.
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    rows, hidden, width, experts = EXPERT_STACKS[cell]
    config = TinyGPTConfig(n_embd=hidden, n_head=16, mlp_act="swiglu", mlp_hidden=width,
                           bias=False, n_experts=experts, expert_top_k=8, capacity_factor=None)
    one = SingleDeviceSharding(v5e_devices[0])
    aval = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    def loss(rows, wgu, wd, counts):
        out = moe._experts_dropless(config, {"moe_wgu": wgu, "moe_wd": wd}, rows, counts)
        return jnp.sum(jnp.square(out.astype(jnp.float32)))  # its gradient needs ``out``

    text = _compile(
        jax.grad(loss, argnums=(0, 1, 2)),
        aval((rows, hidden), jnp.bfloat16), aval((experts, hidden, 2 * width), jnp.float32),
        aval((experts, width, hidden), jnp.float32), aval((experts,), jnp.int32),
    )
    assert text.count('custom_call_target="tpu_custom_call"') == 6
    for scope in ("jit(gmm)", "jit(tgmm)"):  # how a trace tells them from the flash kernels
        assert scope in text


@pytest.mark.parametrize("hidden, width, top_k, held, factor, buffer", [
    (2048, 1408, 6, 8, 1.5, 18432), (2304, 896, 8, 16, 1.5, 49152),
], ids=["deepseek", "mellum"])
def test_the_held_rows_passes_compile_at_the_cells_sizes(
        v5e_devices, monkeypatch, hidden, width, top_k, held, factor, buffer):
    """A chip's share of DeepSeek-V2-Lite's routed layer at the benchmark
    cell's sizes (16,384 tokens x 6 choices over 64 experts, 8 held, a buffer
    of 18,432 rows of 2048) and of Mellum's (8 choices, 16 held, 49,152 rows of
    2304: one column tile, where 2048 would not divide): tokens to rows, rows
    back to tokens, and the transposes of both. The way back is a ``tgmm`` over
    the spans of rows that tiles of tokens own: one in the forward pass, one in
    the backward."""
    from distributed_llm_training_benchmark_framework_tpu.models import moe
    from distributed_llm_training_benchmark_framework_tpu.models.tinygpt import TinyGPTConfig

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    config = TinyGPTConfig(n_embd=hidden, n_head=16, mlp_act="swiglu", mlp_hidden=width,
                           bias=False, n_experts=64, expert_top_k=top_k, capacity_factor=None,
                           experts_held=(0, held), held_rows_factor=factor)
    tokens = 16384
    assert moe.held_buffer_rows(config, tokens) == buffer
    one = SingleDeviceSharding(v5e_devices[0])
    aval = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    def loss(xt, expert_idx, counts):
        gates = jnp.ones(expert_idx.shape, jnp.float32)
        _, _, rows_of, _, _, _ = moe._held_plan(config, expert_idx, counts, gates)
        back = moe._tokens_from_rows(moe._rows_from_tokens(xt, rows_of), rows_of, tokens)
        return jnp.sum(jnp.square(back.astype(jnp.float32)))  # its gradient needs ``back``

    text = _compile(
        jax.grad(loss), aval((tokens, hidden), jnp.bfloat16), aval((tokens, top_k), jnp.int32),
        aval((64,), jnp.int32),
    )
    assert text.count('custom_call_target="tpu_custom_call"') == 2
    assert "jit(tgmm)" in text


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


# --- the sharded-parameter step's MLP at Mistral widths (about 10 s a compile) ---

def _script(name):
    import importlib.util

    path = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "scripts", name + ".py",
    )
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _fsdp_collectives_tool():
    return _script("fsdp_collectives")


_MISTRAL_DP4_STEPS = {}  # (strategy, mlp_act, pinned, chips, pinned_grads) -> Compiled: tests share them


def _mistral_dp4_step(v5e_devices, monkeypatch, strategy, mlp_act, pinned=True, chips=4,
                      pinned_grads=True):
    """The fsdp4 cell's step (one 4096-token sequence a chip, flash, unrolled,
    remat dots) at Mistral-7B's widths and depth 1, compiled for the
    described 2x2; ``pinned=False`` leaves the MLP's layout to propagation,
    ``chips=1`` is one chip of it, ``pinned_grads=False`` leaves the gradients'
    device layouts to the compiler."""
    import dataclasses

    from distributed_llm_training_benchmark_framework_tpu.models.tinygpt import TinyGPTConfig
    from distributed_llm_training_benchmark_framework_tpu.parallel import get_strategy, make_mesh
    from distributed_llm_training_benchmark_framework_tpu.train import step as step_mod

    key = (strategy, mlp_act, pinned, chips, pinned_grads)
    if key not in _MISTRAL_DP4_STEPS:
        config = TinyGPTConfig(
            vocab_size=32768, n_embd=4096, n_head=32, n_kv_head=8, n_layer=1,
            block_size=4096, dropout=0.0, causal=True, attention_impl="flash",
            scan_layers=False, norm="rmsnorm", norm_eps=1e-5, pos_embed="rope",
            rope_theta=1e6, mlp_act=mlp_act, mlp_hidden=14336, bias=False,
            tie_embeddings=False,
        )
        mesh = make_mesh((chips,), ("data",), devices=v5e_devices[:chips])
        with monkeypatch.context() as patch:
            # The program asks jax.default_backend() to choose kernel vs
            # interpret mode; the compile target is the described chip.
            patch.setattr(jax, "default_backend", lambda: "tpu")
            if not pinned:
                patch.setattr(step_mod, "mlp_hidden_spec", lambda *a, **k: None)
            if not pinned_grads:
                patch.setattr(step_mod, "_in_the_layouts_the_state_lives_in",
                              lambda grads, shardings: grads)
            _MISTRAL_DP4_STEPS[key] = step_mod.abstract_compile_step(
                config, dataclasses.replace(get_strategy(strategy), remat="dots"), mesh,
                grad_accum=1, global_micro=chips, seq_len=4096, dataset_size=1000,
            )
    return _MISTRAL_DP4_STEPS[key]


# What the pins may add to a chip's peak, a layer: the whole fsdp4 cell (depth
# 8) goes from 12.65 to 13.29 GB, 0.08 GB a layer.
MLP_PIN_PEAK_ALLOWANCE = 0.15e9


@pytest.mark.parametrize("mlp_act", ["swiglu", "gelu"])
@pytest.mark.parametrize("strategy", ["fsdp", "zero3"])
def test_sharded_param_mlp_moves_no_weight_and_no_all_to_all(
    v5e_devices, monkeypatch, strategy, mlp_act
):
    """With ``mlp_hidden_spec`` armed, the MLP's F-wide activations sit where
    the weight shards are: no all-to-all anywhere in the step, and every
    collective under ``mlp`` carries (B, S, D) activations or a norm scale,
    never a slice of ``wgu`` / ``wfc`` / ``wproj`` (F / 4 = 3584 wide). Left
    to propagation (the field unarmed) the SwiGLU step reshards the wide
    activations with all-to-alls; the pins may cost the stated allowance."""
    tool = _fsdp_collectives_tool()
    pinned = _mistral_dp4_step(v5e_devices, monkeypatch, strategy, mlp_act)
    rows = tool.collectives(pinned.as_text())
    assert rows and not [r for r in rows if r.kind == "all-to-all"]
    mlp = [r for r in rows if r.module == "mlp"]
    assert mlp and not [r for r in mlp if "3584" in r.shapes], mlp

    unpinned = _mistral_dp4_step(v5e_devices, monkeypatch, strategy, mlp_act, pinned=False)
    crossings = [r for r in tool.collectives(unpinned.as_text()) if r.kind == "all-to-all"]
    # (the GELU branch's one elementwise op between the projections follows
    # the matmuls' layout unpinned too: the pin states what it had)
    assert all(r.module == "mlp" for r in crossings)
    assert crossings or mlp_act == "gelu"
    peaks = [c.memory_analysis().peak_memory_in_bytes for c in (pinned, unpinned)]
    assert peaks[0] <= peaks[1] + MLP_PIN_PEAK_ALLOWANCE, peaks
    print(f"{strategy}/{mlp_act}: peak {peaks[0] / 1e9:.2f} GB pinned, "
          f"{peaks[1] / 1e9:.2f} unpinned; {len(crossings)} all-to-alls unpinned")


@pytest.mark.parametrize("strategy, chips", [("fsdp", 4), ("zero3", 4), ("zero2", 1)])
def test_no_entry_copy_has_a_state_leafs_shape(v5e_devices, monkeypatch, strategy, chips):
    """AdamW runs in the layout the parameters and moments live in
    (``train/step.py``: the gradients are pinned to it): the compiler makes no
    copy of a state leaf's shape at the step's boundary, and the state is
    where it was: ``wgu`` (its second-to-last axis has size 2) in the default
    layout's tiles of two rows."""
    tool = _script("state_copies")
    compiled = _mistral_dp4_step(v5e_devices, monkeypatch, strategy, "swiglu", chips=chips)
    leaves = tool.state_leaves(compiled)
    same, _ = tool.state_shaped(tool.entry_copies(compiled.as_text()), leaves)
    assert not same, same
    assert compiled.input_formats[0][:2] == tuple(compiled.output_formats[:2])
    wgu = {(layout.major_to_minor, layout.tiling) for path, _, dims, layout in leaves
           if path.endswith("['wgu']")}
    assert wgu == {((0, 1, 2, 3), ((2, 128),))}, wgu


def test_unpinned_gradients_copy_the_state_at_the_boundary(v5e_devices, monkeypatch):
    """The control: its gradients' layouts left to the compiler, the same
    one-chip step runs AdamW in the layout the matmul writes and copies the new
    ``wgu`` and ``wkv`` and their two moments back through a relayout, so the
    case above can fail."""
    tool = _script("state_copies")
    compiled = _mistral_dp4_step(v5e_devices, monkeypatch, "zero2", "swiglu", chips=1,
                                 pinned_grads=False)
    same, _ = tool.state_shaped(
        tool.entry_copies(compiled.as_text()), tool.state_leaves(compiled))
    assert {c.dims for c in same} == {(1, 4096, 2, 14336), (1, 4096, 2, 1024)}, same
    assert len(same) == 6 and not any(c.named for c in same)


def test_entry_copies_reads_the_entry_computation_only():
    tool = _script("state_copies")
    text = """
%fused (p: f32[4,8]) -> f32[4,8] {
  %copy.9 = f32[4,8]{0,1:T(8,128)} copy(%p)
}

ENTRY %main (a: f32[2,4,2,8]) -> f32[2,4,2,8] {
  %a = f32[2,4,2,8]{3,1,2,0:T(8,128)} parameter(0)
  %copy.1 = f32[2,4,2,8]{3,2,1,0:T(2,128)} copy(%a), backend_config={"estimated_cycles":"1200"}
  %copy.2 = bf16[2,4,2,8]{3,1,2,0:T(8,128)(2,1)} copy(%x), metadata={op_name="jit(f)/convert"}
  ROOT %copy.3 = s32[] copy(%s)
}
"""
    rows = tool.entry_copies(text)
    assert [(r.dtype, r.dims, r.nbytes, r.cycles, r.named) for r in rows] == [
        ("f32", (2, 4, 2, 8), 512, 1200, False), ("bf16", (2, 4, 2, 8), 256, 0, True),
        ("s32", (), 4, 0, False)]
    leaves = [("params['w']", "float32", (2, 4, 2, 8), None), ("params['b']", "float32", (8,), None)]
    same, cast = tool.state_shaped(rows, leaves)
    assert [r.dims for r in same] == [(2, 4, 2, 8)] and [r.dtype for r in cast] == ["bf16"]


@pytest.mark.parametrize("strategy", ["fsdp", "zero3"])
def test_forward_weight_rings_interleave_with_dots(v5e_devices, monkeypatch, strategy):
    """What ``tests/test_overlap.py`` pinned on the CPU lowering until jax
    0.9.0's CPU scheduler stopped producing it (weight gathers interleaved
    with the forward's dots, never bundled above the first one), on the
    compile that counts. On the TPU the attention weights travel on
    collective-permute rings inside their own windowed einsum, so the guard
    reads: in the scheduled step, most forward hops of ``attention``'s rings
    have a matmul between their start and their done (a ring's first hop
    starts above the first matmul by design: that is the prefetch)."""
    import re

    lines = _mistral_dp4_step(v5e_devices, monkeypatch, strategy, "swiglu").as_text().splitlines()
    entry = next(i for i, line in enumerate(lines) if line.startswith("ENTRY"))
    started, dots, covered, hops = {}, [], 0, 0
    for i, line in enumerate(lines[entry:]):
        if " fusion(" in line and "kind=kOutput" in line:  # the TPU's matmul fusions
            dots.append(i)
        elif found := re.match(r"\s+%(collective-permute-start[\w.\-]*) = ", line):
            op_name = re.search(r'op_name="([^"]*)"', line)
            if op_name and "jvp(attention)" in op_name.group(1) \
                    and "transpose(" not in op_name.group(1):
                started[found.group(1)] = i
        elif found := re.search(r" collective-permute-done\(%?([\w.\-]+)\)", line):
            if (start := started.get(found.group(1))) is not None:
                hops += 1
                covered += any(start < dot < i for dot in dots)
    assert hops >= 9 and dots  # wq, wkv, wo: three hops each on four chips
    assert covered >= 0.75 * hops, (covered, hops)


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


def test_flash_compiles_at_latent_attention_widths(v5e_devices):
    """192-wide q and k over 128-wide v with the caller's scale (DeepSeek-V2's
    MLA under YaRN), forward and the fused backward at S 8192: two Mosaic
    calls, no padding of either width."""
    one = SingleDeviceSharding(v5e_devices[0])
    aval = lambda d: jax.ShapeDtypeStruct((1, 8192, 16, d), jnp.bfloat16, sharding=one)

    def loss(q, k, v):
        out = fa.flash_attention(q, k, v, causal=True, interpret=False, scale=0.114721)
        return jnp.sum(out.astype(jnp.float32))

    text = _compile(jax.grad(loss, argnums=(0, 1, 2)), aval(192), aval(192), aval(128))
    assert text.count("tpu_custom_call") == 2
    assert "bf16[16,8192,192]" in text and "bf16[16,8192,128]" in text
    assert "bf16[16,8192,256]" not in text  # nothing padded to a lane multiple in HBM


# --- ops/rotary.py: QK-norm and rotary in one pass (about 5 s a compile) ---

def _prologue_loss(norm, q, k, q_scale, k_scale, positions):
    from distributed_llm_training_benchmark_framework_tpu.ops import rotary

    table = rotary.table(positions, 128, 1e6)
    q, k = rotary.qk_prologue(
        q, k, q_scale if norm else None, k_scale if norm else None, table, 1e-6)
    return jnp.sum(q.astype(jnp.float32)) + jnp.sum(k.astype(jnp.float32))


@pytest.mark.parametrize(
    "batch,seq,heads,kv,norm", [(1, 16384, 32, 4, True), (2, 4096, 32, 8, False)],
    ids=["sdar-and-mellum-16384x32/4-head-norm", "mistral-2x4096x32/8-rotary-alone"])
def test_qk_prologue_compiles_at_the_cells_widths(v5e_devices, batch, seq, heads, kv, norm):
    """Forward and backward of the pass at the claimed cells' operand (16,384
    rows of a 4096-wide q, 4 KV heads, the per-head norm) and at
    ``mistral-7b.d2``'s (rotary alone): one Mosaic call a direction, under
    the scoped VMEM its row block asks for."""
    one = SingleDeviceSharding(v5e_devices[0])
    aval = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=one)
    args = (aval((batch, seq, heads * 128), jnp.bfloat16), aval((batch, seq, kv * 128), jnp.bfloat16),
            aval((128,), jnp.float32), aval((128,), jnp.float32), aval((seq,), jnp.int32))
    loss = functools.partial(_prologue_loss, norm)
    text = _compile(jax.value_and_grad(loss, argnums=(0, 1, 2, 3)), *args)
    assert text.count('custom_call_target="tpu_custom_call"') == 2
    assert "qk_prologue_fwd" in text and "qk_prologue_bwd" in text
    assert "flash_" not in text


def test_qk_prologue_partitions_over_a_four_device_data_mesh(v5e_devices):
    """``mistral-7b.fsdp4``'s case: the batch over four chips, one example a
    chip. The call shard_maps itself, rows are independent, and the only
    thing exchanged is the sum of the scale gradients."""
    import numpy as np

    mesh = Mesh(np.asarray(v5e_devices).reshape(4), ("data",))
    rows, whole = NamedSharding(mesh, P("data")), NamedSharding(mesh, P())
    aval = lambda shape, dtype, s: jax.ShapeDtypeStruct(shape, dtype, sharding=s)
    args = (aval((4, 4096, 32 * 128), jnp.bfloat16, rows), aval((4, 4096, 8 * 128), jnp.bfloat16, rows),
            aval((128,), jnp.float32, whole), aval((128,), jnp.float32, whole),
            aval((4096,), jnp.int32, whole))
    with jax.set_mesh(mesh):
        text = _compile(
            jax.grad(functools.partial(_prologue_loss, True), argnums=(0, 1, 2, 3)), *args)
    assert "all-gather" not in text and "all-to-all" not in text
    assert "bf16[1,4096,4096]" in text  # a chip's own example


@pytest.mark.parametrize("chunk,heads_per_step", [(64, 4), (128, 4), (64, 8)])
def test_the_kda_kernels_compile_at_the_cells_operand(v5e_devices, chunk, heads_per_step):
    """``ops/kda.py`` at the Kimi-Linear cell's operand (one sequence of 16,384
    positions, 32 heads of 128 keys and values): ``kda_fwd`` and ``kda_bwd``,
    whose body is ``jax.vjp`` of the chunk's own body traced into the kernel,
    so what Mosaic has to take is also every transpose jax makes of it: the
    scan's sublane rolls and tile moves among them. The pairs are those
    ``scripts/microbench_kda.py``'s sweep times ((128, 8) is not one: its
    backward's spilled registers pass the kernel's 16 MiB of VMEM)."""
    from distributed_llm_training_benchmark_framework_tpu.ops import kda

    assert (kda.DEFAULT_CHUNK, kda.HEADS_PER_STEP) == (128, 4)  # the cell's pair is a case
    one = SingleDeviceSharding(v5e_devices[0])
    aval = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=one)
    wide = aval((1, 16384, 32, 128), jnp.bfloat16)

    def loss(q, k, v, g, beta):
        out = kda.kda(q, k, v, g, beta, chunk, interpret=False, heads_per_step=heads_per_step)
        return jnp.sum(out.astype(jnp.float32))

    text = _compile(jax.grad(loss, argnums=(0, 1, 2, 3, 4)), wide, wide, wide,
                    aval((1, 16384, 32, 128), jnp.float32), aval((1, 16384, 32), jnp.float32))
    assert text.count('custom_call_target="tpu_custom_call"') == 2
    assert "kda_fwd" in text and "kda_bwd" in text


@pytest.mark.parametrize("epilogue", [False, True], ids=["bare", "qkv_prologue"])
def test_the_kda_convolutions_kernels_compile_at_the_cells_operand(v5e_devices, epilogue):
    """The three depthwise convolutions of a KDA layer over the 12,288 columns
    of q, k and v, forward and backward (XLA's grouped convolution at that many
    groups takes the chip's compiler minutes): bare as one call each way, and
    as a layer runs them, with SiLU and q's and k's l2norms inside, a call a
    third each way, the backward's three writing one dx with no copy."""
    from distributed_llm_training_benchmark_framework_tpu.ops import short_conv

    one = SingleDeviceSharding(v5e_devices[0])
    aval = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    def loss(x, taps):
        out = (short_conv.qkv_prologue(x, taps, 32, interpret=False) if epilogue
               else (short_conv.causal_conv(x, taps, interpret=False),))
        return sum(jnp.sum(jnp.square(o.astype(jnp.float32))) for o in out)

    text = _compile(jax.grad(loss, argnums=(0, 1)),
                    aval((1, 16384, 12288), jnp.bfloat16), aval((4, 12288), jnp.float32))
    calls = 3 if epilogue else 1
    assert text.count('custom_call_target="tpu_custom_call"') == 2 * calls
    assert "kda_conv_fwd" in text and "kda_conv_bwd" in text
    assert " copy(" not in text and " concatenate(" not in text.replace("f32[4,", "")


def test_the_kimi_cells_stack_compiles_a_layer_of_each_kind(v5e_devices, monkeypatch):
    """A KDA layer with a routed MLP and the NoPE latent-attention layer at the
    cell's widths and its 16,384 positions, forward and backward under the
    cell's remat policy: the recurrence's two kernels, the flash pair at
    192 / 128, the held experts' grouped matmuls."""
    from distributed_llm_training_benchmark_framework_tpu.models import tinygpt
    from distributed_llm_training_benchmark_framework_tpu.models.tinygpt import TinyGPTConfig

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    config = TinyGPTConfig(
        vocab_size=20480, n_embd=2304, n_head=32, n_layer=2, block_size=16384, dropout=0.0,
        causal=True, attention_impl="flash", scan_layers=False, norm="rmsnorm", pos_embed="rope",
        mlp_act="swiglu", mlp_hidden=1024, bias=False, tie_embeddings=False, kv_lora_rank=512,
        qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128, mla_nope=True, n_experts=256,
        expert_top_k=8, capacity_factor=None, router_score="sigmoid", routed_scaling_factor=2.446,
        n_shared_experts=1, experts_held=(0, 8), held_rows_factor=1.5,
        remat="full_keep_kernels", layer_types=("kda", "global"), kda_heads=32, kda_head_dim=128)
    one = SingleDeviceSharding(v5e_devices[0])
    shapes = jax.eval_shape(lambda k: tinygpt.init_params(config, k), jax.random.key(0))
    params = jax.tree.map(lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one), shapes)
    x = jax.ShapeDtypeStruct((1, 16384, 2304), jnp.bfloat16, sharding=one)

    def loss(params, x):
        y, _ = tinygpt.apply_layers(config, params, x)
        return jnp.sum(y.astype(jnp.float32))

    text = _compile(jax.grad(loss), params, x)
    for name in ("kda_fwd", "kda_bwd", "kda_conv_fwd", "kda_conv_bwd", "flash_fwd",
                 "flash_bwd_fused", "jit(gmm)", "jit(tgmm)"):
        assert name in text, name
    assert text.count("kda_fwd") >= 1 and "attention/kda/kda_core" in text


def test_the_ssd_kernels_compile_at_the_cells_operand(v5e_devices):
    """``ops/ssd.py`` at the Nemotron cell's operand (one sequence of 16,384
    positions, 64 heads of 64 channels in 8 groups over a state of 128, x | B |
    C as one array of 6144 columns): ``ssd_fwd`` and ``ssd_bwd``, whose body is
    ``jax.vjp`` of the slab's own body traced into the kernel, a group of four
    128-lane slabs a grid step; the backward's three gradients joined once."""
    from distributed_llm_training_benchmark_framework_tpu.ops import ssd

    one = SingleDeviceSharding(v5e_devices[0])
    aval = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    def loss(xbc, dt, g):
        out = ssd.ssd_flat(xbc, dt, g, 64, 8, 64, 128, interpret=False)
        return jnp.sum(jnp.square(out.astype(jnp.float32)))

    text = _compile(jax.grad(loss, argnums=(0, 1, 2)), aval((1, 16384, 6144), jnp.bfloat16),
                    aval((1, 16384, 64), jnp.float32), aval((1, 16384, 64), jnp.float32))
    assert text.count('custom_call_target="tpu_custom_call"') == 2
    assert "ssd_fwd" in text and "ssd_bwd" in text


def test_the_convolution_with_bias_and_silu_compiles_at_the_cells_operand(v5e_devices):
    """``ops.short_conv.conv_silu`` over the 6144 columns of a Mamba-2 block's x | B |
    C: the convolution's two kernels with the bias as the row behind the taps
    and SiLU as their epilogue, one call each way."""
    from distributed_llm_training_benchmark_framework_tpu.ops import short_conv

    one = SingleDeviceSharding(v5e_devices[0])
    aval = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    def loss(x, taps, bias):
        return jnp.sum(jnp.square(short_conv.conv_silu(x, taps, bias, interpret=False).astype(jnp.float32)))

    text = _compile(jax.grad(loss, argnums=(0, 1, 2)), aval((1, 16384, 6144), jnp.bfloat16),
                    aval((4, 6144), jnp.float32), aval((6144,), jnp.float32))
    assert text.count('custom_call_target="tpu_custom_call"') == 2
    assert "kda_conv_fwd" in text and "kda_conv_bwd" in text


def test_the_nemotron_cells_stacks_compile_a_block_of_each_kind(v5e_devices, monkeypatch):
    """A Mamba-2 block, the attention block (32 query heads over 2 KV heads, no
    positions) and a routed block of relu2 experts at the cell's widths and its
    16,384 positions, forward and backward under the cell's remat policy: the
    scan's two kernels, the convolution's, the flash pair, the held experts'
    grouped matmuls at 2688 x 1856."""
    from distributed_llm_training_benchmark_framework_tpu.models import tinygpt
    from distributed_llm_training_benchmark_framework_tpu.models.tinygpt import TinyGPTConfig

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    config = TinyGPTConfig(
        vocab_size=16384, n_embd=2688, n_head=32, n_kv_head=2, head_width=128, n_layer=3,
        block_size=16384, dropout=0.0, causal=True, attention_impl="flash", scan_layers=False,
        norm="rmsnorm", pos_embed="none", mlp_act="relu2", mlp_hidden=1856, bias=False,
        tie_embeddings=False, n_experts=128, expert_top_k=6, capacity_factor=None,
        router_score="sigmoid", routed_scaling_factor=2.5, router_aux_coef=0.0,
        n_shared_experts=1, shared_expert_hidden=3712, experts_held=(0, 8), held_rows_factor=3.0,
        remat="full_keep_kernels", layer_types=("ssd", "global", "mlp"), block_halves=True,
        ssd_heads=64, ssd_head_dim=64, ssd_groups=8, ssd_state=128)
    one = SingleDeviceSharding(v5e_devices[0])
    shapes = jax.eval_shape(lambda k: tinygpt.init_params(config, k), jax.random.key(0))
    params = jax.tree.map(lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one), shapes)
    x = jax.ShapeDtypeStruct((1, 16384, 2688), jnp.bfloat16, sharding=one)

    def loss(params, x):
        y, _ = tinygpt.apply_layers(config, params, x)
        return jnp.sum(y.astype(jnp.float32))

    text = _compile(jax.grad(loss), params, x)
    for name in ("ssd_fwd", "ssd_bwd", "kda_conv_fwd", "kda_conv_bwd", "flash_fwd",
                 "flash_bwd_fused", "jit(gmm)", "jit(tgmm)"):
        assert name in text, name
    assert "attention/ssd/ssd_core" in text and "attention/global" in text


def test_the_gated_convolution_compiles_at_the_cells_operand(v5e_devices):
    """``ops.short_conv.gated_conv`` over the (2, 16384, 6144) operand of a gated
    short-convolution mixer, B | C | x~ of 2048 columns each and 3 taps: one
    Mosaic call each way, the three thirds found by the calls' block specs."""
    from distributed_llm_training_benchmark_framework_tpu.ops import short_conv

    one = SingleDeviceSharding(v5e_devices[0])
    aval = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    def loss(bcx, taps):
        return jnp.sum(jnp.square(short_conv.gated_conv(bcx, taps, interpret=False).astype(jnp.float32)))

    text = _compile(jax.grad(loss, argnums=(0, 1)), aval((2, 16384, 6144), jnp.bfloat16),
                    aval((3, 2048), jnp.float32))
    assert text.count('custom_call_target="tpu_custom_call"') == 2
    assert "sconv_fwd" in text and "sconv_bwd" in text


def test_the_lfm2_cells_stacks_compile_a_layer_of_each_kind(v5e_devices, monkeypatch):
    """A gated-convolution layer before the dense 7168 MLP, an attention layer
    (32 query heads over 8 KV heads of 64 under rotary and per-head QK-norm,
    causal) and a gated-convolution layer before 8 held experts of 32 at 2048 x
    1792, two sequences of 16,384, forward and backward under the cell's remat
    policy: the gated convolution's two kernels, the flash pair at head width
    64 with k and v at their own head count, the held experts' grouped
    matmuls."""
    from distributed_llm_training_benchmark_framework_tpu.models import tinygpt
    from distributed_llm_training_benchmark_framework_tpu.models.tinygpt import TinyGPTConfig

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    config = TinyGPTConfig(
        vocab_size=16384, n_embd=2048, n_head=32, n_kv_head=8, n_layer=3, block_size=16384,
        dropout=0.0, causal=True, attention_impl="flash", scan_layers=False, norm="rmsnorm",
        pos_embed="rope", rope_theta=1e6, qk_norm="head", mlp_act="swiglu", mlp_hidden=1792,
        bias=False, tie_embeddings=True, n_experts=32, expert_top_k=4, capacity_factor=None,
        router_score="sigmoid", router_aux_coef=0.0, first_k_dense=1, dense_mlp_hidden=7168,
        experts_held=(0, 8), held_rows_factor=1.5, remat="full_keep_kernels",
        layer_types=("conv", "global", "conv"), conv_taps=3)
    one = SingleDeviceSharding(v5e_devices[0])
    shapes = jax.eval_shape(lambda k: tinygpt.init_params(config, k), jax.random.key(0))
    params = jax.tree.map(lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one), shapes)
    x = jax.ShapeDtypeStruct((2, 16384, 2048), jnp.bfloat16, sharding=one)

    def loss(params, x):
        y, _ = tinygpt.apply_layers(config, params, x)
        return jnp.sum(y.astype(jnp.float32))

    text = _compile(jax.grad(loss), params, x)
    for name in ("sconv_fwd", "sconv_bwd", "flash_fwd", "flash_bwd_fused", "jit(gmm)",
                 "jit(tgmm)"):
        assert name in text, name
    assert "attention/conv/sconv_core" in text and "attention/global" in text
