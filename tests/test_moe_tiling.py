"""The tiling of the experts' grouped matmuls (``models/moe.py::gmm_tiling``):
a function of each call's own (m, k, n) whose tiles divide the widths they
multiply, and the expert stack under it against a dense per-expert reference.

CPU only: megablox runs interpreted. What the chip's compiler says of the same
tiles at the benchmark's widths is ``tests/test_tpu_compile.py``'s.
"""

import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_llm_training_benchmark_framework_tpu.models import moe

# (hidden D, expert width F, rows a layer's grouped matmuls see) of the
# benchmark's four routed cells, and the tile area over operand area the one
# constant (512, 1024, 1024), clipped by the forward's widths, gave each of the
# stack's six calls (ISSUE 42's table): gate+up, down.
CELLS = {
    "olmoe": (2048, 1024, 65536, (1.0, 1.0, 1.0), (1.0, 1.0, 1.0)),
    "deepseek": (2048, 1408, 18432, (12 / 11,) * 3, (16 / 11,) * 3),
    "sdar": (2048, 768, 40960, (4 / 3,) * 3, (1.0, 1.5, 1.0)),
    "mellum": (2304, 896, 49152, (32 / 21,) * 3, (4 / 3,) * 3),
}
KINDS = ["forward", "rows_gradient", "weights_gradient"]


def _call(kind, k, n):
    """What megablox hands the tiling for a forward (k, n) matmul's three
    calls: the rows' gradient contracts over n."""
    return (n, k) if kind == "rows_gradient" else (k, n)


@pytest.mark.parametrize("kind", KINDS)
@pytest.mark.parametrize("matmul", ["gate_up", "down"])
@pytest.mark.parametrize("cell", sorted(CELLS))
def test_the_tiles_divide_the_widths_and_fit(cell, matmul, kind):
    D, F, m, up_area, down_area = CELLS[cell]
    forward = (D, 2 * F) if matmul == "gate_up" else (F, D)
    k, n = _call(kind, *forward)
    tm, tk, tn = moe.gmm_tiling(m, k, n)
    assert tm == 512
    assert tk % 128 == 0 and tn % 128 == 0 and k % tk == 0 and n % tn == 0
    assert moe._tiles_vmem(tm, tk, tn) <= moe._GMM_VMEM
    assert moe.gmm_tile_fill(m, k, n) == 1.0
    if cell == "olmoe":  # PR 26's sweep stands: the same Mosaic modules as before
        assert (tm, tk, tn) == (512, 1024, 1024)
    # The constant the program had: one tuple for all three calls, clipped by
    # the forward's widths.
    old = (512, min(1024, forward[0]), min(1024, forward[1]))
    area = (up_area if matmul == "gate_up" else down_area)[KINDS.index(kind)]
    assert 1 / moe.gmm_tile_fill(m, k, n, old) == pytest.approx(area)


@pytest.mark.parametrize("m, k, n, want", [
    (640, 64, 96, (128, 64, 96)),  # no multiple of 128 divides: the whole width, as before
    (512, 1100, 2048, (512, 1024, 1024)),  # ... or the tile the kernel pads
    (4096, 7168, 4096, (512, 1024, 1024)),
    (4096, 4096, 14336, (512, 1024, 1024)),
])
def test_widths_no_tile_divides_keep_the_tile_the_kernel_pads(m, k, n, want):
    assert moe.gmm_tiling(m, k, n) == want


def test_the_sum_back_to_tokens_takes_a_column_tile_that_divides():
    assert moe._sum_tiling(49152, 16384, 2304) == (256, 128, 2304)
    for rows, tokens in ((18432, 16384), (40960, 16384)):  # PR 32's sweep stands
        assert moe._sum_tiling(rows, tokens, 2048) == (256, 128, 2048)
    assert moe._sum_tiling(96, 24, 64) == (32, 8, 64)


def _dense_experts(rows, wgu, wd, sizes):
    """The expert stack one expert at a time, plain f32 matmuls."""
    width, out, start = wd.shape[1], [], 0
    for e, size in enumerate(sizes):
        gu = jnp.dot(rows[start:start + size], wgu[e], precision="highest")
        out.append(jnp.dot(jax.nn.silu(gu[:, :width]) * gu[:, width:], wd[e], precision="highest"))
        start += size
    return jnp.concatenate(out)


SMALL = {
    # hidden, width, rows an expert (uneven, one empty), (contraction, columns) aimed at
    "d384_f128": (384, 128, (200, 0, 312, 128), None),
    "d256_f384": (256, 384, (1, 255, 0, 384), None),
    "d384_f128_split": (384, 128, (130, 254, 0, 128), (128, 128)),  # k and n in several tiles
}


PARTS = ["forward", "rows_gradient", "gate_up_gradient", "down_gradient"]


def _calls(D, F):
    """(k, n) of both matmuls and of their rows' gradients."""
    return (D, 2 * F), (2 * F, D), (F, D), (D, F)


def _small_case(D, F, sizes):
    """The program's stack and the reference's -> (got, want) a part, and the
    tilings the stack's calls took."""
    keys = jax.random.split(jax.random.key(42), 4)
    rows = jax.random.normal(keys[0], (sum(sizes), D), jnp.float32)
    wgu = 0.1 * jax.random.normal(keys[1], (len(sizes), D, 2 * F), jnp.float32)
    wd = 0.1 * jax.random.normal(keys[2], (len(sizes), F, D), jnp.float32)
    cotangent = jax.random.normal(keys[3], (sum(sizes), D), jnp.float32)
    c = types.SimpleNamespace(compute_dtype=jnp.float32, mlp_dim=F)
    counts = jnp.asarray(sizes, jnp.int32)

    def program(rows, wgu, wd):
        out = moe._experts_dropless(c, {"moe_wgu": wgu, "moe_wd": wd}, rows, counts)
        return jnp.sum(out * cotangent), out

    def reference(rows, wgu, wd):
        out = _dense_experts(rows, wgu, wd, sizes)
        return jnp.sum(out * cotangent), out

    both = lambda f: jax.value_and_grad(f, argnums=(0, 1, 2), has_aux=True)(rows, wgu, wd)
    ((_, got), got_grads), ((_, want), want_grads) = both(program), both(reference)
    tilings = [moe.gmm_tiling(sum(sizes), k, n) for k, n in _calls(D, F)]
    return dict(zip(PARTS, zip((got,) + got_grads, (want,) + want_grads))), tilings


@pytest.fixture(scope="module")
def small_results():
    """Each small case once: forward and the three gradients."""
    results = {}
    for name, (D, F, sizes, aim) in SMALL.items():
        with pytest.MonkeyPatch.context() as patch:
            if aim is not None:  # the tiling is a static argument of megablox's jits
                patch.setattr(moe, "_GMM_TILE", aim)
                jax.clear_caches()
            results[name] = _small_case(D, F, sizes)
        if aim is not None:
            jax.clear_caches()
    return results


@pytest.mark.parametrize("part", PARTS)
@pytest.mark.parametrize("name", sorted(SMALL))
def test_the_expert_stack_matches_a_dense_reference(small_results, name, part):
    pairs, tilings = small_results[name]
    D, F, sizes, aim = SMALL[name]
    for (_, tk, tn), (k, n) in zip(tilings, _calls(D, F)):
        assert k % tk == 0 and n % tn == 0
        assert aim is None or (tk, tn) == aim
    got, want = pairs[part]
    assert got.shape == want.shape
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4 * float(jnp.max(jnp.abs(want))))
    if part.endswith("_gradient") and part != "rows_gradient":
        # an expert without rows gets a gradient of zeros, not what its tile held
        assert not np.any(np.asarray(got[sizes.index(0)]))
