"""``ops/kda.py``: the chunked gated delta rule, its ``jnp`` path and its two
Pallas kernels (interpreted here), against the recurrence position by position
in float32 (the benchmark's reference, ``reference_kda.delta_rule``: one
``lax.scan`` step a position, no code of the op's): the output and all five
operands' gradients.

With float32 operands both sides differ by the order of summation only, a few
1e-7 of the result; the tolerance sits two orders above that. One case runs at
the strongest decay the model's initialisation allows (a rate of 16 a head and
a step of 0.1: a chunk of 64 accumulates -102, and e^102 is no float32): a
chunkwise form that takes its decays relative to the chunk's start overflows
there, this one forms no exponential of a positive sum.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_llm_training_benchmark_framework_tpu.ops import kda
from perfbench.harness import reference_kda

TOLERANCE = 2e-5
OPERANDS = ("q", "k", "v", "g", "beta")


def recurrent(q, k, v, g, beta, state_dtype="float32"):
    """The reference's scan over the positions, a sequence of the batch at a
    time, in float32. ``state_dtype``: its wrong model's, the state rounded
    after every position."""
    f32 = lambda x: x.astype(jnp.float32)
    rule = lambda *a: reference_kda.delta_rule({"state_dtype": state_dtype}, *a)
    return jax.vmap(rule)(f32(q), f32(k), f32(v), g, beta)


def operands(seq=256, heads=2, width=32, decay="seeded", dtype=jnp.float32, batch=1):
    """Operands as a KDA layer makes them: unit q and k, decays from a rate a
    head in [1, 16] times a softplus, beta a sigmoid."""
    ks = jax.random.split(jax.random.key(0), 6)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    shape = (batch, seq, heads, width)
    q, k = unit(jax.random.normal(ks[0], shape)), unit(jax.random.normal(ks[1], shape))
    v = jax.random.normal(ks[2], shape)
    if decay == "strongest":  # A 16, a step of 0.1, every channel
        g = jnp.full(shape, -16.0 * 0.1)
    else:
        rate = jnp.exp(jax.random.uniform(ks[3], (heads, 1), maxval=jnp.log(16.0)))
        g = -rate * jax.nn.softplus(jax.random.normal(ks[4], shape) - 2.0)
    beta = jax.nn.sigmoid(jax.random.normal(ks[5], shape[:3]))
    return q.astype(dtype), k.astype(dtype), v.astype(dtype), g, beta


def relative(got, want):
    got, want = got.astype(jnp.float32), want.astype(jnp.float32)
    return float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))


def out_and_grads(f, args):
    weights = jax.random.normal(jax.random.key(9), args[2].shape)
    loss = lambda *a: jnp.sum(f(*a).astype(jnp.float32) * weights)
    return f(*args), jax.grad(loss, argnums=tuple(range(5)))(*args)


@pytest.mark.parametrize("decay", ["seeded", "strongest"])
@pytest.mark.parametrize("path", ["jnp", "kernels"])
def test_output_and_all_five_gradients_match_the_recurrence(path, decay):
    width, interpret = (128, True) if path == "kernels" else (32, None)
    args = operands(width=width, decay=decay)
    with jax.default_matmul_precision("highest"):
        got, got_grads = out_and_grads(lambda *a: kda.kda(*a, chunk=64, interpret=interpret), args)
        want, want_grads = out_and_grads(recurrent, args)
    assert bool(jnp.all(jnp.isfinite(got)))
    assert relative(got, want) < TOLERANCE
    for name, g, w in zip(OPERANDS, got_grads, want_grads):
        assert bool(jnp.all(jnp.isfinite(g))) and relative(g, w) < TOLERANCE, name


def test_the_strongest_decay_is_the_e102_case():
    """What the case above guards against: over one chunk of 64 the decays
    sum to -102.4, and a form that divides by the chunk's running decay makes
    exp(102.4), which float32 does not hold."""
    g = operands(decay="strongest")[3]
    total = float(jnp.sum(g[0, :64, 0, 0]))
    assert total == pytest.approx(-102.4) and not np.isfinite(np.exp(np.float32(-total)))


@pytest.mark.parametrize("chunk", [16, 32, kda.DEFAULT_CHUNK])
def test_other_chunks_give_the_same(chunk):
    args = operands()
    with jax.default_matmul_precision("highest"):
        got, got_grads = out_and_grads(lambda *a: kda.kda(*a, chunk=chunk), args)
        want, want_grads = out_and_grads(recurrent, args)
    assert relative(got, want) < TOLERANCE
    assert max(relative(g, w) for g, w in zip(got_grads, want_grads)) < TOLERANCE


@pytest.mark.parametrize("heads_per_step", [1, 2, 4])
def test_the_kernels_walk_any_number_of_heads_a_step(heads_per_step):
    args = operands(seq=128, heads=4, width=128, batch=2)
    with jax.default_matmul_precision("highest"):
        got, got_grads = out_and_grads(
            lambda *a: kda.kda(*a, chunk=64, interpret=True, heads_per_step=heads_per_step), args)
        want, want_grads = out_and_grads(lambda *a: kda.kda(*a, chunk=64), args)  # the jnp path
    assert relative(got, want) < TOLERANCE
    assert max(relative(g, w) for g, w in zip(got_grads, want_grads)) < TOLERANCE


def test_bfloat16_operands_stay_within_their_rounding():
    args = operands(dtype=jnp.bfloat16)
    got, got_grads = out_and_grads(kda.kda, args)
    want, want_grads = out_and_grads(recurrent, args)
    assert got.dtype == jnp.bfloat16 and relative(got, want) < 0.02
    assert max(relative(g, w) for g, w in zip(got_grads, want_grads)) < 0.03


def test_a_bfloat16_state_is_another_result():
    """The state is float32 in the op and there is no switch for another: the
    wrong model lives in the reference (its state rounded to bfloat16 after
    every position), and moves the output by hundreds of times what the op
    and the float32 recurrence differ by."""
    args = operands(seq=512)
    with jax.default_matmul_precision("highest"):
        exact = recurrent(*args)
        rounded = relative(recurrent(*args, state_dtype="bfloat16"), exact)
        assert relative(kda.kda(*args), exact) < TOLERANCE
    assert rounded > 100 * TOLERANCE


def test_the_scale_is_an_argument_and_defaults_to_the_key_width():
    args = operands(seq=kda.DEFAULT_CHUNK)
    with jax.default_matmul_precision("highest"):
        assert relative(kda.kda(*args, scale=1.0) * 32 ** -0.5, kda.kda(*args)) < 1e-6


@pytest.mark.parametrize("change, match", [
    (dict(seq=100), "not whole chunks of 64"),
    (dict(g_dtype=jnp.bfloat16), "log-decays are float32"),
    (dict(width=32, interpret=True), "whole 128-lane tiles"),
    (dict(chunk=48), "power of two"),
])
def test_what_the_op_refuses_by_name(change, match):
    """A sequence that is not whole chunks is refused, not padded."""
    q, k, v, g, beta = operands(seq=change.get("seq", 192 if "chunk" in change else 128),
                                width=change.get("width", 32))
    with pytest.raises(ValueError, match=match):
        kda.kda(q, k, v, g.astype(change.get("g_dtype", jnp.float32)), beta,
                chunk=change.get("chunk", 64), interpret=change.get("interpret"))


@pytest.mark.parametrize("chunk", [2, 16, 64, 128])
def test_the_levels_cut_the_lower_triangle_once_and_sum_only_decays(chunk):
    """The quadrants of all levels are the strict lower triangle, each pair
    once; a row of ``sums`` holds 0s and 1s only, so every exponent the chunk
    forms is a sum of log-decays, which are <= 0: none is positive. And a
    pair's two factors cover exactly the positions between them."""
    levels = kda._levels(chunk)
    assert set(np.unique(levels.sums)) <= {0.0, 1.0}
    covered = levels.quadrant.sum(0)
    np.testing.assert_array_equal(covered, np.tril(np.ones((chunk, chunk)), -1))
    assert levels.sums.shape == ((2 + int(np.log2(chunk))) * chunk + 8, chunk)
    for level, quadrant in enumerate(levels.quadrant):
        rows = levels.sums[(2 + level) * chunk:(3 + level) * chunk]
        for r, i in zip(*np.nonzero(quadrant)):
            between = np.zeros(chunk)
            between[i + 1:r + 1] = 1  # exp(G_r - G_i) is over positions i+1..r
            np.testing.assert_array_equal(rows[r] + rows[i], between)
            assert levels.upper[level][r] == 1 and levels.lower[level][i] == 1


def test_the_sums_of_decays_are_exact_in_three_passes():
    """``_exact_mm``: 0s and 1s times float32 as three bfloat16 pieces is the
    float32 sum, where one bfloat16 pass loses 16 of the 24 bits."""
    g = -jnp.exp(jax.random.normal(jax.random.key(1), (64, 8)) * 2.0)
    m = jnp.asarray(kda._levels(64).sums)
    want = np.asarray(m, np.float64) @ np.asarray(g, np.float64)
    got = kda._exact_mm(m, g, (1, 0))
    np.testing.assert_allclose(got, want, rtol=2e-6)
    one_pass = kda._mm(m, g, (1, 0), jnp.bfloat16)
    assert float(jnp.max(jnp.abs(one_pass - want) / jnp.abs(want).clip(1e-6))) > 1e-3


def test_the_kernels_are_two_calls_by_their_names():
    args = operands(seq=128, width=128)
    loss = lambda *a: jnp.sum(kda.kda(*a, interpret=True))
    text = str(jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2, 3, 4)))(*args))
    assert text.count("name=kda_fwd") == 1 and text.count("name=kda_bwd") == 1
    assert "pallas_call" not in str(jax.make_jaxpr(lambda *a: kda.kda(*a))(*args))


@pytest.mark.parametrize("seq", [512, 1536])
def test_the_convolutions_kernels_are_the_equation_tap_by_tap(seq):
    """``causal_conv``'s two kernels (interpreted) against y_t = sum_i taps_i
    x_{t-3+i} written with numpy, over one tile of rows and over three (a tile
    reads the 8 rows before it forward and the 8 after it backward), and
    against XLA's grouped convolution, which is the ``jnp`` path."""
    x = jax.random.normal(jax.random.key(0), (2, seq, 256))
    taps = jax.random.uniform(jax.random.key(1), (4, 256), minval=-0.5, maxval=0.5)
    weights = jax.random.normal(jax.random.key(2), x.shape)
    want = np.zeros(x.shape)
    for i in range(4):
        d = 3 - i
        want[:, d:] += np.asarray(x)[:, :seq - d] * np.asarray(taps)[i]
    got = kda.causal_conv(x, taps, interpret=True)
    np.testing.assert_allclose(got, want, atol=2e-6)
    np.testing.assert_allclose(kda.causal_conv(x, taps), want, atol=2e-6)
    loss = lambda interpret: lambda x, t: jnp.sum(kda.causal_conv(x, t, interpret=interpret) * weights)
    (dx, dtaps), (dx_want, dtaps_want) = (jax.grad(loss(i), (0, 1))(x, taps) for i in (True, None))
    np.testing.assert_allclose(dx, dx_want, atol=2e-6)
    np.testing.assert_allclose(dtaps, dtaps_want, rtol=1e-4, atol=1e-3)
    assert "kda_conv_fwd" in str(jax.make_jaxpr(lambda x, t: kda.causal_conv(x, t, interpret=True))(x, taps))


def test_the_convolution_takes_any_width_on_its_jnp_path():
    x = jax.random.normal(jax.random.key(0), (1, 30, 48))  # no whole tile of rows or lanes
    taps = jax.random.normal(jax.random.key(1), (4, 48))
    got = kda.causal_conv(x, taps, interpret=True)  # falls back: the kernels take whole tiles
    assert "pallas_call" not in str(jax.make_jaxpr(
        lambda x, t: kda.causal_conv(x, t, interpret=True))(x, taps))
    assert float(jnp.abs(got[:, 0] - x[:, 0] * taps[3]).max()) < 1e-6  # zeros before the sequence
