"""``ops/ssd.py`` (Mamba-2's scalar-decay state-space scan, chunkwise) against
the recurrence position by position in float32: the ``jnp`` path at any widths
and the two kernels interpreted at whole 128-lane slabs (two heads of 64
channels side by side), output, the state and every gradient; and the
convolution with its bias and SiLU (``ops/short_conv.py::conv_silu``) against XLA's.

Both sides compute in float32 here, so they differ by the order of summation
only; the tolerances sit two orders above that.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from distributed_llm_training_benchmark_framework_tpu.ops import short_conv, ssd

# (head width P, state N, heads, groups, positions): the jnp path's and the kernels'
SMALL = (16, 16, 4, 2, 64)
SLABS = (64, 128, 4, 2, 256)  # a group of two heads: one 128-lane slab sharing B and C
TOLERANCE = 2e-4  # of the largest value, float32 against float32


def operands(widths, decay="seeded", dtype=jnp.float32, batch=2):
    """xbc (B, S, H P + 2 G N), dt (B, S, H), g (B, S, H) <= 0. ``decay``:
    ``seeded`` rates of e^-1 to e^2 a unit of dt, ``near_one`` a = exp(g) within
    1e-4 of 1, ``near_zero`` a under e^-6, e^-50 in the middle (a state forgotten a position)."""
    P, N, H, G, S = widths
    keys = jax.random.split(jax.random.key(0), 4)
    xbc = (0.5 * jax.random.normal(keys[0], (batch, S, H * P + 2 * G * N))).astype(dtype)
    dt = jax.nn.softplus(jax.random.normal(keys[1], (batch, S, H)))
    rate = {"seeded": jnp.exp(jax.random.uniform(keys[2], (H,), minval=-1.0, maxval=2.0)),
            "near_one": jnp.full((H,), 1e-5), "near_zero": jnp.full((H,), 60.0)}[decay]
    return xbc, dt, -(dt + 0.1) * rate


def positions(xbc, dt, g, widths):
    """The recurrence a position at a time, float32 -> (y (B, S, H P), the
    state after the last position (B, H, P, N))."""
    P, N, H, G, S = widths
    B = xbc.shape[0]
    by_head = lambda t: jnp.repeat(t.reshape(B, S, G, N), H // G, axis=2).astype(jnp.float32)
    x = xbc[..., :H * P].reshape(B, S, H, P).astype(jnp.float32)
    Bm, Cm = by_head(xbc[..., H * P:H * P + G * N]), by_head(xbc[..., H * P + G * N:])

    def step(state, t):
        x_t, B_t, C_t, dt_t, g_t = t
        state = (jnp.exp(g_t)[..., None, None] * state
                 + (dt_t[..., None] * x_t)[..., None] * B_t[..., None, :])
        return state, jnp.einsum("bhpn,bhn->bhp", state, C_t, precision="highest")

    last, y = lax.scan(step, jnp.zeros((B, H, P, N)),
                       tuple(jnp.moveaxis(a, 1, 0) for a in (x, Bm, Cm, dt, g)))
    return jnp.moveaxis(y, 0, 1).reshape(B, S, H * P), last


def run(xbc, dt, g, widths, chunk, interpret, **kw):
    P, N, H, G, _ = widths
    return ssd.ssd_flat(xbc, dt, g, H, G, P, chunk, interpret=interpret, **kw)


def relative(got, want):
    return float(jnp.max(jnp.abs(got.astype(jnp.float32) - want)) / jnp.max(jnp.abs(want)))


PATHS = {"jnp": (SMALL, None, 16), "kernels": (SLABS, True, 128)}


@pytest.mark.parametrize("decay", ["seeded", "near_one", "near_zero"])
@pytest.mark.parametrize("path", sorted(PATHS))
def test_output_and_every_gradient_match_the_recurrence(path, decay):
    widths, interpret, chunk = PATHS[path]
    xbc, dt, g = operands(widths, decay)
    weight = jax.random.normal(jax.random.key(5), (*xbc.shape[:2], widths[0] * widths[2]))
    with jax.default_matmul_precision("highest"):
        got = run(xbc, dt, g, widths, chunk, interpret)
        want, _ = positions(xbc, dt, g, widths)
        assert relative(got, want) < TOLERANCE
        grads = jax.grad(lambda *a: jnp.sum(run(*a, widths, chunk, interpret) * weight),
                         (0, 1, 2))(xbc, dt, g)
        wants = jax.grad(lambda *a: jnp.sum(positions(*a, widths)[0] * weight),
                         (0, 1, 2))(xbc, dt, g)
    # g's gradient is the reverse running sum of the sums' own: where every a is
    # near 0 it is a sum of terms that cancel to 1e-4 of themselves, in float32
    loose = 10 * TOLERANCE if decay == "near_zero" else TOLERANCE
    for name, a, b in zip(("xbc", "dt", "g"), grads, wants):
        assert bool(jnp.all(jnp.isfinite(a))), name
        assert relative(a, b) < (loose if name == "g" else TOLERANCE), name


@pytest.mark.parametrize("path, chunk", [("jnp", 8), ("jnp", 32), ("kernels", 64)])
def test_other_chunks_give_the_same(path, chunk):
    widths, interpret, _ = PATHS[path]
    xbc, dt, g = operands(widths)
    with jax.default_matmul_precision("highest"):
        assert relative(run(xbc, dt, g, widths, chunk, interpret),
                        positions(xbc, dt, g, widths)[0]) < TOLERANCE


@pytest.mark.parametrize("path", sorted(PATHS))
def test_padding_adds_nothing_and_its_chunk_is_entered_with_the_final_state(path):
    """The rule for a sequence that is not whole chunks: it is refused, and a
    caller pads it with g = 0 and dt = 0, which leave the state as it is: the
    output over the sequence is unchanged, and the state the padding's chunk is
    entered with is the recurrence's after the last position."""
    widths, interpret, chunk = PATHS[path]
    P, N, H, G, S = widths
    xbc, dt, g = operands(widths)
    pad = lambda t: jnp.concatenate([t, jnp.zeros((t.shape[0], chunk, t.shape[2]), t.dtype)], 1)
    with jax.default_matmul_precision("highest"):
        y, states = run(pad(xbc), pad(dt), pad(g), widths, chunk, interpret, final_state=True)
        want, last = positions(xbc, dt, g, widths)
    assert relative(y[:, :S], want) < TOLERANCE and float(jnp.abs(y[:, S:]).max()) == 0.0
    entered = states[:, :, -1].reshape(xbc.shape[0], H, P, N)  # (B, G, N, Hg P, N) -> the last chunk's
    assert relative(entered, last) < TOLERANCE
    with pytest.raises(ValueError, match="not whole chunks"):
        run(xbc[:, :S - 3], dt[:, :S - 3], g[:, :S - 3], widths, chunk, interpret)


def test_the_heads_of_a_group_share_b_and_c_and_no_other_groups():
    """Head h reads group h // (H / groups): with the second group's B zeroed
    its two heads give nothing, and the first group's are untouched."""
    P, N, H, G, S = SMALL
    xbc, dt, g = operands(SMALL)
    cut = xbc.at[..., H * P + N:H * P + 2 * N].set(0.0)  # group 1's B
    y, y_cut = (run(t, dt, g, SMALL, 16, None) for t in (xbc, cut))
    half = H // G * P
    np.testing.assert_array_equal(np.asarray(y[..., :half]), np.asarray(y_cut[..., :half]))
    assert float(jnp.abs(y_cut[..., half:]).max()) == 0.0 and float(jnp.abs(y[..., half:]).max()) > 0


def test_bfloat16_operands_stay_within_their_rounding():
    xbc, dt, g = operands(SLABS, dtype=jnp.bfloat16)
    got = run(xbc, dt, g, SLABS, 128, True)
    with jax.default_matmul_precision("highest"):
        want, _ = positions(xbc, dt, g, SLABS)
    assert got.dtype == jnp.bfloat16 and 1e-4 < relative(got, want) < 2e-2


def test_the_running_sums_are_float32_non_positive_and_restart_at_every_chunk():
    _, _, g = operands(SMALL, "near_zero")
    G = ssd.chunk_sums(g, 16)
    assert G.dtype == jnp.float32 and float(G.max()) <= 0.0
    np.testing.assert_allclose(np.asarray(G[:, 16]), np.asarray(g[:, 16]))  # a chunk's first: g itself
    np.testing.assert_allclose(np.asarray(G[:, 15]), np.asarray(g[:, :16].sum(1)), rtol=1e-5)


def test_the_kernels_are_two_calls_by_their_names():
    xbc, dt, g = operands(SLABS)
    jaxpr = str(jax.make_jaxpr(jax.grad(
        lambda x: jnp.sum(run(x, dt, g, SLABS, 128, True))))(xbc))
    assert jaxpr.count("name=ssd_fwd") == 1 and jaxpr.count("name=ssd_bwd") == 1
    assert ssd.SSD_RESIDUAL_NAMES == ("ssd_out", "ssd_states")
    assert "name=ssd_out" in jaxpr and "name=ssd_states" in jaxpr


def test_the_kernels_take_whole_slabs_and_the_jnp_path_the_rest():
    assert ssd.fits(64, 128, 64, 8) and ssd.fits(128, 128, 8, 2)
    assert not ssd.fits(64, 64, 64, 8) and not ssd.fits(48, 128, 64, 8)
    assert not ssd.fits(64, 128, 8, 8)  # one head a group: half a slab


@pytest.mark.parametrize("change, match", [
    (dict(g=jnp.bfloat16), "float32"),
    (dict(columns=-1), "H x P \\+ 2 x groups x N"),
    (dict(interpret=True), "whole 128-lane slabs"),
])
def test_what_the_op_refuses_by_name(change, match):
    P, N, H, G, S = SMALL
    xbc, dt, g = operands(SMALL)
    if "g" in change:
        g = g.astype(change["g"])
    if "columns" in change:
        xbc = xbc[..., :-1]
    with pytest.raises(ValueError, match=match):
        ssd.ssd_flat(xbc, dt, g, H, G, P, 16, interpret=change.get("interpret"))


def conv_operands(seq, width, dtype=jnp.float32):
    keys = jax.random.split(jax.random.key(7), 4)
    x = jax.random.normal(keys[0], (2, seq, width)).astype(dtype)
    taps = jax.random.uniform(keys[1], (4, width), minval=-0.5, maxval=0.5)
    bias = jax.random.uniform(keys[2], (width,), minval=-0.5, maxval=0.5)
    return x, taps, bias, jax.random.normal(keys[3], (2, seq, width))


def conv_by_the_equation(x, taps, bias):
    S = x.shape[1]
    padded = jnp.pad(x.astype(jnp.float32), ((0, 0), (3, 0), (0, 0)))
    return jax.nn.silu(sum(padded[:, i:i + S] * taps[i] for i in range(4)) + bias)


@pytest.mark.parametrize("interpret", [None, True], ids=["xla", "kernels"])
def test_the_convolution_with_bias_and_silu_is_the_equation_and_its_gradients(interpret):
    x, taps, bias, weight = conv_operands(seq=1032, width=256)  # three tiles of rows, the last short
    got = short_conv.conv_silu(x, taps, bias, interpret=interpret)
    assert relative(got, conv_by_the_equation(x, taps, bias)) < 1e-5
    grads = jax.grad(lambda *a: jnp.sum(short_conv.conv_silu(*a, interpret=interpret) * weight),
                     (0, 1, 2))(x, taps, bias)
    wants = jax.grad(lambda *a: jnp.sum(conv_by_the_equation(*a) * weight), (0, 1, 2))(x, taps, bias)
    for name, a, b in zip(("x", "taps", "bias"), grads, wants):
        assert a.shape == b.shape and relative(a, b) < 1e-4, name


def test_the_convolutions_kernels_are_one_call_each_way_and_fall_back_off_whole_lanes():
    x, taps, bias, _ = conv_operands(seq=64, width=256)
    jaxpr = str(jax.make_jaxpr(jax.grad(
        lambda x: jnp.sum(short_conv.conv_silu(x, taps, bias, interpret=True))))(x))
    assert jaxpr.count("name=kda_conv_fwd") == 1 and jaxpr.count("name=kda_conv_bwd") == 1
    narrow = conv_operands(seq=64, width=96)[:3]
    assert "pallas_call" not in str(jax.make_jaxpr(
        lambda *a: short_conv.conv_silu(*a, interpret=True))(*narrow))
    assert relative(short_conv.conv_silu(*narrow, interpret=True), conv_by_the_equation(*narrow)) < 1e-5
