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

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from distributed_llm_training_benchmark_framework_tpu.models.mixers import (
    kda as kda_mixer,
)
from distributed_llm_training_benchmark_framework_tpu.ops import kda, short_conv
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


def decays(chunk, decay, width=8):
    """g (chunk, width) float32 as ``operands`` makes it, and in float64."""
    g = operands(seq=chunk, heads=1, width=width, decay=decay)[3][0, :, 0, :]
    return g, np.asarray(g, np.float64)


def sums_directly(g, level=None):
    """float64: what ``_decay_sums`` sums, a row at a time from g itself. A
    level (half-size s = 2^level): from its block's middle to an upper row,
    from after a lower row to the middle; ``None``: the inclusive running sum
    and the sum over the later rows."""
    C = g.shape[0]
    if level is None:
        return (np.stack([g[:r + 1].sum(0) for r in range(C)]),
                np.stack([g[r + 1:].sum(0) for r in range(C)]))
    s = 2 ** level
    rows = []
    for r in range(C):
        middle = (r // (2 * s)) * 2 * s + s
        rows.append(g[middle:r + 1].sum(0) if r >= middle else g[r + 1:middle].sum(0))
    return np.stack(rows)


@pytest.mark.parametrize("chunk", [2, 16, 64, 128])
def test_the_levels_cut_the_lower_triangle_once_and_halves_change_places(chunk):
    """The quadrants of all levels are the strict lower triangle, each pair
    once, a pair's row in its block's upper half and its column in the lower;
    and ``_sibling`` hands a row the row of the block's other half (r ^ s),
    by tiles (s >= 8) and by rolls (below) alike, and undoes itself."""
    quadrants = kda._quadrants(chunk)
    np.testing.assert_array_equal(quadrants.sum(0), np.tril(np.ones((chunk, chunk)), -1))
    rows = jnp.broadcast_to(jnp.arange(chunk, dtype=jnp.float32)[:, None], (chunk, 4))
    for level, quadrant in enumerate(quadrants):
        s = 2 ** level
        upper = np.asarray(kda._in_upper_half((chunk, 1), s))[:, 0]
        r, i = np.nonzero(quadrant)
        assert upper[r].all() and not upper[i].any() and (r // (2 * s) == i // (2 * s)).all()
        np.testing.assert_array_equal(kda._sibling(rows, s)[:, 0], np.arange(chunk) ^ s)
        np.testing.assert_array_equal(kda._sibling(kda._sibling(rows, s), s), rows)


@pytest.mark.parametrize("decay", ["seeded", "strongest"])
@pytest.mark.parametrize("chunk", [2, 16, 64, 128])
def test_the_sums_of_decays_are_the_float64_sums_and_never_positive(chunk, decay):
    """Every stage of the scan against float64 sums taken from g a row at a
    time: the running sum, the sum over the later rows, each level's sums and
    the chunk's total; none is positive, so no exponent the chunk forms is;
    and a pair's two factors cover exactly the positions between them."""
    g, g64 = decays(chunk, decay)
    running, after, levels, total = kda._decay_sums(g)
    want_running, want_after = sums_directly(g64)
    np.testing.assert_allclose(running, want_running, rtol=2e-6)
    np.testing.assert_allclose(after, want_after, rtol=2e-6)
    np.testing.assert_allclose(total, g64.sum(0, keepdims=True), rtol=2e-6)
    assert len(levels) == int(np.log2(chunk))
    for x in (running, after, total, *levels):
        assert float(jnp.max(x)) <= 0.0
    for level, (got, quadrant) in enumerate(zip(levels, kda._quadrants(chunk))):
        np.testing.assert_allclose(got, sums_directly(g64, level), rtol=2e-6)
        got = np.asarray(got, np.float64)
        for r, i in zip(*np.nonzero(quadrant)):  # exp(G_r - G_i) is over positions i+1..r
            np.testing.assert_allclose(got[r] + got[i], g64[i + 1:r + 1].sum(0), rtol=2e-6)


@pytest.mark.parametrize("chunk", [16, 128])
def test_the_sums_transpose_is_that_of_the_plain_sums(chunk):
    """``jax.vjp`` of the scan (the halves' exchange and the total's row have
    their transposes written out) against ``jax.vjp`` of the same sums as 0/1
    matrices times g."""
    g, _ = decays(chunk, "seeded")
    i, t = np.arange(chunk)[:, None], np.arange(chunk)[None, :]
    matrices = [t <= i, t > i]
    for level in range(int(np.log2(chunk))):
        s = 2 ** level
        middle, up = (i // (2 * s)) * 2 * s + s, (i % (2 * s)) >= s
        matrices.append(np.where(up, (t >= middle) & (t <= i), (t > i) & (t < middle)))
    matrices = jnp.asarray(np.stack(matrices), jnp.float32)

    def plain(g):
        running, after, *levels = jnp.einsum("lrt,td->lrd", matrices, g, precision="highest")
        return running, after, levels, jnp.sum(g, 0, keepdims=True)

    out, pull_back = jax.vjp(kda._decay_sums, g)
    want, want_pull_back = jax.vjp(plain, g)
    keys = iter(jax.random.split(jax.random.key(3), 64))
    cotangents = jax.tree.map(lambda x: jax.random.normal(next(keys), x.shape), want)
    assert jax.tree.structure(out) == jax.tree.structure(want)
    np.testing.assert_allclose(pull_back(cotangents)[0], want_pull_back(cotangents)[0],
                               rtol=1e-5, atol=1e-5)


def _equations(jaxpr, scope=""):
    """(name stack, equation) for every equation of a jaxpr and of the jaxprs
    inside its equations (whose name stacks are relative to it), a
    ``pallas_call``'s body aside."""
    for eqn in jaxpr.eqns:
        path = f"{scope}/{eqn.source_info.name_stack}"
        yield path, eqn
        if eqn.primitive.name == "pallas_call":
            continue
        for param in eqn.params.values():
            for inner in param if isinstance(param, (tuple, list)) else (param,):
                inner = getattr(inner, "jaxpr", inner)
                if hasattr(inner, "eqns"):
                    yield from _equations(inner, path)


def _dots(jaxpr):
    """Every ``dot_general`` of a jaxpr and of the jaxprs inside its equations."""
    return (eqn for _, eqn in _equations(jaxpr) if eqn.primitive.name == "dot_general")


def test_the_chunks_body_forms_no_product_for_its_sums_of_decays():
    """At the cell's chunk the body's matrix products are q k^T, a level's two
    (seven levels), the six T - T A T updates' two each, and W, U's two, o's
    two and S1's: 33, each of operands no taller than the chunk; its ``vjp``
    adds two transposes a product and no other. (The sums of decays were a
    1,160-row 0/1 product in three passes, and the same again transposed.)"""
    C, d = 128, 128
    q, k, v, g = (x[0, :, 0] for x in operands(seq=C, heads=1, width=d)[:4])
    body = functools.partial(kda._chunk, jnp.asarray(kda._quadrants(C)), d ** -0.5)
    args = (q, k, v, g, jnp.full((1, C), 0.5), jnp.zeros((d, d)))
    forward = list(_dots(jax.make_jaxpr(body)(*args).jaxpr))
    both = list(_dots(jax.make_jaxpr(
        lambda *a: jax.vjp(body, *a)[1]((jnp.ones((C, d)), jnp.ones((d, d)))))(*args).jaxpr))
    assert len(forward) == 33 and len(both) == 3 * 33
    for eqn in forward + both:
        assert max(x.aval.shape[0] for x in eqn.invars) <= C, eqn


def test_a_kernels_heads_share_one_trace_of_the_chunks_body(monkeypatch):
    """``_chunk`` is a ``jit``: a kernel's heads find one jaxpr of it (the
    backward's, under ``jax.vjp``'s trace, a second), and a second program
    with the same operand traces it no more (a cell's set-up holds the
    kernels dozens of times: traced a head at a time the scan cost more
    set-up than the product it replaced)."""
    products, mm = [], kda._mm  # the body's 33 at chunk 128, 25 at chunk 32: five levels
    monkeypatch.setattr(kda, "_mm", lambda *a: (products.append(1), mm(*a))[1])
    args = operands(seq=64, heads=4, width=128)
    op = lambda *a: kda.kda(*a, chunk=32, interpret=True, heads_per_step=4, scale=0.12345)
    loss = lambda *a: jnp.sum(op(*a))  # a scale no other test has: the body is traced anew
    jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2, 3, 4)))(*args)
    assert len(products) == 2 * 25  # not 2 x 4 heads x 25
    jax.make_jaxpr(jax.value_and_grad(loss, argnums=(0, 3)))(*args)
    assert len(products) == 2 * 25


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
    got = short_conv.causal_conv(x, taps, interpret=True)
    np.testing.assert_allclose(got, want, atol=2e-6)
    np.testing.assert_allclose(short_conv.causal_conv(x, taps), want, atol=2e-6)
    loss = lambda interpret: lambda x, t: jnp.sum(short_conv.causal_conv(x, t, interpret=interpret) * weights)
    (dx, dtaps), (dx_want, dtaps_want) = (jax.grad(loss(i), (0, 1))(x, taps) for i in (True, None))
    np.testing.assert_allclose(dx, dx_want, atol=2e-6)
    np.testing.assert_allclose(dtaps, dtaps_want, rtol=1e-4, atol=1e-3)
    assert "kda_conv_fwd" in str(jax.make_jaxpr(lambda x, t: short_conv.causal_conv(x, t, interpret=True))(x, taps))


def test_the_convolution_takes_any_width_on_its_jnp_path():
    x = jax.random.normal(jax.random.key(0), (1, 30, 48))  # no whole tile of rows or lanes
    taps = jax.random.normal(jax.random.key(1), (4, 48))
    got = short_conv.causal_conv(x, taps, interpret=True)  # falls back: the kernels take whole tiles
    assert "pallas_call" not in str(jax.make_jaxpr(
        lambda x, t: short_conv.causal_conv(x, t, interpret=True))(x, taps))
    assert float(jnp.abs(got[:, 0] - x[:, 0] * taps[3]).max()) < 1e-6  # zeros before the sequence


# ``qkv_prologue``: the convolution's kernels with their epilogue.

def prologue_operands(seq, dtype, heads=4, width=128, batch=2):
    """x as a projection's output with q's, k's and v's columns in that order,
    every head at a scale of its own (1 to 100: a head's sum that took in a
    neighbour's lanes would be another number by orders), taps, and a weight a
    result for the loss."""
    ks = jax.random.split(jax.random.key(3), 5)
    scale = jnp.repeat(10.0 ** jax.random.uniform(ks[0], (3 * heads,), maxval=2.0), width)
    x = (jax.random.normal(ks[1], (batch, seq, 3 * heads * width)) * scale).astype(dtype)
    taps = jax.random.uniform(ks[2], (4, 3 * heads * width), minval=-0.5, maxval=0.5)
    shape = (batch, seq, heads * width)
    return x, taps, [jax.random.normal(k, shape) for k in jax.random.split(ks[3], 3)]


def chain(x, taps, heads):
    """What stands between the projection and the recurrence, written out in
    float32: y_t = sum_i taps_i x_{t-3+i} tap by tap with zeros before the
    sequence, SiLU, and on q's and k's thirds each head over its l2 norm with
    1e-6 under the root; v's third SiLU alone."""
    (B, S, C), K = x.shape, taps.shape[0]
    xf = jnp.pad(x.astype(jnp.float32), ((0, 0), (K - 1, 0), (0, 0)))
    y = sum(xf[:, i:i + S] * taps[i] for i in range(K))
    a = (y * jax.nn.sigmoid(y)).reshape(B, S, 3, heads, -1)
    unit = a * lax.rsqrt(jnp.sum(a * a, axis=-1, keepdims=True) + 1e-6)
    return tuple(t.reshape(B, S, -1) for t in (unit[:, :, 0], unit[:, :, 1], a[:, :, 2]))


def prologue_results(f, x, taps, weights):
    """(q, k, v, dx, dtaps) of ``f`` under a loss that weighs every element."""
    loss = lambda x, t: sum(jnp.sum(o.astype(jnp.float32) * w) for o, w in zip(f(x, t), weights))
    return (*f(x, taps), *jax.grad(loss, (0, 1))(x, taps))


@pytest.mark.parametrize("dtype,tolerance", [(jnp.float32, TOLERANCE), (jnp.bfloat16, 6e-3)],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("seq", [512, 1536])
def test_the_prologues_kernels_are_the_chain_and_its_gradients(seq, dtype, tolerance):
    """``kda_conv_fwd`` / ``kda_conv_bwd`` with their epilogue (interpreted)
    against the chain written out, over one tile of rows and over three (the
    first tile's zero history; the backward's 8 rows after a tile, computed
    again from x, and zeros after the last one), four heads of 128 lanes a
    tile of columns at scales of their own, a batch of two: q, k, v, and the
    gradients by x and by the taps. With bfloat16 operands the kernels round
    once, at the end, and the gradient by x once: within that rounding of the
    float32 chain on the same operands."""
    x, taps, weights = prologue_operands(seq, dtype)
    want = prologue_results(lambda x, t: chain(x, t, 4), x.astype(jnp.float32), taps, weights)
    got = prologue_results(lambda x, t: short_conv.qkv_prologue(x, t, 4, interpret=True), x, taps, weights)
    for name, a, b in zip(("q", "k", "v", "dx", "dtaps"), got, want):
        assert a.shape == b.shape and a.dtype == (taps.dtype if name == "dtaps" else dtype), name
        assert relative(a, b) < tolerance, (name, relative(a, b))
    # a head of q and of k is a unit vector, the largest head (100 x) as the least
    norms = jnp.linalg.norm(got[0].astype(jnp.float32).reshape(2, seq, 4, 128), axis=-1)
    assert float(jnp.abs(norms - 1.0).max()) < (1e-5 if dtype == jnp.float32 else 4e-3)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["float32", "bfloat16"])
def test_the_prologues_jnp_path_is_the_same_chain(dtype):
    """``interpret=None``: XLA's convolution, ``jax.nn.silu`` and the l2norm
    as products with 0s and 1s at full precision, each step rounded to the
    operands' type as a model's ``jnp`` chain rounds."""
    x, taps, weights = prologue_operands(256, dtype)
    want = prologue_results(lambda x, t: chain(x, t, 4), x.astype(jnp.float32), taps, weights)
    got = prologue_results(lambda x, t: short_conv.qkv_prologue(x, t, 4), x, taps, weights)
    for name, a, b in zip(("q", "k", "v", "dx", "dtaps"), got, want):
        assert relative(a, b) < (TOLERANCE if dtype == jnp.float32 else 2e-2), name


def test_the_prologue_is_a_call_a_third_each_way_and_keeps_x_alone():
    """q, k and v leave ``kda_conv_fwd`` as three arrays and their cotangents
    enter ``kda_conv_bwd`` as three: a call a third, the three backward calls
    writing one dx (each takes the one before it aliased); no slice and no
    concatenation of anything as wide as a third, and nothing kept for the
    backward but the operands."""
    x, taps, weights = prologue_operands(512, jnp.bfloat16, batch=1)
    f = lambda x, t: short_conv.qkv_prologue(x, t, 4, interpret=True)
    loss = lambda x, t: sum(jnp.sum(o.astype(jnp.float32) * w) for o, w in zip(f(x, t), weights))
    text = str(jax.make_jaxpr(jax.grad(loss, (0, 1)))(x, taps))
    assert text.count("name=kda_conv_fwd") == 3 and text.count("name=kda_conv_bwd") == 3
    assert text.count("input_output_aliases=((6, 0),)") == 2
    wide = [eqn for eqn in jax.make_jaxpr(jax.grad(loss, (0, 1)))(x, taps).jaxpr.eqns
            if eqn.primitive.name in ("slice", "concatenate", "dynamic_slice", "pad")
            and max(v.aval.size for v in eqn.outvars) >= x.size // 3]
    assert not wide, wide
    _, residuals = jax.vjp(f, x, taps)
    kept = {leaf.shape for leaf in jax.tree.leaves(residuals)}
    assert kept == {x.shape, taps.shape}, kept


def test_the_prologue_falls_back_where_a_head_is_not_whole_lanes():
    x, taps, weights = prologue_operands(64, jnp.float32, heads=2, width=48)
    f = lambda x, t: short_conv.qkv_prologue(x, t, 2, interpret=True)
    assert "pallas_call" not in str(jax.make_jaxpr(f)(x, taps))
    want = chain(x, taps, 2)
    for a, b in zip(f(x, taps), want):
        assert relative(a, b) < TOLERANCE


@pytest.mark.parametrize("backend", ["tpu", "cpu"])
def test_on_the_kernel_path_kda_prep_holds_no_sigmoid_and_no_full_precision_product(
        monkeypatch, backend):
    """A KDA layer's mixer traced for a TPU at a 128-lane head, forward and
    backward: under ``kda_prep`` the only ``logistic`` left outside the
    convolution's calls is beta's (a column a head) and no product asks for
    ``Precision.HIGHEST`` (the l2norms' 0/1 products went into the calls; the
    two of ``kda_out``'s head norm stay, in their scope). Traced for another
    backend the chain is the ``jnp`` one: SiLU's ``logistic`` over q, k, v
    and q's and k's products, and no call."""
    from distributed_llm_training_benchmark_framework_tpu.models import tinygpt

    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    config = tinygpt.TinyGPTConfig(
        vocab_size=64, n_embd=64, n_head=2, n_layer=1, block_size=128, dropout=0.0, causal=True,
        norm="rmsnorm", pos_embed="rope", mlp_act="swiglu", mlp_hidden=64, bias=False,
        tie_embeddings=False, scan_layers=False, layer_types=("kda",), kda_heads=2,
        kda_head_dim=128, kda_chunk=64)
    params = jax.eval_shape(lambda k: tinygpt.init_params(config, k), jax.random.key(0))
    layer = jax.tree.map(lambda leaf: jnp.zeros(leaf.shape[1:], leaf.dtype), params["kda_blocks"])
    x = jnp.zeros((1, 128, 64), config.compute_dtype)
    loss = lambda layer, x: jnp.sum(kda_mixer.sublayer(config, x, layer).astype(jnp.float32))
    eqns = list(_equations(jax.make_jaxpr(jax.grad(loss, (0, 1)))(layer, x).jaxpr))
    prep = [e for path, e in eqns if "kda_prep" in path]
    assert prep and len(prep) < len(eqns)
    calls = [e.params["name"] for e in prep if e.primitive.name == "pallas_call"]
    sigmoids = [e for e in prep if e.primitive.name == "logistic"
                and e.invars[0].aval.shape[-1] > config.kda_heads]
    exact = [e for e in prep if e.primitive.name == "dot_general"
             and e.params["precision"] is not None
             and lax.Precision.HIGHEST in tuple(e.params["precision"])]
    if backend == "tpu":
        assert sorted(calls) == ["kda_conv_bwd"] * 3 + ["kda_conv_fwd"] * 3
        assert not sigmoids and not exact
    else:
        assert not calls and sigmoids and exact
    # kda_out's head norm keeps its two products (and their transposes) either way
    out = [e for path, e in eqns if "kda_out" in path
           and e.primitive.name == "dot_general" and e.params["precision"] is not None
           and lax.Precision.HIGHEST in tuple(e.params["precision"])]
    assert len(out) >= 2


def test_the_prologues_calls_are_made_once_a_shape():
    """A layer, remat's copy and the benchmark's check call the same
    ``pallas_call`` object again (``_conv_call`` is cached by shape and static
    choices), so the kernels' bodies are traced once a program."""
    x, taps, _ = prologue_operands(512, jnp.bfloat16, batch=1)
    short_conv._conv_call.cache_clear()
    for _ in range(3):
        jax.make_jaxpr(lambda x, t: short_conv.qkv_prologue(x, t, 4, interpret=True))(x, taps)
    info = short_conv._conv_call.cache_info()
    assert info.misses == 3 and info.hits == 6
