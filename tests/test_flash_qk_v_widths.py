"""The flash kernels where q and k are wider than v (latent attention: a
rotary part rides on the keys only) and the softmax scale is the caller's:
forward, the einsum backward, the fused Pallas backward and the kernel pair,
interpreted, against ``reference_attention``; and equal widths with no scale
trace the program they always traced."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_llm_training_benchmark_framework_tpu.ops import flash_attention as fa

B, S, H, DQK, DV = 2, 64, 2, 24, 16
SCALE = 0.173  # not 24 ** -0.5 = 0.204


def _qkv(seed=0, dqk=DQK, dv=DV):
    kq, kk, kv, kw = jax.random.split(jax.random.key(seed), 4)
    q = jax.random.normal(kq, (B, S, H, dqk), jnp.float32)
    k = jax.random.normal(kk, (B, S, H, dqk), jnp.float32)
    v = jax.random.normal(kv, (B, S, H, dv), jnp.float32)
    w = jax.random.normal(kw, (B, S, H, dv), jnp.float32)  # a cotangent that is not all ones
    return q, k, v, w


@pytest.mark.parametrize("causal", [False, True])
def test_forward_matches_reference(causal):
    q, k, v, _ = _qkv()
    out = fa.flash_attention(q, k, v, causal=causal, interpret=True, block_q=32,
                             block_k=32, scale=SCALE)
    assert out.shape == (B, S, H, DV)
    want = fa.reference_attention(q, k, v, causal=causal, scale=SCALE)
    np.testing.assert_allclose(out, want, atol=2e-5, rtol=2e-5)
    # the scale is used: the default one gives another answer
    other = fa.reference_attention(q, k, v, causal=causal)
    assert float(jnp.max(jnp.abs(other - want))) > 1e-2


@pytest.mark.parametrize("pallas_backward", [False, True], ids=["einsum", "fused"])
@pytest.mark.parametrize("causal", [False, True])
def test_backward_matches_reference(causal, pallas_backward):
    q, k, v, w = _qkv(1)

    def flash(q, k, v):
        return jnp.sum(w * fa.flash_attention(
            q, k, v, causal=causal, interpret=True, block_q=32, block_k=32,
            block_k_bwd=16, pallas_backward=pallas_backward, scale=SCALE))

    def plain(q, k, v):
        return jnp.sum(w * fa.reference_attention(q, k, v, causal=causal, scale=SCALE))

    got = jax.grad(flash, argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(plain, argnums=(0, 1, 2))(q, k, v)
    for g, r, name in zip(got, want, "qkv"):
        assert g.shape == r.shape, name
        np.testing.assert_allclose(g, r, atol=5e-5, rtol=5e-4, err_msg=name)


@pytest.mark.parametrize("causal", [False, True])
def test_pair_backward_matches_fused(causal):
    """The fallback past the fused kernel's VMEM cap takes the same widths."""
    q, k, v, w = _qkv(2)
    to = lambda t: t.transpose(0, 2, 1, 3).reshape(B * H, S, t.shape[-1])
    q, k, v, do = map(to, (q, k, v, w))
    seed, bhv = jnp.zeros((1,), jnp.uint32), jnp.arange(B * H, dtype=jnp.int32)
    out, lse = fa._flash_forward(q, k, v, causal, True, 32, 32, 0.0, seed, bhv, scale=SCALE)
    delta = jnp.sum(do * out, -1)
    lse3 = jnp.broadcast_to(lse[:, None, :], (B * H, 8, S))
    delta3 = jnp.broadcast_to(delta[:, None, :], (B * H, 8, S))
    args = (q, k, v, do, lse3, delta3, seed, bhv, causal, 0.0, 16, 32, True)
    fused = fa._fused_backward(*args, scale=SCALE)
    pair = fa._pair_backward(*args, scale=SCALE)
    for f, p, width in zip(fused, pair, (DQK, DQK, DV)):
        assert f.shape == (B * H, S, width)
        # f32 rounding of the factors the fused chain folds (PR 33).
        np.testing.assert_allclose(f, p, atol=5e-6, rtol=1e-5)


def test_fused_vmem_bound_follows_the_wider_width():
    """dq's resident row is as wide as q: 192 pads to 256 lanes."""
    tile = fa._FUSED_TILE_VMEM
    assert fa._fused_vmem_bytes(8192, 192, jnp.bfloat16) - tile == 8192 * 256 * 8
    assert fa._fused_vmem_bytes(8192, 128, jnp.bfloat16) - tile == 8192 * 128 * 8
    assert fa._fused_fits(8192, 192, jnp.bfloat16)


@pytest.mark.parametrize("grad", [False, True], ids=["forward", "backward"])
def test_equal_widths_without_scale_trace_todays_program(grad):
    """No ``scale`` is 1 / sqrt(D) as a Python float, the constant the kernels
    always closed over: the jaxpr with the default equals the jaxpr with that
    number, character for character, and holds no op of another width."""
    q, k, v, w = _qkv(3, dqk=DV, dv=DV)

    def program(scale):
        def f(q, k, v):
            return jnp.sum(w * fa.flash_attention(
                q, k, v, causal=True, interpret=True, block_q=32, block_k=32, scale=scale))
        return str(jax.make_jaxpr(jax.grad(f, argnums=(0, 1, 2)) if grad else f)(q, k, v))

    default = program(None)
    assert default == program(1.0 / (DV ** 0.5))
    assert default != program(SCALE)
