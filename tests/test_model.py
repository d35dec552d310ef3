"""TinyGPT model unit tests: shapes, param counts, tying, loss semantics.

Covers the model-math checks the reference only performs operationally via
``scripts/verify_offline.sh:63-83`` (CPU instantiation + param counting).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_llm_training_benchmark_framework_tpu.models.mixers import (
    attention as attention_mixer,
)
from distributed_llm_training_benchmark_framework_tpu.models import (
    TinyGPTConfig,
    get_model_config,
    init_params,
    forward,
    loss_fn,
    count_params,
)


def small_cfg(**kw):
    kw.setdefault("dropout", 0.0)
    return get_model_config("S", 64, **kw)


def test_tier_table_matches_reference():
    a = get_model_config("A", 2048)
    assert (a.vocab_size, a.n_embd, a.n_head, a.n_layer, a.block_size) == (
        32000, 1024, 16, 16, 2048,
    )
    b = get_model_config("B", 2048)
    assert (b.n_embd, b.n_head, b.n_layer) == (2048, 32, 32)
    with pytest.raises(ValueError):
        get_model_config("Z", 128)


def test_param_count_tier_a():
    """Tier A with tied embeddings is ~236M params (SURVEY §2.1 C3)."""
    cfg = get_model_config("A", 2048)
    shapes = jax.eval_shape(lambda k: init_params(cfg, k), jax.random.key(0))
    n = sum(int(np.prod(l.shape)) for l in jax.tree_util.tree_leaves(shapes))
    # Analytic: wte 32000*1024 + wpe 2048*1024 + 16 blocks * 12*1024^2ish + ln_f
    assert 230e6 < n < 245e6, n


def test_forward_shapes_and_dtypes():
    cfg = small_cfg()
    params = init_params(cfg, jax.random.key(0))
    idx = jnp.zeros((2, 64), jnp.int32)
    logits, loss = forward(cfg, params, idx, idx)
    assert logits.shape == (2, 64, cfg.vocab_size)
    assert logits.dtype == jnp.float32
    assert loss.shape == () and loss.dtype == jnp.float32
    # Untrained loss should be near ln(V).
    assert abs(float(loss) - np.log(cfg.vocab_size)) < 1.0


def test_weight_tying_is_structural():
    """There is no separate LM head leaf — logits come from wte itself."""
    cfg = small_cfg()
    params = init_params(cfg, jax.random.key(0))
    flat = {"/".join(str(getattr(k, "key", k)) for k in path): v
            for path, v in jax.tree_util.tree_flatten_with_path(params)[0]}
    assert not any("head" in k for k in flat)
    assert flat["wte"].shape == (cfg.vocab_size, cfg.n_embd)


def test_loss_ignore_index():
    """Positions with target == -1 are excluded (parity: ignore_index=-1)."""
    cfg = small_cfg()
    params = init_params(cfg, jax.random.key(0))
    idx = jax.random.randint(jax.random.key(1), (2, 64), 0, cfg.vocab_size)
    full = loss_fn(cfg, params, idx, idx)
    half_tgt = idx.at[:, 32:].set(-1)
    half = loss_fn(cfg, params, idx, half_tgt)
    assert np.isfinite(float(half))
    assert float(half) != float(full)
    all_ignored = loss_fn(cfg, params, idx, jnp.full_like(idx, -1))
    assert float(all_ignored) == 0.0


def test_block_size_enforced():
    cfg = small_cfg()
    params = init_params(cfg, jax.random.key(0))
    with pytest.raises(ValueError):
        forward(cfg, params, jnp.zeros((1, 128), jnp.int32))


def test_loss_decreases_when_training():
    """A few SGD steps on a fixed batch must reduce the loss."""
    import optax

    cfg = small_cfg()
    params = init_params(cfg, jax.random.key(0))
    idx = jax.random.randint(jax.random.key(1), (4, 64), 0, cfg.vocab_size)
    tx = optax.adamw(1e-3)
    opt = tx.init(params)

    @jax.jit
    def step(p, o):
        l, g = jax.value_and_grad(lambda p_: loss_fn(cfg, p_, idx, idx))(p)
        u, o = tx.update(g, o, p)
        return optax.apply_updates(p, u), o, l

    losses = []
    for _ in range(8):
        params, opt, l = step(params, opt)
        losses.append(float(l))
    assert losses[-1] < losses[0] - 0.5, losses


def test_causal_option_changes_output():
    cfg = small_cfg()
    params = init_params(cfg, jax.random.key(0))
    idx = jax.random.randint(jax.random.key(1), (1, 64), 0, cfg.vocab_size)
    bi, _ = forward(cfg, params, idx)
    causal_cfg = small_cfg(causal=True)
    ca, _ = forward(causal_cfg, params, idx)
    assert not np.allclose(np.asarray(bi), np.asarray(ca))


def test_dropout_rng_determinism():
    cfg = small_cfg(dropout=0.1)
    params = init_params(cfg, jax.random.key(0))
    idx = jnp.zeros((1, 64), jnp.int32)
    k = jax.random.key(7)
    _, l1 = forward(cfg, params, idx, idx, dropout_key=k, deterministic=False)
    _, l2 = forward(cfg, params, idx, idx, dropout_key=k, deterministic=False)
    _, l3 = forward(
        cfg, params, idx, idx, dropout_key=jax.random.key(8), deterministic=False
    )
    assert float(l1) == float(l2)
    assert float(l1) != float(l3)


@pytest.mark.parametrize("policy", ["full", True, "dots"])
def test_remat_matches_no_remat(policy):
    """Every remat policy (incl. the legacy bool spelling) is semantically
    a no-op — same loss, same gradients up to recompute rounding."""
    cfg = small_cfg()
    params = init_params(cfg, jax.random.key(0))
    idx = jax.random.randint(jax.random.key(1), (2, 64), 0, cfg.vocab_size)
    import dataclasses

    l_plain = loss_fn(cfg, params, idx, idx)
    l_remat = loss_fn(dataclasses.replace(cfg, remat=policy), params, idx, idx)
    g_plain = jax.grad(lambda p: loss_fn(cfg, p, idx, idx))(params)
    g_remat = jax.grad(
        lambda p: loss_fn(dataclasses.replace(cfg, remat=policy), p, idx, idx)
    )(params)
    assert np.allclose(float(l_plain), float(l_remat), rtol=1e-5)
    for a, b in zip(jax.tree_util.tree_leaves(g_plain), jax.tree_util.tree_leaves(g_remat)):
        # bf16 recompute reorders roundings; elementwise comparison is too
        # brittle — require relative L2 error under 1% per leaf instead.
        a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
        denom = np.linalg.norm(a) + 1e-12
        assert np.linalg.norm(a - b) / denom < 1e-2


def test_unrolled_layer_loop_matches_scan():
    """scan_layers=False (the published-benchmark default via bench.py and
    the suite) computes the identical loss and gradients as the lax.scan
    path, deterministically AND with live dropout keys (per-layer fold_in
    indices must agree between the two loops)."""
    import dataclasses

    cfg = small_cfg()
    unrolled = dataclasses.replace(cfg, scan_layers=False)
    params = init_params(cfg, jax.random.key(0))
    idx = jax.random.randint(jax.random.key(1), (2, 64), 0, cfg.vocab_size)

    l_scan = loss_fn(cfg, params, idx, idx)
    l_unroll = loss_fn(unrolled, params, idx, idx)
    # Not bitwise: XLA fuses the unrolled bodies differently, reordering
    # bf16 roundings (observed rel diff ~1.5e-5 on CPU).
    np.testing.assert_allclose(float(l_scan), float(l_unroll), rtol=1e-4)

    key = jax.random.key(7)
    l_scan_d = loss_fn(cfg, params, idx, idx, dropout_key=key, deterministic=False)
    l_unroll_d = loss_fn(
        unrolled, params, idx, idx, dropout_key=key, deterministic=False
    )
    np.testing.assert_allclose(float(l_scan_d), float(l_unroll_d), rtol=1e-4)

    g_scan = jax.grad(lambda p: loss_fn(cfg, p, idx, idx))(params)
    g_unroll = jax.grad(lambda p: loss_fn(unrolled, p, idx, idx))(params)
    for a, b in zip(
        jax.tree_util.tree_leaves(g_scan), jax.tree_util.tree_leaves(g_unroll)
    ):
        a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
        denom = np.linalg.norm(a) + 1e-12
        assert np.linalg.norm(a - b) / denom < 1e-2


@pytest.mark.parametrize(
    "impl, manual_axis, op, own",
    [
        ("flash", None, "flash_attention.flash_attention", {}),
        ("ring", None, "ring_attention.ring_attention", {"zigzag": False}),
        ("ulysses", None, "ulysses_attention.ulysses_attention", {}),
        ("ring", "seq", "ring_attention.ring_attention_sharded",
         {"axis_name": "seq", "zigzag": False}),
        ("ulysses", "seq", "ulysses_attention.ulysses_attention_sharded",
         {"axis_name": "seq"}),
    ],
    ids=["flash", "ring", "ulysses", "ring-manual", "ulysses-manual"],
)
def test_attention_dispatch_says_which_attention_never_how(
    monkeypatch, impl, manual_axis, op, own
):
    """``_attention`` hands every op ``causal``, ``dropout_rate`` and
    ``dropout_seed`` plus that branch's own arguments, and nothing else:
    tiles and the backward's choice belong to ``ops/flash_attention.py``."""
    import importlib

    from distributed_llm_training_benchmark_framework_tpu.models import tinygpt

    module, name = op.split(".")
    calls = []
    monkeypatch.setattr(
        importlib.import_module(
            f"distributed_llm_training_benchmark_framework_tpu.ops.{module}"
        ),
        name,
        lambda q, k, v, **kwargs: calls.append(kwargs) or q,
    )
    cfg = small_cfg(
        dropout=0.1, causal=True, attention_impl=impl, ring_zigzag=False,
        seq_manual_axis=manual_axis,
    )
    q = jnp.zeros((1, 8, 2, 4))
    assert attention_mixer._attention(cfg, q, q, q, jax.random.key(0), False) is q
    assert attention_mixer._attention(cfg, q, q, q, jax.random.key(0), True) is q
    training, deterministic = calls
    seed = training.pop("dropout_seed")
    assert seed.dtype == jnp.uint32 and seed.shape == ()
    assert training == {"causal": True, "dropout_rate": 0.1, **own}
    assert deterministic == {
        "causal": True, "dropout_rate": 0.0, "dropout_seed": None, **own
    }
