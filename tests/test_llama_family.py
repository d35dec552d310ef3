"""Llama-family architecture knobs: RMSNorm, RoPE, SwiGLU, GQA, no-bias,
untied head.

The gold-standard check is logits parity against HuggingFace transformers'
``LlamaForCausalLM`` (torch CPU, fp32) with identical weights — one test that
pins all five knobs' numerics at once (RoPE rotate-half convention, RMSNorm
eps placement, SiLU gating, GQA head grouping, untied head). The reference
framework has no second model family at all (its TinyGPT is the only
architecture, reference ``benchmarking/train_harness.py:36-131``); this
family is beyond-parity surface.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_llm_training_benchmark_framework_tpu.models.mixers import (
    attention as attention_mixer,
)
from distributed_llm_training_benchmark_framework_tpu.models import (
    TinyGPTConfig,
    init_params,
    forward,
    loss_fn,
    count_params,
)
from distributed_llm_training_benchmark_framework_tpu.models.tinygpt import (
    embed_param_names,
    head_param_names,
)


def llama_cfg(**kw):
    base = dict(
        vocab_size=64,
        n_embd=32,
        n_head=4,
        n_layer=2,
        block_size=32,
        dropout=0.0,
        causal=True,
        norm="rmsnorm",
        pos_embed="rope",
        mlp_act="swiglu",
        mlp_hidden=48,
        n_kv_head=2,
        bias=False,
        tie_embeddings=False,
        param_dtype=jnp.float32,
        compute_dtype=jnp.float32,
    )
    base.update(kw)
    return TinyGPTConfig(**base)


def test_param_tree_shape():
    cfg = llama_cfg()
    params = init_params(cfg, jax.random.key(0))
    assert sorted(params.keys()) == ["blocks", "lm_head", "lnf_scale", "wte"]
    blocks = params["blocks"]
    assert sorted(blocks.keys()) == [
        "ln1_scale", "ln2_scale", "wgu", "wkv", "wo", "wproj", "wq",
    ]
    L, D, F = cfg.n_layer, cfg.n_embd, cfg.mlp_dim
    assert blocks["wq"].shape == (L, D, cfg.n_head * cfg.head_dim)
    assert blocks["wkv"].shape == (L, D, 2, cfg.kv_heads * cfg.head_dim)
    assert blocks["wgu"].shape == (L, D, 2, F)
    assert blocks["wproj"].shape == (L, F, D)
    assert params["lm_head"].shape == (cfg.vocab_size, D)


def test_knob_validation():
    with pytest.raises(ValueError):
        llama_cfg(norm="batchnorm")
    with pytest.raises(ValueError):
        llama_cfg(pos_embed="alibi")
    with pytest.raises(ValueError):
        llama_cfg(n_kv_head=3)  # does not divide n_head=4
    with pytest.raises(ValueError):
        llama_cfg(n_experts=4)  # MoE is dense-GELU only


def test_legacy_tree_unchanged():
    """The default config's param tree (names, shapes, and VALUES) is
    untouched by the family knobs — published artifacts must reproduce."""
    cfg = TinyGPTConfig(
        vocab_size=64, n_embd=32, n_head=4, n_layer=2, block_size=16, dropout=0.0
    )
    params = init_params(cfg, jax.random.key(0))
    flat = {"/".join(str(getattr(k, "key", k)) for k in p): v
            for p, v in jax.tree_util.tree_flatten_with_path(params)[0]}
    assert sorted(flat) == [
        "blocks/bfc", "blocks/bo", "blocks/bproj", "blocks/bqkv",
        "blocks/ln1_bias", "blocks/ln1_scale", "blocks/ln2_bias",
        "blocks/ln2_scale", "blocks/wfc", "blocks/wo", "blocks/wproj",
        "blocks/wqkv", "lnf_bias", "lnf_scale", "wpe", "wte",
    ]
    # Init values come from an 8-way key split regardless of the new knobs'
    # existence (pinned: jax.random.split(key, 8) -> wqkv, wo, wfc, wproj,
    # wte, wpe in that order). Spot-pin one scalar.
    k = jax.random.split(jax.random.key(0), 8)
    expected = 0.02 * jax.random.normal(k[0], (2, 32, 3, 32))
    np.testing.assert_array_equal(np.asarray(params["blocks"]["wqkv"]),
                                  np.asarray(expected))


def test_embed_head_param_names():
    assert embed_param_names(llama_cfg()) == ("wte",)
    assert head_param_names(llama_cfg()) == ("lnf_scale", "lm_head")
    dflt = TinyGPTConfig()
    assert embed_param_names(dflt) == ("wte", "wpe")
    assert head_param_names(dflt) == ("lnf_scale", "lnf_bias", "wte")


def test_llama_tier_table():
    """Tier design: head_dim 128 (the MXU-width shape, PERFORMANCE.md §15),
    GQA 2:1, causal, no dropout; budgets comparable to the TinyGPT tiers
    (A ~254M vs 236M, B ~1.64B vs 1.68B)."""
    from distributed_llm_training_benchmark_framework_tpu.models.llama import (
        get_llama_config,
    )

    a = get_llama_config("A", 2048)
    assert (a.head_dim, a.kv_heads, a.causal, a.dropout) == (128, 4, True, 0.0)
    assert (a.norm, a.pos_embed, a.mlp_act) == ("rmsnorm", "rope", "swiglu")
    assert not a.bias and not a.tie_embeddings
    shapes = jax.eval_shape(lambda k: init_params(a, k), jax.random.key(0))
    n = sum(int(np.prod(l.shape)) for l in jax.tree_util.tree_leaves(shapes))
    assert 245e6 < n < 265e6, n

    b = get_llama_config("B", 1024)
    assert b.head_dim == 128
    shapes = jax.eval_shape(lambda k: init_params(b, k), jax.random.key(0))
    n = sum(int(np.prod(l.shape)) for l in jax.tree_util.tree_leaves(shapes))
    assert 1.55e9 < n < 1.72e9, n

    with pytest.raises(ValueError):
        get_llama_config("Z", 128)
    # Overrides pass through like get_model_config's.
    assert get_llama_config("S", 64, dropout=0.1).dropout == 0.1


def test_gqa_matches_repeated_kv_mha():
    """A GQA model equals an MHA model whose fused wqkv repeats each kv head
    over its query group — pins the grouping convention (head h uses kv head
    h // rep, consecutive blocks)."""
    cfg = llama_cfg()
    params = init_params(cfg, jax.random.key(0))
    H, Hkv, Dh, D = cfg.n_head, cfg.kv_heads, cfg.head_dim, cfg.n_embd
    rep = H // Hkv

    mha_cfg = dataclasses.replace(cfg, n_kv_head=None)
    mha_params = jax.tree.map(lambda x: x, params)
    wq = params["blocks"]["wq"]          # (L, D, H*Dh)
    wkv = params["blocks"]["wkv"]        # (L, D, 2, Hkv*Dh)
    L = cfg.n_layer
    k_rep = np.repeat(np.asarray(wkv[:, :, 0]).reshape(L, D, Hkv, Dh), rep, axis=2)
    v_rep = np.repeat(np.asarray(wkv[:, :, 1]).reshape(L, D, Hkv, Dh), rep, axis=2)
    wqkv = np.stack(
        [np.asarray(wq), k_rep.reshape(L, D, H * Dh), v_rep.reshape(L, D, H * Dh)],
        axis=2,
    )  # (L, D, 3, H*Dh)
    del mha_params["blocks"]["wq"], mha_params["blocks"]["wkv"]
    mha_params["blocks"]["wqkv"] = jnp.asarray(wqkv)

    idx = jax.random.randint(jax.random.key(1), (2, 32), 0, cfg.vocab_size)
    lg_gqa, _ = forward(cfg, params, idx)
    lg_mha, _ = forward(mha_cfg, mha_params, idx)
    np.testing.assert_allclose(np.asarray(lg_gqa), np.asarray(lg_mha),
                               atol=1e-5, rtol=1e-5)


def test_rope_position_convention():
    """RoPE positions are absolute: running tokens through with positions
    [0..S) vs a shifted window must change the logits (position-dependence),
    and the _rope helper must agree with slicing a longer position range —
    the property the sequence-manual offset (pos + S*axis_index) relies on."""
    from distributed_llm_training_benchmark_framework_tpu.models.mixers.attention import _rope

    x = jax.random.normal(jax.random.key(0), (1, 8, 2, 16), jnp.float32)
    pos_a = jnp.arange(8, dtype=jnp.int32)
    pos_b = pos_a + 8
    ra, rb = _rope(x, pos_a, 1e4), _rope(x, pos_b, 1e4)
    assert not np.allclose(np.asarray(ra), np.asarray(rb))
    # Offset slice == slicing the rotation of the concatenated range: the
    # per-shard rule rope(x_shard, shard*S + arange(S)) composes into the
    # full-sequence rotation.
    x2 = jnp.concatenate([x, x], axis=1)  # (1, 16, 2, 16)
    full = _rope(x2, jnp.arange(16, dtype=jnp.int32), 1e4)
    np.testing.assert_allclose(np.asarray(full[:, 8:]), np.asarray(rb),
                               atol=1e-6, rtol=1e-6)


def test_rope_sequence_parallel_trajectory(eight_devices):
    """End-to-end pin of the seq-manual RoPE offset: a causal RoPE/GQA/
    SwiGLU model trained over a 4-way sequence-parallel ring matches the
    single-replica trajectory — a wrong per-shard position offset (sign,
    scale, or applied after the zigzag redistribution) diverges step 0."""
    from distributed_llm_training_benchmark_framework_tpu.parallel import (
        make_mesh, get_strategy,
    )
    from distributed_llm_training_benchmark_framework_tpu.train import (
        create_train_state,
    )
    from distributed_llm_training_benchmark_framework_tpu.data import (
        SyntheticDataset,
    )

    cfg = llama_cfg(
        vocab_size=512, n_embd=128, n_head=4, n_kv_head=2, n_layer=2,
        block_size=64, mlp_hidden=176, attention_impl="ring",
        compute_dtype=jnp.float32,
    )

    def run(mesh_shape):
        import numpy as _np

        mesh = make_mesh(
            mesh_shape, ("data", "seq", "model"),
            devices=jax.devices()[: int(_np.prod(mesh_shape))],
        )
        state = create_train_state(cfg, get_strategy("ddp"), mesh, seed=42)
        ds = SyntheticDataset(vocab_size=512, seq_len=64, size=32)
        params, opt = state.params, state.opt_state
        losses = []
        for step in range(3):
            batch = ds.batch_for_step(step, 2).reshape(1, 2, 64)
            batch = jax.device_put(batch, state.batch_sharding)
            params, opt, loss = state.step_fn(params, opt, batch, step)
            losses.append(float(loss))
        return losses

    base = run((1, 1, 1))
    sp = run((1, 4, 1))
    np.testing.assert_allclose(sp, base, rtol=5e-3)


def test_loss_decreases_when_training():
    cfg = llama_cfg(block_size=16)
    params = init_params(cfg, jax.random.key(0))
    idx = jax.random.randint(jax.random.key(1), (4, 16), 0, cfg.vocab_size)

    import optax

    opt = optax.adamw(1e-2)
    state = opt.init(params)
    grad_fn = jax.jit(jax.value_and_grad(lambda p: loss_fn(cfg, p, idx, idx)))
    losses = []
    for _ in range(12):
        loss, g = grad_fn(params)
        losses.append(float(loss))
        upd, state = opt.update(g, state, params)
        params = optax.apply_updates(params, upd)
    assert losses[-1] < losses[0] - 0.5, losses


def _hf_llama_and_weights(cfg, key):
    """Build an HF LlamaForCausalLM with OUR init weights copied in."""
    torch = pytest.importorskip("torch")
    transformers = pytest.importorskip("transformers")

    params = init_params(cfg, key)
    hf_cfg = transformers.LlamaConfig(
        vocab_size=cfg.vocab_size,
        hidden_size=cfg.n_embd,
        intermediate_size=cfg.mlp_dim,
        num_hidden_layers=cfg.n_layer,
        num_attention_heads=cfg.n_head,
        num_key_value_heads=cfg.kv_heads,
        max_position_embeddings=cfg.block_size,
        rms_norm_eps=cfg.norm_eps,
        rope_theta=cfg.rope_theta,
        attention_bias=False,
        mlp_bias=False,
        tie_word_embeddings=False,
        attention_dropout=0.0,
    )
    model = transformers.LlamaForCausalLM(hf_cfg).eval()

    t = lambda a: torch.from_numpy(np.asarray(a, dtype=np.float32))
    b = params["blocks"]
    with torch.no_grad():
        model.model.embed_tokens.weight.copy_(t(params["wte"]))
        model.model.norm.weight.copy_(t(params["lnf_scale"]))
        model.lm_head.weight.copy_(t(params["lm_head"]))
        for i, layer in enumerate(model.model.layers):
            layer.input_layernorm.weight.copy_(t(b["ln1_scale"][i]))
            layer.post_attention_layernorm.weight.copy_(t(b["ln2_scale"][i]))
            # Ours: x @ W (in, out). HF Linear stores (out, in) -> transpose.
            layer.self_attn.q_proj.weight.copy_(t(b["wq"][i]).T)
            layer.self_attn.k_proj.weight.copy_(t(b["wkv"][i, :, 0]).T)
            layer.self_attn.v_proj.weight.copy_(t(b["wkv"][i, :, 1]).T)
            layer.self_attn.o_proj.weight.copy_(t(b["wo"][i]).T)
            layer.mlp.gate_proj.weight.copy_(t(b["wgu"][i, :, 0]).T)
            layer.mlp.up_proj.weight.copy_(t(b["wgu"][i, :, 1]).T)
            layer.mlp.down_proj.weight.copy_(t(b["wproj"][i]).T)
    return model, params


def test_logits_parity_vs_hf_transformers():
    """Bit-for-convention parity with HF LlamaForCausalLM: same weights,
    same input, fp32 -> logits agree to float tolerance. Pins the RoPE
    rotate-half layout, RMSNorm numerics, SiLU gating, GQA grouping and the
    untied head in one shot."""
    torch = pytest.importorskip("torch")
    cfg = llama_cfg()
    model, params = _hf_llama_and_weights(cfg, jax.random.key(0))

    idx = np.asarray(
        jax.random.randint(jax.random.key(7), (2, 32), 0, cfg.vocab_size)
    )
    ours, _ = forward(cfg, params, jnp.asarray(idx))
    with torch.no_grad():
        theirs = model(torch.from_numpy(idx)).logits.numpy()
    np.testing.assert_allclose(np.asarray(ours), theirs, atol=2e-4, rtol=2e-3)


def _run_llama_trajectory(mesh_shape, axis_names, strategy="zero2", steps=3,
                          dp=1, grad_accum=1, pipeline_schedule="gpipe",
                          **cfg_kw):
    import numpy as _np

    from distributed_llm_training_benchmark_framework_tpu.parallel import (
        make_mesh, get_strategy,
    )
    from distributed_llm_training_benchmark_framework_tpu.train import (
        create_train_state,
    )
    from distributed_llm_training_benchmark_framework_tpu.data import (
        SyntheticDataset,
    )

    cfg = llama_cfg(
        vocab_size=512, n_embd=128, n_head=4, n_kv_head=2, n_layer=2,
        block_size=64, mlp_hidden=176, compute_dtype=jnp.float32, **cfg_kw
    )
    mesh = make_mesh(
        mesh_shape, axis_names,
        devices=jax.devices()[: int(_np.prod(mesh_shape))],
    )
    state = create_train_state(
        cfg, get_strategy(strategy), mesh, seed=42, grad_accum=grad_accum,
        pipeline_schedule=pipeline_schedule,
    )
    ds = SyntheticDataset(vocab_size=512, seq_len=64, size=32)
    params, opt = state.params, state.opt_state
    losses = []
    for step in range(steps):
        batch = ds.batch_for_step(step, dp * 2 * grad_accum)
        batch = batch.reshape(grad_accum, dp * 2, 64)
        batch = jax.device_put(batch, state.batch_sharding)
        params, opt, loss = state.step_fn(params, opt, batch, step)
        losses.append(float(loss))
    return losses


@pytest.mark.slow
def test_llama_pipeline_trajectory(eight_devices):
    """Llama under pipeline parallelism: the generalized embed/head leaf
    plumbing (untied lm_head, no wpe, rmsnorm scale-only final norm) must
    reproduce the single-replica trajectory through the 1F1B schedule's
    stage-sliced vjp accumulation."""
    axes = ("data", "seq", "model", "pipe")
    base = _run_llama_trajectory((1, 1, 1, 1), axes, grad_accum=2)
    pp = _run_llama_trajectory(
        (1, 1, 1, 2), axes, grad_accum=2, pipeline_schedule="1f1b"
    )
    np.testing.assert_allclose(pp, base, rtol=5e-3)


@pytest.mark.slow
def test_llama_pp_sp_rope_manual_offset(eight_devices):
    """Llama under pp x sp (ring): the ONLY path where RoPE runs inside a
    sequence-manual shard_map (config.seq_manual_axis set by the pipeline
    schedule) — each shard must rotate with its global offset
    (pos + S_local*axis_index), or the trajectory diverges from the
    single-replica run at step 0."""
    axes = ("data", "seq", "model", "pipe")
    base = _run_llama_trajectory((1, 1, 1, 1), axes, grad_accum=2)
    ppsp = _run_llama_trajectory(
        (1, 2, 1, 2), axes, grad_accum=2, attention_impl="ring"
    )
    np.testing.assert_allclose(ppsp, base, rtol=5e-3)


def test_flops_accounting_generalizes():
    """GQA shrinks only the K/V projection term; SwiGLU runs 3 matrices."""
    from distributed_llm_training_benchmark_framework_tpu.utils.flops import (
        forward_flops_per_token,
    )

    mha = llama_cfg(n_kv_head=None)
    gqa = llama_cfg(n_kv_head=2)
    D, Dh = mha.n_embd, mha.head_dim
    # Exactly the K/V projection savings: 2*D*(2*(H-Hkv)*Dh) per layer.
    saved = forward_flops_per_token(mha) - forward_flops_per_token(gqa)
    assert saved == mha.n_layer * 2 * D * 2 * (4 - 2) * Dh

    gelu = llama_cfg(mlp_act="gelu", mlp_hidden=48)
    swi = llama_cfg(mlp_act="swiglu", mlp_hidden=48)
    extra = forward_flops_per_token(swi) - forward_flops_per_token(gelu)
    assert extra == swi.n_layer * 2 * D * 48  # the gate matrix

    # The default TinyGPT accounting is unchanged: 8D^2 attn + 16D^2 mlp
    # + 4*S*D attn math per layer + 2DV head.
    dflt = TinyGPTConfig(vocab_size=64, n_embd=32, n_head=4, n_layer=2,
                         block_size=16)
    expect = 2 * (24 * 32 * 32 + 4 * 16 * 32) + 2 * 32 * 64
    assert forward_flops_per_token(dflt) == expect


def test_memory_estimator_handles_family():
    """The pre-flight estimator runs on a Llama config (exact param bytes
    via eval_shape; SwiGLU widens the analytic activation term)."""
    from distributed_llm_training_benchmark_framework_tpu.utils.memory import (
        estimate_hbm,
    )
    from distributed_llm_training_benchmark_framework_tpu.parallel.strategies import (
        get_strategy,
    )
    from distributed_llm_training_benchmark_framework_tpu.parallel.mesh import (
        make_mesh,
    )

    mesh = make_mesh((1,), ("data",))
    est = estimate_hbm(llama_cfg(), get_strategy("ddp"), mesh, 2, 32)
    n_param_bytes = count_params(init_params(llama_cfg(), jax.random.key(0))) * 4
    assert est.params == n_param_bytes
    assert est.total > 0


def test_flash_matches_reference_impl_llama():
    """The Pallas flash path (interpret mode on CPU) agrees with the jnp
    reference attention for a causal RoPE/GQA model."""
    cfg = llama_cfg(block_size=128)
    params = init_params(cfg, jax.random.key(0))
    idx = jax.random.randint(jax.random.key(1), (1, 128), 0, cfg.vocab_size)
    ref, _ = forward(cfg, params, idx)
    flash_cfg = dataclasses.replace(cfg, attention_impl="flash")
    fl, _ = forward(flash_cfg, params, idx)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(fl),
                               atol=5e-3, rtol=5e-3)


def test_through_the_one_pass_prologue_the_loss_and_gradients_are_the_chains(monkeypatch):
    """On a chip a llama-family layer with heads of 128 lanes rotates q and k
    in ``ops/rotary.py``'s one pass (rotary alone: no QK-norm, Mistral's
    case). One small layer, GQA, batch 2, with the kernels interpreted,
    against the ``jnp`` chain (``_rope``) the cases above pin."""
    from distributed_llm_training_benchmark_framework_tpu.models import tinygpt
    from distributed_llm_training_benchmark_framework_tpu.ops import rotary

    cfg = llama_cfg(n_embd=512, n_layer=1, block_size=64, attention_impl="flash")
    params = init_params(cfg, jax.random.key(0))
    idx = jax.random.randint(jax.random.key(1), (2, 64), 0, cfg.vocab_size)
    run = lambda: jax.value_and_grad(lambda p: tinygpt.loss_fn(cfg, p, idx, idx))(params)
    want_loss, want = run()
    monkeypatch.setattr(rotary, "kernel_mode", lambda: True)  # as a chip, interpreted
    stats = attention_mixer.qk_prologue_stats(cfg, 64)
    assert (stats["rotary_layers"], stats["pass_layers"], stats["norm_stage_layers"]) == (1, 1, 0)
    got_loss, got = run()
    np.testing.assert_allclose(float(got_loss), float(want_loss), rtol=1e-6)
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(
            np.asarray(g), np.asarray(w), atol=5e-3 * float(jnp.abs(w).max()), rtol=5e-3,
            err_msg=jax.tree_util.keystr(path))


def _derived_shapes(jaxpr, tainted, stop):
    """(shapes of every array in ``jaxpr`` computed from the ``tainted``
    variables, which of its results are), walked into the calls it holds and
    not past one of the primitives named in ``stop`` (the attention itself:
    what leaves it is the layer's output, not k or v)."""
    from jax.extend.core import Literal

    tainted, shapes = set(tainted), []
    for eqn in jaxpr.eqns:
        hit = [not isinstance(x, Literal) and x in tainted for x in eqn.invars]
        if not any(hit) or eqn.primitive.name in stop:
            continue
        made = [True] * len(eqn.outvars)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            if len(sub.invars) == len(eqn.invars) and len(sub.outvars) == len(eqn.outvars):
                inner, made = _derived_shapes(sub, [x for x, h in zip(sub.invars, hit) if h], stop)
                shapes += inner
        tainted.update(x for x, m in zip(eqn.outvars, made) if m)
        shapes += [tuple(x.aval.shape) for x, m in zip(eqn.outvars, made) if m]
    return shapes, [not isinstance(x, Literal) and x in tainted for x in jaxpr.outvars]


@pytest.mark.parametrize("impl", ["flash", "reference"])
def test_k_and_v_reach_the_flash_kernels_at_the_models_kv_heads(impl):
    """32 query heads over 4: under 'flash' nothing made from the kv
    projection has a query head's worth of heads in front of the kernels, in
    the layer or inside ``flash_attention``, and the counter says 4; the
    'reference' body still takes k and v broadcast to 32."""
    from distributed_llm_training_benchmark_framework_tpu.models import tinygpt

    B, S, H, KV, Dh = 2, 64, 32, 4, 8
    cfg = llama_cfg(n_embd=H * Dh, n_head=H, n_kv_head=KV, n_layer=1, block_size=S, attention_impl=impl)
    layer = jax.tree.map(lambda leaf: leaf[0], init_params(cfg, jax.random.key(0))["blocks"])
    x = jnp.zeros((B, S, cfg.n_embd), jnp.float32)
    names = sorted(layer)
    closed = jax.make_jaxpr(
        lambda x, *leaves: attention_mixer.sublayer(cfg, x, dict(zip(names, leaves)), None, True)
    )(x, *(layer[n] for n in names))
    wkv = closed.jaxpr.invars[1 + names.index("wkv")]
    shapes, _ = _derived_shapes(closed.jaxpr, [wkv], stop=("custom_vjp_call", "custom_vjp_call_jaxpr"))
    assert (B, S, KV, Dh) in shapes
    whole = {(B, S, H, Dh), (B, H, S, Dh), (B * H, S, Dh)}
    stats = attention_mixer.attn_mask_stats(cfg, S)["global"]
    assert stats["heads"] == H
    if impl == "flash":
        assert not whole & set(shapes), sorted(whole & set(shapes))
        assert (B * KV, S, Dh) in shapes  # a (S, D) slab a kv head, the kernels' operand
        assert stats["kv_heads_in_kernel"] == KV
    else:
        assert (B, S, H, Dh) in shapes
        assert stats["kv_heads_in_kernel"] == H
