"""Overlap rounds 2 + 3 coverage: the zero2 per-block grad-comms path
(PR 8), the fsdp/zero3 forward-side per-block param placement, the
scan-carry kill, the collective-matmul tp fusion (round 15), the
latency-hiding scheduler flag plumbing, and the registry lineage
separation for scheduler-flagged / remat-swept / collective-matmul runs.

Three layers:

- model/step units: ``tinygpt._with_cotangent_spec`` constrains the
  COTANGENT (the gradient adopts its ZeRO-2 placement inside the backward
  layer loop), and ``make_train_step`` arms ``block_grad_spec`` exactly
  for sharded-grad/replicated-param (zero2-shaped) strategies;
- an HLO-level pin that the zero2 arm's gradient collectives lower
  INTERLEAVED with backward compute (not one tail bundle) — the
  structural property the latency-hiding scheduler needs to overlap them;
- platform/registry units: ``apply_latency_hiding_flags`` is idempotent,
  ``scheduler_flags_fingerprint`` extracts exactly the scheduling flags,
  and the A/A proof that ``xla_scheduler_flags`` / ``remat_policy`` join
  the regress config key so flagged/unflagged (and per-policy) lineages
  never cross-gate.
"""

import dataclasses
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from distributed_llm_training_benchmark_framework_tpu.analysis.static import (
    hlo_audit,
)
from distributed_llm_training_benchmark_framework_tpu.models import tinygpt
from distributed_llm_training_benchmark_framework_tpu.parallel.mesh import (
    make_mesh,
)
from distributed_llm_training_benchmark_framework_tpu.regress import (
    store as rstore,
)
from distributed_llm_training_benchmark_framework_tpu.utils import (
    platform as platform_mod,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# Model/step units: the cotangent-spec hook
# ---------------------------------------------------------------------------


def test_with_cotangent_spec_is_identity_forward(eight_devices):
    x = jnp.arange(8.0).reshape(2, 4)
    y = tinygpt._with_cotangent_spec(P("data"), x)
    assert (y == x).all()


def test_with_cotangent_spec_constrains_the_cotangent(eight_devices):
    """The whole point of the hook: the CONSTRAINT lands on the gradient,
    inside the backward — visible as a sharding_constraint eqn in the
    grad jaxpr (the forward stays constraint-free)."""
    mesh = make_mesh((4,), ("data",), devices=jax.devices()[:4])
    spec = P("data")

    def f(x):
        y = tinygpt._with_cotangent_spec(spec, x)
        return (y * y).sum()

    x = jnp.ones((8, 4))
    with mesh:
        fwd = str(jax.make_jaxpr(f)(x))
        bwd = str(jax.make_jaxpr(jax.grad(f))(x))
    assert "sharding_constraint" not in fwd
    assert "sharding_constraint" in bwd


def test_constrain_layer_grads_wraps_only_spec_leaves():
    cfg = tinygpt.get_model_config("S", 64)
    cfg = dataclasses.replace(
        cfg, block_grad_spec=(("wq", P("data")),)
    )
    layer = {"wq": jnp.ones((4, 4)), "wo": jnp.ones((4, 4))}
    out = tinygpt._constrain_layer_grads(cfg, layer)
    # Identity values either way; the wq leaf went through the custom-vjp
    # identity (same values), wo passed through untouched (same object).
    assert (out["wq"] == layer["wq"]).all()
    assert out["wo"] is layer["wo"]
    # No spec -> exact passthrough.
    assert tinygpt._constrain_layer_grads(
        dataclasses.replace(cfg, block_grad_spec=None), layer
    ) is layer


# ---------------------------------------------------------------------------
# HLO-level pin: zero2 grad collectives interleave with backward compute
# ---------------------------------------------------------------------------


ZERO2_UNROLLED = hlo_audit.ArmSpec(
    "zero2-dp4-unrolled", "zero2", (4,), ("data",),
    global_batch=4, model_family="tinygpt",
    config_overrides=(("scan_layers", False),),
)


@pytest.fixture(scope="module")
def zero2_hlo(eight_devices):
    return hlo_audit.lower_arm(ZERO2_UNROLLED).as_text()


def _grad_collective_and_dot_lines(txt):
    lines = txt.splitlines()
    colls = [i for i, l in enumerate(lines)
             if re.search(r"= \S+ (all-reduce|reduce-scatter)", l)]
    dots = [i for i, l in enumerate(lines)
            if re.search(r"= \S+ dot\(", l)]
    return colls, dots


def test_zero2_grad_comms_interleave_not_tail_bundle(zero2_hlo):
    """Round-8 overlap shape: the zero2 arm's gradient reduce-scatters
    (lowered as all-reduce+slice on the CPU backend) must appear
    INTERLEAVED with the backward's dot ops in the optimized module, not
    as one bundle after the last dot — a tail bundle is unoverlappable
    no matter what the scheduler does. Regressing the per-block grad
    placement (tinygpt.block_grad_spec / the step's grad constraint)
    shows up here as the collectives sinking past the final dot."""
    colls, dots = _grad_collective_and_dot_lines(zero2_hlo)
    assert colls, "zero2 arm lowered no gradient collectives at all?"
    assert dots
    last_dot = max(dots)
    interleaved = [i for i in colls if i < last_dot]
    assert len(interleaved) >= len(colls) // 2, (
        f"only {len(interleaved)}/{len(colls)} grad collectives appear "
        "before the last backward dot — the grad comms have collapsed "
        "into a tail bundle"
    )


def test_zero2_shape_arms_block_grad_spec(eight_devices):
    """The step arms the per-layer-slice grad placement exactly for the
    zero2 shape (sharded grads, replicated params, no pipeline) —
    fsdp/zero3 keep the param-equal layout via the tail constraint, ddp
    has nothing to scatter, pipeline schedules keep the tail path."""
    import functools

    from distributed_llm_training_benchmark_framework_tpu.parallel import (
        get_strategy,
        strategies as strat,
    )
    from distributed_llm_training_benchmark_framework_tpu.train.step import (
        zero2_block_grad_spec,
    )

    mesh = make_mesh((4,), ("data",), devices=jax.devices()[:4])
    cfg = tinygpt.get_model_config("S", 64)
    params_shape = jax.eval_shape(
        functools.partial(tinygpt.init_params, cfg), jax.random.key(0)
    )
    specs = strat.param_partition_specs(
        params_shape, mesh, shard=True, kv_heads=cfg.kv_heads,
    )
    armed = zero2_block_grad_spec(get_strategy("zero2"), specs, False)
    assert armed, "zero2 must arm the per-block grad placement"
    names = dict(armed)
    assert set(names) == set(specs["blocks"])
    for name, spec in armed:
        # The layer-slice spec is the stacked spec minus its layers axis.
        assert tuple(spec) == tuple(specs["blocks"][name])[1:]
    # A leaf whose shard fell back to the stacked LAYERS axis is skipped:
    # its per-layer slice is replicated, and pinning that mid-backward
    # would ADD a per-layer round-trip instead of hiding one.
    forced = {**specs, "blocks": {**specs["blocks"], "wq": P("data")}}
    armed_forced = zero2_block_grad_spec(get_strategy("zero2"), forced, False)
    assert "wq" not in dict(armed_forced)
    only_layer_axis = {
        **specs,
        "blocks": {k: P("data") for k in specs["blocks"]},
    }
    assert zero2_block_grad_spec(
        get_strategy("zero2"), only_layer_axis, False
    ) is None  # nothing armable -> no config change at all
    assert zero2_block_grad_spec(get_strategy("ddp"), specs, False) is None
    assert zero2_block_grad_spec(get_strategy("fsdp"), specs, False) is None
    assert zero2_block_grad_spec(get_strategy("zero3"), specs, False) is None
    # Pipeline runs keep the tail path even for zero2.
    assert zero2_block_grad_spec(get_strategy("zero2"), specs, True) is None


# ---------------------------------------------------------------------------
# Round 15 (a): fsdp/zero3 forward-side per-block param placement
# ---------------------------------------------------------------------------


def _tier_s_specs(mesh, cfg, shard=True):
    import functools

    from distributed_llm_training_benchmark_framework_tpu.parallel import (
        strategies as strat,
    )

    params_shape = jax.eval_shape(
        functools.partial(tinygpt.init_params, cfg), jax.random.key(0)
    )
    return strat.param_partition_specs(
        params_shape, mesh, shard=shard, kv_heads=cfg.kv_heads,
        scan_stacked=cfg.scan_layers,
    )


def test_fsdp_shape_arms_block_param_spec(eight_devices):
    """The step arms the per-layer-slice PARAM placement exactly for the
    sharded-param shapes (fsdp/zero3, incl. composed dp x tp meshes) —
    ddp/zero2 have nothing to gather, pipeline keeps the manual path, and
    layers-axis-sharded leaves are skipped like the zero2 grad rule."""
    from distributed_llm_training_benchmark_framework_tpu.parallel import (
        get_strategy,
    )
    from distributed_llm_training_benchmark_framework_tpu.train import (
        step as step_mod,
    )

    mesh = make_mesh((4,), ("data",), devices=jax.devices()[:4])
    cfg = tinygpt.get_model_config("S", 64)
    specs = _tier_s_specs(mesh, cfg)
    for name in ("fsdp", "zero3"):
        armed = step_mod.fsdp_block_param_spec(get_strategy(name), specs, False)
        assert armed, f"{name} must arm the per-block param placement"
        for leaf, spec in armed:
            # The layer-slice spec is the stacked spec minus its layers axis.
            assert tuple(spec) == tuple(specs["blocks"][leaf])[1:]
    # Replicated-param strategies and pipeline shapes stay None.
    assert step_mod.fsdp_block_param_spec(get_strategy("ddp"), specs, False) is None
    assert step_mod.fsdp_block_param_spec(get_strategy("zero2"), specs, False) is None
    assert step_mod.fsdp_block_param_spec(get_strategy("fsdp"), specs, True) is None
    # A leaf whose shard fell back to the stacked LAYERS axis is skipped.
    forced = {**specs, "blocks": {**specs["blocks"], "wqkv": P("data")}}
    assert "wqkv" not in dict(
        step_mod.fsdp_block_param_spec(get_strategy("fsdp"), forced, False)
    )


# The forward-overlap shape itself (weight movement interleaved with the
# forward's dots, never one bundle above the first) is asserted on the compile
# that counts, tests/test_tpu_compile.py::
# test_forward_weight_rings_interleave_with_dots: jax 0.9.0's CPU scheduler
# hoists every all-gather above the first dot whatever the program says.


def test_mlp_hidden_spec_arming_matrix(eight_devices):
    """mlp_hidden_spec arms exactly where a sharded-param strategy shards
    the first projection's weight over 'data' along F (every pure-dp mesh:
    the largest-axis rule): the hidden dim takes 'data' and the batch dim
    gives it up. Never for replicated parameters, a pipeline, the
    collective-matmul path, one chip, the MoE branch, or a composed dp x tp
    mesh (F is 'model''s there, 'data' takes D)."""
    from distributed_llm_training_benchmark_framework_tpu.parallel import (
        get_strategy,
    )
    from distributed_llm_training_benchmark_framework_tpu.train import (
        step as step_mod,
    )

    pure_dp = make_mesh((4,), ("data",), devices=jax.devices()[:4])
    dp_sp = make_mesh((2, 2), ("data", "seq"), devices=jax.devices()[:4])
    composed = make_mesh(
        (2, 1, 2), ("data", "seq", "model"), devices=jax.devices()[:4]
    )
    one_chip = make_mesh((1,), ("data",), devices=jax.devices()[:1])
    gelu = tinygpt.get_model_config("S", 64)
    swiglu = dataclasses.replace(gelu, mlp_act="swiglu", bias=False)

    def armed(strategy, mesh, cfg, pipelined=False):
        shard = get_strategy(strategy).shard_params
        return step_mod.mlp_hidden_spec(
            get_strategy(strategy), mesh, cfg, _tier_s_specs(mesh, cfg, shard),
            pipelined,
        )

    for cfg in (gelu, swiglu):
        for name in ("fsdp", "zero3"):
            assert armed(name, pure_dp, cfg) == P(None, None, "data")
        assert armed("fsdp", dp_sp, cfg) == P(None, "seq", "data")
        assert armed("ddp", pure_dp, cfg) is None
        assert armed("zero2", pure_dp, cfg) is None
        assert armed("fsdp", pure_dp, cfg, pipelined=True) is None
        assert armed("fsdp", one_chip, cfg) is None
        assert armed("fsdp", composed, cfg) is None
        assert armed(
            "fsdp", pure_dp, dataclasses.replace(cfg, tp_collective_matmul=True)
        ) is None
    assert armed("fsdp", pure_dp, dataclasses.replace(gelu, n_experts=4)) is None
    # The model's hook: an unset field is an exact no-op.
    x = jnp.ones((2, 8, 16))
    assert tinygpt._pin_mlp_hidden(gelu, x) is x


@pytest.mark.parametrize("strategy", ["ddp", "zero2", "fsdp"])
def test_replicated_param_steps_do_not_see_mlp_hidden_spec(
    eight_devices, monkeypatch, strategy
):
    """The one-chip cells' guarantee, on a dp=4 mesh: under ddp and zero2
    the compiled step's text is the same with ``mlp_hidden_spec`` as the
    step arms it and with it forced off; under fsdp it is not (so the
    comparison can see the field)."""
    from distributed_llm_training_benchmark_framework_tpu.train import (
        step as step_mod,
    )

    spec = hlo_audit.ArmSpec(
        f"{strategy}-dp4-unrolled", strategy, (4,), ("data",),
        global_batch=4, model_family="tinygpt",
        config_overrides=(("scan_layers", False),),
    )
    texts = []
    for forced_off in (False, True):  # one call site: the text names its lines
        if forced_off:
            monkeypatch.setattr(step_mod, "mlp_hidden_spec", lambda *a, **k: None)
        texts.append(hlo_audit.lower_arm(spec).as_text())
    assert (texts[0] == texts[1]) == (strategy != "fsdp")


def test_scan_carry_spec_arming_matrix(eight_devices):
    """scan_carry_spec arms exactly for sharded-param (fsdp/zero3),
    scanned, non-pipelined arms on composed dp x tp meshes — never for
    replicated-param strategies (they cannot exhibit the stash-reshard
    pathology, so e.g. the ddp llama-tp2-gqa topology clients stay
    byte-frozen) and never for the collective-matmul path, which owns
    its own (sequence-sharded) residual layout."""
    import dataclasses as _dc

    from distributed_llm_training_benchmark_framework_tpu.parallel import (
        get_strategy,
    )
    from distributed_llm_training_benchmark_framework_tpu.train import (
        step as step_mod,
    )

    composed = make_mesh(
        (2, 1, 2), ("data", "seq", "model"), devices=jax.devices()[:4]
    )
    pure_dp = make_mesh((4,), ("data",), devices=jax.devices()[:4])
    cfg = tinygpt.get_model_config("S", 64)
    fsdp, zero3 = get_strategy("fsdp"), get_strategy("zero3")
    assert step_mod.scan_carry_spec(
        fsdp, composed, cfg, False
    ) == P(("data",), None, None)
    assert step_mod.scan_carry_spec(
        zero3, composed, cfg, False
    ) == P(("data",), None, None)
    # Replicated-param strategies never arm.
    assert step_mod.scan_carry_spec(
        get_strategy("ddp"), composed, cfg, False
    ) is None
    assert step_mod.scan_carry_spec(
        get_strategy("zero2"), composed, cfg, False
    ) is None
    assert step_mod.scan_carry_spec(fsdp, pure_dp, cfg, False) is None
    assert step_mod.scan_carry_spec(fsdp, composed, cfg, True) is None
    assert step_mod.scan_carry_spec(
        fsdp, composed, _dc.replace(cfg, scan_layers=False), False
    ) is None
    assert step_mod.scan_carry_spec(
        fsdp, composed, _dc.replace(cfg, tp_collective_matmul=True), False
    ) is None


def test_scan_carry_budget_floor():
    """The scan-carry kill's new floor is FROZEN: the banked 4
    replication-reshard suspects on llama-fsdp-dp4-tp2-scan are gone from
    the committed budget (target 0, achieved 0 — the composed-mesh scan
    lowering no longer pays permute chains), and the unrolled sibling's
    budget stayed at its round-8 profile."""
    budgets = hlo_audit.load_budgets()
    scan = budgets["arms"]["llama-fsdp-dp4-tp2-scan"]
    assert scan["replication_reshard_suspects"] == 0
    assert scan["collectives"]["collective-permute"] == 0
    unrolled = budgets["arms"]["llama-fsdp-dp4-tp2"]
    assert unrolled["replication_reshard_suspects"] == 0
    assert unrolled["collectives"]["collective-permute"] == 0


def test_contraction_skip_rule_is_scan_scoped(eight_devices):
    """The _COMPOSED_CONTRACTION_DATA_SKIP rule (wq stays model-only) only
    applies to the scanned lowering: unrolled specs keep the round-8
    placement so the suite's measured arm budget stays byte-identical."""
    import functools

    from distributed_llm_training_benchmark_framework_tpu.models.llama import (
        get_llama_config,
    )
    from distributed_llm_training_benchmark_framework_tpu.parallel import (
        strategies as strat,
    )

    mesh = make_mesh(
        (4, 1, 2), ("data", "seq", "model"), devices=jax.devices()[:8]
    )
    cfg = get_llama_config("S", 64)
    shapes = jax.eval_shape(
        functools.partial(tinygpt.init_params, cfg), jax.random.key(0)
    )
    scanned = strat.param_partition_specs(
        shapes, mesh, shard=True, kv_heads=cfg.kv_heads, scan_stacked=True
    )
    unrolled = strat.param_partition_specs(
        shapes, mesh, shard=True, kv_heads=cfg.kv_heads, scan_stacked=False
    )
    assert tuple(scanned["blocks"]["wq"]) == (None, None, "model")
    assert tuple(unrolled["blocks"]["wq"]) == (None, "data", "model")
    # The big leaves keep their fsdp 'data' split in BOTH lowerings.
    assert "data" in tuple(scanned["blocks"]["wgu"])
    assert "data" in tuple(scanned["blocks"]["wkv"])


# ---------------------------------------------------------------------------
# Round 15 (b): collective-matmul tp fusion
# ---------------------------------------------------------------------------


CMM_ARM = hlo_audit.ROSTER["llama-tp2-gqa-cmm"]


def _cmm_configs(family):
    import jax.numpy as jnp

    from distributed_llm_training_benchmark_framework_tpu.models.llama import (
        get_llama_config,
    )

    base = (
        get_llama_config("S", 64) if family == "llama"
        else tinygpt.get_model_config("S", 64)
    )
    cfg = dataclasses.replace(
        base, dropout=0.0,
        compute_dtype=jnp.float32, param_dtype=jnp.float32,
    )
    return cfg, dataclasses.replace(cfg, tp_collective_matmul=True)


@pytest.mark.parametrize("family", ["llama", "tinygpt"])
def test_cmm_matches_plain_tp_forward_and_grads(eight_devices, family):
    """Lowering equivalence: the collective-matmul path computes the SAME
    loss and gradients as the plain tp lowering (fp32, tp=2) — llama
    covers the GQA split projections incl. the misaligned-kv replicated
    ring; tinygpt covers the fused-wqkv and GELU-MLP shapes."""
    import jax.numpy as jnp

    cfg, cfg_cmm = _cmm_configs(family)
    mesh = make_mesh(
        (1, 1, 2), ("data", "seq", "model"), devices=jax.devices()[:2]
    )
    params = tinygpt.init_params(cfg, jax.random.key(0))
    idx = jax.random.randint(jax.random.key(1), (2, 64), 0, cfg.vocab_size)

    def loss_of(c):
        return lambda p: tinygpt.loss_fn(c, p, idx, idx, None, True)

    with jax.set_mesh(mesh):
        l0, g0 = jax.jit(jax.value_and_grad(loss_of(cfg)))(params)
        l1, g1 = jax.jit(jax.value_and_grad(loss_of(cfg_cmm)))(params)
    assert abs(float(l0) - float(l1)) < 1e-5
    for a, b in zip(jax.tree.leaves(g0), jax.tree.leaves(g1)):
        assert float(jnp.max(jnp.abs(a - b))) < 1e-5


def test_cmm_falls_back_to_plain_einsum_without_model_axis(eight_devices):
    """The knob is inert on a pure-dp mesh: ag_proj/rs_proj fall back to
    the plain einsum, so a --tp-collective-matmul run without tensor
    parallelism computes identically (and lowers no rings)."""
    import jax.numpy as jnp

    from distributed_llm_training_benchmark_framework_tpu.ops import (
        collective_matmul as cm,
    )

    mesh = make_mesh((2,), ("data",), devices=jax.devices()[:2])
    x = jax.random.normal(jax.random.key(0), (2, 8, 16))
    w = jax.random.normal(jax.random.key(1), (16, 12))
    with jax.set_mesh(mesh):
        y = jax.jit(lambda a, b: cm.ag_proj(a, b))(x, w)
        z = jax.jit(lambda a, b: cm.rs_proj(a, b))(y, w.T)
    ref = jnp.einsum("bsd,df->bsf", x, w, preferred_element_type=jnp.float32)
    assert float(jnp.max(jnp.abs(y - ref))) < 1e-5
    assert z.shape == (2, 8, 16)


def test_cmm_ring_replaces_projection_gathers(eight_devices):
    """The fusion's HLO signature on the audited arm: the layer stack
    (the scanned while-loop bodies) lowers ZERO all-gathers — every
    projection's comms are ppermute ring hops — and the only gathers left
    sit in ENTRY (the embed/head/loss boundary outside the stack)."""
    txt = hlo_audit.lower_arm(CMM_ARM).as_text()
    comp = None
    body_gathers, permutes = [], 0
    for l in txt.splitlines():
        if l and not l[0].isspace() and "{" in l:
            comp = l.split("{")[0].strip()
        if re.search(r"= \S+ all-gather\(", l) and not comp.startswith("ENTRY"):
            body_gathers.append(l.strip()[:80])
        if re.search(r"= \S+ collective-permute\(", l):
            permutes += 1
    assert body_gathers == [], (
        "projection all-gathers survived inside the layer stack:\n"
        + "\n".join(body_gathers)
    )
    assert permutes > 0, "no ppermute ring lowered at all?"


def test_cmm_arm_budget_is_frozen_with_ring_signature():
    """The committed budget holds the ring's signature: ppermutes on the
    cmm arm and none on the plain one, reshard suspects 0. (Under jax 0.4
    the plain arm also held 21 all-gathers against the cmm arm's 5; this
    jax's CPU partitioner gives the plain arm no projection all-gather to
    collapse, so the pair no longer differs there; what the ring removes
    is asserted against its own unfused form in
    ``test_cmm_injection_registry_and_flag_restore``.)"""
    budgets = hlo_audit.load_budgets()
    cmm = budgets["arms"]["llama-tp2-gqa-cmm"]
    plain = budgets["arms"]["llama-tp2-gqa"]
    assert cmm["collectives"]["collective-permute"] > 0
    assert cmm["replication_reshard_suspects"] == 0
    assert plain["collectives"]["collective-permute"] == 0


def test_cmm_refuses_incompatible_compositions(eight_devices):
    """--tp-collective-matmul refuses pipeline / sequence-parallel / MoE
    compositions loudly (both want to own the sequence/token layout)."""
    from distributed_llm_training_benchmark_framework_tpu.parallel import (
        get_strategy,
    )
    from distributed_llm_training_benchmark_framework_tpu.train.loop import (
        run_benchmark,
    )

    common = dict(
        strategy=get_strategy("ddp"), tier="S", seq_len=64, steps=2,
        warmup_steps=0, per_device_batch=1, grad_accum=1, world_size=4,
        results_dir=None, telemetry=False, tp_collective_matmul=True,
    )
    with pytest.raises(ValueError, match="pipeline"):
        run_benchmark(pipeline_parallel=2, tensor_parallel=2, **common)
    with pytest.raises(ValueError, match="sequence"):
        run_benchmark(sequence_parallel=2, tensor_parallel=2,
                      attention_impl="ring", **common)
    with pytest.raises(ValueError, match="MoE"):
        run_benchmark(n_experts=4, tensor_parallel=2, **common)


def test_cmm_injection_registry_and_flag_restore(eight_devices):
    """bad-cmm-ring is a registered injection: it swaps the ring bodies for
    the duration of the lowering and puts the originals back."""
    import dataclasses as _dc

    from distributed_llm_training_benchmark_framework_tpu.ops import (
        collective_matmul as cm,
    )
    assert "bad-cmm-ring" in hlo_audit._INJECTIONS
    real = cm.ag_proj_sharded, cm.rs_proj_sharded
    rep = hlo_audit.audit_arm(
        _dc.replace(CMM_ARM, inject="bad-cmm-ring")
    )
    assert (cm.ag_proj_sharded, cm.rs_proj_sharded) == real  # restored
    # The unfused lowering: bulk collectives back, ring gone.
    assert rep.collectives["collective-permute"] == 0
    assert rep.collectives["reduce-scatter"] > 0
    budgets = hlo_audit.load_budgets()
    deltas = hlo_audit.diff_against_budget(rep, budgets)
    assert any("all-gather" in d and "REGRESSED" in d for d in deltas), deltas
    # The fusion claim, on this pair: the ring removes bulk collectives the
    # unfused form of the same projections pays (the plain tp arm no longer
    # shows it: this jax gives it no projection all-gather to collapse).
    bulk = lambda c: c["all-gather"] + c["reduce-scatter"]
    fused = budgets["arms"]["llama-tp2-gqa-cmm"]["collectives"]
    assert bulk(fused) < bulk(rep.collectives), (fused, rep.collectives)


def test_cmm_arm_joins_topology_roster_with_flat_ring():
    """Satellite: the cmm arm is audited at the topology tiers, and its
    frozen ppermute count is FLAT along the data axis (the ring is a
    function of the tp degree alone)."""
    assert "llama-tp2-gqa-cmm" in hlo_audit.TOPOLOGY_ARMS
    budgets = hlo_audit.load_budgets()
    tiers = budgets["topology_tiers"]
    counts = {
        t: tiers[t]["arms"]["llama-tp2-gqa-cmm"]["collectives"][
            "collective-permute"
        ]
        for t in ("v5e-16", "v5e-64")
        if "llama-tp2-gqa-cmm" in tiers.get(t, {}).get("arms", {})
    }
    assert len(counts) == 2, tiers.keys()
    assert len(set(counts.values())) == 1, counts
    assert all(
        tiers[t]["arms"]["llama-tp2-gqa-cmm"]["replication_reshard_suspects"]
        == 0
        for t in counts
    )


# ---------------------------------------------------------------------------
# Platform units: the latency-hiding flag set
# ---------------------------------------------------------------------------


def test_apply_latency_hiding_flags_is_idempotent(monkeypatch):
    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    monkeypatch.setenv("XLA_FLAGS", "--xla_foo=1")
    first = platform_mod.apply_latency_hiding_flags()
    assert "--xla_foo=1" in first
    for f in platform_mod.LATENCY_HIDING_XLA_FLAGS:
        assert f in first.split()
    second = platform_mod.apply_latency_hiding_flags()
    assert second == first  # no duplicate appends
    assert os.environ["XLA_FLAGS"] == first


def test_apply_latency_hiding_flags_skips_without_tpu(monkeypatch, capsys):
    """XLA ABORTS the whole process on unknown flags in XLA_FLAGS, and
    the latency-hiding set is --xla_tpu_*: on a forced-CPU host the
    apply must warn and no-op (leaving the unflagged lineage intact),
    never let the fatal unknown-flag check fire."""
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    monkeypatch.setenv("XLA_FLAGS", "--xla_foo=1")
    out = platform_mod.apply_latency_hiding_flags()
    assert out == "--xla_foo=1"
    assert os.environ["XLA_FLAGS"] == "--xla_foo=1"
    assert "skipped" in capsys.readouterr().err
    # Any tpu-like forced platform (incl. multi-platform lists) applies.
    monkeypatch.setenv("JAX_PLATFORMS", "tpu,cpu")
    assert platform_mod.tpu_xla_plausible() is True
    # Another forced accelerator platform is not our flag set either.
    monkeypatch.setenv("JAX_PLATFORMS", "cuda")
    assert platform_mod.tpu_xla_plausible() is False


def test_require_tpu_refuses_a_silent_cpu_fallback(monkeypatch):
    """This backend is the CPU. Asked for by name it is allowed (the tests'
    mode); found by default — no chip, run carries on — it is an error."""
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    platform_mod.require_tpu()
    monkeypatch.setenv("JAX_PLATFORMS", "tpu,cpu")  # a list naming it counts
    platform_mod.require_tpu()
    monkeypatch.delenv("JAX_PLATFORMS")
    with pytest.raises(RuntimeError, match="no TPU: jax came up on 'cpu'"):
        platform_mod.require_tpu()


def test_compile_cache_is_placed_from_outside(monkeypatch):
    """JAX_COMPILATION_CACHE_DIR set: jax reads it, no directory is set in
    code. Unset: one fixed path inside the checkout — never a temp name."""
    import jax

    updates = []
    monkeypatch.setattr(jax.config, "update", lambda k, v: updates.append((k, v)))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
    assert platform_mod.enable_compile_cache() == "/somewhere/else"
    assert updates == []
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    want = os.path.join(repo, ".jax_cache")
    assert platform_mod.enable_compile_cache() == want
    assert platform_mod.enable_compile_cache() == want  # fixed, not per call
    assert updates == [("jax_compilation_cache_dir", want)] * 2


def test_scheduler_flags_fingerprint_extracts_scheduling_subset():
    flags = ("--xla_force_host_platform_device_count=8 "
             "--xla_tpu_enable_latency_hiding_scheduler=true "
             "--xla_tpu_enable_async_collective_fusion=true")
    fp = platform_mod.scheduler_flags_fingerprint(flags)
    assert "latency_hiding" in fp and "async_collective" in fp
    assert "host_platform_device_count" not in fp
    # Sorted + deduped: order/duplication in XLA_FLAGS cannot fork lineages.
    assert fp == platform_mod.scheduler_flags_fingerprint(
        " ".join(reversed(fp.split())) + " " + fp
    )
    assert platform_mod.scheduler_flags_fingerprint("") == ""


def test_full_flag_set_fingerprint_covers_every_flag():
    fp = platform_mod.scheduler_flags_fingerprint(
        " ".join(platform_mod.LATENCY_HIDING_XLA_FLAGS)
    )
    assert set(fp.split()) == set(platform_mod.LATENCY_HIDING_XLA_FLAGS)


def test_harness_and_entrypoint_carry_the_flag():
    from distributed_llm_training_benchmark_framework_tpu.train.harness import (
        build_parser,
    )

    flags = {o for a in build_parser()._actions for o in a.option_strings}
    assert "--xla-latency-hiding" in flags
    entry = open(os.path.join(REPO, "docker", "entrypoint.sh")).read()
    assert "XLA_LATENCY_HIDING" in entry
    assert "--xla-latency-hiding" in entry
    # bench.py stamps the fingerprint into its contract rows (additive,
    # only when flags are live) — without this a flagged bench run would
    # land in the unflagged regress lineage.
    bench_src = open(os.path.join(REPO, "bench.py")).read()
    assert "--xla-latency-hiding" in bench_src
    assert 'row_extra["xla_scheduler_flags"]' in bench_src


# ---------------------------------------------------------------------------
# Registry lineage: scheduler flags + remat policy join the config key
# ---------------------------------------------------------------------------


def _rec(**row):
    base = {
        "metric": "tinygpt_tierA_seq2048_tokens_per_sec_per_chip",
        "value": 41000.0, "strategy": "zero2", "tier": "A",
        "seq_len": 2048, "steps": 100, "warmup_steps": 5,
    }
    base.update(row)
    return rstore.record_from_bench_row(base, source="test")


def test_scheduler_flags_join_config_key_aa():
    """A/A: identical measurements with and without the scheduler flags
    are DIFFERENT lineages — the flag changes the collective schedule, so
    cross-gating them would verdict a compiler change as a perf delta.
    Legacy rows (no field) stay in the unflagged lineage."""
    plain = _rec()
    flagged = _rec(xla_scheduler_flags=" ".join(
        platform_mod.LATENCY_HIDING_XLA_FLAGS
    ))
    same = _rec()
    assert rstore.config_key(plain) == rstore.config_key(same)
    assert rstore.config_key(plain) != rstore.config_key(flagged)
    # Legacy record (field absent) == unflagged lineage.
    legacy = _rec()
    legacy["result"].pop("xla_scheduler_flags", None)
    assert rstore.config_key(legacy) == rstore.config_key(plain)
    # The flags are triage-visible in the env fingerprint too.
    assert flagged["env"]["xla_scheduler_flags"] != ""


def test_cmm_joins_config_key_aa():
    """A/A: identical measurements with and without the collective-matmul
    fusion are DIFFERENT lineages (the projection schedule changed), so
    cmm and plain-tp runs never cross-gate; legacy rows (no field) stay
    in the plain lineage. Mirrors the xla_scheduler_flags split."""
    plain = _rec()
    cmm = _rec(tp_collective_matmul=True)
    assert rstore.config_key(plain) == rstore.config_key(_rec())
    assert rstore.config_key(plain) != rstore.config_key(cmm)
    legacy = _rec()
    legacy["result"].pop("tp_collective_matmul", None)
    assert rstore.config_key(legacy) == rstore.config_key(plain)
    # Triage-visible in the env fingerprint too.
    assert cmm["env"]["tp_collective_matmul"] is True
    assert plain["env"]["tp_collective_matmul"] is False


def test_cmm_flag_surface_and_row_stamp():
    """Wiring pins: the harness, bench.py and the container env all carry
    --tp-collective-matmul, and bench.py stamps the row only when live
    (default rows stay byte-identical — the plain lineage)."""
    from distributed_llm_training_benchmark_framework_tpu.train.harness import (
        build_parser,
    )

    flags = {o for a in build_parser()._actions for o in a.option_strings}
    assert "--tp-collective-matmul" in flags
    entry = open(os.path.join(REPO, "docker", "entrypoint.sh")).read()
    assert "TP_COLLECTIVE_MATMUL" in entry
    assert "--tp-collective-matmul" in entry
    bench_src = open(os.path.join(REPO, "bench.py")).read()
    assert "--tp-collective-matmul" in bench_src
    assert 'row_extra["tp_collective_matmul"]' in bench_src
    suite = open(
        os.path.join(REPO, "scripts", "run_all_benchmarks.sh")
    ).read()
    assert "llama-tp2-cmm" in suite
    launch = open(os.path.join(REPO, "scripts", "launch_multi.sh")).read()
    assert "--tp-collective-matmul" in launch


def test_remat_policy_joins_config_key_per_policy():
    keys = {
        pol: rstore.config_key(_rec(remat_policy=pol))
        for pol in ("none", "dots", "full", "auto")
    }
    assert len(set(keys.values())) == 4
    # Absent (ordinary bench/flagship rows) is its own lineage as well.
    assert rstore.config_key(_rec()) not in set(keys.values())
