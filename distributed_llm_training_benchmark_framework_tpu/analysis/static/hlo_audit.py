"""Engine 1: the HLO collective-budget auditor.

The compiled HLO for every strategy arm is a deterministic, CPU-lowerable
artifact: ``train.step.abstract_compile_step`` compiles the REAL train-step
executable from ``ShapeDtypeStruct``s over a virtual CPU mesh (the same
machinery the auto-remat probe and ``tests/test_collective_lowering.py``
use), so regressions in collective counts, donation, and dtype promotion
are catchable in CI before any TPU time is spent. PR 1's motivating case:
a single unchased GSPMD full-replication fallback on the llama x tp GQA kv
projections cost 6 collective-permutes + 8 all-gathers per step and was
only caught by a one-off HLO test — this module makes that class of check
systematic, per arm, against frozen budgets.

Determinism contract: counts are a property of (jax/XLA version, backend,
device count, arm config). Budgets are frozen on the CPU backend with 8
forced host devices (``scripts/graftcheck.sh`` / the CLI force both); a
jax upgrade legitimately moves counts — regenerate with
``--update-budgets`` and review the diff like any other lockfile change.
"""

from __future__ import annotations

import dataclasses
import json
import os
import re
from typing import Any, Dict, List, Mapping, Optional, Tuple

REPO_ROOT = os.path.abspath(
    os.path.join(os.path.dirname(__file__), "..", "..", "..")
)
DEFAULT_BUDGETS_PATH = os.path.join(REPO_ROOT, "configs", "collective_budgets.json")

#: The collective opcodes the auditor counts, in report order.
COLLECTIVE_OPS = (
    "all-gather",
    "reduce-scatter",
    "all-reduce",
    "collective-permute",
    "all-to-all",
)

_INJECTIONS = ("bad-kv-spec", "bad-fsdp-axis", "bad-cmm-ring")


@dataclasses.dataclass(frozen=True)
class ArmSpec:
    """One auditable arm: strategy x model family x mesh geometry.

    ``config_overrides`` is a tuple of (key, value) pairs passed to the
    model-config factory (tuple, not dict, so the spec stays hashable);
    ``inject`` deliberately reintroduces a known-bad configuration for
    self-tests — 'bad-kv-spec' disables the kv-head-aligned PartitionSpec
    rule, bringing back the GQA full-replicate resharding fallback PR 1
    fixed (the auditor must flag it).

    ``pipeline_schedule``/``virtual_stages`` only matter when the mesh
    carries a >1 'pipe' axis (the schedule-auditor roster below); they
    flow into ``train.step.abstract_compile_step`` unchanged.
    """

    name: str
    strategy: str
    mesh_shape: Tuple[int, ...]
    axes: Tuple[str, ...]
    global_batch: int
    model_family: str = "tinygpt"
    tier: str = "S"
    seq_len: int = 64
    grad_accum: int = 1
    config_overrides: Tuple[Tuple[str, Any], ...] = ()
    inject: Optional[str] = None
    pipeline_schedule: str = "gpipe"
    virtual_stages: int = 1


@dataclasses.dataclass(frozen=True)
class ArmReport:
    """Structured audit result for one arm — everything the budget pins."""

    arm: str
    collectives: Mapping[str, int]
    # collective-permutes in an arm whose mesh has no >1 'seq'/'pipe' axis:
    # rings and pipelines legitimately permute; a pure dp/tp/ep arm only
    # emits them when the SPMD partitioner fell back to
    # full-replicate-then-repartition resharding (the PR 1 GQA fallback
    # lowered exactly so on this jaxlib).
    replication_reshard_suspects: int
    # Donation: aliased entry-parameter buffers vs donatable leaves
    # (params + optimizer state, donate_argnums=(0, 1) in the train step).
    donated_inputs: int
    donatable_inputs: int
    # bf16 -> f32 convert instructions in the module. bf16-compute arms
    # expect a stable population (fp32 loss/accum upcasts); growth means a
    # new unintended promotion of bf16 tensors to f32.
    bf16_to_f32_converts: int

    def to_budget_entry(self) -> Dict[str, Any]:
        return {
            "collectives": dict(self.collectives),
            "replication_reshard_suspects": self.replication_reshard_suspects,
            "donated_inputs": self.donated_inputs,
            "donatable_inputs": self.donatable_inputs,
            "bf16_to_f32_converts": self.bf16_to_f32_converts,
        }


#: The audit roster: one arm per (strategy x model-family x mesh-geometry)
#: shape the suite roster exercises (scripts/run_all_benchmarks.sh), scaled
#: to tier S / seq 64 so each compiles in seconds on the CPU backend. All
#: arms assume 8 devices (the virtual-mesh test geometry).
ROSTER: Dict[str, ArmSpec] = {
    spec.name: spec
    for spec in (
        # The pure-strategy matrix at dp=8.
        ArmSpec("ddp-dp8", "ddp", (8,), ("data",), global_batch=16),
        ArmSpec("fsdp-dp8", "fsdp", (8,), ("data",), global_batch=16),
        ArmSpec("zero2-dp8", "zero2", (8,), ("data",), global_batch=16),
        ArmSpec("zero3-dp8", "zero3", (8,), ("data",), global_batch=16),
        # llama x tensor parallel — the GQA kv-alignment arm (PR 1): a
        # 'model' degree that does not divide the family's kv heads must
        # NOT trip the full-replicate resharding fallback.
        ArmSpec(
            "llama-tp2-gqa", "ddp", (1, 1, 2), ("data", "seq", "model"),
            global_batch=2, model_family="llama",
        ),
        # llama x fsdp x tp — the suite's llama-tp2 composition arm shape,
        # compiled with the UNROLLED layer loop because that is what the
        # suite actually runs (scripts/run_all_benchmarks.sh LAYER_LOOP
        # defaults to 'unrolled'; through PR 7 this arm audited the scan
        # lowering the suite never measures). Round 8 fixed the composed
        # dp x tp fsdp-axis placement (strategies._shard_largest_free_axis
        # tile-order hygiene): the 13 banked replication-reshard suspects
        # (collective-permutes against transposed device orders) are now 0.
        # `--inject bad-fsdp-axis` proves the auditor still catches the old
        # placement.
        ArmSpec(
            "llama-fsdp-dp4-tp2", "fsdp", (4, 1, 2), ("data", "seq", "model"),
            global_batch=8, model_family="llama",
            config_overrides=(("scan_layers", False),),
        ),
        # The same composition under the scan layer loop (the harness
        # default; pipeline-sharded runs and compile-time-sensitive runs
        # still use it). The round-8 spec rules cut its fallback 13 -> 4;
        # the residue is the scan-carry layout XLA picks for the stacked
        # activation stash — banked here so it cannot grow, and so a future
        # scan-carry fix shows up as a bankable improvement.
        ArmSpec(
            "llama-fsdp-dp4-tp2-scan", "fsdp", (4, 1, 2),
            ("data", "seq", "model"),
            global_batch=8, model_family="llama",
        ),
        # llama x tp with the collective-matmul fusion (round 15,
        # ops/collective_matmul.py): the gqa arm's shape with
        # --tp-collective-matmul on. Its frozen budget IS the fusion's
        # signature — the plain arm's 21 projection all-gathers collapse
        # to the 5 embed/head-boundary gathers outside the layer stack,
        # replaced by the ppermute ring (2 hops per projection class per
        # layer, fwd+bwd), reshard suspects 0 (ring permutes are the
        # budgeted schedule — audit_arm knows cmm arms permute
        # legitimately). `--inject bad-cmm-ring` reverts the ring to the
        # unfused all-gather/reduce-scatter lowering and the audit must
        # flag the arm by name.
        ArmSpec(
            "llama-tp2-gqa-cmm", "ddp", (1, 1, 2), ("data", "seq", "model"),
            global_batch=2, model_family="llama",
            config_overrides=(("tp_collective_matmul", True),),
        ),
        # Sequence parallel: the ring's collective-permute hops are the
        # budgeted schedule, not a regression.
        ArmSpec(
            "zero2-sp4-ring", "zero2", (1, 4, 1), ("data", "seq", "model"),
            global_batch=2,
            config_overrides=(("attention_impl", "ring"),),
        ),
        # Expert parallel: the MoE dispatch/combine all-to-alls.
        ArmSpec(
            "zero2-ep2-moe", "zero2", (4, 1, 1, 1, 2),
            ("data", "seq", "model", "pipe", "expert"),
            global_batch=16,
            config_overrides=(("n_experts", 4),),
        ),
    )
}


def _model_config(spec: ArmSpec):
    from ...models import get_model_config
    from ...models.llama import get_llama_config

    overrides = dict(spec.config_overrides)
    # Dropout adds RNG ops whose count is batch-geometry noise; the audit
    # pins the communication schedule, so arms lower dropout-free (the same
    # choice the original HLO pin tests made).
    overrides.setdefault("dropout", 0.0)
    if spec.model_family == "llama":
        return get_llama_config(spec.tier, spec.seq_len, **overrides)
    if spec.model_family == "tinygpt":
        return get_model_config(spec.tier, spec.seq_len, **overrides)
    raise ValueError(
        f"arm {spec.name!r}: unknown model_family {spec.model_family!r}"
    )


def lower_arm(spec: ArmSpec, devices=None):
    """Compile the arm's train step abstractly; return the jax.stages.Compiled.

    Pure compiler work — no params are initialized and no device memory is
    allocated. Needs ``prod(mesh_shape)`` visible devices (the CLI forces
    8 virtual CPU devices; in-process callers run under the test mesh).
    ``jax.sharding.AbstractMesh`` lowering is not used because the
    collective schedule only exists in the POST-partitioning executable,
    which requires a concrete backend to build.
    """
    import jax

    from ...parallel import get_strategy, make_mesh
    from ...train.step import abstract_compile_step

    if spec.inject is not None and spec.inject not in _INJECTIONS:
        raise ValueError(
            f"arm {spec.name!r}: unknown injection {spec.inject!r} "
            f"(expected one of {_INJECTIONS})"
        )
    if devices is None:
        devices = jax.devices()
    n_needed = 1
    for d in spec.mesh_shape:
        n_needed *= d
    if len(devices) < n_needed:
        raise RuntimeError(
            f"arm {spec.name!r} needs {n_needed} devices, have "
            f"{len(devices)} (run under "
            f"XLA_FLAGS=--xla_force_host_platform_device_count=8)"
        )
    cfg = _model_config(spec)
    mesh = make_mesh(spec.mesh_shape, spec.axes, devices=devices[:n_needed])
    strategy = get_strategy(spec.strategy)

    def compile_():
        return abstract_compile_step(
            cfg, strategy, mesh,
            grad_accum=spec.grad_accum, seed=0, from_table=False,
            global_micro=spec.global_batch, seq_len=spec.seq_len,
            pipeline_schedule=spec.pipeline_schedule,
            virtual_stages=spec.virtual_stages,
        )

    if spec.inject == "bad-kv-spec":
        return _with_bad_kv_spec(compile_)
    if spec.inject == "bad-fsdp-axis":
        return _with_bad_fsdp_axis(compile_)
    if spec.inject == "bad-cmm-ring":
        return _with_bad_cmm_ring(compile_)
    return compile_()


def _swapped(fn, module, **bodies):
    """Run ``fn`` with ``module``'s named functions swapped for ``bodies``,
    and the originals back whatever ``fn`` does: how every injection breaks
    the module it names, which carries no switch for it."""
    real = {name: getattr(module, name) for name in bodies}
    for name, body in bodies.items():
        setattr(module, name, body)
    try:
        return fn()
    finally:
        for name, body in real.items():
            setattr(module, name, body)


def _with_bad_kv_spec(fn):
    """Run ``fn`` with the kv-head-aligned PartitionSpec rule disabled.

    Forcing ``kv_heads=None`` makes ``param_partition_specs`` column-shard
    wkv/bkv over 'model' even when the degree does not divide the kv-head
    count — the misaligned split whose consecutive-block kv repeat has no
    in-place reshard, so GSPMD falls back to full replication (measured on
    this jaxlib as collective-permute + all-gather chains). This is the
    regression the llama-tp2-gqa budget exists to catch; the injection
    exists so CI can prove the auditor catches it.
    """
    from ...parallel import strategies as strat

    real = strat.param_partition_specs

    def misaligned(params, mesh, shard, kv_heads=None, scan_stacked=False):
        return real(params, mesh, shard=shard, kv_heads=None,
                    scan_stacked=scan_stacked)

    return _swapped(fn, strat, param_partition_specs=misaligned)


def _with_bad_fsdp_axis(fn):
    """Run ``fn`` with the composed dp x tp fsdp-axis hygiene disabled.

    Swaps ``strategies._shard_largest_free_axis`` for the pre-round-8
    unrestricted largest-free-axis placement: fsdp 'data' lands AFTER the
    leaf's 'model' axis on row-parallel/vocab leaves (wo/wproj/wte/
    lm_head), producing the transposed device-order tilings whose reshard
    chains lowered as 13 collective-permutes per step on the
    llama-fsdp-dp4-tp2 arm. The audit must flag the regression; the
    injection exists so CI can prove it does.
    """
    from ...parallel import strategies as strat

    def unrestricted(spec, shape, n_shards, is_block_leaf, composed=False):
        axes = list(range(len(shape)))
        if is_block_leaf and len(shape) > 1:
            axes = axes[1:] + axes[:1]
        free = [ax for ax in axes
                if spec[ax] is None and shape[ax] % n_shards == 0
                and shape[ax] >= n_shards]
        if free:
            spec[max(free, key=lambda ax: shape[ax])] = "data"

    return _swapped(fn, strat, _shard_largest_free_axis=unrestricted)


def _with_bad_cmm_ring(fn):
    """Run ``fn`` with the collective-matmul ppermute decomposition broken.

    The two ring bodies of ``ops.collective_matmul`` swapped for their
    unfused all_gather / psum_scatter forms — mathematically equal,
    structurally the bulk collectives the fusion exists to remove. The
    llama-tp2-gqa-cmm frozen budget (projection all-gathers gone, ring
    permutes in their place) must flag the arm by name with the
    all-gather/reduce-scatter growth and the vanished permutes.
    """
    from jax import lax

    from ...ops import collective_matmul as cm

    def ag_unfused(x, w, axis_name="model"):
        xg = lax.all_gather(x, axis_name, axis=1, tiled=True)
        return cm._proj_einsum(xg, w).astype(x.dtype)

    def rs_unfused(y, w, axis_name="model"):
        full = cm._proj_einsum(y, w)
        return lax.psum_scatter(
            full, axis_name, scatter_dimension=1, tiled=True
        ).astype(y.dtype)

    return _swapped(
        fn, cm, ag_proj_sharded=ag_unfused, rs_proj_sharded=rs_unfused
    )


# One instruction definition per line: "%name = <shape> <opcode>(...". The
# instruction NAME usually embeds the opcode too (%all-gather.3), so a raw
# substring count double-counts — anchor on the "= ... opcode(" form.
# Tuple-shaped (variadic / async -start) definitions are counted once;
# async -done halves are not re-counted.
_COLLECTIVE_DEF = re.compile(
    r"= .*?\b(" + "|".join(re.escape(op) for op in COLLECTIVE_OPS)
    + r")(?:-start)?\("
)
# One definition per line, "%name = dtype[dims]..."; an f32 convert names its
# operand, and only some XLA printers repeat the operand's shape beside it.
_INSTRUCTION_DTYPE = re.compile(r"^\s*(?:ROOT )?%(\S+) = (\w+)\[", re.M)
_F32_CONVERT_OPERAND = re.compile(
    r"= f32\[[^\]]*\]\S* convert\((?:(\w+)\[[^\]]*\]\S* )?%([^\s,)]+)\)"
)


def count_bf16_to_f32_converts(hlo_text: str) -> int:
    """f32 ``convert`` instructions whose operand is bf16. The operand's
    dtype is read beside it where the printer gives it (jax 0.4) and from
    the operand's own definition where it does not (jax 0.9 prints
    ``convert(%name)``: a pattern that wants ``convert(bf16[`` counts 0 in
    every module)."""
    dtype_of = dict(_INSTRUCTION_DTYPE.findall(hlo_text))
    return sum(
        (inline or dtype_of.get(name)) == "bf16"
        for inline, name in _F32_CONVERT_OPERAND.findall(hlo_text)
    )


def count_collectives(hlo_text: str) -> Dict[str, int]:
    counts = {op: 0 for op in COLLECTIVE_OPS}
    for line in hlo_text.splitlines():
        m = _COLLECTIVE_DEF.search(line)
        if m:
            counts[m.group(1)] += 1
    return counts


def _donatable_leaves(spec: ArmSpec) -> int:
    """Leaf count of (params, opt_state) — the donate_argnums=(0, 1) trees."""
    import jax

    from ...models import tinygpt
    from ...parallel import get_strategy
    from ...parallel import strategies as strat
    from ...train.step import _resolve_model_config

    strategy = get_strategy(spec.strategy)
    cfg = _resolve_model_config(_model_config(spec), strategy)
    params_shape = jax.eval_shape(
        lambda k: tinygpt.init_params(cfg, k), jax.random.key(0)
    )
    optimizer = strat.make_optimizer(strategy)
    opt_shape = jax.eval_shape(optimizer.init, params_shape)
    return len(jax.tree.leaves(params_shape)) + len(jax.tree.leaves(opt_shape))


def audit_arm(spec: ArmSpec, devices=None) -> ArmReport:
    """Lower one arm and extract its structured collective report."""
    compiled = lower_arm(spec, devices=devices)
    txt = compiled.as_text()
    collectives = count_collectives(txt)
    seq = dict(zip(spec.axes, spec.mesh_shape)).get("seq", 1)
    pipe = dict(zip(spec.axes, spec.mesh_shape)).get("pipe", 1)
    # Collective-matmul arms permute legitimately too: the ppermute ring
    # IS the fusion's comms (the exact pin still catches drift — a real
    # reshard fallback grows the frozen permute count by name).
    cmm = bool(dict(spec.config_overrides).get("tp_collective_matmul"))
    permutes_legit = seq > 1 or pipe > 1 or cmm
    return ArmReport(
        arm=spec.name,
        collectives=collectives,
        replication_reshard_suspects=(
            0 if permutes_legit else collectives["collective-permute"]
        ),
        donated_inputs=txt.count("may-alias") + txt.count("must-alias"),
        donatable_inputs=_donatable_leaves(spec),
        bf16_to_f32_converts=count_bf16_to_f32_converts(txt),
    )


# ---------------------------------------------------------------------------
# Pipeline schedule auditor: closed-form send/recv + bubble laws
# ---------------------------------------------------------------------------

#: Pipeline arms in the audited roster — the suite's pp compositions
#: (scripts/run_all_benchmarks.sh pp2-{gpipe,1f1b,interleaved}) at the
#: interleaved-CLI mesh shape (dp=2 x pipe=2, 4 of the 8 virtual
#: devices), plus a llama-family composition so the GQA blocks audit
#: under pipeline layer sharding too. Unlike the CPU arm roster these
#: lower WITH live dropout keys (``dropout`` pinned to the family
#: default instead of the roster's dropout-free choice): the typed-key
#: shard_map boundary was the seed-old interleaved compile failure, and
#: an audit that DCEs the keys away could never catch its return.
#: Dropout adds RNG ops but no collectives, so the pinned schedule stays
#: deterministic. The interleaved arm runs V=2 real virtual chunks
#: (n_layer=4) so the audit covers actual interleaving, not the V=1
#: degenerate shape.
PIPELINE_ROSTER: Dict[str, ArmSpec] = {
    spec.name: spec
    for spec in (
        ArmSpec(
            "pp2-gpipe", "ddp", (2, 1, 1, 2),
            ("data", "seq", "model", "pipe"),
            global_batch=4, grad_accum=4, pipeline_schedule="gpipe",
            config_overrides=(("dropout", 0.1),),
        ),
        ArmSpec(
            "pp2-1f1b", "ddp", (2, 1, 1, 2),
            ("data", "seq", "model", "pipe"),
            global_batch=4, grad_accum=4, pipeline_schedule="1f1b",
            config_overrides=(("dropout", 0.1),),
        ),
        ArmSpec(
            "pp2-interleaved-v2", "ddp", (2, 1, 1, 2),
            ("data", "seq", "model", "pipe"),
            global_batch=4, grad_accum=4, pipeline_schedule="interleaved",
            virtual_stages=2,
            config_overrides=(("dropout", 0.1), ("n_layer", 4)),
        ),
        ArmSpec(
            "llama-pp2-1f1b", "ddp", (2, 1, 1, 2),
            ("data", "seq", "model", "pipe"),
            global_batch=4, grad_accum=4, model_family="llama",
            pipeline_schedule="1f1b",
            config_overrides=(("dropout", 0.1),),
        ),
    )
}

#: Second microbatch count each pipeline arm is audited at: the growth
#: law needs two M points to verdict the affine-in-M shape.
PIPELINE_GROWTH_M_FACTOR = 2


def expected_pipeline_permutes(
    schedule: str, stages: int, microbatches: int, virtual: int = 1
) -> int:
    """Closed-form collective-permute count of the compiled step.

    Counts are HLO *instructions* in the lowered module, which is what
    :func:`count_collectives` measures — each instruction moves every
    stage's current payload one ring hop, so the per-direction data
    movement (e.g. GPipe forward: M*(S-1) stage-to-stage sends) rides
    fewer instructions than sends:

    - **gpipe**: the Python tick loop unrolls — forward emits ticks-1 =
      M+S-2 ppermutes and ``jax.value_and_grad`` transposes each for the
      backward: 2*(M+S-2). Affine in M, slope 2.
    - **1f1b**: hand-scheduled — M+S-2 forward-ring + M+S-2
      backward-ring instructions: 2*(M+S-2). Affine in M, slope 2.
    - **interleaved**: the executor replays the schedule tables with ONE
      ``lax.scan`` tick body holding exactly one fwd-ring and one
      bwd-ring ppermute — 2 instructions regardless of M (the tick count
      lives in the scan trip count, not the instruction count). Slope 0.
    """
    S, M = stages, microbatches
    if schedule == "gpipe":
        return 2 * (M + S - 2)
    if schedule == "1f1b":
        return 2 * (M + S - 2)
    if schedule == "interleaved":
        return 2
    raise ValueError(f"unknown pipeline schedule {schedule!r}")


def pipeline_permute_slope(schedule: str) -> int:
    """d(collective-permute instructions)/dM for the affine growth law."""
    return 0 if schedule == "interleaved" else 2


def pipeline_bubble_bound(
    schedule: str, stages: int, microbatches: int, virtual: int = 1
) -> float:
    """Structural bubble-fraction upper bound for one schedule.

    The fraction of schedule capacity the fill/drain ramps waste —
    trace-measured ``bubble_frac`` (step-anatomy device idle) must not
    exceed this plus measurement slack; exceeding it means the executed
    overlap does NOT match the schedule's structure (an
    anatomy/structure mismatch, not noise):

    - **gpipe**: (S-1)/(M+S-1) for each of the forward and transposed
      backward phases — the classic fill/drain ratio.
    - **1f1b (lockstep)**: fill+drain are 2(S-1) of the M+2(S-1) ticks,
      each tick holding up to one fwd and one bwd unit:
      2(S-1)/(M+2(S-1)).
    - **interleaved**: the exact idle fraction of the (ticks x P) unit
      grid from the real scheduler tables
      (``parallel.interleaved.build_schedule().bubble_fraction``) — the
      v*S-aware variant, tighter than any closed form because the greedy
      scheduler's concrete tick count is known.
    """
    S, M = stages, microbatches
    if schedule == "gpipe":
        return (S - 1) / (M + S - 1)
    if schedule == "1f1b":
        return 2 * (S - 1) / (M + 2 * (S - 1))
    if schedule == "interleaved":
        from ...parallel.interleaved import build_schedule

        return float(build_schedule(S, virtual, M).bubble_fraction)
    raise ValueError(f"unknown pipeline schedule {schedule!r}")


@dataclasses.dataclass(frozen=True)
class PipelineAuditResult:
    """One pipeline arm's audit: counts at two M values + the law inputs.

    ``compile_error`` set (and both reports None) when the arm failed to
    lower — for pipeline arms that is a FINDING (the schedule-compiles
    law), not an operational error: these arms have a known compile-
    failure history (the seed-old interleaved bug) and the injection
    proof reverts exactly that fix.
    """

    arm: str
    schedule: str
    stages: int
    microbatches: int
    virtual: int
    grown_microbatches: int
    base: Optional[ArmReport] = None
    grown: Optional[ArmReport] = None
    compile_error: Optional[str] = None

    def to_budget_entry(self) -> Dict[str, Any]:
        assert self.base is not None and self.grown is not None
        return {
            "schedule": {
                "schedule": self.schedule,
                "stages": self.stages,
                "microbatches": self.microbatches,
                "virtual": self.virtual,
                "grown_microbatches": self.grown_microbatches,
                "expected_collective_permutes": expected_pipeline_permutes(
                    self.schedule, self.stages, self.microbatches,
                    self.virtual,
                ),
                "bubble_frac_bound": round(pipeline_bubble_bound(
                    self.schedule, self.stages, self.microbatches,
                    self.virtual,
                ), 6),
            },
            "base": self.base.to_budget_entry(),
            "grown": self.grown.to_budget_entry(),
        }


def audit_pipeline_arm(
    spec: ArmSpec, devices=None
) -> PipelineAuditResult:
    """Audit one pipeline arm at its roster M and at M*growth-factor.

    The (S, M, V) law inputs mirror ``train.step.pipeline_schedule_meta``
    (M == grad_accum — the step feeds its whole accumulation axis to the
    schedule); a test pins the two against each other so the laws cannot
    drift from what the step compiles.
    """
    pipe = dict(zip(spec.axes, spec.mesh_shape)).get("pipe", 1)
    if pipe <= 1:
        raise ValueError(
            f"arm {spec.name!r} has no >1 'pipe' axis — not a pipeline arm"
        )
    m2 = spec.grad_accum * PIPELINE_GROWTH_M_FACTOR
    meta = {
        "schedule": spec.pipeline_schedule,
        "stages": pipe,
        "microbatches": spec.grad_accum,
        "virtual": (
            spec.virtual_stages
            if spec.pipeline_schedule == "interleaved" else 1
        ),
    }
    try:
        base = audit_arm(spec, devices=devices)
        grown = audit_arm(
            dataclasses.replace(spec, grad_accum=m2), devices=devices
        )
    except Exception as e:
        msg = f"{type(e).__name__}: {e}"
        return PipelineAuditResult(
            arm=spec.name, grown_microbatches=m2,
            compile_error=msg[:500], **meta,
        )
    return PipelineAuditResult(
        arm=spec.name, grown_microbatches=m2, base=base, grown=grown,
        **meta,
    )


def pipeline_law_findings(result: PipelineAuditResult) -> List[str]:
    """The schedule laws, each named per arm + law when broken.

    - **schedule-compiles**: the arm must lower at all (the seed-old
      interleaved bug class).
    - **permute-law**: collective-permute instructions must equal the
      closed form at BOTH audited M values — the excess is the pipeline
      analogue of a replication-reshard suspect (GSPMD resharding the
      manual region's operands lowers as extra permute chains).
    - **affine-growth**: the count must grow affinely in M with the
      schedule's slope (2 for the unrolled tick loops, 0 for the
      scanned interleaved executor) — a superlinear term means
      per-microbatch resharding.
    """
    arm, sched = result.arm, result.schedule
    if result.compile_error is not None:
        return [
            f"schedule-law: {arm} VIOLATES schedule-compiles "
            f"[{sched} S={result.stages} M={result.microbatches} "
            f"V={result.virtual}]: {result.compile_error}"
        ]
    findings: List[str] = []
    for label, rep, m in (
        ("base", result.base, result.microbatches),
        ("grown", result.grown, result.grown_microbatches),
    ):
        want = expected_pipeline_permutes(
            sched, result.stages, m, result.virtual
        )
        got = rep.collectives.get("collective-permute", 0)
        if got != want:
            findings.append(
                f"schedule-law: {arm} VIOLATES permute-law at {label} "
                f"M={m}: {got} collective-permutes != closed-form {want} "
                f"for {sched}(S={result.stages}, V={result.virtual}) — "
                f"{max(got - want, 0)} excess permute(s) are pipeline "
                "reshard suspects"
            )
    d_got = (
        result.grown.collectives.get("collective-permute", 0)
        - result.base.collectives.get("collective-permute", 0)
    )
    d_m = result.grown_microbatches - result.microbatches
    slope = pipeline_permute_slope(sched)
    if d_got != slope * d_m:
        findings.append(
            f"schedule-law: {arm} VIOLATES affine-growth: permutes grew "
            f"{d_got:+d} over {d_m:+d} microbatches (expected slope "
            f"{slope}/microbatch for {sched})"
        )
    return findings


def write_pipeline_budgets(
    results: List[PipelineAuditResult],
    path: str = DEFAULT_BUDGETS_PATH,
    existing: Optional[Dict[str, Any]] = None,
) -> Dict[str, Any]:
    """Freeze pipeline-arm budgets into the ``pipeline_schedules`` section.

    Merges over the existing document — the CPU arm roster and the
    topology tiers pass through byte-unchanged, mirroring
    :func:`write_budgets` / :func:`write_topology_budgets`.
    """
    import jax

    failed = [r.arm for r in results if r.compile_error is not None]
    if failed:
        raise ValueError(
            "refusing to freeze pipeline budgets with arms that failed "
            f"to compile: {failed}"
        )
    doc = (
        dict(existing) if existing is not None
        else (load_budgets(path) if os.path.exists(path) else {"arms": {}})
    )
    section = dict(doc.get("pipeline_schedules", {}))
    arms = dict(section.get("arms", {}))
    frozen = section.get("jax_version")
    if frozen is not None and frozen != jax.__version__:
        # Same refusal as write_budgets: merging fresh counts over arms
        # frozen on a different jax and restamping the section's version
        # would claim incomparable counts are commensurable.
        regenerated = {r.arm for r in results}
        stale = set(arms) - regenerated
        if stale:
            raise ValueError(
                f"pipeline_schedules budgets were frozen on jax {frozen} "
                f"but this is jax {jax.__version__}: a partial --arms "
                "regeneration would mix incomparable counts — regenerate "
                f"the full pipeline roster (missing: {sorted(stale)})"
            )
        arms = {}
    for r in results:
        arms[r.arm] = r.to_budget_entry()
    doc["pipeline_schedules"] = {
        "jax_version": jax.__version__,
        "arms": arms,
    }
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(doc, f, indent=2, sort_keys=True)
        f.write("\n")
    return doc


def diff_pipeline_against_budget(
    result: PipelineAuditResult, budgets: Dict[str, Any]
) -> List[str]:
    """Law findings + exact-pin diffs for one pipeline arm.

    The laws run unconditionally (they need no frozen state); the pins
    then hold the full collective/donation/convert profile at both M
    values against the frozen ``pipeline_schedules`` budgets, so even a
    law-respecting drift (e.g. +2 all-reduces) fails loudly.
    """
    findings = pipeline_law_findings(result)
    if result.compile_error is not None:
        return findings
    section = budgets.get("pipeline_schedules", {})
    arm_budget = section.get("arms", {}).get(result.arm)
    if arm_budget is None:
        return findings + [
            f"{result.arm}: no frozen pipeline_schedules budget for this "
            "arm (run --update-budgets to freeze one)"
        ]
    frozen_meta = dict(arm_budget.get("schedule", {}))
    live_meta = result.to_budget_entry()["schedule"]
    if frozen_meta != live_meta:
        findings.append(
            f"{result.arm}: schedule metadata drifted from the frozen "
            f"budget ({frozen_meta} != {live_meta}) — regenerate with "
            "--update-budgets and review"
        )
    for label, rep in (("base", result.base), ("grown", result.grown)):
        scoped = {"arms": {result.arm: arm_budget.get(label, {})}}
        findings.extend(
            f"{label}: {d}" for d in diff_against_budget(rep, scoped)
        )
    return findings


# ---------------------------------------------------------------------------
# GC110: the memory-budget audit (compile-time memory anatomy, frozen)
# ---------------------------------------------------------------------------

#: Slack the per-chip XLA temp bytes may grow along the data axis before
#: the GC110 temp-flat growth law fires. Weak scaling keeps per-chip work
#: constant, so temps should be flat; a few percent covers partitioner
#: padding differences between tier shapes.
MEMORY_TEMP_FLAT_TOL = 0.10


@dataclasses.dataclass(frozen=True)
class MemoryReport:
    """One arm's compile-time memory accounting — what GC110 pins.

    Bytes come from the compiled step's ``memory_analysis()`` via
    ``analysis.memory_anatomy.compile_memory_fields`` (ONE extractor for
    the static audit and the runtime reconciliation, so the two layers
    cannot disagree about what "temp bytes" means). Per-device under
    GSPMD — the module is the per-chip program.
    """

    arm: str
    argument_bytes: int
    output_bytes: int
    temp_bytes: int
    alias_bytes: int
    peak_bytes: int

    def to_budget_entry(self) -> Dict[str, Any]:
        return {
            "argument_bytes": self.argument_bytes,
            "output_bytes": self.output_bytes,
            "temp_bytes": self.temp_bytes,
            "alias_bytes": self.alias_bytes,
            "peak_bytes": self.peak_bytes,
        }


def arm_shards_state_over_data(arm_name: str) -> bool:
    """True when the arm's strategy shards params or optimizer state over
    the 'data' axis (fsdp/zero) — the class whose per-chip argument bytes
    must SHRINK as the data axis grows (a flat curve there means the
    state is silently replicating, the exact regression GC110 exists to
    catch AOT)."""
    from ...parallel import get_strategy

    spec = ROSTER.get(arm_name) or PIPELINE_ROSTER.get(arm_name)
    if spec is None:
        raise KeyError(f"unknown arm {arm_name!r}")
    strategy = get_strategy(spec.strategy)
    return bool(
        getattr(strategy, "shard_params", False)
        or getattr(strategy, "shard_opt_state", False)
    )


def audit_arm_memory(spec: ArmSpec, devices=None) -> MemoryReport:
    """Lower one arm and extract its compile-time memory accounting."""
    from ...analysis.memory_anatomy import compile_memory_fields

    compiled = lower_arm(spec, devices=devices)
    fields = compile_memory_fields(compiled)
    if fields is None:
        raise RuntimeError(
            f"arm {spec.name!r}: backend exposes no memory_analysis() — "
            "the memory audit needs a compiler that reports buffer sizes"
        )
    return MemoryReport(
        arm=spec.name,
        argument_bytes=fields["argument_bytes"],
        output_bytes=fields["output_bytes"],
        temp_bytes=fields["temp_bytes"],
        alias_bytes=fields["alias_bytes"],
        peak_bytes=fields["peak_bytes"],
    )


def audit_topology_tier_memory(
    tier: "TopologyTier",
    arm_names: Optional[Tuple[str, ...]] = None,
    inject: Optional[str] = None,
) -> List[MemoryReport]:
    """Memory accounting of the scalable roster subset at one real tier."""
    devices = topology_devices(tier)
    reports: List[MemoryReport] = []
    for name in arm_names or TOPOLOGY_ARMS:
        spec = ROSTER.get(name) or PIPELINE_ROSTER[name]
        scaled = scale_spec_to_devices(spec, tier.device_count)
        if inject:
            scaled = dataclasses.replace(scaled, inject=inject)
        reports.append(audit_arm_memory(scaled, devices=devices))
    return reports


def write_memory_budgets(
    reports: List[MemoryReport],
    path: str = DEFAULT_BUDGETS_PATH,
    tier_reports: Optional[Dict[str, List[MemoryReport]]] = None,
) -> Dict[str, Any]:
    """Freeze GC110 budgets into the ``memory_budgets`` section.

    Merges over the existing document (the collective/pipeline/topology
    sections pass through byte-unchanged); a partial regeneration across
    jax versions refuses like :func:`write_budgets` — byte counts from
    two compilers are not commensurable.
    """
    import jax

    doc = load_budgets(path) if os.path.exists(path) else {"arms": {}}
    section = dict(doc.get("memory_budgets", {}))
    arms = dict(section.get("arms", {}))
    frozen = section.get("jax_version")
    if frozen is not None and frozen != jax.__version__ and reports:
        regenerated = {r.arm for r in reports}
        stale = set(arms) - regenerated
        if stale:
            raise ValueError(
                f"memory_budgets were frozen on jax {frozen} but this is "
                f"jax {jax.__version__}: a partial regeneration would mix "
                "incomparable byte counts — regenerate the full roster "
                f"(missing: {sorted(stale)})"
            )
        arms = {}
    for r in reports:
        arms[r.arm] = r.to_budget_entry()
    tiers = dict(section.get("topology_tiers", {}))
    for tier_name, reps in (tier_reports or {}).items():
        tier = TOPOLOGY_TIERS[tier_name]
        tiers[tier_name] = {
            "device_count": tier.device_count,
            "topology_name": tier.topology_name,
            "jax_version": jax.__version__,
            "arms": {r.arm: r.to_budget_entry() for r in reps},
        }
    doc["memory_budgets"] = {
        "jax_version": jax.__version__ if reports else section.get(
            "jax_version", jax.__version__
        ),
        "arms": arms,
        "topology_tiers": tiers,
    }
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(doc, f, indent=2, sort_keys=True)
        f.write("\n")
    return doc


def diff_memory_against_budget(
    report: MemoryReport, budgets: Dict[str, Any],
    arms_override: Optional[Dict[str, Any]] = None,
) -> List[str]:
    """GC110 exact-pin deltas for one arm vs the frozen memory budgets.

    Same posture as the collective pins: growth of argument/output/temp/
    peak bytes REGRESSES (an accidental replication of optimizer state
    shows up as argument growth; a remat regression as temp growth),
    shrinkage is an improvement to bank; LOST donation aliasing (alias
    bytes shrinking) regresses in the other direction.
    """
    arms = (
        arms_override if arms_override is not None
        else budgets.get("memory_budgets", {}).get("arms", {})
    )
    entry = arms.get(report.arm)
    if entry is None:
        return [
            f"GC110: {report.arm}: no frozen memory budget for this arm "
            "(run --memory --update-budgets to freeze one)"
        ]
    deltas: List[str] = []

    def check(label: str, got: int, want: int, more_is_worse: bool = True):
        if got == want:
            return
        delta = got - want
        pct = 100.0 * delta / want if want else float("inf")
        if (delta > 0) == more_is_worse:
            deltas.append(
                f"GC110: {report.arm}: {label} REGRESSED {want} -> {got} "
                f"({delta:+d} bytes, {pct:+.1f}%)"
            )
        else:
            deltas.append(
                f"GC110: {report.arm}: {label} improved {want} -> {got} "
                f"({delta:+d} bytes) — bank it with --memory "
                "--update-budgets"
            )

    check("argument bytes", report.argument_bytes, entry["argument_bytes"])
    check("output bytes", report.output_bytes, entry["output_bytes"])
    check("temp bytes", report.temp_bytes, entry["temp_bytes"])
    check("donation-alias bytes", report.alias_bytes, entry["alias_bytes"],
          more_is_worse=False)
    check("buffer-assignment peak bytes", report.peak_bytes,
          entry["peak_bytes"])
    return deltas


def memory_growth_law_findings(
    per_tier: Dict[str, Dict[str, Dict[str, Any]]],
) -> List[str]:
    """GC110 cross-tier memory laws over the topology tiers.

    ``per_tier`` maps tier name -> arm -> memory budget entry (frozen
    and/or fresh — the caller overlays). Two laws, one per sharded axis
    class, each named per arm + tier pair when broken:

    - **temp-flat (dp law)**: per-chip XLA temp bytes must stay flat
      (within :data:`MEMORY_TEMP_FLAT_TOL`) as the data axis grows —
      weak scaling keeps per-chip batch constant, so growing temps mean
      per-chip activation/staging state is scaling with the MESH (a
      remat or collective-staging regression that only hurts at pod
      scale).
    - **sharded-state-shrinks (fsdp/zero law)**: arms whose strategy
      shards params/optimizer state over 'data'
      (:func:`arm_shards_state_over_data`) must show per-chip argument
      bytes strictly DECREASING as the data axis grows — a flat curve
      means the sharded state silently replicated (the exact failure
      class the ZeRO papers' memory math exists to prevent).
    """
    findings: List[str] = []
    tiers = sorted(
        (t for t in per_tier if t in TOPOLOGY_TIERS),
        key=lambda t: TOPOLOGY_TIERS[t].device_count,
    )
    arms = sorted({a for t in tiers for a in per_tier[t]})
    for arm in arms:
        present = [t for t in tiers if arm in per_tier[t]]
        try:
            shrinks = arm_shards_state_over_data(arm)
        except KeyError:
            shrinks = False
        for lo, hi in zip(present, present[1:]):
            e_lo, e_hi = per_tier[lo][arm], per_tier[hi][arm]
            t_lo = int(e_lo.get("temp_bytes", 0))
            t_hi = int(e_hi.get("temp_bytes", 0))
            if t_lo > 0 and t_hi > t_lo * (1.0 + MEMORY_TEMP_FLAT_TOL):
                findings.append(
                    f"GC110 growth-law: {arm} per-chip temp bytes grew "
                    f"{100.0 * (t_hi - t_lo) / t_lo:+.1f}% along the data "
                    f"axis ({lo}: {t_lo} -> {hi}: {t_hi}; weak scaling "
                    "must keep per-chip temps flat within "
                    f"{100 * MEMORY_TEMP_FLAT_TOL:.0f}%)"
                )
            if shrinks:
                a_lo = int(e_lo.get("argument_bytes", 0))
                a_hi = int(e_hi.get("argument_bytes", 0))
                if a_lo > 0 and a_hi >= a_lo:
                    findings.append(
                        f"GC110 growth-law: {arm} per-chip argument bytes "
                        f"did not shrink along the fsdp/zero shard axis "
                        f"({lo}: {a_lo} -> {hi}: {a_hi}) — sharded "
                        "param/optimizer state is replicating instead of "
                        "sharding"
                    )
    return findings


def commensurable_memory_tiers(
    budgets: Dict[str, Any],
    fresh_tiers: Tuple[str, ...] = (),
    jax_version: Optional[str] = None,
) -> Tuple[Dict[str, Dict[str, Dict[str, Any]]], List[str]]:
    """(per-tier memory entries with cross-version tiers dropped, dropped).

    The memory analogue of :func:`commensurable_topology_tiers`: byte
    counts from a different compiler must not enter the cross-tier laws.
    Returns the assembled ``{tier: {arm: entry}}`` view directly.
    """
    if jax_version is None:
        import jax

        jax_version = jax.__version__
    blocks = budgets.get("memory_budgets", {}).get("topology_tiers", {})
    stale = sorted(
        t for t, b in blocks.items()
        if t not in fresh_tiers
        and b.get("jax_version") not in (None, jax_version)
    )
    per_tier = {
        t: dict(b.get("arms", {}))
        for t, b in blocks.items() if t not in stale
    }
    return per_tier, stale


# ---------------------------------------------------------------------------
# Topology tiers: AOT audits of pod-scale meshes on the CPU host
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class TopologyTier:
    """One auditable TPU topology the host compiles AGAINST, not ON.

    ``jax.experimental.topologies.get_topology_desc`` builds a
    compile-only PJRT client from libtpu's topology tables — no chips,
    no runtime — so a 1-core CPU host can lower the REAL train step for
    a v5e-256 mesh and read its collective schedule off the compiled
    module. The wall clock of such a run is unknowable here; its
    *structure* (collective counts, reshard suspects, donation) is
    exact, and that is what the per-tier budgets and growth laws pin.
    """

    name: str
    topology_name: str  # libtpu topology string, e.g. "v5e:8x8"
    device_count: int
    accelerator_type: str  # silences libtpu's metadata-probe warnings


TOPOLOGY_TIERS: Dict[str, TopologyTier] = {
    t.name: t
    for t in (
        TopologyTier("v5e-16", "v5e:4x4", 16, "v5litepod-16"),
        TopologyTier("v5e-64", "v5e:8x8", 64, "v5litepod-64"),
        TopologyTier("v5e-256", "v5e:16x16", 256, "v5litepod-256"),
    )
}

#: Roster arms audited per tier — the scalable subset: each scales its
#: 'data' axis (and global batch with it) to fill the tier's device
#: count, so the growth laws below have one well-defined growing axis.
#: ``pp2-gpipe`` (from PIPELINE_ROSTER) brings a pipeline composition
#: under the per-tier budgets: its pipe degree is identity, the data
#: axis absorbs the tier, and its ring-permute count must stay CONSTANT
#: as data grows (the growth laws' at-most-linear bound covers it).
#: ``llama-tp2-gqa-cmm`` (round 15) rides the same contract for the
#: collective-matmul ring: the ppermute count is a function of the tp
#: degree alone (2 hops per projection class per layer at tp=2), so it
#: must stay FLAT along the data axis — each tier's exact pin freezes
#: it, and the at-most-linear law bounds any drift between tiers.
TOPOLOGY_ARMS = (
    "zero2-dp8", "fsdp-dp8", "llama-tp2-gqa", "pp2-gpipe",
    "llama-tp2-gqa-cmm",
)

#: Tiers ``graftcheck --all`` audits by default. v5e-256 compiles in
#: ~40s+ per arm on a small host — audit it explicitly with
#: ``--topology v5e-256`` (its budgets are frozen like the others).
TOPOLOGY_DEFAULT_TIERS = ("v5e-16", "v5e-64")


class TopologyUnavailable(RuntimeError):
    """libtpu topology tables are not loadable on this host."""


def _topology_env() -> None:
    """Compile-only client env, BEFORE libtpu first loads.

    Without ``TPU_SKIP_MDS_QUERY`` libtpu retries the GCE metadata
    server for minutes on any non-GCP host; the worker vars silence the
    single-host init warnings. All setdefault — a real TPU VM's env wins.
    """
    os.environ.setdefault("TPU_SKIP_MDS_QUERY", "true")
    os.environ.setdefault("TPU_WORKER_HOSTNAMES", "localhost")
    os.environ.setdefault("TPU_WORKER_ID", "0")
    # Compile-only clients hold no chips, but libtpu still takes the
    # host-wide lockfile on load; without this a test process auditing a
    # topology would block the CLI subprocess it spawns (and vice versa).
    os.environ.setdefault("ALLOW_MULTIPLE_LIBTPU_LOAD", "1")


#: Set once we claim TPU_ACCELERATOR_TYPE: a real TPU VM's own value is
#: never overwritten, but OUR per-tier value must not stick across tiers
#: (setdefault alone would pin the first tier's type on every later one).
_ACCEL_ENV_OWNED = "_GRAFTCHECK_OWNS_TPU_ACCELERATOR_TYPE"


def topology_devices(tier: TopologyTier):
    """The tier's compile-only device list (raises TopologyUnavailable)."""
    _topology_env()
    if (
        os.environ.get(_ACCEL_ENV_OWNED)
        or "TPU_ACCELERATOR_TYPE" not in os.environ
    ):
        os.environ["TPU_ACCELERATOR_TYPE"] = tier.accelerator_type
        os.environ[_ACCEL_ENV_OWNED] = "1"
    try:
        from jax.experimental import topologies

        topo = topologies.get_topology_desc(
            platform="tpu", topology_name=tier.topology_name
        )
        devices = list(topo.devices)
    except Exception as e:
        raise TopologyUnavailable(
            f"cannot build a compile-only client for {tier.name} "
            f"({tier.topology_name}): {type(e).__name__}: {e} — topology "
            "AOT audits need a libtpu with topology tables (the benchmark "
            "image has one; plain CPU wheels may not)"
        )
    if len(devices) != tier.device_count:
        raise TopologyUnavailable(
            f"topology {tier.topology_name} yielded {len(devices)} devices, "
            f"expected {tier.device_count}"
        )
    return devices


def topology_available() -> bool:
    """Cheap availability probe (the description is table lookup only)."""
    try:
        topology_devices(TOPOLOGY_TIERS["v5e-16"])
        return True
    except TopologyUnavailable:
        return False


def scale_spec_to_devices(spec: ArmSpec, n_devices: int) -> ArmSpec:
    """The roster arm at a tier's device count: only 'data' grows.

    The non-data axes (tp/sp/pp/ep degree) are the arm's identity; the
    data axis absorbs the tier, and the global batch scales with it so
    per-replica work is constant (weak-scaling shape — the same shape
    the scaling suite sweeps). Refuses non-divisible tiers loudly.
    """
    if "data" not in spec.axes:
        raise ValueError(f"arm {spec.name!r} has no 'data' axis to scale")
    di = spec.axes.index("data")
    other = 1
    for i, d in enumerate(spec.mesh_shape):
        if i != di:
            other *= d
    if n_devices % other:
        raise ValueError(
            f"arm {spec.name!r}: non-data axes fill {other} devices, which "
            f"does not divide the tier's {n_devices}"
        )
    new_data = n_devices // other
    old_data = spec.mesh_shape[di]
    if new_data % old_data and old_data % new_data:
        raise ValueError(
            f"arm {spec.name!r}: data axis {old_data} does not scale "
            f"evenly to {new_data}"
        )
    shape = list(spec.mesh_shape)
    shape[di] = new_data
    return dataclasses.replace(
        spec,
        mesh_shape=tuple(shape),
        global_batch=max(spec.global_batch * new_data // old_data, 1),
    )


def audit_topology_tier(
    tier: TopologyTier,
    arm_names: Optional[Tuple[str, ...]] = None,
    inject: Optional[str] = None,
) -> List[ArmReport]:
    """Audit the scalable roster subset against one tier's real topology."""
    devices = topology_devices(tier)
    reports: List[ArmReport] = []
    for name in arm_names or TOPOLOGY_ARMS:
        # Pipeline compositions live in their own roster; per-tier they
        # audit as plain count pins (the dual-M schedule laws run on the
        # CPU roster — the tier audit pins the at-scale lowering).
        spec = ROSTER.get(name) or PIPELINE_ROSTER[name]
        scaled = scale_spec_to_devices(spec, tier.device_count)
        if inject:
            scaled = dataclasses.replace(scaled, inject=inject)
        reports.append(audit_arm(scaled, devices=devices))
    return reports


def write_topology_budgets(
    tier_reports: Dict[str, List[ArmReport]],
    path: str = DEFAULT_BUDGETS_PATH,
) -> Dict[str, Any]:
    """Freeze per-tier budgets into the ``topology_tiers`` section.

    Merges over the existing file: regenerating one tier never drops
    another tier's (or the CPU roster's) budgets, and the serialization
    stays deterministic so diffs always mean a schedule change.
    """
    import jax

    doc = load_budgets(path) if os.path.exists(path) else {"arms": {}}
    topo = dict(doc.get("topology_tiers", {}))
    for tier_name, reports in tier_reports.items():
        tier = TOPOLOGY_TIERS[tier_name]
        topo[tier_name] = {
            "device_count": tier.device_count,
            "topology_name": tier.topology_name,
            "jax_version": jax.__version__,
            "arms": {rep.arm: rep.to_budget_entry() for rep in reports},
        }
    doc["topology_tiers"] = topo
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(doc, f, indent=2, sort_keys=True)
        f.write("\n")
    return doc


def diff_topology_against_budget(
    tier_name: str, reports: List[ArmReport], budgets: Dict[str, Any],
) -> List[str]:
    """Per-tier exact-pin diffs, mirroring :func:`diff_against_budget`."""
    tier_budget = budgets.get("topology_tiers", {}).get(tier_name)
    if tier_budget is None:
        return [
            f"{tier_name}: no frozen topology budgets for this tier "
            "(run --topology " + tier_name + " --update-budgets)"
        ]
    scoped = {"arms": tier_budget.get("arms", {})}
    out: List[str] = []
    for rep in reports:
        out.extend(
            f"{tier_name}/{d}" for d in diff_against_budget(rep, scoped)
        )
    return out


def growth_law_findings(
    per_tier: Dict[str, Dict[str, Dict[str, Any]]],
) -> List[str]:
    """Cross-tier structural laws a scalable program must obey.

    ``per_tier`` maps tier name -> arm -> budget entry (fresh reports
    and/or frozen budgets — the caller overlays). Two laws, both named
    per arm + tier + collective when broken:

    - **Reshard suspects stay zero.** A full-replication reshard
      fallback that appears at ANY tier is a scaling bug by definition —
      its cost grows with the mesh (the PR 1 GQA fallback and the PR 8
      composed-mesh fallback were exactly this class).
    - **Per-collective counts grow at most linearly in the data axis.**
      SPMD per-step collective COUNTS should be near-constant as the
      data axis grows (each instruction just spans more devices); a
      count that grows faster than the device ratio between two tiers —
      or appears from zero — means the partitioner is emitting
      per-shard chains, the structure that killed the pod-scale curves
      in the MLPerf TPU papers. Counts may always drop.
    """
    findings: List[str] = []
    tiers = sorted(
        (t for t in per_tier if t in TOPOLOGY_TIERS),
        key=lambda t: TOPOLOGY_TIERS[t].device_count,
    )
    arms = sorted({a for t in tiers for a in per_tier[t]})
    for arm in arms:
        present = [t for t in tiers if arm in per_tier[t]]
        for t in present:
            entry = per_tier[t][arm]
            suspects = int(entry.get("replication_reshard_suspects", 0))
            if suspects > 0:
                findings.append(
                    f"growth-law: {arm}@{t} has {suspects} full-replication "
                    "reshard suspect(s) — reshard suspects must stay 0 "
                    "across topology tiers (a reshard's cost grows with "
                    "the mesh)"
                )
        for lo, hi in zip(present, present[1:]):
            ratio = (
                TOPOLOGY_TIERS[hi].device_count
                / TOPOLOGY_TIERS[lo].device_count
            )
            lo_c = per_tier[lo][arm].get("collectives", {})
            hi_c = per_tier[hi][arm].get("collectives", {})
            for op in COLLECTIVE_OPS:
                n_lo, n_hi = int(lo_c.get(op, 0)), int(hi_c.get(op, 0))
                if n_lo == 0 and n_hi > 0:
                    findings.append(
                        f"growth-law: {arm} {op} appears from zero "
                        f"({lo}: 0 -> {hi}: {n_hi}) — a collective the "
                        "small mesh never needed is growing with the mesh"
                    )
                elif n_lo > 0 and n_hi > n_lo * ratio:
                    findings.append(
                        f"growth-law: {arm} {op} grows superlinearly in "
                        f"the data axis ({lo}: {n_lo} -> {hi}: {n_hi}; "
                        f"linear ceiling {int(n_lo * ratio)} at "
                        f"{ratio:g}x devices)"
                    )
    return findings


def commensurable_topology_tiers(
    budgets: Dict[str, Any],
    fresh_tiers: Tuple[str, ...] = (),
    jax_version: Optional[str] = None,
) -> Tuple[Dict[str, Any], List[str]]:
    """(budgets view with cross-version tiers dropped, dropped tier names).

    The growth laws compare counts ACROSS tiers, so overlaying a fresh
    audit on a tier frozen under a different jax would mix incomparable
    compiler outputs — minting spurious appears-from-zero/superlinear
    findings (or masking real ones), the exact cross-version mixing
    write_budgets refuses for the CPU roster. Frozen tiers whose
    ``jax_version`` differs from the running one are excluded from the
    overlay (fresh-audited tiers always stay: their counts ARE current).
    """
    if jax_version is None:
        import jax

        jax_version = jax.__version__
    blocks = budgets.get("topology_tiers", {})
    stale = sorted(
        t for t, b in blocks.items()
        if t not in fresh_tiers
        and b.get("jax_version") not in (None, jax_version)
    )
    if not stale:
        return budgets, []
    kept = {t: b for t, b in blocks.items() if t not in stale}
    return dict(budgets, topology_tiers=kept), stale


def assemble_per_tier(
    budgets: Dict[str, Any],
    fresh: Optional[Dict[str, List[ArmReport]]] = None,
) -> Dict[str, Dict[str, Dict[str, Any]]]:
    """Frozen topology budgets overlaid with fresh reports, for the
    growth laws: an audit of ONE tier still judges growth against the
    other tiers' frozen structure."""
    per_tier: Dict[str, Dict[str, Dict[str, Any]]] = {
        t: dict(block.get("arms", {}))
        for t, block in budgets.get("topology_tiers", {}).items()
    }
    for tier_name, reports in (fresh or {}).items():
        per_tier.setdefault(tier_name, {})
        per_tier[tier_name].update(
            {rep.arm: rep.to_budget_entry() for rep in reports}
        )
    return per_tier


# ---------------------------------------------------------------------------
# Budget file I/O + diffing
# ---------------------------------------------------------------------------


def load_budgets(path: str = DEFAULT_BUDGETS_PATH) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def write_budgets(
    reports: List[ArmReport], path: str = DEFAULT_BUDGETS_PATH,
    existing: Optional[Dict[str, Any]] = None,
) -> Dict[str, Any]:
    """Freeze ``reports`` as the budget file (merging over ``existing`` so a
    partial ``--arms`` regeneration never drops the other arms' budgets).
    Deterministic serialization (sorted keys, fixed indent) — regenerating
    without a real change is a byte-level no-op, so budget diffs in review
    always mean something."""
    import jax

    doc: Dict[str, Any] = {
        "_comment": (
            "Frozen per-arm collective budgets — regenerate with "
            "`python -m distributed_llm_training_benchmark_framework_tpu"
            ".analysis.static --update-budgets` and review the diff. "
            "Counts are pinned on the CPU backend with 8 forced host "
            "devices; see docs/STATIC_ANALYSIS.md."
        ),
        "backend": "cpu",
        "device_count": 8,
        "jax_version": jax.__version__,
        "arms": dict((existing or {}).get("arms", {})),
    }
    if existing is not None and existing.get("topology_tiers"):
        # The topology-tier budgets are frozen by their own writer
        # (write_topology_budgets); an arm-roster regeneration must carry
        # them through untouched, not silently drop a whole section.
        doc["topology_tiers"] = existing["topology_tiers"]
    if existing is not None and existing.get("pipeline_schedules"):
        # Same carry-through contract for the pipeline-schedule budgets
        # (frozen by write_pipeline_budgets).
        doc["pipeline_schedules"] = existing["pipeline_schedules"]
    if existing is not None and existing.get("memory_budgets"):
        # ...and for the GC110 memory budgets (write_memory_budgets).
        doc["memory_budgets"] = existing["memory_budgets"]
    if existing is not None:
        # A partial regeneration on a different jax than the file was
        # frozen on would mix incomparable counts — and silently dropping
        # the stale arms would break the merge promise above, so a partial
        # regen across versions refuses with the remedy instead.
        frozen = existing.get("jax_version")
        if frozen is not None and frozen != jax.__version__:
            kept = set(existing.get("arms", {}))
            regenerated = {rep.arm for rep in reports}
            if kept - regenerated:
                raise ValueError(
                    f"budgets were frozen on jax {frozen} but this is jax "
                    f"{jax.__version__}: a partial --arms regeneration "
                    "would mix incomparable counts — regenerate the full "
                    f"roster (missing: {sorted(kept - regenerated)})"
                )
            doc["arms"] = {}
    for rep in reports:
        doc["arms"][rep.arm] = rep.to_budget_entry()
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(doc, f, indent=2, sort_keys=True)
        f.write("\n")
    return doc


def diff_against_budget(
    report: ArmReport, budgets: Dict[str, Any]
) -> List[str]:
    """Human-readable deltas between a fresh report and the frozen budget.

    Empty list = within budget. Budgets are EXACT pins, not ceilings:
    an improvement (fewer collectives) also fails, with wording telling
    you to bank it via --update-budgets — otherwise the next regression
    hides inside the slack the improvement left behind.
    """
    arm_budget = budgets.get("arms", {}).get(report.arm)
    if arm_budget is None:
        return [
            f"{report.arm}: no frozen budget for this arm "
            "(run --update-budgets to freeze one)"
        ]
    deltas: List[str] = []

    def check(label: str, got: int, want: int, more_is_worse: bool = True):
        if got == want:
            return
        delta = got - want
        if (delta > 0) == more_is_worse:
            deltas.append(
                f"{report.arm}: {label} REGRESSED {want} -> {got} "
                f"({delta:+d} per step)"
            )
        else:
            deltas.append(
                f"{report.arm}: {label} improved {want} -> {got} "
                f"({delta:+d}) — bank it with --update-budgets"
            )

    for op in COLLECTIVE_OPS:
        check(op, report.collectives.get(op, 0), arm_budget["collectives"].get(op, 0))
    check(
        "full-replication reshard suspects",
        report.replication_reshard_suspects,
        arm_budget["replication_reshard_suspects"],
    )
    check(
        "donated inputs", report.donated_inputs, arm_budget["donated_inputs"],
        more_is_worse=False,
    )
    check(
        "donatable inputs", report.donatable_inputs,
        arm_budget["donatable_inputs"], more_is_worse=False,
    )
    check(
        "bf16->f32 converts", report.bf16_to_f32_converts,
        arm_budget["bf16_to_f32_converts"],
    )
    return deltas
