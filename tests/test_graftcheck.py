"""graftcheck (analysis.static) tier-1 coverage: both engines on CPU.

Three layers, cheapest first:

- diff-logic unit tests against the hand-written frozen fixture budgets
  (``tests/fixtures/graftcheck_budgets_frozen.json``) — no compiles;
- lint-rule behavior against scratch repo roots (each rule must fire on a
  doctored tree, honor the ``# graftcheck: disable=`` pragma, and run
  clean on HEAD);
- the HLO auditor end-to-end on a roster subset against the LIVE budgets
  in ``configs/collective_budgets.json`` (HEAD must be within budget), the
  deliberate bad-PartitionSpec injection (the auditor must flag the GQA
  full-replicate fallback), and ``--update-budgets`` round-trip stability
  (regenerate -> diff clean -> regenerate again is byte-identical).
"""

import dataclasses
import os
import subprocess
import sys
import textwrap

import pytest

from distributed_llm_training_benchmark_framework_tpu.analysis.static import (
    hlo_audit,
    lint,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXTURE_BUDGETS = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "fixtures",
    "graftcheck_budgets_frozen.json",
)
PKG = "distributed_llm_training_benchmark_framework_tpu"


# ---------------------------------------------------------------------------
# Budget diff logic (frozen fixture, no compiles)
# ---------------------------------------------------------------------------


def _fixture_report(**overrides):
    base = dict(
        arm="fixture-arm",
        collectives={
            "all-gather": 4, "reduce-scatter": 2, "all-reduce": 7,
            "collective-permute": 0, "all-to-all": 0,
        },
        replication_reshard_suspects=0,
        donated_inputs=12,
        donatable_inputs=12,
        bf16_to_f32_converts=10,
    )
    base.update(overrides)
    return hlo_audit.ArmReport(**base)


@pytest.fixture(scope="module")
def fixture_budgets():
    return hlo_audit.load_budgets(FIXTURE_BUDGETS)


def test_within_budget_is_clean(fixture_budgets):
    assert hlo_audit.diff_against_budget(_fixture_report(), fixture_budgets) == []


def test_collective_regression_is_named_with_delta(fixture_budgets):
    rep = _fixture_report(collectives={
        "all-gather": 6, "reduce-scatter": 2, "all-reduce": 7,
        "collective-permute": 0, "all-to-all": 0,
    })
    deltas = hlo_audit.diff_against_budget(rep, fixture_budgets)
    assert len(deltas) == 1
    # The failure names the arm, the collective, and the budget delta.
    assert "fixture-arm" in deltas[0]
    assert "all-gather" in deltas[0]
    assert "REGRESSED 4 -> 6" in deltas[0] and "+2" in deltas[0]


def test_improvement_also_fails_but_says_bank_it(fixture_budgets):
    rep = _fixture_report(collectives={
        "all-gather": 3, "reduce-scatter": 2, "all-reduce": 7,
        "collective-permute": 0, "all-to-all": 0,
    })
    deltas = hlo_audit.diff_against_budget(rep, fixture_budgets)
    assert len(deltas) == 1
    assert "improved" in deltas[0] and "--update-budgets" in deltas[0]


def test_lost_donation_is_a_regression(fixture_budgets):
    deltas = hlo_audit.diff_against_budget(
        _fixture_report(donated_inputs=10), fixture_budgets
    )
    assert len(deltas) == 1
    assert "donated inputs REGRESSED" in deltas[0]


def test_unknown_arm_demands_a_budget(fixture_budgets):
    deltas = hlo_audit.diff_against_budget(
        _fixture_report(arm="never-frozen"), fixture_budgets
    )
    assert deltas and "no frozen budget" in deltas[0]


# ---------------------------------------------------------------------------
# Lint rules (scratch roots + HEAD)
# ---------------------------------------------------------------------------


def test_lint_is_clean_on_head():
    violations = lint.run_lint()
    assert violations == [], "\n".join(str(v) for v in violations)


def test_rule_catalog_is_complete():
    assert set(lint.RULES) == {
        "GC101", "GC102", "GC103", "GC104", "GC105", "GC106", "GC107",
        "GC108", "GC109", "GC111", "GC112", "GC201",
    }
    for rule in lint.RULES.values():
        assert rule.fix_hint and rule.description


def _scratch_root(tmp_path, rel, source):
    path = tmp_path / PKG / rel
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(textwrap.dedent(source))
    return str(tmp_path)


def test_gc101_fires_on_undonated_jit_and_honors_suppression(tmp_path):
    root = _scratch_root(tmp_path, "train/scratch.py", """\
        import jax

        def bad(f, x):
            return jax.jit(f)(x)

        def sanctioned(f, x):
            return jax.jit(f)(x)  # graftcheck: disable=GC101

        def fine(f, x, sh):
            return jax.jit(f, out_shardings=sh)(x)
    """)
    violations = lint.run_lint(root=root, rules=("GC101",))
    assert [v.line for v in violations] == [4]
    assert violations[0].rule_id == "GC101"
    assert "donate" in violations[0].fix_hint


def test_gc102_fires_on_host_sync_in_timed_loop(tmp_path):
    root = _scratch_root(tmp_path, "train/loop.py", """\
        def run(steps, step_fn, state):
            losses = []
            for step in range(steps):
                state, loss = step_fn(state, step)
                losses.append(float(loss))
            return losses
    """)
    violations = lint.run_lint(root=root, rules=("GC102",))
    assert len(violations) == 1 and violations[0].line == 5
    assert "host sync" in violations[0].message


def test_gc102_ignores_syncs_in_nested_window_helpers(tmp_path):
    root = _scratch_root(tmp_path, "train/loop.py", """\
        def run(steps, step_fn, state):
            pending = []

            def sync_window():
                return [float(l) for l in pending]

            for step in range(steps):
                state, loss = step_fn(state, step)
                pending.append(loss)
            return sync_window()
    """)
    assert lint.run_lint(root=root, rules=("GC102",)) == []


def test_gc103_fires_on_unknown_axis(tmp_path):
    _scratch_root(tmp_path, "parallel/mesh.py", """\
        import dataclasses

        @dataclasses.dataclass(frozen=True)
        class MeshAxes:
            data: str = "data"
            model: str = "model"
    """)
    root = _scratch_root(tmp_path, "train/scratch.py", """\
        from jax import lax
        from jax.sharding import PartitionSpec as P

        def constrain(x):
            x = lax.with_sharding_constraint(x, P("data", "modle"))
            return lax.with_sharding_constraint(x, P(None, "model"))
    """)
    violations = lint.run_lint(root=root, rules=("GC103",))
    assert len(violations) == 1
    assert "'modle'" in violations[0].message
    assert "data" in violations[0].message  # known axes listed in the finding


def test_gc105_fires_on_unfenced_io_in_timed_loop(tmp_path):
    """Telemetry/file-IO/print in the timed loop must sit AFTER a
    sync_window fence in its block; the sanctioned sync_window helper
    itself (a nested def) is exempt."""
    root = _scratch_root(tmp_path, "train/loop.py", """\
        def run(steps, step_fn, state, recorder, f):
            pending = []

            def sync_window():
                recorder.step_window(last_step=0, losses=[],
                                     window_mean_step_time_sec=0.1)

            for step in range(steps):
                state, loss = step_fn(state, step)
                print("unfenced progress")
                recorder.begin_phase("timed")
                f.write("unfenced io")
                with open("/tmp/marker", "w"):
                    pass
                if step % 10 == 0:
                    sync_window()
                    print("fenced: after the sync in this block")
                    recorder.step_window(last_step=step, losses=[],
                                         window_mean_step_time_sec=0.1)
            return state
    """)
    violations = lint.run_lint(root=root, rules=("GC105",))
    assert [v.line for v in violations] == [10, 11, 12, 13]
    assert {v.rule_id for v in violations} == {"GC105"}
    assert "sync_window" in violations[0].fix_hint
    messages = [v.message for v in violations]
    assert any("print()" in m for m in messages)
    assert any("recorder.begin_phase()" in m for m in messages)
    assert any(".write()" in m for m in messages)


def test_gc105_conditional_fence_and_suppression(tmp_path):
    """A sibling `if` containing sync_window fences the rest of the block
    (the loop's warmup-boundary idiom), and the pragma is honored."""
    root = _scratch_root(tmp_path, "train/loop.py", """\
        def run(steps, step_fn, state, recorder, sync_every):
            def sync_window():
                pass

            for step in range(steps):
                state, loss = step_fn(state, step)
                if sync_every > 1:
                    sync_window()
                recorder.begin_phase("timed")
                print("also fenced")
                open("/tmp/log")  # still fenced

            for step in range(steps):
                state, loss = step_fn(state, step)
                print("deliberate")  # graftcheck: disable=GC105
            return state
    """)
    assert lint.run_lint(root=root, rules=("GC105",)) == []


def test_gc105_clean_on_head():
    """train/loop.py's real recorder call sites all sit at sync
    boundaries — the discipline the rule exists to keep."""
    assert lint.run_lint(rules=("GC105",)) == []


def test_gc106_fires_on_signal_install_in_timed_loop(tmp_path):
    """A signal-handler swap inside the loop is flagged even when fenced —
    handlers install once, outside (faults/preemption.py)."""
    root = _scratch_root(tmp_path, "train/loop.py", """\
        import signal

        def run(steps, step_fn, state, handler):
            def sync_window():
                pass

            for step in range(steps):
                state, loss = step_fn(state, step)
                sync_window()
                signal.signal(signal.SIGTERM, handler)  # fenced, still wrong
            return state
    """)
    violations = lint.run_lint(root=root, rules=("GC106",))
    assert len(violations) == 1
    assert "signal.signal" in violations[0].message


def test_gc106_fires_on_unfenced_fsync_and_honors_fence(tmp_path):
    root = _scratch_root(tmp_path, "train/loop.py", """\
        import os

        def run(steps, step_fn, state, fd):
            def sync_window():
                pass

            for step in range(steps):
                state, loss = step_fn(state, step)
                os.fsync(fd)  # unfenced: blocks inside the timed window
                sync_window()
                os.fsync(fd)  # fenced: checkpoint-boundary durability
            return state
    """)
    violations = lint.run_lint(root=root, rules=("GC106",))
    assert len(violations) == 1
    assert "os.fsync" in violations[0].message
    assert violations[0].line == 9


def test_gc106_suppression_and_outside_loop_clean(tmp_path):
    root = _scratch_root(tmp_path, "train/loop.py", """\
        import os
        import signal

        def run(steps, step_fn, state, fd, handler):
            signal.signal(signal.SIGTERM, handler)  # outside: sanctioned

            def sync_window():
                pass

            for step in range(steps):
                state, loss = step_fn(state, step)
                os.fsync(fd)  # graftcheck: disable=GC106
            return state
    """)
    assert lint.run_lint(root=root, rules=("GC106",)) == []


def test_gc106_clean_on_head():
    """The real loop installs its SIGTERM guard in run_benchmark, before
    the first dispatch; durable writes live in runtime/checkpoint.py at
    checkpoint boundaries — the discipline this rule pins."""
    assert lint.run_lint(rules=("GC106",)) == []


def test_gc104_fires_on_time_time(tmp_path):
    root = _scratch_root(tmp_path, "ops/scratch.py", """\
        import time

        def kernel_host_wrap():
            t0 = time.time()
            return time.perf_counter() - t0
    """)
    violations = lint.run_lint(root=root, rules=("GC104",))
    assert [v.line for v in violations] == [4]


def test_gc107_fires_on_dtypeless_constructors(tmp_path):
    root = _scratch_root(tmp_path, "models/scratch.py", """\
        import jax.numpy as jnp

        def bad_asarray(x):
            return jnp.asarray(x) * x

        def bad_ones(s):
            return jnp.ones(s)

        def bad_full(s):
            return jnp.full(s, 0.5)

        def fine_kwarg(x):
            return jnp.asarray(x, dtype=jnp.bfloat16)

        def fine_positional(s, dt):
            return jnp.zeros(s, dt)

        def fine_full_positional(s, dt):
            return jnp.full(s, 0.5, dt)

        def sanctioned(x):
            return jnp.asarray(x)  # graftcheck: disable=GC107
    """)
    violations = lint.run_lint(root=root, rules=("GC107",))
    assert [v.line for v in violations] == [4, 7, 10]
    assert all(v.rule_id == "GC107" for v in violations)
    assert "dtype=" in violations[0].fix_hint


def test_gc107_scope_is_models_and_train_step(tmp_path):
    # The same dtype-less constructor outside jitted model code (analysis,
    # telemetry, train/loop.py host orchestration) is host-side
    # bookkeeping — out of scope; train/step.py (the jitted step) is in.
    src = """\
        import jax.numpy as jnp

        def host_side(x):
            return jnp.asarray(x)
    """
    out_root = _scratch_root(tmp_path / "out", "analysis/scratch.py", src)
    _scratch_root(tmp_path / "out", "train/loop.py", src)
    assert lint.run_lint(root=out_root, rules=("GC107",)) == []
    in_root = _scratch_root(tmp_path / "in", "train/step.py", src)
    violations = lint.run_lint(root=in_root, rules=("GC107",))
    assert [(v.path, v.line) for v in violations] == [
        (os.path.join(PKG, "train", "step.py"), 4)
    ]


def test_gc107_clean_on_head():
    assert lint.run_lint(rules=("GC107",)) == []


def test_suppression_accepts_lists_and_all(tmp_path):
    root = _scratch_root(tmp_path, "models/scratch.py", """\
        import jax

        def a(f, x):
            # graftcheck: disable=GC104, GC101
            return jax.jit(f)(x)

        def b(f, x):
            return jax.jit(f)(x)  # graftcheck: disable=all
    """)
    assert lint.run_lint(root=root, rules=("GC101",)) == []


# ---------------------------------------------------------------------------
# HLO auditor end-to-end (CPU compiles, roster subset)
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def gqa_report(eight_devices):
    return hlo_audit.audit_arm(hlo_audit.ROSTER["llama-tp2-gqa"])


def test_head_is_within_frozen_budget(gqa_report, eight_devices):
    budgets = hlo_audit.load_budgets()
    reports = [gqa_report, hlo_audit.audit_arm(hlo_audit.ROSTER["ddp-dp8"])]
    deltas = [
        d for rep in reports
        for d in hlo_audit.diff_against_budget(rep, budgets)
    ]
    assert deltas == [], "\n".join(deltas)


def test_roster_covers_strategy_family_and_geometry_axes():
    strategies = {s.strategy for s in hlo_audit.ROSTER.values()}
    families = {s.model_family for s in hlo_audit.ROSTER.values()}
    geometries = {s.mesh_shape for s in hlo_audit.ROSTER.values()}
    assert {"ddp", "fsdp", "zero2", "zero3"} <= strategies
    assert families == {"tinygpt", "llama"}
    assert len(geometries) >= 4  # dp, tp, sp, ep shapes at minimum
    budgets = hlo_audit.load_budgets()
    assert set(budgets["arms"]) == set(hlo_audit.ROSTER), (
        "configs/collective_budgets.json out of sync with the roster — "
        "run --update-budgets"
    )


def test_budget_pins_fsdp_dp4_tp2_fallback_dead():
    """The round-8 acceptance pin: the banked llama-fsdp-dp4-tp2 fallback
    is GONE from the frozen budgets — 13 replication-reshard suspects
    (collective-permutes in a pure dp x tp mesh) -> 0, permute/all-to-all
    counts 0. Round 15's scan-carry kill retired the scan sibling's
    banked residue too: its floor is now 0 (test_overlap.py pins it)."""
    budgets = hlo_audit.load_budgets()
    arm = budgets["arms"]["llama-fsdp-dp4-tp2"]
    assert arm["replication_reshard_suspects"] == 0
    assert arm["collectives"]["collective-permute"] == 0
    assert arm["collectives"]["all-to-all"] == 0
    scan = budgets["arms"]["llama-fsdp-dp4-tp2-scan"]
    assert scan["replication_reshard_suspects"] == 0  # round-15 floor


def test_injection_registry_covers_bad_fsdp_axis():
    assert set(hlo_audit._INJECTIONS) == set(_INJECTION_TOUCHES)


#: What each injection swaps while it runs: (module, attribute) pairs.
_INJECTION_TOUCHES = {
    "bad-kv-spec": (("parallel.strategies", "param_partition_specs"),),
    "bad-fsdp-axis": (("parallel.strategies", "_shard_largest_free_axis"),),
    "bad-cmm-ring": (
        ("ops.collective_matmul", "ag_proj_sharded"),
        ("ops.collective_matmul", "rs_proj_sharded"),
    ),
}

#: The module-level revert switches the injections used to flip, each name
#: in two pieces so that a search of the repository for one finds nothing.
_REVERT_SWITCHES = tuple(a + b for a, b in (
    ("_FORWARD_GATHER", "_OVERLAP"), ("_COMPOSED_FSDP", "_HYGIENE"),
    ("_CMM", "_RING"), ("_TYPED_KEY_BOUNDARY", "_FIX"),
))


@pytest.fixture(scope="module")
def package_sources():
    import distributed_llm_training_benchmark_framework_tpu as pkg

    return {
        os.path.join(dirpath, f): open(os.path.join(dirpath, f)).read()
        for dirpath, _, files in os.walk(os.path.dirname(pkg.__file__))
        for f in files if f.endswith(".py")
    }


@pytest.mark.parametrize("inject", sorted(_INJECTION_TOUCHES))
def test_injection_swaps_functions_and_puts_them_back(inject, package_sources):
    """An injection holds its own bad body and swaps one function of the
    module it breaks for the length of a call: inside, each attribute it
    touches is another object; afterwards it is the original again (``is``),
    also when the call raises. No production module carries a switch for it."""
    import importlib

    import distributed_llm_training_benchmark_framework_tpu as pkg

    with_bad = getattr(hlo_audit, "_with_" + inject.replace("-", "_"))
    touched = [
        (importlib.import_module(f"{pkg.__name__}.{mod}"), attr)
        for mod, attr in _INJECTION_TOUCHES[inject]
    ]
    before = [getattr(mod, attr) for mod, attr in touched]
    inside = with_bad(lambda: [getattr(mod, attr) for mod, attr in touched])
    assert all(a is not b for a, b in zip(inside, before))
    assert all(getattr(mod, attr) is b for (mod, attr), b in zip(touched, before))

    def boom():
        raise RuntimeError("compile failed")

    with pytest.raises(RuntimeError, match="compile failed"):
        with_bad(boom)
    assert all(getattr(mod, attr) is b for (mod, attr), b in zip(touched, before))

    for path, text in package_sources.items():
        for name in _REVERT_SWITCHES:
            assert name not in text, (name, path)


@pytest.mark.parametrize("section", [
    "roster", "v5e-16", "v5e-64", "pipeline", "memory",
])
def test_every_budget_section_is_stamped_with_the_installed_jax(section):
    """A count is a property of (jax, backend, devices, arm): a section
    frozen on another jax is not comparable, and the audit refuses it."""
    import jax

    budgets = hlo_audit.load_budgets()
    stamps = {
        "roster": [budgets],
        "v5e-16": [budgets["topology_tiers"]["v5e-16"],
                   budgets["memory_budgets"]["topology_tiers"]["v5e-16"]],
        "v5e-64": [budgets["topology_tiers"]["v5e-64"],
                   budgets["memory_budgets"]["topology_tiers"]["v5e-64"]],
        "pipeline": [budgets["pipeline_schedules"]],
        "memory": [budgets["memory_budgets"]],
    }[section]
    assert [b["jax_version"] for b in stamps] == [jax.__version__] * len(stamps)


def test_bf16_to_f32_converts_counted_under_either_printer():
    """jax 0.4 printed ``convert(bf16[4]{0} %a)``, jax 0.9 prints
    ``convert(%a)``: the operand's dtype comes from beside it or from its
    definition, and only an f32 result of a bf16 operand counts."""
    text = """
  %a = bf16[4]{0} parameter(0)
  %i = s32[4]{0} parameter(1)
  %old = f32[4]{0} convert(bf16[4]{0} %a)
  %new = f32[4]{0:T(128)} convert(%a), metadata={op_name="x"}
  ROOT %chained = f32[4]{0} convert(%new)
  %ints = f32[4]{0} convert(%i)
  %down = bf16[4]{0} convert(%new)
"""
    assert hlo_audit.count_bf16_to_f32_converts(text) == 2


def test_bad_fsdp_axis_injection_reverts_composed_placement(eight_devices):
    """Spec-level proof of the --inject bad-fsdp-axis mechanism (the
    compile-level exit-1 proof is the CLI run in docs/PERFORMANCE.md):
    under the composed dp4 x tp2 mesh the hygiene rules keep 'data' off
    every axis AFTER a leaf's 'model' axis (row-parallel/vocab leaves:
    wo/wproj/wte/lm_head) and off vector-like leaves; the injection
    reverts both, reintroducing the transposed-tile-order placement whose
    reshard chains were the 13 banked collective-permutes."""
    import functools

    import jax

    from distributed_llm_training_benchmark_framework_tpu.models import (
        tinygpt as tg,
    )
    from distributed_llm_training_benchmark_framework_tpu.models.llama import (
        get_llama_config,
    )
    from distributed_llm_training_benchmark_framework_tpu.parallel import (
        strategies as strat,
    )
    from distributed_llm_training_benchmark_framework_tpu.parallel.mesh import (
        make_mesh,
    )

    cfg = get_llama_config("S", 64, dropout=0.0)
    mesh = make_mesh((4, 1, 2), ("data", "seq", "model"),
                     devices=jax.devices())
    shapes = jax.eval_shape(
        functools.partial(tg.init_params, cfg), jax.random.key(0)
    )

    def leaf_specs():
        specs = strat.param_partition_specs(
            shapes, mesh, shard=True, kv_heads=cfg.kv_heads
        )
        flat = jax.tree_util.tree_flatten_with_path(specs)[0]
        return {
            "/".join(str(getattr(p, "key", p)) for p in path): tuple(spec)
            for path, spec in flat
        }

    def data_after_model(spec):
        return ("model" in spec and "data" in spec
                and spec.index("data") > spec.index("model"))

    clean = leaf_specs()
    assert not any(data_after_model(s) for s in clean.values()), clean
    # Row-parallel leaves keep model-only sharding; vector-like leaves
    # stay replicated over 'data'; column-parallel leaves keep the split.
    assert "data" not in clean["blocks/wo"]
    assert "data" not in clean["lm_head"]
    assert clean["blocks/ln1_scale"] == (None, None)
    assert "data" in clean["blocks/wq"]

    real = strat._shard_largest_free_axis
    injected = hlo_audit._with_bad_fsdp_axis(leaf_specs)
    bad = [n for n, s in injected.items() if data_after_model(s)]
    assert "blocks/wo" in bad and "lm_head" in bad, injected
    assert "data" in injected["blocks/ln1_scale"]
    # The injection put the placement rule back on the way out.
    assert strat._shard_largest_free_axis is real
    assert leaf_specs() == clean


def test_injected_bad_kv_spec_is_flagged(gqa_report, eight_devices):
    """The acceptance regression: deliberately reintroduce the PR 1 GQA
    kv full-replicate resharding (misaligned 'model' split of wkv/bkv) and
    the auditor must fail the arm, naming the collective and the delta."""
    bad = dataclasses.replace(
        hlo_audit.ROSTER["llama-tp2-gqa"], inject="bad-kv-spec"
    )
    rep = hlo_audit.audit_arm(bad)
    assert rep.collectives["collective-permute"] > 0
    assert rep.replication_reshard_suspects > 0
    # The clean arm stays clean — the injection is what flipped it.
    assert gqa_report.collectives["collective-permute"] == 0
    deltas = hlo_audit.diff_against_budget(rep, hlo_audit.load_budgets())
    joined = "\n".join(deltas)
    assert "llama-tp2-gqa" in joined
    assert "collective-permute REGRESSED" in joined


def test_update_budgets_round_trip_is_stable(gqa_report, tmp_path):
    path = str(tmp_path / "budgets.json")
    hlo_audit.write_budgets([gqa_report], path)
    budgets = hlo_audit.load_budgets(path)
    # Regenerating from the same report diffs clean...
    assert hlo_audit.diff_against_budget(gqa_report, budgets) == []
    first = open(path).read()
    # ...and re-freezing (merge over the existing file) is byte-identical:
    # budget diffs in review always mean a real schedule change.
    hlo_audit.write_budgets([gqa_report], path, existing=budgets)
    assert open(path).read() == first


def test_partial_update_preserves_other_arms(gqa_report, tmp_path):
    path = str(tmp_path / "budgets.json")
    live = hlo_audit.load_budgets()
    hlo_audit.write_budgets([gqa_report], path, existing=live)
    merged = hlo_audit.load_budgets(path)
    # A partial --arms regeneration must not drop the rest of the roster.
    assert set(merged["arms"]) == set(live["arms"])


def test_partial_update_across_jax_versions_is_refused(tmp_path, fixture_budgets):
    # The fixture file was "frozen" on jax 0.0.0-fixture and carries an arm
    # the regeneration does not cover — silently dropping it would mix
    # incomparable counts into one file, so write_budgets must refuse.
    path = str(tmp_path / "budgets.json")
    with pytest.raises(ValueError, match="regenerate the full roster"):
        hlo_audit.write_budgets(
            [_fixture_report(arm="some-other-arm")], path,
            existing=fixture_budgets,
        )
    # Covering every frozen arm IS a full regeneration: allowed, and the
    # stale-version counts are replaced rather than merged.
    hlo_audit.write_budgets([_fixture_report()], path, existing=fixture_budgets)
    assert set(hlo_audit.load_budgets(path)["arms"]) == {"fixture-arm"}


# ---------------------------------------------------------------------------
# CLI contract
# ---------------------------------------------------------------------------


def _cli(*args):
    return subprocess.run(
        [sys.executable, "-m", f"{PKG}.analysis.static", *args],
        capture_output=True, text=True, cwd=REPO, timeout=600,
    )


def test_cli_lint_exits_zero_on_head():
    proc = _cli("--lint")
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "graftcheck lint: clean" in proc.stderr


def test_cli_rejects_unknown_arm():
    proc = _cli("--audit", "--arms", "no-such-arm")
    assert proc.returncode == 2
    assert "unknown arm" in proc.stderr


def test_cli_refuses_to_freeze_injected_budgets():
    # --inject + --update-budgets would pin the deliberately-bad schedule
    # as the audited baseline; the CLI must refuse before any compile.
    proc = _cli("--update-budgets", "--inject", "bad-kv-spec")
    assert proc.returncode == 2
    assert "cannot be combined" in proc.stderr


def test_cli_lists_roster_and_rules():
    proc = _cli("--list-arms")
    assert proc.returncode == 0
    for name in hlo_audit.ROSTER:
        assert name in proc.stdout
    proc = _cli("--list-rules")
    assert proc.returncode == 0
    for rule_id in lint.RULES:
        assert rule_id in proc.stdout


# ---------------------------------------------------------------------------
# GC108: collective axis names vs the enclosing shard_map axis set
# ---------------------------------------------------------------------------


def test_gc108_fires_on_axis_outside_shard_map_set(tmp_path):
    root = _scratch_root(tmp_path, "ops/scratch.py", """\
        import jax
        from jax import lax
        from jax.sharding import PartitionSpec as P

        def body(x):
            y = lax.psum(x, "seq")          # in the set (in_specs literal)
            z = lax.ppermute(y, "model", [(0, 1)])  # NOT in the set
            return z

        def run(mesh, x):
            fn = jax.shard_map(
                body, mesh=mesh, in_specs=(P("seq"),), out_specs=P("seq"),
                axis_names=("seq",),
            )
            return fn(x)
    """)
    violations = lint.run_lint(root=root, rules=("GC108",))
    assert len(violations) == 1
    assert "ppermute" in violations[0].message
    assert "'model'" in violations[0].message
    assert "seq" in violations[0].message  # the known set is named


def test_gc108_honors_suppression_and_axis_name_kwarg(tmp_path):
    root = _scratch_root(tmp_path, "ops/scratch.py", """\
        import jax
        from jax import lax
        from jax.sharding import PartitionSpec as P

        def body(x):
            # graftcheck: disable=GC108
            a = lax.all_gather(x, axis_name="model")
            return a

        def run(mesh, x):
            return jax.shard_map(
                body, mesh=mesh, in_specs=(P("seq"),), out_specs=P(),
                axis_names=("seq",),
            )(x)
    """)
    assert lint.run_lint(root=root, rules=("GC108",)) == []


def test_gc108_skips_open_axis_sets(tmp_path):
    # A spec VARIABLE (models/moe.py's dp-conditional batch spec shape)
    # under-determines the axis set: the site must be skipped, not
    # guessed at.
    root = _scratch_root(tmp_path, "ops/scratch.py", """\
        import jax
        from jax import lax
        from jax.sharding import PartitionSpec as P

        def body(x):
            return lax.psum(x, "data")

        def run(mesh, x, xspec):
            return jax.shard_map(
                body, mesh=mesh, in_specs=(xspec,), out_specs=P("expert"),
            )(x)
    """)
    assert lint.run_lint(root=root, rules=("GC108",)) == []


def test_gc108_checks_lambda_bodies_and_axis_tuples(tmp_path):
    root = _scratch_root(tmp_path, "ops/scratch.py", """\
        import jax
        from jax import lax
        from jax.sharding import PartitionSpec as P

        def run(mesh, x):
            return jax.shard_map(
                lambda v: lax.pmean(v, ("pipe", "bogus")),
                mesh=mesh, in_specs=(P("pipe"),), out_specs=P(),
                axis_names=("pipe",),
            )(x)
    """)
    violations = lint.run_lint(root=root, rules=("GC108",))
    assert len(violations) == 1
    assert "'bogus'" in violations[0].message


def test_gc108_clean_on_head():
    assert lint.run_lint(rules=("GC108",)) == []


# ---------------------------------------------------------------------------
# Topology tiers: AOT audits + growth laws
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def topo_ok():
    if not hlo_audit.topology_available():
        pytest.skip("libtpu topology tables unavailable on this host")
    return True


def test_topology_tier_registry_and_frozen_budgets():
    assert set(hlo_audit.TOPOLOGY_TIERS) == {"v5e-16", "v5e-64", "v5e-256"}
    budgets = hlo_audit.load_budgets()
    tiers = budgets.get("topology_tiers", {})
    assert set(tiers) == set(hlo_audit.TOPOLOGY_TIERS), (
        "configs/collective_budgets.json topology_tiers out of sync — "
        "run --topology <tier> --update-budgets"
    )
    for name, block in tiers.items():
        assert block["device_count"] == (
            hlo_audit.TOPOLOGY_TIERS[name].device_count
        )
        assert set(block["arms"]) == set(hlo_audit.TOPOLOGY_ARMS)
        for entry in block["arms"].values():
            # The committed structure already obeys the reshard law.
            assert entry["replication_reshard_suspects"] == 0


def test_scale_spec_to_devices():
    zero2 = hlo_audit.scale_spec_to_devices(
        hlo_audit.ROSTER["zero2-dp8"], 64
    )
    assert zero2.mesh_shape == (64,)
    assert zero2.global_batch == 16 * 8  # batch scales with the data axis
    gqa = hlo_audit.scale_spec_to_devices(
        hlo_audit.ROSTER["llama-tp2-gqa"], 64
    )
    assert gqa.mesh_shape == (32, 1, 2)  # tp degree is identity, data grows
    assert gqa.global_batch == 64
    with pytest.raises(ValueError, match="does not divide"):
        hlo_audit.scale_spec_to_devices(hlo_audit.ROSTER["zero2-ep2-moe"], 7)


def test_growth_law_findings_pure():
    def entry(suspects=0, **ops):
        c = {op: 0 for op in hlo_audit.COLLECTIVE_OPS}
        c.update(ops)
        return {"collectives": c, "replication_reshard_suspects": suspects}

    # Constant counts and drops are lawful.
    clean = {
        "v5e-16": {"a": entry(**{"all-reduce": 8, "all-gather": 29})},
        "v5e-64": {"a": entry(**{"all-reduce": 8, "all-gather": 0})},
    }
    assert hlo_audit.growth_law_findings(clean) == []
    # Linear-in-devices growth is the ceiling; one past it is a finding.
    at_ceiling = {
        "v5e-16": {"a": entry(**{"all-reduce": 2})},
        "v5e-64": {"a": entry(**{"all-reduce": 8})},
    }
    assert hlo_audit.growth_law_findings(at_ceiling) == []
    superlinear = {
        "v5e-16": {"a": entry(**{"all-reduce": 2})},
        "v5e-64": {"a": entry(**{"all-reduce": 9})},
    }
    findings = hlo_audit.growth_law_findings(superlinear)
    assert len(findings) == 1 and "superlinearly" in findings[0]
    assert "a" in findings[0] and "all-reduce" in findings[0]
    # A collective appearing from zero is worse than linear by definition.
    from_zero = {
        "v5e-16": {"a": entry()},
        "v5e-256": {"a": entry(**{"collective-permute": 3})},
    }
    findings = hlo_audit.growth_law_findings(from_zero)
    assert len(findings) == 1 and "appears from zero" in findings[0]
    # Reshard suspects must be 0 at EVERY tier.
    suspects = {"v5e-64": {"a": entry(suspects=5)}}
    findings = hlo_audit.growth_law_findings(suspects)
    assert len(findings) == 1
    assert "must stay 0" in findings[0] and "a@v5e-64" in findings[0]


def test_topology_audit_v5e16_head_within_budget(topo_ok):
    """The smallest tier compiles the full scalable subset in seconds and
    must match its frozen budgets AND the cross-tier growth laws (fresh
    reports overlaid on the other tiers' frozen structure)."""
    tier = hlo_audit.TOPOLOGY_TIERS["v5e-16"]
    reports = hlo_audit.audit_topology_tier(tier)
    budgets = hlo_audit.load_budgets()
    deltas = hlo_audit.diff_topology_against_budget(
        "v5e-16", reports, budgets
    )
    assert deltas == [], "\n".join(deltas)
    growth = hlo_audit.growth_law_findings(
        hlo_audit.assemble_per_tier(budgets, {"v5e-16": reports})
    )
    assert growth == [], "\n".join(growth)


def test_topology_injection_breaks_growth_law(topo_ok):
    """The acceptance injection: bad-kv-spec reintroduces the GQA
    full-replicate fallback at topology scale — the llama arm's reshard
    suspects go nonzero, which is both a budget delta and a growth-law
    violation by name."""
    tier = hlo_audit.TOPOLOGY_TIERS["v5e-16"]
    reports = hlo_audit.audit_topology_tier(
        tier, arm_names=("llama-tp2-gqa",), inject="bad-kv-spec"
    )
    (rep,) = reports
    assert rep.replication_reshard_suspects > 0
    budgets = hlo_audit.load_budgets()
    deltas = hlo_audit.diff_topology_against_budget(
        "v5e-16", reports, budgets
    )
    assert any("REGRESSED" in d for d in deltas), deltas
    growth = hlo_audit.growth_law_findings(
        hlo_audit.assemble_per_tier(budgets, {"v5e-16": reports})
    )
    assert any(
        "llama-tp2-gqa@v5e-16" in g and "must stay 0" in g for g in growth
    ), growth


@pytest.fixture(scope="module")
def topo_cli_freeze(topo_ok, tmp_path_factory):
    """ONE v5e-64 CLI compile serves two acceptance tests: the clean
    verdict (the freeze rewrites the tier from the fresh compile, so
    byte-identical budgets ARE the exact-pin clean verdict) and the
    freeze-only-topology no-silent-churn rule. Sharing the subprocess
    halves the CLI topology compile cost in tier-1."""
    import json as _json
    import shutil

    path = str(tmp_path_factory.mktemp("topo_freeze") / "budgets.json")
    shutil.copy(hlo_audit.DEFAULT_BUDGETS_PATH, path)
    before = _json.load(open(path))
    proc = _cli("--topology", "v5e-64", "--update-budgets", "--lint",
                "--budgets", path)
    after = _json.load(open(path))
    return proc, before, after


def test_cli_topology_v5e64_clean(topo_cli_freeze):
    """The acceptance CLI: --topology v5e-64 compiles the roster subset
    (>= 2 arms) AOT on the CPU host; the refrozen tier must match the
    committed pins exactly and break no growth law."""
    proc, before, after = topo_cli_freeze
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert proc.stderr.count(f"compiling {len(hlo_audit.TOPOLOGY_ARMS)} arm(s)") == 1
    assert "froze 1 tier budget(s)" in proc.stderr
    # The freeze path judges growth laws over the merged document and
    # would warn by arm name; a clean head stays silent.
    assert "WARNING (frozen anyway)" not in proc.stderr
    # Fresh compile == committed pins (device_count, topology_name,
    # jax_version, and every arm's counts) — the exact-pin clean verdict.
    assert (after["topology_tiers"]["v5e-64"]
            == before["topology_tiers"]["v5e-64"])


def test_cli_topology_injection_exits_one(topo_ok):
    proc = _cli("--topology", "v5e-16", "--inject", "bad-kv-spec",
                "--arms", "llama-tp2-gqa")
    assert proc.returncode == 1, proc.stderr[-3000:]
    assert "compiling 1 arm(s)" in proc.stderr
    assert "graftcheck topology: 1 tier(s)," in proc.stderr
    assert "must stay 0" in proc.stderr
    assert "llama-tp2-gqa" in proc.stderr


def test_cli_topology_unknown_arm_exits_two():
    proc = _cli("--topology", "v5e-16", "--arms", "no-such-arm")
    assert proc.returncode == 2
    assert "unknown arm(s)" in proc.stderr
    assert "no-such-arm" in proc.stderr


def test_cli_topology_partial_freeze_refused():
    # Freezing an --arms subset would drop the tier's other pins.
    proc = _cli("--topology", "v5e-16", "--arms", "llama-tp2-gqa",
                "--update-budgets")
    assert proc.returncode == 2
    assert "partial tier" in proc.stderr


def test_cli_topology_unknown_tier_exits_two():
    proc = _cli("--topology", "v5e-9999")
    assert proc.returncode == 2
    assert "unknown topology tier" in proc.stderr


def test_all_includes_default_topology_tiers_in_script():
    # --all picks up the default tiers (16 + 64) without disturbing the
    # frozen CPU arm budgets; v5e-256 stays explicit (compile cost).
    assert hlo_audit.TOPOLOGY_DEFAULT_TIERS == ("v5e-16", "v5e-64")
    budgets = hlo_audit.load_budgets()
    assert set(budgets["arms"]) == set(hlo_audit.ROSTER)  # untouched


def test_update_budgets_preserves_topology_section(gqa_report, tmp_path):
    # An arm-roster regeneration must carry topology_tiers through.
    live = hlo_audit.load_budgets()
    assert "topology_tiers" in live
    path = str(tmp_path / "budgets.json")
    hlo_audit.write_budgets([gqa_report], path, existing=live)
    merged = hlo_audit.load_budgets(path)
    assert merged["topology_tiers"] == live["topology_tiers"]


def test_gc108_partially_literal_axis_names_opens_the_set(tmp_path):
    # ("data", extra_axis): one runtime element means unknown axes exist
    # — the site must be skipped, not judged against the literal half.
    root = _scratch_root(tmp_path, "ops/scratch.py", """\
        import jax
        from jax import lax
        from jax.sharding import PartitionSpec as P

        def body(x):
            return lax.psum(x, "model")

        def run(mesh, x, extra_axis):
            return jax.shard_map(
                body, mesh=mesh, in_specs=(P("data"),), out_specs=P("data"),
                axis_names=("data", extra_axis),
            )(x)
    """)
    assert lint.run_lint(root=root, rules=("GC108",)) == []


def test_gc108_no_axis_names_means_open_set(tmp_path):
    # Without a literal axis_names=, shard_map's manual set defaults to
    # ALL mesh axes — a runtime value — so fully-literal specs alone must
    # NOT close the set (a psum over an unnamed mesh axis is legal).
    root = _scratch_root(tmp_path, "ops/scratch.py", """\
        import jax
        from jax import lax
        from jax.sharding import PartitionSpec as P

        def body(x):
            return lax.psum(x, "model")

        def run(mesh, x):
            return jax.shard_map(
                body, mesh=mesh, in_specs=(P("data"),), out_specs=P("data"),
            )(x)
    """)
    assert lint.run_lint(root=root, rules=("GC108",)) == []


def test_commensurable_topology_tiers_filters_cross_version():
    budgets = {"topology_tiers": {
        "v5e-16": {"jax_version": "0.9.9", "arms": {}},
        "v5e-64": {"jax_version": "0.4.37", "arms": {}},
        "v5e-256": {"jax_version": "0.4.37", "arms": {}},
    }}
    # A fresh v5e-16 audit stays (its counts ARE the running compiler's);
    # no other tier is stale at the matching version.
    kept, stale = hlo_audit.commensurable_topology_tiers(
        budgets, fresh_tiers=("v5e-16",), jax_version="0.4.37"
    )
    assert stale == []
    # Without the fresh overlay, the off-version tier drops with a name.
    kept, stale = hlo_audit.commensurable_topology_tiers(
        budgets, fresh_tiers=(), jax_version="0.4.37"
    )
    assert stale == ["v5e-16"]
    assert set(kept["topology_tiers"]) == {"v5e-64", "v5e-256"}
    # The input document is never mutated.
    assert set(budgets["topology_tiers"]) == {"v5e-16", "v5e-64", "v5e-256"}


def test_topology_freeze_never_touches_roster_budgets_with_lint(
    topo_cli_freeze,
):
    # `--topology X --update-budgets --lint` must freeze ONLY the
    # topology section: a read-only lint flag cannot flip the invocation
    # into regenerating the CPU arm budgets (the no-silent-churn rule).
    proc, before, after = topo_cli_freeze
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "graftcheck audit:" not in proc.stderr  # roster audit never ran
    assert "graftcheck lint:" in proc.stderr  # the lint leg still ran
    assert after["arms"] == before["arms"]
    assert after["jax_version"] == before["jax_version"]


def test_gc108_nested_shard_map_owns_its_own_axis_scope(tmp_path):
    # A collective inside an INNER shard_map must be judged against the
    # inner site's axis set, never the enclosing one — and the inner
    # site's own literal set still fires on a genuinely bad axis.
    root = _scratch_root(tmp_path, "ops/scratch.py", """\
        import jax
        from jax import lax
        from jax.sharding import PartitionSpec as P

        def outer_body(x):
            inner = jax.shard_map(
                lambda v: lax.psum(v, "model"),
                mesh=None, in_specs=(P("model"),), out_specs=P(),
                axis_names=("model",),
            )
            return inner(lax.psum(x, "data"))

        def run(mesh, x):
            return jax.shard_map(
                outer_body, mesh=mesh, in_specs=(P("data"),),
                out_specs=P(), axis_names=("data",),
            )(x)
    """)
    assert lint.run_lint(root=root, rules=("GC108",)) == []
    bad = _scratch_root(tmp_path / "bad", "ops/scratch.py", """\
        import jax
        from jax import lax
        from jax.sharding import PartitionSpec as P

        def outer_body(x):
            inner = jax.shard_map(
                lambda v: lax.psum(v, "bogus"),
                mesh=None, in_specs=(P("model"),), out_specs=P(),
                axis_names=("model",),
            )
            return inner(x)

        def run(mesh, x):
            return jax.shard_map(
                outer_body, mesh=mesh, in_specs=(P("data"),),
                out_specs=P(), axis_names=("data",),
            )(x)
    """)
    violations = lint.run_lint(root=bad, rules=("GC108",))
    assert len(violations) == 1 and "'bogus'" in violations[0].message


# ---------------------------------------------------------------------------
# Schedule auditor: pipeline arms, closed-form laws, budgets, injection
# ---------------------------------------------------------------------------


def test_pipeline_roster_covers_schedules_and_budgets_in_sync():
    """All three schedules audit (tinygpt) plus a llama composition, with
    live dropout keys (a typed key crossing the shard_map boundary was the
    seed-old compile failure), and the frozen pipeline_schedules budgets
    track the roster exactly."""
    scheds = {s.pipeline_schedule for s in hlo_audit.PIPELINE_ROSTER.values()}
    assert scheds == {"gpipe", "1f1b", "interleaved"}
    fams = {s.model_family for s in hlo_audit.PIPELINE_ROSTER.values()}
    assert fams == {"tinygpt", "llama"}
    for spec in hlo_audit.PIPELINE_ROSTER.values():
        assert dict(zip(spec.axes, spec.mesh_shape)).get("pipe", 1) > 1
        assert ("dropout", 0.1) in spec.config_overrides, (
            f"{spec.name}: pipeline arms must audit with LIVE dropout "
            "keys: an audit that drops the keys cannot see them break"
        )
    budgets = hlo_audit.load_budgets()
    section = budgets.get("pipeline_schedules", {})
    assert set(section.get("arms", {})) == set(hlo_audit.PIPELINE_ROSTER), (
        "pipeline_schedules out of sync with PIPELINE_ROSTER — run "
        "--update-budgets"
    )


def test_expected_pipeline_permutes_and_slopes_pure():
    e = hlo_audit.expected_pipeline_permutes
    # gpipe/1f1b: 2*(M+S-2); interleaved: constant 2 (one scan body).
    assert e("gpipe", 2, 4) == 8
    assert e("gpipe", 4, 8) == 20
    assert e("1f1b", 2, 4) == 8
    assert e("1f1b", 4, 16) == 36
    assert e("interleaved", 2, 4, 2) == 2
    assert e("interleaved", 4, 32, 4) == 2
    assert hlo_audit.pipeline_permute_slope("gpipe") == 2
    assert hlo_audit.pipeline_permute_slope("1f1b") == 2
    assert hlo_audit.pipeline_permute_slope("interleaved") == 0
    with pytest.raises(ValueError):
        e("mpmd", 2, 4)


def test_pipeline_bubble_bounds_pure():
    b = hlo_audit.pipeline_bubble_bound
    assert b("gpipe", 2, 4) == pytest.approx(1 / 5)
    assert b("gpipe", 4, 8) == pytest.approx(3 / 11)
    assert b("1f1b", 2, 4) == pytest.approx(2 / 6)
    # Interleaved: the exact scheduler-table idle fraction, and MORE
    # microbatches shrink it (the fill/drain amortizes).
    from distributed_llm_training_benchmark_framework_tpu.parallel.interleaved import (
        build_schedule,
    )

    assert b("interleaved", 2, 4, 2) == pytest.approx(
        build_schedule(2, 2, 4).bubble_fraction
    )
    # More microbatches amortize the fill/drain (P=2's head-unit saving
    # makes it exactly M-independent, so assert at P=4 where it shrinks).
    assert b("interleaved", 4, 32, 2) < b("interleaved", 4, 4, 2)


def test_pipeline_schedule_meta_matches_audit_inputs(eight_devices):
    """The law inputs (S, M, V) come from the same contract the train
    step compiles: train.step.pipeline_schedule_meta on the arm's real
    mesh equals the auditor's derivation from the spec."""
    import jax as _jax

    from distributed_llm_training_benchmark_framework_tpu.parallel import (
        make_mesh,
    )
    from distributed_llm_training_benchmark_framework_tpu.train.step import (
        pipeline_schedule_meta,
    )

    for spec in hlo_audit.PIPELINE_ROSTER.values():
        n = 1
        for d in spec.mesh_shape:
            n *= d
        mesh = make_mesh(spec.mesh_shape, spec.axes,
                         devices=_jax.devices()[:n])
        meta = pipeline_schedule_meta(
            mesh, spec.grad_accum, spec.pipeline_schedule,
            spec.virtual_stages,
        )
        result = hlo_audit.PipelineAuditResult(
            arm=spec.name, grown_microbatches=0, **{
                "schedule": spec.pipeline_schedule,
                "stages": dict(zip(spec.axes, spec.mesh_shape))["pipe"],
                "microbatches": spec.grad_accum,
                "virtual": (
                    spec.virtual_stages
                    if spec.pipeline_schedule == "interleaved" else 1
                ),
            },
        )
        assert meta == {
            "schedule": result.schedule, "stages": result.stages,
            "microbatches": result.microbatches,
            "virtual": result.virtual,
        }
    # Non-pipeline meshes yield no schedule meta.
    flat = make_mesh((8,), ("data",), devices=_jax.devices())
    assert pipeline_schedule_meta(flat, 4) is None


def _pipe_result(base_perm, grown_perm, schedule="gpipe", stages=2, m=4,
                 compile_error=None):
    def rep(perm):
        return hlo_audit.ArmReport(
            arm="fake-pp", collectives={
                "all-gather": 0, "reduce-scatter": 0, "all-reduce": 18,
                "collective-permute": perm, "all-to-all": 0,
            },
            replication_reshard_suspects=0, donated_inputs=10,
            donatable_inputs=10, bf16_to_f32_converts=0,
        )

    return hlo_audit.PipelineAuditResult(
        arm="fake-pp", schedule=schedule, stages=stages, microbatches=m,
        virtual=1, grown_microbatches=m * 2,
        base=None if compile_error else rep(base_perm),
        grown=None if compile_error else rep(grown_perm),
        compile_error=compile_error,
    )


def test_pipeline_law_findings_pure():
    # Lawful: exact closed forms at both M values.
    ok = _pipe_result(8, 16)
    assert hlo_audit.pipeline_law_findings(ok) == []
    # Permute law broken at base M: named with the excess-suspect count.
    bad = _pipe_result(11, 16)
    findings = hlo_audit.pipeline_law_findings(bad)
    assert any(
        "VIOLATES permute-law at base M=4: 11" in f
        and "3 excess permute(s)" in f for f in findings
    ), findings
    # Affine growth broken (slope 2 expected, got superlinear).
    sup = _pipe_result(8, 26)
    findings = hlo_audit.pipeline_law_findings(sup)
    assert any("VIOLATES affine-growth" in f for f in findings), findings
    # Compile failure IS the schedule-compiles law, named per arm.
    dead = _pipe_result(0, 0, compile_error="XlaRuntimeError: u32[2] ...")
    findings = hlo_audit.pipeline_law_findings(dead)
    assert len(findings) == 1
    assert "fake-pp VIOLATES schedule-compiles" in findings[0]
    assert "u32[2]" in findings[0]


def test_diff_pipeline_against_budget_pure(tmp_path):
    ok = _pipe_result(8, 16)
    doc = hlo_audit.write_pipeline_budgets(
        [ok], str(tmp_path / "b.json"), existing={"arms": {}}
    )
    # Clean against its own freeze.
    assert hlo_audit.diff_pipeline_against_budget(ok, doc) == []
    # A law-respecting drift (extra all-reduce) still pins.
    import copy

    drift = copy.deepcopy(doc)
    drift["pipeline_schedules"]["arms"]["fake-pp"]["base"][
        "collectives"]["all-reduce"] = 17
    deltas = hlo_audit.diff_pipeline_against_budget(ok, drift)
    assert any("base:" in d and "all-reduce" in d for d in deltas), deltas
    # Metadata drift names a regenerate remedy.
    meta_drift = copy.deepcopy(doc)
    meta_drift["pipeline_schedules"]["arms"]["fake-pp"]["schedule"][
        "stages"] = 4
    deltas = hlo_audit.diff_pipeline_against_budget(ok, meta_drift)
    assert any("schedule metadata drifted" in d for d in deltas), deltas
    # Unknown arm demands a freeze.
    deltas = hlo_audit.diff_pipeline_against_budget(ok, {"arms": {}})
    assert any("no frozen pipeline_schedules budget" in d for d in deltas)


def test_write_pipeline_budgets_refuses_compile_errors(tmp_path):
    dead = _pipe_result(0, 0, compile_error="boom")
    with pytest.raises(ValueError, match="failed to compile"):
        hlo_audit.write_pipeline_budgets([dead], str(tmp_path / "b.json"))


def test_write_pipeline_budgets_refuses_partial_cross_version(tmp_path):
    """Same contract as write_budgets: merging fresh counts over pipeline
    arms frozen on a DIFFERENT jax (and restamping the section version)
    would claim incomparable counts are commensurable; a full-roster
    regen is allowed and resets the section."""
    path = str(tmp_path / "b.json")
    ok = _pipe_result(8, 16)
    doc = hlo_audit.write_pipeline_budgets([ok], path, existing={"arms": {}})
    doc["pipeline_schedules"]["jax_version"] = "9.9.9-not-this-one"
    other = dataclasses.replace(ok, arm="other-pp")
    with pytest.raises(ValueError, match="partial --arms regeneration"):
        hlo_audit.write_pipeline_budgets([other], path, existing=doc)
    # Regenerating every frozen arm across the version boundary is fine.
    doc2 = hlo_audit.write_pipeline_budgets([ok], path, existing=doc)
    import jax as _jax

    assert doc2["pipeline_schedules"]["jax_version"] == _jax.__version__
    assert set(doc2["pipeline_schedules"]["arms"]) == {"fake-pp"}


def test_write_budgets_carries_pipeline_section_through(tmp_path):
    """An arm-roster regeneration must not drop (or alter) the frozen
    pipeline_schedules section — the --update-budgets carry-through
    contract the topology tiers already have."""
    path = str(tmp_path / "budgets.json")
    ok = _pipe_result(8, 16)
    hlo_audit.write_pipeline_budgets([ok], path, existing={"arms": {}})
    before = hlo_audit.load_budgets(path)
    rep = _fixture_report(arm="some-arm")
    hlo_audit.write_budgets([rep], path, existing=before)
    after = hlo_audit.load_budgets(path)
    assert after["pipeline_schedules"] == before["pipeline_schedules"]
    assert "some-arm" in after["arms"]


@pytest.fixture(scope="module")
def interleaved_audit(eight_devices):
    """ONE real dual-M audit shared by the in-process proofs (the
    interleaved executor compiles in seconds — scan body)."""
    return hlo_audit.audit_pipeline_arm(
        hlo_audit.PIPELINE_ROSTER["pp2-interleaved-v2"]
    )


def test_pipeline_head_is_lawful_and_within_budget(interleaved_audit):
    assert interleaved_audit.compile_error is None
    budgets = hlo_audit.load_budgets()
    deltas = hlo_audit.diff_pipeline_against_budget(
        interleaved_audit, budgets
    )
    assert deltas == [], "\n".join(deltas)


def test_topology_arms_include_pipeline_composition():
    """ROADMAP PR 11 follow-up: a pp composition joins the per-tier
    audits, with frozen budgets at every tier and the permute count
    CONSTANT across tiers (only 'data' grows; the ring is pipe-local)."""
    assert "pp2-gpipe" in hlo_audit.TOPOLOGY_ARMS
    budgets = hlo_audit.load_budgets()
    perms = set()
    for tier, block in budgets["topology_tiers"].items():
        assert "pp2-gpipe" in block["arms"], tier
        perms.add(
            block["arms"]["pp2-gpipe"]["collectives"]["collective-permute"]
        )
    assert len(perms) == 1  # constant in the data axis
    # And the growth laws accept the frozen cross-tier structure.
    growth = hlo_audit.growth_law_findings(
        hlo_audit.assemble_per_tier(budgets)
    )
    assert growth == [], "\n".join(growth)


# ---------------------------------------------------------------------------
# GC109: per-microbatch reshard hazard in parallel/ schedule loops
# ---------------------------------------------------------------------------


def _scratch_parallel(tmp_path, body):
    root = tmp_path / "scratch"
    pkg = root / PKG / "parallel"
    pkg.mkdir(parents=True)
    (pkg / "sched.py").write_text(textwrap.dedent(body))
    return str(root)


def test_gc109_fires_on_reshard_and_sync_in_schedule_loop(tmp_path):
    root = _scratch_parallel(tmp_path, """
        import jax
        from jax import lax

        def run(state, specs, ticks, xs):
            for t in range(ticks):
                state = lax.with_sharding_constraint(state, specs)
                state = jax.device_put(state)
                v = float(state[0])
                w = xs.item()
            return state
    """)
    violations = lint.run_lint(root=root, rules=("GC109",))
    lines = {v.line for v in violations}
    assert len(violations) == 4, violations
    assert all(v.rule_id == "GC109" for v in violations)
    msgs = "\n".join(v.message for v in violations)
    assert "with_sharding_constraint" in msgs
    assert "device_put" in msgs
    assert ".item()" in msgs
    assert "host sync" in msgs


def test_gc109_sees_into_loop_local_closures(tmp_path):
    """The real tick loops put per-tick work in closures invoked via
    lax.cond each unrolled tick — a hazard inside one is still one copy
    per microbatch, so GC109 walks nested defs (unlike the GC102/105
    fence walk, whose nested-def exemption is about sync_window
    helpers)."""
    root = _scratch_parallel(tmp_path, """
        from jax import lax

        def run(state, specs, ticks):
            for t in range(ticks):
                def head_work(s=state):
                    return lax.with_sharding_constraint(s, specs)

                state = lax.cond(t > 0, head_work, lambda: state)
            return state
    """)
    violations = lint.run_lint(root=root, rules=("GC109",))
    assert len(violations) == 1, violations
    assert "with_sharding_constraint" in violations[0].message


def test_gc109_honors_suppression_and_ignores_non_range_loops(tmp_path):
    root = _scratch_parallel(tmp_path, """
        import jax
        from jax import lax

        def ok(states, specs, ticks):
            # Not a range() loop: a host iteration over a real container.
            for s in states:
                jax.device_put(s)
            # Outside any loop.
            lax.with_sharding_constraint(states[0], specs)
            for t in range(ticks):
                x = lax.with_sharding_constraint(  # graftcheck: disable=GC109
                    states[0], specs
                )
                y = lax.ppermute(x, "pipe", [(0, 1)])  # fine
            return y
    """)
    assert lint.run_lint(root=root, rules=("GC109",)) == []


def test_gc109_clean_on_head():
    assert lint.run_lint(rules=("GC109",)) == []


# ---------------------------------------------------------------------------
# GC111: blocking input IO / host-iterator pulls in the timed loop
# ---------------------------------------------------------------------------


def test_gc111_fires_on_blocking_io_and_next_in_timed_loop(tmp_path):
    """Direct file reads, next() pulls and sleeps inside the timed loop
    are flagged; the prefetch fence (any *prefetch* receiver) and a
    sync_window-fenced tail are sanctioned."""
    root = _scratch_root(tmp_path, "train/loop.py", """\
        import time

        def run(steps, step_fn, state, it, f, prefetch):
            def sync_window():
                pass

            for step in range(steps):
                batch = next(it)
                raw = f.read(128)
                f.seek(0)
                time.sleep(0.01)
                with open("/data/shard") as g:
                    pass
                good, meta, waited = prefetch.get(step, timeout=5)
                state = step_fn(state, good)
                if step % 10 == 0:
                    sync_window()
                    f.read(128)  # fenced: after the sync in this block
            return state
    """)
    violations = lint.run_lint(root=root, rules=("GC111",))
    assert [v.line for v in violations] == [8, 9, 10, 11, 12]
    assert {v.rule_id for v in violations} == {"GC111"}
    msgs = "\n".join(v.message for v in violations)
    assert "next() host-iterator pull" in msgs
    assert ".read()" in msgs and ".seek()" in msgs
    assert "time.sleep()" in msgs and "open()" in msgs
    assert "prefetch" in violations[0].fix_hint


def test_gc111_scans_data_package_and_honors_suppression(tmp_path):
    root = _scratch_root(tmp_path, "data/scratch.py", """\
        def consume(steps, it):
            out = []
            for step in range(steps):
                out.append(next(it))
                out.append(next(it))  # graftcheck: disable=GC111
            return out
    """)
    violations = lint.run_lint(root=root, rules=("GC111",))
    assert [v.line for v in violations] == [4]


def test_gc111_ignores_non_step_loops(tmp_path):
    """The producer thread's own loop (data/prefetch.py) legitimately
    blocks — only the timed `for step` shape is policed."""
    root = _scratch_root(tmp_path, "data/scratch.py", """\
        def produce(n, it):
            out = []
            for produced in range(n):
                out.append(next(it))
            return out
    """)
    assert lint.run_lint(root=root, rules=("GC111",)) == []


def test_gc111_clean_on_head():
    assert lint.run_lint(rules=("GC111",)) == []


# ---------------------------------------------------------------------------
# GC112: hard-coded exit-code literals outside the central EXIT_* registry
# ---------------------------------------------------------------------------


def test_gc112_fires_on_literal_exit_codes_and_exempts_registry(tmp_path):
    """A registry value (harvested from the scratch tree's own EXIT_*
    assignments) as a bare literal in an exit call or an exit-code
    comparison is flagged — including both members of the
    ``rc in (75, 76)`` tuple shape; the defining assignment, named-
    constant usage, non-registry integers, and non-exit-shaped
    receivers are not."""
    _scratch_root(tmp_path, "faults/codes.py", """\
        EXIT_PREEMPTED = 75
        EXIT_HUNG = 76
    """)
    root = _scratch_root(tmp_path, "runtime/scratch.py", """\
        import sys

        from ..faults.codes import EXIT_PREEMPTED

        def classify(rc, percentile):
            if rc == 75:
                sys.exit(75)
            if rc in (75, 76):
                return "retryable"
            if rc == EXIT_PREEMPTED:
                return "named is fine"
            if rc == 1:
                return "not a registry value"
            if percentile == 75:
                return "not an exit-code receiver"
            sys.exit(EXIT_PREEMPTED)
    """)
    violations = lint.run_lint(root=root, rules=("GC112",))
    assert [v.line for v in violations] == [6, 7, 8, 8]
    assert {v.rule_id for v in violations} == {"GC112"}
    msgs = "\n".join(v.message for v in violations)
    assert "EXIT_PREEMPTED" in msgs and "EXIT_HUNG" in msgs
    assert "from ..faults import" in violations[0].fix_hint


def test_gc112_honors_suppression(tmp_path):
    _scratch_root(tmp_path, "faults/codes.py", """\
        EXIT_HUNG = 76
    """)
    root = _scratch_root(tmp_path, "runtime/scratch.py", """\
        def is_hang(returncode):
            if returncode == 76:  # graftcheck: disable=GC112
                return True
            return returncode == 76
    """)
    violations = lint.run_lint(root=root, rules=("GC112",))
    assert [v.line for v in violations] == [4]


def test_gc112_clean_on_head():
    """HEAD keeps every exit-code comparison on the named EXIT_*
    constants (faults/, runtime/supervisor.py) — the registry harvest
    sees 75/76/77/78 and nothing outside the defining assignments."""
    assert lint.run_lint(rules=("GC112",)) == []


# ---------------------------------------------------------------------------
# --changed fast lint mode
# ---------------------------------------------------------------------------


def test_run_lint_files_filter_scopes_findings(tmp_path):
    """The --changed machinery: findings are scoped to the changed set
    while rules still see the whole tree for context."""
    root = _scratch_parallel(tmp_path, """
        import jax

        def run(x, ticks):
            for t in range(ticks):
                x = jax.device_put(x)
            return x
    """)
    all_v = lint.run_lint(root=root, rules=("GC109",))
    assert len(all_v) == 1
    rel = all_v[0].path
    assert lint.run_lint(root=root, rules=("GC109",), files=(rel,)) == all_v
    assert lint.run_lint(
        root=root, rules=("GC109",), files=("somewhere/else.py",)
    ) == []


def test_changed_mode_covers_collective_matmul(tmp_path):
    """Round-15 satellite: the --changed pre-commit path covers
    ops/collective_matmul.py — the real file lints clean when scoped to
    exactly it, and a cmm-shaped scratch file (shard_map ring body naming
    a wrong literal axis) is caught by GC108 under the same scoping."""
    rel = (
        "distributed_llm_training_benchmark_framework_tpu/ops/"
        "collective_matmul.py"
    )
    assert lint.run_lint(files=(rel,)) == []
    root = _scratch_root(tmp_path, "ops/collective_matmul.py", """\
        import jax
        from jax import lax
        from jax.sharding import PartitionSpec as P

        def ring(x, w):
            chunk = lax.ppermute(x, "data", [(0, 1)])  # wrong axis
            return chunk @ w

        def ag_proj(mesh, x, w):
            return jax.shard_map(
                ring, mesh=mesh, in_specs=(P(None, "model", None), P()),
                out_specs=P(None, None, "model"),
                axis_names=("model",),
            )(x, w)
    """)
    violations = lint.run_lint(root=root, rules=("GC108",))
    assert len(violations) == 1 and "ppermute" in violations[0].message
    rel_scratch = violations[0].path
    assert rel_scratch.endswith("ops/collective_matmul.py")
    # ...and the --changed scoping keeps the finding when the file is in
    # the changed set, drops it when not.
    assert lint.run_lint(
        root=root, rules=("GC108",), files=(rel_scratch,)
    ) == violations
    assert lint.run_lint(
        root=root, rules=("GC108",), files=("somewhere/else.py",)
    ) == []


def test_cli_changed_is_lint_only():
    proc = _cli("--changed", "--all")
    assert proc.returncode == 2
    assert "fast lint-only" in proc.stderr


def test_cli_changed_smoke():
    proc = _cli("--changed")
    assert proc.returncode in (0, 1), proc.stderr[-2000:]
    assert "graftcheck lint:" in proc.stderr


@pytest.mark.slow
def test_cli_pipeline_audit_clean():
    """Acceptance CLI pin: the pipeline roster audits green against the
    frozen pipeline_schedules budgets."""
    proc = _cli("--audit", "--arms",
                "pp2-gpipe,pp2-1f1b,pp2-interleaved-v2,llama-pp2-1f1b")
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "4 pipeline arm(s), 0 finding(s)" in proc.stderr


# ---------------------------------------------------------------------------
# GC110: the memory-budget audit (compile-time memory anatomy, frozen)
# ---------------------------------------------------------------------------


def _mem_report(**overrides):
    base = dict(
        arm="mem-arm", argument_bytes=1000, output_bytes=1000,
        temp_bytes=5000, alias_bytes=900, peak_bytes=6100,
    )
    base.update(overrides)
    return hlo_audit.MemoryReport(**base)


def _mem_budgets(**overrides):
    entry = dict(argument_bytes=1000, output_bytes=1000, temp_bytes=5000,
                 alias_bytes=900, peak_bytes=6100)
    entry.update(overrides)
    return {"memory_budgets": {"arms": {"mem-arm": entry}}}


def test_gc110_within_budget_is_clean():
    assert hlo_audit.diff_memory_against_budget(
        _mem_report(), _mem_budgets()
    ) == []


def test_gc110_temp_growth_is_named_with_delta():
    deltas = hlo_audit.diff_memory_against_budget(
        _mem_report(temp_bytes=6000, peak_bytes=7100), _mem_budgets()
    )
    assert len(deltas) == 2
    assert any("GC110" in d and "temp bytes REGRESSED 5000 -> 6000" in d
               and "+20.0%" in d for d in deltas), deltas


def test_gc110_argument_growth_regresses_and_shrink_banks():
    # Argument growth = replicated state (the GC110 motivating class).
    deltas = hlo_audit.diff_memory_against_budget(
        _mem_report(argument_bytes=2000), _mem_budgets()
    )
    assert any("argument bytes REGRESSED" in d for d in deltas), deltas
    deltas = hlo_audit.diff_memory_against_budget(
        _mem_report(temp_bytes=4000, peak_bytes=5100), _mem_budgets()
    )
    assert all("improved" in d and "--update-budgets" in d
               for d in deltas), deltas


def test_gc110_lost_donation_alias_regresses():
    deltas = hlo_audit.diff_memory_against_budget(
        _mem_report(alias_bytes=100), _mem_budgets()
    )
    assert any("donation-alias bytes REGRESSED" in d for d in deltas), deltas


def test_gc110_unknown_arm_demands_a_budget():
    deltas = hlo_audit.diff_memory_against_budget(
        _mem_report(arm="never-frozen"), _mem_budgets()
    )
    assert deltas and "no frozen memory budget" in deltas[0]


def test_gc110_growth_laws_pure():
    flat = dict(argument_bytes=100, output_bytes=100, temp_bytes=500,
                alias_bytes=90, peak_bytes=610)
    # Clean: ddp-style temps flat, fsdp/zero arguments shrinking.
    per_tier = {
        "v5e-16": {"llama-tp2-gqa": dict(flat),
                   "fsdp-dp8": dict(flat, argument_bytes=400)},
        "v5e-64": {"llama-tp2-gqa": dict(flat),
                   "fsdp-dp8": dict(flat, argument_bytes=120)},
    }
    assert hlo_audit.memory_growth_law_findings(per_tier) == []
    # Temp growth along the data axis fires the dp-flat law by name.
    per_tier["v5e-64"]["llama-tp2-gqa"] = dict(flat, temp_bytes=900)
    findings = hlo_audit.memory_growth_law_findings(per_tier)
    assert any("GC110 growth-law" in f and "temp bytes grew" in f
               and "llama-tp2-gqa" in f for f in findings), findings
    # Non-shrinking fsdp arguments fire the sharded-state law by name.
    per_tier["v5e-64"]["llama-tp2-gqa"] = dict(flat)
    per_tier["v5e-64"]["fsdp-dp8"] = dict(flat, argument_bytes=400)
    findings = hlo_audit.memory_growth_law_findings(per_tier)
    assert any("did not shrink" in f and "fsdp-dp8" in f
               for f in findings), findings
    # A zero-temp entry (the v5e-64 accounting artifact) never anchors
    # the temp law: 0 -> anything is skipped, not a finding.
    per_tier = {
        "v5e-16": {"llama-tp2-gqa": dict(flat, temp_bytes=0)},
        "v5e-64": {"llama-tp2-gqa": dict(flat, temp_bytes=900)},
    }
    assert hlo_audit.memory_growth_law_findings(per_tier) == []


def test_gc110_shard_axis_classifier():
    assert hlo_audit.arm_shards_state_over_data("fsdp-dp8")
    assert hlo_audit.arm_shards_state_over_data("zero2-dp8")
    assert not hlo_audit.arm_shards_state_over_data("ddp-dp8")
    assert not hlo_audit.arm_shards_state_over_data("llama-tp2-gqa")
    with pytest.raises(KeyError):
        hlo_audit.arm_shards_state_over_data("no-such-arm")


def test_gc110_frozen_budgets_cover_roster_and_obey_laws():
    budgets = hlo_audit.load_budgets()
    section = budgets.get("memory_budgets", {})
    assert set(section.get("arms", {})) == set(hlo_audit.ROSTER), (
        "configs/collective_budgets.json memory_budgets out of sync — "
        "run --memory --update-budgets"
    )
    # The committed tier structure already obeys both growth laws (the
    # v5e-256 tier is deliberately absent: at 256-way dp the tier-S probe
    # model's 128-wide leaves stop dividing, so fsdp/zero state
    # legitimately replicates and the shrink law cannot hold there).
    per_tier, stale = hlo_audit.commensurable_memory_tiers(
        budgets, jax_version=section.get("jax_version")
    )
    assert set(per_tier) == {"v5e-16", "v5e-64"}
    assert stale == []
    assert hlo_audit.memory_growth_law_findings(per_tier) == []


def test_gc110_head_within_frozen_memory_budget(eight_devices):
    budgets = hlo_audit.load_budgets()
    deltas = []
    for arm in ("ddp-dp8", "llama-tp2-gqa"):
        rep = hlo_audit.audit_arm_memory(hlo_audit.ROSTER[arm])
        deltas.extend(hlo_audit.diff_memory_against_budget(rep, budgets))
    assert deltas == [], "\n".join(deltas)


def test_gc110_budget_drift_is_flagged(eight_devices, tmp_path):
    # The budget-drift proof: doctor one frozen byte count and the audit
    # must name the arm + field + delta.
    import json as _json

    budgets = hlo_audit.load_budgets()
    doctored = _json.loads(_json.dumps(budgets))
    doctored["memory_budgets"]["arms"]["ddp-dp8"]["temp_bytes"] -= 4096
    rep = hlo_audit.audit_arm_memory(hlo_audit.ROSTER["ddp-dp8"])
    deltas = hlo_audit.diff_memory_against_budget(rep, doctored)
    assert len(deltas) == 1
    assert "GC110" in deltas[0] and "ddp-dp8" in deltas[0]
    assert "temp bytes REGRESSED" in deltas[0]


def test_gc110_write_budgets_round_trip_and_carry_through(tmp_path):
    import json as _json

    path = str(tmp_path / "budgets.json")
    # Seed a file with the OTHER sections; the memory writer must carry
    # them through untouched, and vice versa.
    seed = {"arms": {"x": {"collectives": {}}},
            "pipeline_schedules": {"jax_version": "v", "arms": {}},
            "topology_tiers": {"v5e-16": {"arms": {}}}}
    with open(path, "w") as f:
        _json.dump(seed, f)
    doc = hlo_audit.write_memory_budgets([_mem_report()], path)
    assert doc["arms"] == seed["arms"]
    assert doc["pipeline_schedules"] == seed["pipeline_schedules"]
    assert doc["topology_tiers"] == seed["topology_tiers"]
    assert "mem-arm" in doc["memory_budgets"]["arms"]
    before = open(path).read()
    hlo_audit.write_memory_budgets([_mem_report()], path)
    assert open(path).read() == before  # deterministic serialization
    # ...and the collective writer carries memory_budgets through.
    rep = _fixture_report()
    doc2 = hlo_audit.write_budgets([rep], path,
                                   existing=hlo_audit.load_budgets(path))
    assert "mem-arm" in doc2["memory_budgets"]["arms"]


def test_gc110_partial_regen_across_jax_versions_refused(tmp_path):
    import json as _json

    path = str(tmp_path / "budgets.json")
    doc = {"arms": {}, "memory_budgets": {
        "jax_version": "0.0.1",
        "arms": {"mem-arm": _mem_report().to_budget_entry(),
                 "other-arm": _mem_report(arm="other-arm").to_budget_entry()},
        "topology_tiers": {},
    }}
    with open(path, "w") as f:
        _json.dump(doc, f)
    with pytest.raises(ValueError, match="incomparable byte counts"):
        hlo_audit.write_memory_budgets([_mem_report()], path)


def test_gc110_commensurable_memory_tiers_filters_cross_version():
    budgets = {"memory_budgets": {"topology_tiers": {
        "v5e-16": {"jax_version": "X", "arms": {"a": {}}},
        "v5e-64": {"jax_version": "Y", "arms": {"a": {}}},
    }}}
    per_tier, stale = hlo_audit.commensurable_memory_tiers(
        budgets, jax_version="X"
    )
    assert stale == ["v5e-64"]
    assert set(per_tier) == {"v5e-16"}
    # Fresh-audited tiers always stay: their counts ARE current.
    per_tier, stale = hlo_audit.commensurable_memory_tiers(
        budgets, fresh_tiers=("v5e-64",), jax_version="X"
    )
    assert stale == []


def test_cli_memory_audit_single_arm_clean():
    proc = subprocess.run(
        [sys.executable, "-m", f"{PKG}.analysis.static",
         "--memory", "--arms", "ddp-dp8"],
        capture_output=True, text=True, cwd=REPO, timeout=300,
    )
    assert proc.returncode == 0, proc.stderr
    assert "graftcheck memory:" in proc.stderr
    assert "0 finding(s)" in proc.stderr


def test_cli_memory_rejects_unknown_arm():
    proc = subprocess.run(
        [sys.executable, "-m", f"{PKG}.analysis.static",
         "--memory", "--arms", "no-such-arm"],
        capture_output=True, text=True, cwd=REPO, timeout=120,
    )
    assert proc.returncode == 2
    assert "unknown arm" in proc.stderr


def test_verify_offline_runs_memory_audit():
    text = open(os.path.join(REPO, "scripts", "verify_offline.sh")).read()
    assert "--memory" in text and "GC110" in text
