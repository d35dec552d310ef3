"""graftcheck — static analysis over the framework's compiled and source artifacts.

Two engines, one CLI (``python -m
distributed_llm_training_benchmark_framework_tpu.analysis.static``):

- ``hlo_audit``: lowers every (strategy x model-family x mesh-geometry) arm
  of the audit roster on CPU — abstract avals, no allocation — and diffs the
  compiled module's collective schedule (all-gather / reduce-scatter /
  all-reduce / collective-permute / all-to-all counts, donation coverage,
  bf16->f32 promotions, full-replication reshard suspects) against the
  frozen per-arm budgets in ``configs/collective_budgets.json``.
- ``lint``: repo-specific AST rules over the package source (jit donation
  discipline, host syncs in the timed loop, unknown mesh axes in sharding
  constraints, wall-clock calls under jit, entrypoint<->harness flag drift),
  each with an id, a fix hint, and ``# graftcheck: disable=RULE``
  suppression.

Both run as a CLI (``scripts/graftcheck.sh``; ``scripts/run_all_benchmarks.sh``
calls it before a suite) and as the tier-1 module
``tests/test_graftcheck.py`` — never on the measured path of ``bench.py``.
Docs: ``docs/STATIC_ANALYSIS.md``.
"""

from .hlo_audit import (  # noqa: F401
    ArmSpec,
    ArmReport,
    ROSTER,
    audit_arm,
    diff_against_budget,
    load_budgets,
    write_budgets,
    DEFAULT_BUDGETS_PATH,
)
from .lint import RULES, Violation, run_lint  # noqa: F401

__all__ = [
    "ArmSpec",
    "ArmReport",
    "ROSTER",
    "audit_arm",
    "diff_against_budget",
    "load_budgets",
    "write_budgets",
    "DEFAULT_BUDGETS_PATH",
    "RULES",
    "Violation",
    "run_lint",
]
