"""graftcheck CLI.

    python -m distributed_llm_training_benchmark_framework_tpu.analysis.static --all

Exit codes: 0 clean, 1 findings (budget deltas / lint violations),
2 operational error (an arm failed to compile, bad usage).

The audit engine is only meaningful under the conditions the budgets were
frozen on — the CPU backend with 8 forced host devices — so this entry
point pins both BEFORE jax initializes a backend, regardless of the
caller's env (the k8s image runs it via scripts/graftcheck.sh; nothing in
the package imports jax before this point). The budgets file records the freeze conditions
and the audit refuses to compare across a jax-version mismatch.
"""

import argparse
import os
import re
import sys


def _force_cpu_audit_env() -> None:
    """CPU backend + exactly 8 virtual host devices, before jax spins up."""
    os.environ["JAX_PLATFORMS"] = "cpu"
    flags = os.environ.get("XLA_FLAGS", "")
    want = "--xla_force_host_platform_device_count=8"
    if "xla_force_host_platform_device_count" in flags:
        flags = re.sub(
            r"--xla_force_host_platform_device_count=\d+", want, flags
        )
    else:
        flags = (flags + " " + want).strip()
    os.environ["XLA_FLAGS"] = flags


def _git_changed_files():
    """Repo-relative paths changed vs the merge-base with the default
    branch, plus staged/unstaged/untracked work. Tuple (possibly empty);
    None only when git itself is unavailable — the caller then falls
    back to a full lint rather than silently passing.
    """
    import subprocess

    from .hlo_audit import REPO_ROOT

    def git(*a):
        try:
            out = subprocess.run(
                ["git", *a], cwd=REPO_ROOT, capture_output=True,
                text=True, timeout=30,
            )
        except Exception:
            return None
        return out.stdout if out.returncode == 0 else None

    if git("rev-parse", "HEAD") is None:
        # No git (or not a repo): the caller must fall back to a FULL
        # lint — an empty changed set here would pass the pre-commit
        # hook without linting anything.
        return None
    # git emits toplevel-relative paths; Violation.path is
    # REPO_ROOT-relative. When this checkout is a SUBDIRECTORY of a
    # larger repo the two bases differ, and comparing them unrebased
    # would scope every finding to nothing — the same silent-pass mode
    # as the no-git case. Rebase (and drop files outside this project).
    toplevel = (git("rev-parse", "--show-toplevel") or "").strip()
    prefix = ""
    if toplevel:
        rel = os.path.relpath(os.path.abspath(REPO_ROOT), toplevel)
        if rel not in (".", ""):
            if rel.startswith(".."):
                return None  # REPO_ROOT outside the repo git sees: full lint
            prefix = rel.replace(os.sep, "/") + "/"

    def rebase(path):
        path = path.replace(os.sep, "/")
        if not prefix:
            return path
        if path.startswith(prefix):
            return path[len(prefix):]
        return None
    base = None
    for ref in ("origin/main", "origin/master", "main", "master"):
        out = git("merge-base", "HEAD", ref)
        if out and out.strip():
            base = out.strip()
            break
    files = set()
    # Committed + working-tree changes vs the merge-base (diff against a
    # commit includes staged AND unstaged edits), plus untracked files.
    # `git diff` paths are toplevel-relative regardless of cwd;
    # `ls-files` paths are cwd-relative, so run everything from
    # REPO_ROOT (the subprocess cwd above) and rebase the diff output.
    if base:
        out = git("diff", "--name-only", base)
    else:
        out = git("diff", "--name-only", "HEAD")
    if out:
        files.update(
            r for l in out.splitlines() if l.strip()
            for r in (rebase(l.strip()),) if r is not None
        )
    out = git("ls-files", "--others", "--exclude-standard")
    if out:
        # cwd-relative (== REPO_ROOT-relative) already.
        files.update(
            l.strip().replace(os.sep, "/")
            for l in out.splitlines() if l.strip()
        )
    return tuple(sorted(files))


def main(argv=None) -> int:
    p = argparse.ArgumentParser(
        prog="python -m distributed_llm_training_benchmark_framework_tpu"
             ".analysis.static",
        description="graftcheck: static collective-budget audit + JAX "
                    "hot-path lint (docs/STATIC_ANALYSIS.md)",
    )
    p.add_argument("--all", action="store_true",
                   help="run both engines over the full arm roster")
    p.add_argument("--audit", action="store_true",
                   help="run the HLO collective-budget auditor")
    p.add_argument("--lint", action="store_true",
                   help="run the AST lint rules")
    p.add_argument("--changed", action="store_true",
                   help="fast pre-commit mode: lint ONLY files changed vs "
                        "the merge-base with the default branch (plus "
                        "staged/unstaged/untracked work) — no audits. "
                        "Rules still read unchanged files for context; "
                        "findings are scoped to the changed set")
    p.add_argument("--memory", action="store_true",
                   help="GC110 memory-budget audit: lower every roster arm "
                        "on the CPU host and verdict its compile-time "
                        "memory accounting (argument/output/temp/alias/"
                        "peak bytes from XLA's memory_analysis) against "
                        "the frozen memory_budgets section, plus the "
                        "cross-tier growth laws (per-chip temps flat "
                        "along the data axis; fsdp/zero argument bytes "
                        "shrinking) over the frozen topology-tier memory "
                        "budgets. With --topology TIERS, the named tiers "
                        "are memory-audited fresh; with --update-budgets, "
                        "freezes the memory_budgets section (only)")
    p.add_argument("--arms", default=None,
                   help="comma-separated arm subset for --audit/--memory/"
                        "--topology (default: the whole roster)")
    p.add_argument("--topology", default=None,
                   help="comma-separated topology tier(s) "
                        "(v5e-16|v5e-64|v5e-256): AOT-compile the scalable "
                        "roster subset against the REAL TPU topology on "
                        "this CPU host and verdict per-tier budgets + "
                        "growth laws (docs/STATIC_ANALYSIS.md). --all "
                        "includes the default tiers "
                        "(v5e-16,v5e-64) when the host's libtpu can build "
                        "compile-only clients")
    p.add_argument("--list-arms", action="store_true",
                   help="print the audit roster and exit")
    p.add_argument("--list-rules", action="store_true",
                   help="print the lint rule catalog and exit")
    p.add_argument("--budgets", default=None,
                   help="budgets file (default: configs/collective_budgets.json)")
    p.add_argument("--update-budgets", action="store_true",
                   help="regenerate the budgets file from fresh audits "
                        "instead of diffing against it")
    p.add_argument("--json", action="store_true",
                   help="emit the audit reports as JSON on stdout")
    p.add_argument("--inject", default=None,
                   choices=["bad-kv-spec", "bad-fsdp-axis", "bad-cmm-ring"],
                   help="self-test: deliberately reintroduce a known-bad "
                        "configuration (bad-kv-spec = the PR 1 GQA kv "
                        "full-replicate fallback; bad-fsdp-axis = the "
                        "pre-round-8 composed dp x tp fsdp placement; "
                        "bad-cmm-ring = the collective-matmul "
                        "ppermute decomposition reverted to bulk "
                        "collectives) — the audit MUST then fail")
    args = p.parse_args(argv)

    if args.changed and (args.all or args.audit or args.topology
                         or args.memory or args.update_budgets):
        p.error("--changed is the fast lint-only pre-commit path; run the "
                "audits separately (--all / --audit / --topology)")

    if args.inject and args.update_budgets:
        # Freezing deliberately-injected-bad counts as the new budget would
        # make the known-bad schedule the audited baseline.
        p.error("--inject is a self-test knob and cannot be combined with "
                "--update-budgets")

    if args.arms and args.topology and args.update_budgets:
        # write_topology_budgets replaces a tier's arms block wholesale;
        # freezing a subset would silently drop the other arms' pins.
        p.error("--arms with --topology --update-budgets would freeze a "
                "partial tier; freeze whole tiers")

    # Static tool: never let it spin up a TPU backend (lint's GC201 imports
    # the harness module, and the audit must match the budgets' freeze
    # conditions), so pin the CPU env before anything queries devices.
    _force_cpu_audit_env()

    from . import hlo_audit, lint

    if args.list_rules:
        for rule in lint.RULES.values():
            print(f"{rule.id}  {rule.name}")
            print(f"       {rule.description}")
            print(f"       fix: {rule.fix_hint}")
        return 0
    if args.list_arms:
        for spec in hlo_audit.ROSTER.values():
            geom = "x".join(map(str, spec.mesh_shape))
            print(f"{spec.name}: {spec.strategy} x {spec.model_family} x "
                  f"mesh {geom} {spec.axes}")
        for spec in hlo_audit.PIPELINE_ROSTER.values():
            geom = "x".join(map(str, spec.mesh_shape))
            print(f"[pipeline] {spec.name}: {spec.pipeline_schedule} "
                  f"(V={spec.virtual_stages}) x {spec.model_family} x "
                  f"mesh {geom} M={spec.grad_accum}")
        for tier in hlo_audit.TOPOLOGY_TIERS.values():
            print(f"[topology] {tier.name}: {tier.topology_name} "
                  f"({tier.device_count} devices; arms "
                  f"{', '.join(hlo_audit.TOPOLOGY_ARMS)})")
        return 0

    topo_tiers = (
        [t.strip() for t in args.topology.split(",") if t.strip()]
        if args.topology else []
    )
    unknown_tiers = [t for t in topo_tiers if t not in hlo_audit.TOPOLOGY_TIERS]
    if unknown_tiers:
        print(f"graftcheck: unknown topology tier(s) {unknown_tiers}; "
              f"tiers: {list(hlo_audit.TOPOLOGY_TIERS)}", file=sys.stderr)
        return 2

    # --topology alone runs only the topology audit; --update-budgets
    # beside it freezes those tiers and NEVER the CPU arm roster — the
    # roster only regenerates when --update-budgets is given with no
    # --topology (or the roster audit is explicitly requested via
    # --all/--audit), so adding a read-only flag like --lint to a
    # topology freeze cannot silently churn the arm budgets.
    # write_budgets carries the other section through untouched.
    # --memory claims --topology for ITSELF (the named tiers are
    # memory-audited); the collective topology audit still runs under
    # --all, or via --topology without --memory. A --memory freeze never
    # regenerates the collective arm budgets (and vice versa).
    do_memory = args.memory
    do_audit = (args.all or args.audit
                or (args.update_budgets and not topo_tiers
                    and not args.memory))
    do_lint = args.all or args.lint or args.changed
    do_topology = (bool(topo_tiers) and not args.memory) or args.all
    if not (do_audit or do_lint or do_topology or do_memory):
        p.error("nothing to do: pass --all, --audit, --lint, --changed, "
                "--memory, --topology or --update-budgets")

    failures = 0

    if do_lint:
        changed_files = None
        if args.changed:
            changed_files = _git_changed_files()
            if changed_files is None:
                # git unavailable: degrade to the FULL lint, visibly —
                # never pass a pre-commit hook by linting nothing.
                print("graftcheck lint: --changed cannot reach git; "
                      "falling back to a FULL lint", file=sys.stderr)
            elif not changed_files:
                print("graftcheck lint: no changed files vs merge-base — "
                      "clean", file=sys.stderr)
                return 0
            else:
                print(f"graftcheck lint: --changed scoping to "
                      f"{len(changed_files)} file(s)", file=sys.stderr)
        violations = lint.run_lint(files=changed_files)
        for v in violations:
            print(str(v), file=sys.stderr)
        n = len(violations)
        print(
            f"graftcheck lint: {n} violation(s) across "
            f"{len(lint.RULES)} rules" if n else
            f"graftcheck lint: clean ({len(lint.RULES)} rules)",
            file=sys.stderr,
        )
        failures += n

    if do_audit:
        budgets_path = args.budgets or hlo_audit.DEFAULT_BUDGETS_PATH
        if args.arms:
            requested = [a.strip() for a in args.arms.split(",") if a.strip()]
            names = [n for n in requested if n in hlo_audit.ROSTER]
            pipe_names = [
                n for n in requested if n in hlo_audit.PIPELINE_ROSTER
            ]
            unknown = [
                n for n in requested
                if n not in hlo_audit.ROSTER
                and n not in hlo_audit.PIPELINE_ROSTER
            ]
            if unknown:
                print(f"graftcheck: unknown arm(s) {unknown}; roster: "
                      f"{list(hlo_audit.ROSTER)} + pipeline roster: "
                      f"{list(hlo_audit.PIPELINE_ROSTER)}", file=sys.stderr)
                return 2
        else:
            names = list(hlo_audit.ROSTER)
            pipe_names = list(hlo_audit.PIPELINE_ROSTER)

        import dataclasses as _dc

        reports = []
        for name in names:
            spec = hlo_audit.ROSTER[name]
            if args.inject:
                spec = _dc.replace(spec, inject=args.inject)
            print(f"graftcheck audit: lowering {name} ...", file=sys.stderr)
            try:
                reports.append(hlo_audit.audit_arm(spec))
            except Exception as e:
                print(f"graftcheck audit: arm {name} failed to compile: "
                      f"{type(e).__name__}: {e}", file=sys.stderr)
                return 2

        pipe_results = []
        for name in pipe_names:
            spec = hlo_audit.PIPELINE_ROSTER[name]
            if args.inject:
                spec = _dc.replace(spec, inject=args.inject)
            m2 = spec.grad_accum * hlo_audit.PIPELINE_GROWTH_M_FACTOR
            print(f"graftcheck audit: lowering {name} (schedule laws, "
                  f"M={spec.grad_accum} and M={m2}) ...", file=sys.stderr)
            # Compile failures become schedule-compiles law findings
            # (exit 1), not operational errors: these arms carry a known
            # compile-failure history and the injection proof reverts
            # exactly that fix.
            pipe_results.append(hlo_audit.audit_pipeline_arm(spec))

        if args.json:
            import json as _json

            doc = {r.arm: r.to_budget_entry() for r in reports}
            doc.update({
                p.arm: (
                    p.to_budget_entry() if p.compile_error is None
                    else {"compile_error": p.compile_error}
                )
                for p in pipe_results
            })
            print(_json.dumps(doc, indent=2, sort_keys=True))

        if args.update_budgets:
            existing = None
            if os.path.exists(budgets_path):
                existing = hlo_audit.load_budgets(budgets_path)
            if reports:
                existing = hlo_audit.write_budgets(
                    reports, budgets_path, existing=existing
                )
                print(f"graftcheck audit: froze {len(reports)} arm "
                      f"budget(s) into {budgets_path}", file=sys.stderr)
            if pipe_results:
                hlo_audit.write_pipeline_budgets(
                    pipe_results, budgets_path, existing=existing
                )
                print(f"graftcheck audit: froze {len(pipe_results)} "
                      f"pipeline_schedules budget(s) into {budgets_path}",
                      file=sys.stderr)
        else:
            if not os.path.exists(budgets_path):
                print(f"graftcheck audit: no budgets file at {budgets_path} "
                      "(run --update-budgets first)", file=sys.stderr)
                return 2
            budgets = hlo_audit.load_budgets(budgets_path)
            import jax

            frozen_on = budgets.get("jax_version")
            if reports and frozen_on is not None and (
                frozen_on != jax.__version__
            ):
                print(
                    f"graftcheck audit: budgets frozen on jax {frozen_on} "
                    f"but running jax {jax.__version__} — counts are not "
                    "comparable; regenerate with --update-budgets",
                    file=sys.stderr,
                )
                return 2
            deltas = []
            for rep in reports:
                deltas.extend(hlo_audit.diff_against_budget(rep, budgets))
            if pipe_results:
                pipe_frozen = budgets.get("pipeline_schedules", {}).get(
                    "jax_version"
                )
                if pipe_frozen is not None and (
                    pipe_frozen != jax.__version__
                ):
                    print(
                        "graftcheck audit: pipeline_schedules budgets "
                        f"frozen on jax {pipe_frozen} but running jax "
                        f"{jax.__version__} — regenerate with "
                        "--update-budgets", file=sys.stderr,
                    )
                    return 2
                for p in pipe_results:
                    deltas.extend(
                        hlo_audit.diff_pipeline_against_budget(p, budgets)
                    )
            for d in deltas:
                print(f"graftcheck audit: {d}", file=sys.stderr)
            print(
                f"graftcheck audit: {len(reports)} arm(s) + "
                f"{len(pipe_results)} pipeline arm(s), "
                f"{len(deltas)} finding(s)", file=sys.stderr,
            )
            failures += len(deltas)

    if do_topology:
        budgets_path = args.budgets or hlo_audit.DEFAULT_BUDGETS_PATH
        tiers = topo_tiers or list(hlo_audit.TOPOLOGY_DEFAULT_TIERS)
        # Subset only an EXPLICIT --topology request: under --all the
        # roster subset in --arms addresses the CPU audit, not the tiers.
        topo_arm_names = None
        if args.arms and topo_tiers:
            requested = [a.strip() for a in args.arms.split(",") if a.strip()]
            unknown = [
                n for n in requested if n not in hlo_audit.TOPOLOGY_ARMS
            ]
            if unknown:
                print(f"graftcheck topology: unknown arm(s) {unknown}; "
                      f"topology roster: {list(hlo_audit.TOPOLOGY_ARMS)}",
                      file=sys.stderr)
                return 2
            topo_arm_names = tuple(requested)
        fresh = {}
        try:
            for tier_name in tiers:
                tier = hlo_audit.TOPOLOGY_TIERS[tier_name]
                n_arms = len(topo_arm_names or hlo_audit.TOPOLOGY_ARMS)
                print(f"graftcheck topology: compiling "
                      f"{n_arms} arm(s) against "
                      f"{tier_name} ({tier.topology_name}, "
                      f"{tier.device_count} devices) ...", file=sys.stderr)
                fresh[tier_name] = hlo_audit.audit_topology_tier(
                    tier, arm_names=topo_arm_names, inject=args.inject
                )
        except hlo_audit.TopologyUnavailable as e:
            if topo_tiers:
                # Explicitly requested: the answer must be loud.
                print(f"graftcheck topology: {e}", file=sys.stderr)
                return 2
            # --all degrades to a visible skip — but findings already
            # computed for earlier tiers must not be discarded with it.
            unaudited = [t for t in tiers if t not in fresh]
            print(f"graftcheck topology: tier(s) {unaudited} SKIPPED "
                  f"under --all ({e})", file=sys.stderr)
        except Exception as e:
            print(f"graftcheck topology: arm failed to compile: "
                  f"{type(e).__name__}: {e}", file=sys.stderr)
            return 2

        if fresh:
            if args.json:
                import json as _json

                print(_json.dumps(
                    {t: {r.arm: r.to_budget_entry() for r in reps}
                     for t, reps in fresh.items()},
                    indent=2, sort_keys=True,
                ))
            if args.update_budgets and topo_tiers:
                doc = hlo_audit.write_topology_budgets(fresh, budgets_path)
                print(f"graftcheck topology: froze {len(fresh)} tier "
                      f"budget(s) into {budgets_path}", file=sys.stderr)
                growth_doc, _stale = hlo_audit.commensurable_topology_tiers(
                    doc, fresh_tiers=tuple(fresh)
                )
                growth = hlo_audit.growth_law_findings(
                    hlo_audit.assemble_per_tier(growth_doc)
                )
                for g in growth:
                    print(f"graftcheck topology: WARNING (frozen anyway): "
                          f"{g}", file=sys.stderr)
            else:
                budgets = (
                    hlo_audit.load_budgets(budgets_path)
                    if os.path.exists(budgets_path) else {}
                )
                import jax

                deltas = []
                for tier_name, reports in fresh.items():
                    frozen_on = budgets.get("topology_tiers", {}).get(
                        tier_name, {}
                    ).get("jax_version")
                    if frozen_on is not None and frozen_on != jax.__version__:
                        print(
                            f"graftcheck topology: {tier_name} budgets "
                            f"frozen on jax {frozen_on} but running jax "
                            f"{jax.__version__} — regenerate with "
                            f"--topology {tier_name} --update-budgets",
                            file=sys.stderr,
                        )
                        return 2
                    deltas.extend(hlo_audit.diff_topology_against_budget(
                        tier_name, reports, budgets
                    ))
                # Growth laws judge the fresh reports overlaid on every
                # OTHER tier's frozen structure, so a one-tier audit still
                # sees the cross-tier shape — but only tiers frozen on
                # THIS jax are commensurable with the fresh counts.
                growth_budgets, stale_tiers = (
                    hlo_audit.commensurable_topology_tiers(
                        budgets, fresh_tiers=tuple(fresh),
                        jax_version=jax.__version__,
                    )
                )
                if stale_tiers:
                    print(
                        "graftcheck topology: growth laws exclude "
                        f"tier(s) {stale_tiers} frozen on a different "
                        "jax — regenerate them with --topology "
                        f"{','.join(stale_tiers)} --update-budgets",
                        file=sys.stderr,
                    )
                deltas.extend(hlo_audit.growth_law_findings(
                    hlo_audit.assemble_per_tier(growth_budgets, fresh)
                ))
                for d in deltas:
                    print(f"graftcheck topology: {d}", file=sys.stderr)
                print(
                    f"graftcheck topology: {len(fresh)} tier(s), "
                    f"{len(deltas)} finding(s)", file=sys.stderr,
                )
                failures += len(deltas)

    if do_memory:
        budgets_path = args.budgets or hlo_audit.DEFAULT_BUDGETS_PATH
        if args.arms:
            mem_names = [a.strip() for a in args.arms.split(",") if a.strip()]
            unknown = [n for n in mem_names if n not in hlo_audit.ROSTER]
            if unknown:
                print(f"graftcheck memory: unknown arm(s) {unknown}; "
                      f"roster: {list(hlo_audit.ROSTER)}", file=sys.stderr)
                return 2
        else:
            mem_names = list(hlo_audit.ROSTER)

        import dataclasses as _dc

        mem_reports = []
        for name in mem_names:
            spec = hlo_audit.ROSTER[name]
            if args.inject:
                spec = _dc.replace(spec, inject=args.inject)
            print(f"graftcheck memory: lowering {name} ...", file=sys.stderr)
            try:
                mem_reports.append(hlo_audit.audit_arm_memory(spec))
            except Exception as e:
                print(f"graftcheck memory: arm {name} failed: "
                      f"{type(e).__name__}: {e}", file=sys.stderr)
                return 2

        fresh_mem_tiers = {}
        if topo_tiers:
            try:
                for tier_name in topo_tiers:
                    tier = hlo_audit.TOPOLOGY_TIERS[tier_name]
                    print(f"graftcheck memory: compiling "
                          f"{len(hlo_audit.TOPOLOGY_ARMS)} arm(s) against "
                          f"{tier_name} ({tier.topology_name}) ...",
                          file=sys.stderr)
                    fresh_mem_tiers[tier_name] = (
                        hlo_audit.audit_topology_tier_memory(
                            tier, inject=args.inject
                        )
                    )
            except hlo_audit.TopologyUnavailable as e:
                # Tiers were explicitly requested with --memory: loud.
                print(f"graftcheck memory: {e}", file=sys.stderr)
                return 2
            except Exception as e:
                print(f"graftcheck memory: tier arm failed: "
                      f"{type(e).__name__}: {e}", file=sys.stderr)
                return 2

        if args.json:
            import json as _json

            doc = {r.arm: r.to_budget_entry() for r in mem_reports}
            doc.update({
                t: {r.arm: r.to_budget_entry() for r in reps}
                for t, reps in fresh_mem_tiers.items()
            })
            print(_json.dumps(doc, indent=2, sort_keys=True))

        if args.update_budgets:
            hlo_audit.write_memory_budgets(
                mem_reports, budgets_path, tier_reports=fresh_mem_tiers,
            )
            print(f"graftcheck memory: froze {len(mem_reports)} arm + "
                  f"{len(fresh_mem_tiers)} tier memory budget(s) into "
                  f"{budgets_path}", file=sys.stderr)
            per_tier, _stale = hlo_audit.commensurable_memory_tiers(
                hlo_audit.load_budgets(budgets_path),
                fresh_tiers=tuple(fresh_mem_tiers),
            )
            for g in hlo_audit.memory_growth_law_findings(per_tier):
                print(f"graftcheck memory: WARNING (frozen anyway): {g}",
                      file=sys.stderr)
        else:
            if not os.path.exists(budgets_path):
                print(f"graftcheck memory: no budgets file at "
                      f"{budgets_path} (run --memory --update-budgets "
                      "first)", file=sys.stderr)
                return 2
            budgets = hlo_audit.load_budgets(budgets_path)
            import jax

            section = budgets.get("memory_budgets", {})
            frozen_on = section.get("jax_version")
            if frozen_on is not None and frozen_on != jax.__version__:
                print(
                    f"graftcheck memory: memory_budgets frozen on jax "
                    f"{frozen_on} but running jax {jax.__version__} — "
                    "byte counts are not comparable; regenerate with "
                    "--memory --update-budgets", file=sys.stderr,
                )
                return 2
            deltas = []
            for rep in mem_reports:
                deltas.extend(
                    hlo_audit.diff_memory_against_budget(rep, budgets)
                )
            per_tier, stale_tiers = hlo_audit.commensurable_memory_tiers(
                budgets, fresh_tiers=tuple(fresh_mem_tiers),
                jax_version=jax.__version__,
            )
            if stale_tiers:
                print(
                    "graftcheck memory: growth laws exclude tier(s) "
                    f"{stale_tiers} frozen on a different jax — "
                    "regenerate with --memory --topology "
                    f"{','.join(stale_tiers)} --update-budgets",
                    file=sys.stderr,
                )
            for tier_name, reps in fresh_mem_tiers.items():
                # Same loud refusal as the collective topology path: a
                # tier frozen on a different jax must not be byte-diffed
                # against fresh counts (commensurable_memory_tiers keeps
                # fresh tiers in the LAW overlay, so the version check
                # has to happen here, before the exact pins).
                tier_frozen = section.get("topology_tiers", {}).get(
                    tier_name, {}
                ).get("jax_version")
                if tier_frozen is not None and tier_frozen != jax.__version__:
                    print(
                        f"graftcheck memory: {tier_name} memory budgets "
                        f"frozen on jax {tier_frozen} but running jax "
                        f"{jax.__version__} — regenerate with --memory "
                        f"--topology {tier_name} --update-budgets",
                        file=sys.stderr,
                    )
                    return 2
                frozen_arms = per_tier.get(tier_name, {})
                for rep in reps:
                    deltas.extend(hlo_audit.diff_memory_against_budget(
                        rep, budgets, arms_override=frozen_arms,
                    ))
                per_tier.setdefault(tier_name, {}).update(
                    {r.arm: r.to_budget_entry() for r in reps}
                )
            deltas.extend(hlo_audit.memory_growth_law_findings(per_tier))
            for d in deltas:
                print(f"graftcheck memory: {d}", file=sys.stderr)
            print(
                f"graftcheck memory: {len(mem_reports)} arm(s) + "
                f"{len(fresh_mem_tiers) or len(per_tier)} tier(s), "
                f"{len(deltas)} finding(s)", file=sys.stderr,
            )
            failures += len(deltas)

    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
