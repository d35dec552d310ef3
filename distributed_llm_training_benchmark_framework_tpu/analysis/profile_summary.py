"""Summarize a ``--profile-dir`` trace: where device time actually goes.

The reference's profiling story is aspirational (its docs *recommend* pynvml
sampling and ``torch.profiler`` as future additions; SURVEY §5.1) — the
harness here already captures real traces (``--profile-dir`` wraps the timed
window in ``jax.profiler``), and this tool closes the loop by reading them
back: per-lane totals (device vs host), an XLA-op *class* breakdown, and the
top individual ops with their HLO provenance. This is exactly the analysis
that produced docs/PERFORMANCE.md §§8-9 (it started as an ad-hoc script;
promoting it makes the workflow reproducible):

    python -u benchmarking/train_harness.py ... --profile-dir /tmp/prof
    python -m distributed_llm_training_benchmark_framework_tpu.analysis.profile_summary \
        --profile-dir /tmp/prof --top 20

Reads the Chrome-trace export (``*.trace.json.gz``) the profiler writes under
``plugins/profile/<run>/``; no TensorBoard or tensorflow dependency.
"""

from __future__ import annotations

import argparse
import collections
import glob
import gzip
import json
import os
import re
import sys
from typing import Dict, List, Optional, Tuple


def list_profile_runs(profile_dir: str) -> List[Tuple[str, str]]:
    """All (run_name, newest trace file) pairs under a profiler directory.

    jax.profiler writes one ``plugins/profile/<run>/`` directory per
    ``start_trace`` call, so a profile dir reused across benchmark arms
    holds several runs. Sorted oldest-first by trace mtime; bare traces at
    the top level (non-standard layouts) appear under run name ``'.'``.
    """
    per_run: Dict[str, str] = {}
    for f in glob.glob(
        os.path.join(profile_dir, "plugins", "profile", "*", "*.trace.json.gz")
    ):
        run = os.path.basename(os.path.dirname(f))
        if run not in per_run or os.path.getmtime(f) > os.path.getmtime(per_run[run]):
            per_run[run] = f
    for f in glob.glob(os.path.join(profile_dir, "*.trace.json.gz")):
        if "." not in per_run or os.path.getmtime(f) > os.path.getmtime(per_run["."]):
            per_run["."] = f
    return sorted(per_run.items(), key=lambda kv: os.path.getmtime(kv[1]))


def find_trace_file(profile_dir: str, run: Optional[str] = None) -> Optional[str]:
    """Chrome-trace file under a jax.profiler output directory.

    With one run present (the common case) its trace is returned. A
    profile dir reused across several runs used to silently yield the
    globally newest trace — an operator summarizing arm A after re-running
    arm B got B's trace under A's name. Now: ``run`` selects by run-dir
    name (exact, then unique substring; ValueError naming the candidates
    otherwise), and with no selector the newest run is still returned but
    the ambiguity is WARNED on stderr with the candidate list.
    """
    runs = list_profile_runs(profile_dir)
    if not runs:
        return None
    if run is not None:
        exact = [f for name, f in runs if name == run]
        if exact:
            return exact[0]
        sub = [(name, f) for name, f in runs if run in name]
        if len(sub) == 1:
            return sub[0][1]
        raise ValueError(
            f"--run {run!r} matches {len(sub)} of the profile runs in "
            f"{profile_dir}; candidates: {[name for name, _ in runs]}"
        )
    if len(runs) > 1:
        print(
            f"WARNING: {profile_dir} holds {len(runs)} profile runs; "
            "summarizing the newest. Pass --run <name> to pick one of: "
            + ", ".join(name for name, _ in runs),
            file=sys.stderr,
        )
    return runs[-1][1]


def load_events(trace_file: str) -> List[dict]:
    with gzip.open(trace_file, "rt") as f:
        return json.load(f).get("traceEvents", [])


def _lane_names(events) -> Tuple[Dict[int, str], Dict[Tuple[int, int], str]]:
    pids: Dict[int, str] = {}
    tids: Dict[Tuple[int, int], str] = {}
    for e in events:
        if e.get("ph") != "M":
            continue
        if e.get("name") == "process_name":
            pids[e["pid"]] = e["args"].get("name", "")
        elif e.get("name") == "thread_name":
            tids[(e["pid"], e["tid"])] = e["args"].get("name", "")
    return pids, tids


def op_class(name: str) -> str:
    """Collapse XLA op names to a class: 'fusion.1234' -> 'fusion',
    'while.35' -> 'while'; the flash kernels, by their own names ('flash_fwd.3',
    'flash_bwd_fused.1') or, where a kernel has none, its jit's
    ('jvp_jit_flash_attention__.3') -> 'flash_kernel'."""
    if any(k in name for k in ("flash_attention", "flash_fwd", "flash_bwd")):
        return "flash_kernel"
    base = re.sub(r"[.\d]+$", "", name)
    return base or name


def summarize(
    events: List[dict], top: int = 15
) -> Dict[str, object]:
    """-> {lanes, op_classes, top_ops, steps} aggregates (durations in us)."""
    pids, tids = _lane_names(events)
    lanes: collections.Counter = collections.Counter()
    classes: collections.Counter = collections.Counter()
    ops: Dict[str, List] = {}
    step_durs: List[float] = []
    for e in events:
        if e.get("ph") != "X" or "dur" not in e:
            continue
        pname = pids.get(e.get("pid"), "")
        lname = tids.get((e.get("pid"), e.get("tid")), "")
        lanes[(pname, lname)] += e["dur"]
        if not pname.startswith("/device:"):
            continue
        if lname == "XLA Ops":
            classes[op_class(e["name"])] += e["dur"]
            rec = ops.setdefault(e["name"], [0, e.get("args", {})])
            rec[0] += e["dur"]
        elif lname == "Steps":
            step_durs.append(e["dur"])
    top_ops = sorted(ops.items(), key=lambda kv: -kv[1][0])[:top]
    return {
        "lanes": lanes,
        "op_classes": classes,
        "top_ops": [
            (name, dur, (args.get("long_name") or args.get("tf_op") or ""))
            for name, (dur, args) in top_ops
        ],
        "step_durs_us": step_durs,
    }


def format_summary(s: Dict[str, object], top: int = 15) -> str:
    out: List[str] = []
    lanes = s["lanes"]
    out.append("== Lanes (total self time) ==")
    for (p, t), dur in lanes.most_common(8):
        out.append(f"  {dur/1e6:9.3f}s  {p} / {t}")
    cls_total = sum(s["op_classes"].values()) or 1
    steps = s["step_durs_us"]
    if steps:
        steps_s = sorted(steps)
        out.append(
            f"\n== Device steps: {len(steps)} traced, "
            f"median {steps_s[len(steps_s)//2]/1e3:.2f} ms, "
            f"max {steps_s[-1]/1e3:.2f} ms =="
        )
    out.append("\n== XLA op classes (device) ==")
    for name, dur in s["op_classes"].most_common(20):
        out.append(f"  {100*dur/cls_total:5.1f}%  {dur/1e6:8.3f}s  {name}")
    out.append(f"\n== Top {top} ops (device) ==")
    for name, dur, prov in s["top_ops"]:
        line = f"  {100*dur/cls_total:5.1f}%  {dur/1e6:8.3f}s  {name[:48]}"
        if prov:
            line += f"\n             {prov[:110]}"
        out.append(line)
    return "\n".join(out)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--profile-dir", required=True,
                   help="the directory passed to the harness's --profile-dir")
    p.add_argument("--top", type=int, default=15,
                   help="individual ops to list with provenance")
    p.add_argument("--run", default=None,
                   help="profile run directory name (or unique substring) "
                        "when --profile-dir holds several runs; default: "
                        "newest, with a warning listing the candidates")
    args = p.parse_args(argv)
    # ERROR lines go to STDERR: a scripted `summary=$(... profile_summary)`
    # capture must see the failure on the terminal (and in the exit code),
    # not swallow it into the captured variable.
    try:
        trace = find_trace_file(args.profile_dir, run=args.run)
    except ValueError as e:
        print(f"ERROR: {e}", file=sys.stderr)
        return 1
    if trace is None:
        print(f"ERROR: no *.trace.json.gz under {args.profile_dir} "
              "(did the run include --profile-dir and >= warmup steps?)",
              file=sys.stderr)
        return 1
    print(f"Trace: {trace}")
    print(format_summary(summarize(load_events(trace), args.top), args.top))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
