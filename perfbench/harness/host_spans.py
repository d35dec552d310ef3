"""The host's side of a run, read from the program's own record.

The program (``utils/scopes.py``) keeps five host spans (``init_params``,
``init_opt_state``, ``step_lower``, ``step_compile``, one ``step_dispatch`` a
step), jax's compile and cache events and the collector's pauses in the
process, on ``time.perf_counter_ns``, which is the clock of ``run.py``'s
``PROCESS_START``. The readers run in the cell's own process after the traced
window, so this module imports the record, cuts it by the cell's own
``warmup_steps``, ``sync_every`` and ``traced_steps`` and reduces each part:

* set-up: everything before the first timed ``step_dispatch``, by phase;
* the stretch: the untraced timed steps (the ones ``tokens_per_s_per_chip``
  comes from), as sync windows cut at every ``sync_every``-th
  ``step_dispatch`` start. A window is its dispatches and, after the last of
  them, the host's wait for the losses; the stretch's last window has no next
  start to end it and is left out;
* the traced steps: each record against its ``TraceAnnotation`` in the newest
  ``.xplane.pb`` (``trace_reduce.load`` drops those names), which checks that
  the record's clock and the profiler's are one clock.

``metric`` is what the ten metric files call; the first call makes the summary
and prints one ``perfbench: host:`` line. On a program without the record (a parent commit)
it is None, and every one of the ten reads None.
"""

import glob
import os
import statistics
import sys

from . import step_loop, trace_reduce

NS = 1e-9


def program():
    """The program's ``utils/scopes`` where it keeps a host record, else None."""
    try:
        from distributed_llm_training_benchmark_framework_tpu.utils import scopes
    except ImportError:
        return None
    return scopes if hasattr(scopes, "host_records") else None


def process_start_ns():
    """``run.py``'s ``PROCESS_START`` (``perf_counter`` seconds) in ns, or None."""
    for module in ("__main__", "perfbench.run"):
        start = getattr(sys.modules.get(module), "PROCESS_START", None)
        if start is not None:
            return int(start * 1e9)
    return None


def cut(records, warmup_steps, sync_every, traced_steps):
    """-> (set-up records, stretch, traced): the ``step_dispatch`` records from
    the last ``step_compile`` on are the warm-up's windows, the stretch and the
    ``traced_steps`` last; the set-up's are everything before the stretch.
    None where the record does not hold such a run."""
    compiles = [i for i, r in enumerate(records) if r[0] == "step_compile"]
    if not compiles:
        return None
    steps = [r for r in records[compiles[-1]:] if r[0] == "step_dispatch"]
    warm = max(1, -(-warmup_steps // sync_every)) * sync_every
    stretch, traced = steps[warm:len(steps) - traced_steps], steps[len(steps) - traced_steps:]
    if warm + traced_steps > len(steps) or len(stretch) < 2 * sync_every:
        return None
    return [r for r in records if r[2] <= stretch[0][1]], stretch, traced


def windows(steps, sync_every):
    """The sync windows of consecutive ``step_dispatch`` records, all but the
    last: {start, end (the next window's first start), dispatches, wait_ns
    (last dispatch's end to the next start: the host waits for the losses)}."""
    out = []
    for i in range(0, len(steps) - sync_every, sync_every):
        mine, following = steps[i:i + sync_every], steps[i + sync_every]
        out.append({"start": mine[0][1], "end": following[1], "dispatches": mine,
                    "wait_ns": following[1] - mine[-1][2]})
    return out


def inside(records, start, end):
    """Records (any tuple whose [1], [2] are start and end) that overlap."""
    return [r for r in records if r[2] > start and r[1] < end]


def split(window, median_wait_ns, collections, backend_compiles):
    """Where one window's time went, ms: its longest dispatch, its wait over the
    median wait, the longest collection and the compilations inside it."""
    pauses = inside(collections, window["start"], window["end"])
    return {
        "window_ms": 1e-6 * (window["end"] - window["start"]),
        "dispatch_max_ms": 1e-6 * max(r[2] - r[1] for r in window["dispatches"]),
        "wait_excess_ms": 1e-6 * (window["wait_ns"] - median_wait_ns),
        "gc_max_ms": 1e-6 * max((r[2] - r[1] for r in pauses), default=0),
        "gc_count": len(pauses),
        "compiles": [name for name, ended, _ in backend_compiles
                     if window["start"] < ended <= window["end"]],
    }


def stretch_metrics(stretch, sync_every, collections, backend_compiles):
    """The five ``timed loop`` metrics and the split of the longest window."""
    dispatch = [r[2] - r[1] for r in stretch]
    wins = windows(stretch, sync_every)
    lengths = [w["end"] - w["start"] for w in wins]
    median_wait = statistics.median(w["wait_ns"] for w in wins)
    pauses = inside(collections, wins[0]["start"], wins[-1]["end"])
    longest = max(wins, key=lambda w: w["end"] - w["start"])
    return {
        "step_dispatch_ms": 1e-6 * statistics.median(dispatch),
        "dispatch_max_ms": 1e-6 * max(dispatch),
        "window_max_over_p50": max(lengths) / statistics.median(lengths),
        "sync_wait_excess_max_ms": 1e-6 * (max(w["wait_ns"] for w in wins) - median_wait),
        "gc_pause_max_ms": 1e-6 * max((r[2] - r[1] for r in pauses), default=0),
        "longest_window": split(longest, median_wait, collections, backend_compiles),
        "windows": len(wins),
        # the device idles through a window's first dispatch and no other
        "first_dispatch_ms": 1e-6 * statistics.median(
            w["dispatches"][0][2] - w["dispatches"][0][1] for w in wins),
        "wait_p50_ms": 1e-6 * median_wait,
    }


def setup_phases(start_ns, imported_ns, setup_records, first_timed_ns):
    """[(phase, start, end)] from ``start_ns`` to the first timed step, in
    order. A span is named after itself; the stretch before a span after what
    runs there, in brackets (no span of the program covers it): the program's
    import and the builders before ``init_params``, the correctness check
    before ``step_lower``. What lies between the others is left unnamed."""
    spans = {r[0]: r for r in setup_records if r[0] != "step_dispatch"}
    warm = [r for r in setup_records if r[0] == "step_dispatch"
            and r[1] >= spans["step_compile"][2]]
    phases = [] if start_ns is None else [("before_program", start_ns, imported_ns)]
    at = imported_ns
    before = {"init_params": "(build)", "step_lower": "(check)"}
    for name in ("init_params", "init_opt_state", "step_lower", "step_compile"):
        if name in before:
            phases.append((before[name], at, spans[name][1]))
        phases.append((name, spans[name][1], spans[name][2]))
        at = spans[name][2]
    phases.append(("warmup", warm[0][1], first_timed_ns))
    return [p for p in phases if p[2] > p[1]]


def busy_inside(busy, start, end):
    """Seconds of the disjoint ``busy`` intervals that lie in [start, end)."""
    return NS * sum(max(0, min(b, end) - max(a, start)) for a, b in busy)


def annotations(cell, names):
    """{name: [(start_ns, end_ns)]} of the host plane's events with one of
    ``names`` in the newest profile of ``cell``, by start; {} without one."""
    from jax.profiler import ProfileData

    files = glob.glob(os.path.join(step_loop.TRACE_DIR, cell, "plugins/profile/*/*.xplane.pb"))
    if not files:
        return {}
    found = {}
    for plane in ProfileData.from_file(max(files)).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name in names:
                    found.setdefault(e.name, []).append((e.start_ns, e.start_ns + e.duration_ns))
    return {name: sorted(spans) for name, spans in found.items()}


def clock_check(traced, wall_ns, profile_spans):
    """The traced ``step_dispatch`` records against their annotations: the one
    constant offset (the median of start differences, s) and what is left after
    it, us. None where the profile holds no such events."""
    if len(profile_spans) != len(traced) or not traced:
        return None
    starts = [wall_ns(r[1]) - span[0] for r, span in zip(traced, profile_spans)]
    lengths = [(r[2] - r[1]) - (span[1] - span[0]) for r, span in zip(traced, profile_spans)]
    offset = statistics.median(starts)
    return {"matched": len(traced), "offset_s": NS * offset,
            "start_residual_max_us": 1e-3 * max(abs(d - offset) for d in starts),
            "length_difference_max_us": 1e-3 * max(abs(d) for d in lengths)}


def traced_gaps(trace, profile_spans):
    """The first chip's idle gaps inside the traced window (``trace_reduce``'s
    window and busy intervals), seconds by the program's span that was open in
    the middle of the gap: [[name, seconds]], longest first; None where the
    trace holds no device."""
    if trace is None or not trace.devices():
        return None
    gaps = {}
    idle = trace_reduce.subtract(
        [trace_reduce.window(trace)], trace_reduce.busy_intervals(trace, trace.devices()[0]))
    for start, end in idle:
        mid = 0.5e9 * (start + end)
        name = next((n for n, spans in profile_spans.items()
                     if any(a <= mid <= b for a, b in spans)), "no_program_span")
        gaps[name] = gaps.get(name, 0.0) + end - start
    return sorted(([n, seconds] for n, seconds in gaps.items()), key=lambda g: -g[1])


def summarize(trace, run):
    """The ten metrics and the printed line's parts for one traced run, from
    the program's record; None where the program keeps none."""
    scopes = program()
    if scopes is None:
        return None
    workload = run["workload"]
    parts = cut(scopes.host_records(), workload["warmup_steps"], workload["sync_every"],
                run["traced_steps"])
    if parts is None:
        return None
    setup_records, stretch, traced = parts
    events = scopes.compile_events()
    first_timed = stretch[0][1]
    out = stretch_metrics(stretch, workload["sync_every"], scopes.host_records(scopes.GC),
                          events["backend_compiles"])
    start = process_start_ns()
    imported = scopes.IMPORTED_AT[0]
    phases = setup_phases(start, imported, setup_records, first_timed)
    seconds = {name: NS * (b - a) for name, a, b in phases}
    out.update(
        before_program_s=seconds.get("before_program"),
        step_lower_s=seconds["step_lower"],
        step_compile_s=seconds["step_compile"],
        setup_jit_s=busy_inside(events["busy"], imported, first_timed),
        setup_cache_misses=sum(1 for at in events["cache_misses"] if at < first_timed),
    )
    total = NS * (first_timed - (imported if start is None else start))
    out["setup"] = {
        "total_s": total,
        "phases": [(name, seconds[name], busy_inside(events["busy"], a, b))
                   for name, a, b in phases],
        "unnamed_s": total - sum(seconds.values()),
        "cache_hits": events["cache_hits"],
    }
    # a function's seconds hold those of the functions traced inside it
    by_function = {}
    for (event, function), (count, seconds) in events["sums"].items():
        by_function.setdefault(function, {})[event.rsplit("/", 1)[-1]] = (count, seconds)
    out["jit_by_function"] = sorted(
        by_function.items(), key=lambda kv: -sum(s for _, s in kv[1].values()))[:6]
    cell = f"{workload['config']}.{workload['traffic']}"
    profile = annotations(cell, scopes.HOST_SPANS)
    out["clock"] = clock_check(traced, scopes.wall_ns, profile.get("step_dispatch", []))
    out["traced_gaps"] = traced_gaps(trace, profile)
    out["traced_dispatch_ms"] = 1e-6 * statistics.median(r[2] - r[1] for r in traced)
    return out


def line(s):
    """The one ``perfbench: host:`` line."""
    setup = s["setup"]
    phases = ", ".join(f"{name} {seconds:.2f} (jit {jit:.2f})" if jit >= 0.005
                       else f"{name} {seconds:.2f}" for name, seconds, jit in setup["phases"])
    functions = "; ".join(
        f"{function}: " + ", ".join(f"{event} {seconds:.2f} s x{count}"
                                    for event, (count, seconds) in sorted(events.items()))
        for function, events in s["jit_by_function"])
    longest = s["longest_window"]
    clock = s["clock"]
    checked = ("no step_dispatch annotation in the profile" if clock is None else
               f"{clock['matched']} records against their annotations: offset "
               f"{clock['offset_s']:.3f} s, start residual {clock['start_residual_max_us']:.1f} "
               f"us, length difference {clock['length_difference_max_us']:.1f} us")
    return (
        f"perfbench: host: set-up {setup['total_s']:.2f} s = {phases}; unnamed "
        f"{setup['unnamed_s']:.2f} s; jit seconds in set-up {s['setup_jit_s']:.2f}, cache hits "
        f"{setup['cache_hits']}, misses {s['setup_cache_misses']}; by function: {functions}. "
        f"Stretch of {s['windows']} windows: dispatch p50 {s['step_dispatch_ms']:.3f} ms (a window's first "
        f"{s['first_dispatch_ms']:.3f}), max {s['dispatch_max_ms']:.3f}, wait p50 "
        f"{s['wait_p50_ms']:.3f}; longest window {longest['window_ms']:.2f} ms = "
        f"{s['window_max_over_p50']:.4f} x p50: longest dispatch {longest['dispatch_max_ms']:.3f} "
        f"ms, wait over the median wait {longest['wait_excess_ms']:+.3f} ms, {longest['gc_count']} "
        f"collections (longest {longest['gc_max_ms']:.3f} ms), compilations "
        f"{longest['compiles']}; longest wait excess {s['sync_wait_excess_max_ms']:.3f} ms, "
        f"longest collection {s['gc_pause_max_ms']:.3f} ms. Traced steps: dispatch p50 "
        f"{s['traced_dispatch_ms']:.3f} ms; the first chip's idle gaps by the program's span, "
        f"s: {s['traced_gaps']}; {checked}"
    )


def metric(name, trace, run):
    """One of the ten for a metric file. The summary is made once a run, kept
    in ``run`` and printed when it is made."""
    if "host_spans" not in run:
        run["host_spans"] = summarize(trace, run)
        if run["host_spans"] is not None:
            print(line(run["host_spans"]), flush=True)
    return None if run["host_spans"] is None else run["host_spans"].get(name)
