#!/usr/bin/env python3
"""Run a cell's step for many sync windows and say where each long one went.

    python3 perfbench/tools/catch_stall.py <cell> [--windows N] [--profile] [--seed n] [--allow-cpu]

A builder's instrument for the stall (PERF.md section 7), not a cell: it
builds the cell's state and step as the cell's driver does (no correctness
check), warms up, runs N sync windows through ``step_loop.steps`` (in pieces
of 128, of which 127 are measured) and prints, for every window over 1.05 x
the median, its split from the program's own
record (``utils/scopes.host_records()`` through ``harness/host_spans``): the
longest ``step_dispatch`` in it, its wait for the losses over the median wait,
the collector's pauses and the compilations inside it.

``--profile`` runs the windows in pieces of 8 under ``jax.profiler`` and keeps
a piece's trace (under ``perfbench/.trace/<cell>.stall/``) only if one of its
windows was long, and prints beside such a window's split what the chip did in
it (``device_side``: the step's runs on the first chip's ``XLA Modules`` line,
put on the record's clock through the window's own ``step_dispatch``
annotation, and when the TPU runtime's thread noticed each run's end), so that
a caught stall is the device's or the host's. Stopping a
trace takes seconds and a piece's last window is not measured, so a profiled
run is slower and sees 7 windows of 8.
"""

import argparse
import glob
import json
import os
import shutil
import statistics
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

LONG = 1.05  # a window over this many medians is reported
PIECE = 128  # windows run before the record is read: 640 of its 4,096 spans
PROFILED_PIECE = 8  # windows to a profile


def long_windows(wins, collections, backend_compiles, long=LONG):
    """-> (every window's ms, the median, [(index, split)] of the long ones) for
    ``host_spans.windows``' windows."""
    from perfbench.harness import host_spans

    lengths = [1e-6 * (w["end"] - w["start"]) for w in wins]
    median = statistics.median(lengths)
    median_wait = statistics.median(w["wait_ns"] for w in wins)
    found = [(i, host_spans.split(w, median_wait, collections, backend_compiles))
             for i, (w, ms) in enumerate(zip(wins, lengths)) if ms > long * median]
    return lengths, median, found


DONE_EVENT = "tpu::System::Execute=>Done"  # the TPU runtime's thread has seen a run end


def profile_lines(piece_dir):
    """-> (the first chip's runs of the step on its ``XLA Modules`` line as
    (start_ns, end_ns), {step number: start_ns} of the ``step_dispatch``
    annotations, when the runtime noticed a run's end (``DONE_EVENT`` on a
    host thread), sorted), all on the profile's clock."""
    from jax.profiler import ProfileData

    modules, dispatches, noticed = [], {}, []
    path = max(glob.glob(os.path.join(piece_dir, "plugins/profile/*/*.xplane.pb")))
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            if plane.name == "/device:TPU:0" and line.name == "XLA Modules":
                modules = sorted((e.start_ns, e.start_ns + e.duration_ns) for e in line.events)
            elif plane.name.startswith("/host:"):
                for e in line.events:
                    if e.name == "step_dispatch":
                        dispatches[dict(e.stats).get("step")] = e.start_ns
                    elif e.name == DONE_EVENT:
                        noticed.append(e.start_ns)
    return modules, dispatches, sorted(noticed)


def device_side(window, modules, offset_ns, noticed=()):
    """What the chip did in one window, ms, from its runs of the step
    (``modules``, on a clock ``offset_ns`` behind the window's): how many runs,
    their median and longest, the longest gap between two of them, how long
    after the window opened the first began, from the end of the last to the
    end of the window (the losses' way to the host and the runner's own code),
    and the longest it took the runtime to notice a run's end (the first
    ``noticed`` time after it). A long run or gap is the device's stall; a
    long tail is the host's, and a late notice says whose. None where the
    profile holds no run inside the window."""
    start, end = window["start"] - offset_ns, window["end"] - offset_ns
    longest = max((b - a for a, b in modules), default=0)
    runs = [(a, b) for a, b in modules if b > start and a < end and b - a > 0.5 * longest]
    if not runs:
        return None
    late = [min((t - b for t in noticed if t >= b), default=None) for _, b in runs]
    return {
        "steps": len(runs),
        "step_ms_p50": 1e-6 * statistics.median(b - a for a, b in runs),
        "step_ms_max": 1e-6 * max(b - a for a, b in runs),
        "gap_ms_max": 1e-6 * max((a1 - b0 for (_, b0), (a1, _) in zip(runs, runs[1:])), default=0),
        "first_start_ms": 1e-6 * (runs[0][0] - start),
        "after_last_ms": 1e-6 * (end - runs[-1][1]),
        "noticed_late_ms_max": None if None in late or not late else 1e-6 * max(late),
    }


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("cell")
    parser.add_argument("--windows", type=int, default=300)
    parser.add_argument("--profile", action="store_true")
    parser.add_argument("--seed", type=int, default=2147483777)
    parser.add_argument("--allow-cpu", action="store_true",
                        help="dry run on the CPU at tiny sizes: control flow only")
    args = parser.parse_args(argv)

    from perfbench.harness import build, manifest

    entry, workload, config = manifest.load_cell(args.cell)

    import jax

    # the same cache, generator and refusal as run.py
    if args.allow_cpu:
        jax.config.update("jax_enable_compilation_cache", False)
    elif not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(manifest.BENCH_DIR, ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_default_prng_impl", "rbg")
    devices = jax.devices()
    platform, chips = devices[0].platform, workload["chips"]
    if args.allow_cpu and platform == "cpu" and len(devices) >= chips:
        devices = devices[:chips]
        workload, config = build.tiny(workload, config)
    elif platform != "tpu" or len(devices) != chips:
        print(f"catch_stall: {args.cell} needs {chips} TPU chip(s); jax came up on "
              f"{len(devices)} x {platform!r}", file=sys.stderr)
        return 3

    from perfbench.harness import host_spans, step_loop

    scopes = host_spans.program()
    if scopes is None:
        print("catch_stall: this program keeps no host record (utils/scopes.host_records)",
              file=sys.stderr)
        return 3
    driver = workload.get("driver", "perfbench.harness.step_loop:run")
    if platform != "tpu":  # each driver's own cut for a dry run
        if driver == "perfbench.harness.moe_loop:run":
            config = {**config, "num_experts": 8, "num_experts_per_tok": 2}
        elif driver == "perfbench.harness.mla_loop:run":
            from perfbench.harness import build_mla

            config = build_mla.tiny_mla(config)
    state, table, _ = build.build_state(workload, config, devices, args.seed)
    held = None
    if driver == "perfbench.harness.mla_loop:run":
        from perfbench.harness import mla_loop

        held = mla_loop.HeldCounter(state)  # the step's fourth output, as the driver keeps it
    sync_every = workload["sync_every"]
    state.aot_compile(state.params, state.opt_state, table, 0)
    warm = max(1, -(-workload["warmup_steps"] // sync_every))
    _, _, step = step_loop.steps(state, table, 0, sync_every, windows=warm)

    # The windows run in pieces, the record read after each (it is bounded). A
    # window ends at the next one's first dispatch, so each piece gives one window
    # fewer than it ran: the last one's end is not in the record (and, profiled,
    # would hold the seconds the profiler takes to stop).
    trace_dir = os.path.join(step_loop.TRACE_DIR, f"{entry['name']}.stall")
    shutil.rmtree(trace_dir, ignore_errors=True)
    wins, kept, device, done = [], [], {}, 0
    while done < args.windows:
        piece = min(PROFILED_PIECE if args.profile else PIECE, args.windows - done)
        piece_dir = os.path.join(trace_dir, f"window{done}")
        if args.profile:
            jax.profiler.start_trace(piece_dir)
        try:
            _, _, after = step_loop.steps(state, table, step, sync_every, windows=piece)
        finally:
            if args.profile:
                jax.profiler.stop_trace()
        if held is not None:
            held.drain()
        mine = [r for r in scopes.host_records("step_dispatch")
                if step <= r[3]["step"] < after]  # the newest, where the record was full
        before = len(wins)
        wins += host_spans.windows(mine, sync_every)
        step, done = after, done + piece
        if args.profile:
            _, _, found = long_windows(wins, [], [])
            if any(i >= before for i, _ in found):
                kept.append(piece_dir)
                modules, dispatches, noticed = profile_lines(piece_dir)
                for i, _ in found:
                    first = wins[i]["dispatches"][0]
                    if i >= before and first[3]["step"] in dispatches:
                        offset = scopes.wall_ns(first[1]) - dispatches[first[3]["step"]]
                        device[i] = device_side(
                            {k: scopes.wall_ns(wins[i][k]) for k in ("start", "end")},
                            modules, offset, noticed)
                        print(f"catch_stall: kept {piece_dir}: window {i} took "
                              f"{1e-6 * (wins[i]['end'] - wins[i]['start']):.2f} ms; the chip "
                              f"in it: {json.dumps(device[i])}", flush=True)
            else:
                shutil.rmtree(piece_dir, ignore_errors=True)

    lengths, median, found = long_windows(
        wins, scopes.host_records(scopes.GC), scopes.compile_events()["backend_compiles"])
    lengths.sort()
    print(f"catch_stall: {entry['name']}: {len(lengths)} windows of {sync_every} steps"
          f"{' in profiled pieces of ' + str(PROFILED_PIECE) if args.profile else ''}; ms a window: "
          f"least {lengths[0]:.2f}, median {median:.2f}, p99 "
          f"{lengths[int(0.99 * (len(lengths) - 1))]:.2f}, longest {lengths[-1]:.2f}; "
          f"{len(found)} over {LONG} x the median; collections in all: "
          f"{len(scopes.host_records(scopes.GC))}; profiles kept: {kept}", flush=True)
    for i, parts in found:
        print(f"catch_stall: window {i}: {json.dumps(parts)}"
              + (f"; the chip in it: {json.dumps(device[i])}" if i in device else ""), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
