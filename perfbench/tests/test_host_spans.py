"""The host-side reader (``harness/host_spans.py``) and its ten metric files.

The cuts and the reductions on made-up records (ns; one ``step_dispatch`` a
step) with one long dispatch, one long wait and one collection planted, each
of which has to land in its own metric and leave the other two still; the
same on real records of a tiny step with a ``time.sleep`` planted between two
windows and a forced collection; the metric files through the manifest; and
two cells' dry runs, which have to print the ``perfbench: host:`` line.
"""

import gc
import json
import os
import subprocess
import sys
import time

import pytest

from perfbench.harness import host_spans, manifest
from perfbench.tools import catch_stall

TEN = ("before_program_s", "step_lower_s", "step_compile_s", "setup_jit_s",
       "setup_cache_misses", "step_dispatch_ms", "dispatch_max_ms", "window_max_over_p50",
       "sync_wait_excess_max_ms", "gc_pause_max_ms")
MS = 1_000_000
STEP, DISPATCH, SYNC = 100 * MS, 1 * MS, 5  # a device step, a dispatch, steps a window


def made_up(windows, long_dispatch=None, long_wait=None, start=0, first_step=0):
    """``step_dispatch`` records of a device-bound loop: a window is 5 dispatches
    of 1 ms back to back, then the host waits until the window's 5 steps of 100
    ms are done. ``long_dispatch`` = (window, ms) makes that window's third
    dispatch longer, ``long_wait`` = (window, ms) its wait."""
    records, at = [], start
    for w in range(windows):
        opened = at
        for i in range(SYNC):
            length = DISPATCH
            if long_dispatch and long_dispatch[0] == w and i == 2:
                length += long_dispatch[1] * MS
            records.append(("step_dispatch", at, at + length, {"step": first_step + len(records)}))
            at += length
        at = max(at, opened + SYNC * STEP)
        if long_wait and long_wait[0] == w:
            at += long_wait[1] * MS
    return records


def whole_run(**planted):
    """Set-up's spans, one warm-up window, ten windows of stretch, five traced steps."""
    setup = [("init_params", 10 * MS, 400 * MS, {}), ("init_opt_state", 401 * MS, 420 * MS, {}),
             ("step_lower", 5000 * MS, 6500 * MS, {}), ("step_compile", 6500 * MS, 6900 * MS, {})]
    steps = made_up(12, start=7000 * MS, **planted)
    return setup + steps


def test_cut_is_by_the_cells_own_numbers():
    records = whole_run()
    setup, stretch, traced = host_spans.cut(records, warmup_steps=5, sync_every=SYNC, traced_steps=5)
    assert [r[3]["step"] for r in stretch] == list(range(5, 55))
    assert [r[3]["step"] for r in traced] == list(range(55, 60))
    assert [r[0] for r in setup] == ["init_params", "init_opt_state", "step_lower",
                                     "step_compile"] + ["step_dispatch"] * 5
    # warm-up rounds up to whole windows, as the drivers' does
    _, stretch, _ = host_spans.cut(records, warmup_steps=7, sync_every=SYNC, traced_steps=5)
    assert stretch[0][3]["step"] == 10
    # an earlier step (a calibration's, say) before the last compile is not counted
    early = [("step_compile", 1 * MS, 2 * MS, {}), ("step_dispatch", 3 * MS, 4 * MS, {"step": 0})]
    _, stretch, _ = host_spans.cut(early + records, 5, SYNC, 5)
    assert stretch[0][3]["step"] == 5
    assert host_spans.cut(records[:12], 5, SYNC, 5) is None  # no stretch in it
    assert host_spans.cut(records[4:], 5, SYNC, 5) is None  # no compile in it


def test_windows_are_cut_at_every_sync_everyth_start():
    wins = host_spans.windows(made_up(4), SYNC)
    assert len(wins) == 3  # the last has no next start to end it
    assert [w["end"] - w["start"] for w in wins] == [SYNC * STEP] * 3
    assert [w["wait_ns"] for w in wins] == [SYNC * STEP - SYNC * DISPATCH] * 3


PLANTED = {
    "dispatch": dict(long_dispatch=(6, 300)),
    "wait": dict(long_wait=(6, 300)),
    "gc": {},
}


@pytest.mark.parametrize("planted", list(PLANTED))
def test_each_planted_stall_lands_in_its_own_metric(planted):
    records = whole_run(**PLANTED[planted])
    _, stretch, _ = host_spans.cut(records, 5, SYNC, 5)
    # a collection of 40 ms while the host waits in the stretch's third window
    at = stretch[2 * SYNC + 4][2] + 50 * MS
    collections = [("gc", at, at + 40 * MS, {"generation": 2})] if planted == "gc" else []
    m = host_spans.stretch_metrics(stretch, SYNC, collections, [])
    assert m["windows"] == 9
    assert m["step_dispatch_ms"] == pytest.approx(1.0)
    if planted == "dispatch":
        # the dispatch is long; the device is no later, so the wait shrinks by
        # as much and the window keeps its length (back-pressure looks so)
        assert m["dispatch_max_ms"] == pytest.approx(301.0)
        assert m["window_max_over_p50"] == pytest.approx(1.0)
        assert m["sync_wait_excess_max_ms"] == pytest.approx(0.0)
        assert m["gc_pause_max_ms"] == 0
    elif planted == "wait":
        assert m["sync_wait_excess_max_ms"] == pytest.approx(300.0)
        assert m["window_max_over_p50"] == pytest.approx(1.6)
        assert m["dispatch_max_ms"] == pytest.approx(1.0)
        assert m["gc_pause_max_ms"] == 0
        assert m["longest_window"]["wait_excess_ms"] == pytest.approx(300.0)
    else:
        assert m["gc_pause_max_ms"] == pytest.approx(40.0)
        assert m["dispatch_max_ms"] == pytest.approx(1.0)
        assert m["sync_wait_excess_max_ms"] == pytest.approx(0.0)
        assert m["window_max_over_p50"] == pytest.approx(1.0)


def test_a_long_window_is_split_by_what_was_inside_it():
    steps = made_up(10, long_wait=(4, 300))
    stall = steps[4 * SYNC][1]
    collections = [("gc", stall + 10 * MS, stall + 12 * MS, {"generation": 0}),
                   ("gc", 1, 2, {"generation": 0})]
    compiles = [("jit(other)", stall + 20 * MS, 0.5), ("jit(train_step)", 5, 3.0)]
    lengths, median, found = catch_stall.long_windows(
        host_spans.windows(steps, SYNC), collections, compiles)
    assert len(lengths) == 9 and median == pytest.approx(500.0)
    ((index, parts),) = found
    assert index == 4 and parts["window_ms"] == pytest.approx(800.0)
    assert parts["wait_excess_ms"] == pytest.approx(300.0)
    assert parts["dispatch_max_ms"] == pytest.approx(1.0)
    assert parts["gc_count"] == 1 and parts["gc_max_ms"] == pytest.approx(2.0)
    assert parts["compiles"] == ["jit(other)"]


def test_the_chips_side_of_a_window_tells_a_late_device_from_a_late_host():
    window = {"start": 1_000 * MS, "end": 1_800 * MS}  # 800 ms for 5 steps of 100
    offset = 990 * MS  # the profile's clock is this far behind
    on_time = [(12 * MS + i * 100 * MS, 112 * MS + i * 100 * MS) for i in range(5)]
    short = [(2 * MS, 3 * MS)]  # another program's run is not a step
    # the runtime noticed the first three ends at once, the other two 290 ms late
    noticed = [b + MS // 2 for _, b in on_time[:3]] + [802 * MS, 802 * MS + 1]
    late_host = catch_stall.device_side(window, short + on_time, offset, noticed)
    assert late_host["steps"] == 5 and late_host["step_ms_max"] == pytest.approx(100.0)
    assert late_host["noticed_late_ms_max"] == pytest.approx(390.0)  # the fourth: 412 -> 802
    assert late_host["gap_ms_max"] == pytest.approx(0.0)
    assert late_host["first_start_ms"] == pytest.approx(2.0)
    assert late_host["after_last_ms"] == pytest.approx(298.0)  # the chip was done on time
    late_chip = on_time[:3] + [(a + 300 * MS, b + 300 * MS) for a, b in on_time[3:]]
    late_chip = catch_stall.device_side(window, late_chip, offset)
    assert late_chip["gap_ms_max"] == pytest.approx(300.0)
    assert late_chip["after_last_ms"] == pytest.approx(-2.0)
    assert late_chip["noticed_late_ms_max"] is None  # this profile holds no such event
    assert catch_stall.device_side(window, [], offset) is None


def test_setup_phases_cover_set_up_and_leave_the_rest_unnamed():
    records = whole_run()
    setup, stretch, _ = host_spans.cut(records, 5, SYNC, 5)
    phases = host_spans.setup_phases(-3000 * MS, 0, setup, stretch[0][1])
    assert [name for name, _, _ in phases] == [
        "before_program", "(build)", "init_params", "init_opt_state", "(check)", "step_lower",
        "step_compile", "warmup"]
    named = sum(b - a for _, a, b in phases)
    # not named: init_params -> init_opt_state (1 ms), step_compile -> the first step (100 ms)
    assert (stretch[0][1] + 3000 * MS) - named == 101 * MS
    busy = [(0, 5 * MS), (4990 * MS, 5010 * MS)]
    assert host_spans.busy_inside(busy, *phases[1][1:]) == pytest.approx(0.005)
    assert host_spans.busy_inside(busy, *phases[5][1:]) == pytest.approx(0.010)


def test_clock_check_finds_the_offset_and_what_is_left():
    traced = made_up(1)
    offset = 1_790_000_000 * 1_000 * MS
    wall_ns = lambda t: t + offset
    profile = [(r[1] + (700 if i == 3 else 0), r[2]) for i, r in enumerate(traced)]
    check = host_spans.clock_check(traced, wall_ns, profile)
    assert check["matched"] == 5 and check["offset_s"] == pytest.approx(offset / 1e9)
    assert check["start_residual_max_us"] == pytest.approx(0.7)
    assert check["length_difference_max_us"] == pytest.approx(0.7)
    assert host_spans.clock_check(traced, wall_ns, profile[:4]) is None


def test_traced_gaps_are_named_by_the_programs_span():
    from perfbench.harness.trace_reduce import HOST_PLANE, OPS_LINE, Event, Trace

    # seconds: the runner's window is 0..1; the device is idle 0..0.1 and 0.5..0.7
    trace = Trace({
        HOST_PLANE: {"python": [Event("dispatch", 0.0, 0.2), Event("loss_fetch", 0.2, 1.0)]},
        "/device:TPU:0": {OPS_LINE: [Event("%fusion.1 = f32[] fusion()", 0.1, 0.5),
                                     Event("%fusion.2 = f32[] fusion()", 0.7, 1.0)]},
    })
    profile = {"step_dispatch": [(0.01e9, 0.15e9)]}  # ns, as the file has them
    gaps = host_spans.traced_gaps(trace, profile)
    assert [g[0] for g in gaps] == ["no_program_span", "step_dispatch"]
    assert gaps[0][1] == pytest.approx(0.2) and gaps[1][1] == pytest.approx(0.1)
    assert host_spans.traced_gaps(Trace({}), profile) is None  # a dry run has no device


@pytest.fixture(scope="module")
def tiny_state():
    import dataclasses

    import jax

    from distributed_llm_training_benchmark_framework_tpu.models.tinygpt import TinyGPTConfig
    from distributed_llm_training_benchmark_framework_tpu.parallel import get_strategy, make_mesh
    from distributed_llm_training_benchmark_framework_tpu.train.step import create_train_state

    config = TinyGPTConfig(vocab_size=256, n_embd=32, n_head=4, n_layer=2, block_size=64,
                           attention_impl="flash", scan_layers=False, dropout=0.0)
    mesh = make_mesh((1, 1, 1, 1, 1), ("data", "seq", "model", "pipe", "expert"),
                     devices=jax.devices()[:1])
    state = create_train_state(config, dataclasses.replace(get_strategy("zero2"), remat="none"),
                               mesh, seed=0, grad_accum=1, from_table=True, global_micro=1,
                               seq_len=64)
    table = jax.numpy.zeros((8, 64), jax.numpy.int32)
    state.aot_compile(state.params, state.opt_state, table)
    return state, table


def test_a_planted_sleep_and_a_forced_collection_on_real_records(tiny_state):
    """The program's own records of a real loop: a sleep between two windows
    reads in the wait and the window and not in the dispatch; a forced
    collection over a large list reads in the collector's metric."""
    from perfbench.harness import step_loop

    state, table = tiny_state
    scopes = host_spans.program()
    _, _, step = step_loop.steps(state, table, 0, SYNC, windows=2)  # warm
    first = step
    _, _, step = step_loop.steps(state, table, step, SYNC, windows=4)
    time.sleep(0.25)  # after the fourth window's losses, before the fifth's first step
    _, _, step = step_loop.steps(state, table, step, SYNC, windows=3)
    garbage = [[i] for i in range(300_000)]
    del garbage
    gc.collect()
    _, _, step = step_loop.steps(state, table, step, SYNC, windows=3)
    stretch = [r for r in scopes.host_records("step_dispatch")
               if r[3].get("step", -1) >= first][-(step - first):]
    assert [r[3]["step"] for r in stretch] == list(range(first, step))
    m = host_spans.stretch_metrics(stretch, SYNC, scopes.host_records(scopes.GC), [])
    assert m["windows"] == 9
    assert m["sync_wait_excess_max_ms"] >= 240
    assert m["window_max_over_p50"] > 1.5
    assert m["dispatch_max_ms"] < 100
    assert m["longest_window"]["wait_excess_ms"] >= 240
    assert m["gc_pause_max_ms"] > 1.0  # generation 2 over 300,000 lists


def test_the_ten_metric_files_resolve_and_read_none_without_a_record(monkeypatch):
    entries = {m["name"]: m for m in manifest.load_manifest()["per_layer"]}
    for name in TEN:
        module = sys.modules.get(f"perfbench.metrics.{name}") or __import__(
            f"perfbench.metrics.{name}", fromlist=["read"])
        assert manifest.metric_reader(name) is module.read
        entry = entries[name]
        assert (module.LAYER, module.UNIT, module.MOVES) == (
            entry["layer"], entry["unit"], entry["moves"])
        assert "workloads" not in entry and entry["better"] == "lower"
        assert entry["source"] in ("program_span", "program_counter")
    # a program without the record (a parent commit): every one reads None, none raises
    monkeypatch.setattr(host_spans, "program", lambda: None)
    run = {"workload": {"warmup_steps": 5, "sync_every": 5, "config": "c", "traffic": "t"},
           "traced_steps": 5}
    assert [manifest.metric_reader(name)(None, run) for name in TEN] == [None] * 10


@pytest.mark.parametrize("cell", ["tinygpt-a.seq2048", "deepseek-v2-lite.share8-seq8192"])
def test_dry_run_prints_the_host_line_and_all_ten(cell):
    run = subprocess.run(
        [sys.executable, "perfbench/run.py", "--workload", cell, "--seed", "3000000019",
         "--seconds", "1", "--trace", "1", "--allow-cpu"],
        cwd=manifest.ROOT, capture_output=True, text=True, timeout=600,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert run.returncode == 0, run.stderr[-2000:]
    last = json.loads(run.stdout.strip().splitlines()[-1])
    assert last["correct"] is True and last["metrics"] == {}
    (line,) = [l for l in run.stdout.splitlines() if l.startswith("perfbench: host: ")]
    for part in ("before_program", "(build)", "init_params", "(check)", "step_lower",
                 "step_compile", "warmup", "unnamed", "by function", "jit(train_step)",
                 "longest window", "5 records against their annotations"):
        assert part in line, part
    values = {}
    for l in run.stdout.splitlines():
        if l.startswith("perfbench: dry run, not reported: "):
            name, _, value = l[len("perfbench: dry run, not reported: "):].partition(" = ")
            values[name] = float(value)
    assert set(TEN) <= set(values)
    assert abs(values["step_lower_s"] + values["step_compile_s"] - values["compile_s"]) < 0.05
    assert values["setup_cache_misses"] == 0  # a dry run keeps no cache
    assert 0 < values["setup_jit_s"] and values["window_max_over_p50"] >= 1.0
