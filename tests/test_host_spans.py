"""The host's half of ``utils/scopes.py``: five spans round the work
``train/step.py`` does on the host, jax's compile and cache events, the
collector's pauses (docs/OBSERVABILITY.md, "Names in a profile").

One tiny step is built through ``create_train_state`` / ``make_train_step``
as ``tests/test_named_scopes.py`` builds one, compiled and run three times;
every case reads what that left in the process's record. The record is shared
with whatever else ran in this process, so each case reads from a mark on.
"""

import dataclasses
import gc
import glob
import statistics
import time

import jax
import pytest

from distributed_llm_training_benchmark_framework_tpu.models.tinygpt import TinyGPTConfig
from distributed_llm_training_benchmark_framework_tpu.parallel import get_strategy, make_mesh
from distributed_llm_training_benchmark_framework_tpu.train.step import create_train_state
from distributed_llm_training_benchmark_framework_tpu.utils import scopes

SEQ = 128


def since(mark, name=None):
    return [r for r in scopes.host_records(name) if r[1] >= mark]


@pytest.fixture(scope="module")
def run():
    """Init, ``aot_compile`` and three steps of a tiny step -> what each part
    left behind: the records, the compile events before and after the steps."""
    mark = time.perf_counter_ns()
    config = TinyGPTConfig(vocab_size=512, n_embd=128, n_head=4, n_layer=2, block_size=SEQ,
                           attention_impl="flash", scan_layers=False, dropout=0.1)
    mesh = make_mesh((1, 1, 1, 1, 1), ("data", "seq", "model", "pipe", "expert"),
                     devices=jax.devices()[:1])
    strategy = dataclasses.replace(get_strategy("zero2"), remat="none")
    state = create_train_state(config, strategy, mesh, seed=0, grad_accum=2, from_table=True,
                               global_micro=4, seq_len=SEQ)
    table = jax.numpy.zeros((8, SEQ), jax.numpy.int32)
    state.aot_compile(state.params, state.opt_state, table)
    params, opt_state = state.params, state.opt_state
    compiled = scopes.compile_events()
    ready = []  # (ns when the call returned, ns when its loss was ready)
    for step in range(3):
        params, opt_state, loss = state.step_fn(params, opt_state, table, step)
        returned = time.perf_counter_ns()
        loss.block_until_ready()
        ready.append((returned, time.perf_counter_ns()))
    return {"mark": mark, "records": since(mark), "ready": ready, "compiled": compiled,
            "stepped": scopes.compile_events(), "state": state, "table": table,
            "carry": (params, opt_state)}


def test_the_five_names_in_order(run):
    names = [r[0] for r in run["records"] if r[0] != scopes.GC]
    assert names == ["init_params", "init_opt_state", "step_lower", "step_compile",
                     "step_dispatch", "step_dispatch", "step_dispatch"]
    assert set(names) == set(scopes.HOST_SPANS)
    for _, start, end, _ in run["records"]:
        assert end >= start >= run["mark"]


def test_one_step_dispatch_a_call_with_its_number(run):
    steps = [r for r in run["records"] if r[0] == scopes.STEP_DISPATCH]
    assert [r[3] for r in steps] == [{"step": 0}, {"step": 1}, {"step": 2}]


def test_a_device_scalar_as_step_is_not_fetched_for_its_number(run):
    mark = time.perf_counter_ns()
    params, opt_state = run["carry"]
    run["carry"] = run["state"].step_fn(params, opt_state, run["table"],
                                        jax.numpy.int32(3))[:2]
    (record,) = since(mark, scopes.STEP_DISPATCH)
    assert record[3] == {}


def test_no_host_name_is_a_runners_or_a_device_name():
    runner = {"dispatch", "loss_fetch"}  # perfbench/harness/trace_reduce.HOST_SPANS
    device = {*scopes.SCOPES, *scopes.MOE_SCOPES, *scopes.MLA_SCOPES, scopes.SHARED}
    assert not set(scopes.HOST_SPANS) & (runner | device | {scopes.GC})
    assert len(set(scopes.HOST_SPANS)) == 5


def test_the_record_is_bounded_and_keeps_the_newest():
    bound = scopes._spans.maxlen
    kept = list(scopes._spans)
    try:
        for i in range(bound + 10):
            with scopes.host_span("filler", i=i):
                pass
        records = scopes.host_records("filler")
        assert len(scopes._spans) == bound == len(records)
        assert records[-1][3] == {"i": bound + 9} and records[0][3] == {"i": 10}
    finally:  # what ran before in this process goes back
        scopes._spans.clear()
        scopes._spans.extend(kept)


def test_a_forced_collection_is_a_gc_record():
    mark = time.perf_counter_ns()
    garbage = [[i] for i in range(200_000)]
    del garbage
    gc.collect()
    found = [r for r in since(mark, scopes.GC) if r[3] == {"generation": 2}]
    assert found and all(r[2] > r[1] for r in found)
    assert found[-1] in scopes.host_records()  # one record, by start


def test_compile_counters_hold_the_functions_names(run):
    sums = run["compiled"]["sums"]
    by_event = {}
    for (event, function), (count, seconds) in sums.items():
        assert count >= 1 and seconds >= 0
        by_event.setdefault(event, set()).add(function)
    assert {"train_step", "init_fn"} <= by_event[scopes.TRACE_EVENT]
    for event in (scopes.LOWER_EVENT, scopes.BACKEND_COMPILE_EVENT):
        assert {"jit(train_step)", "jit(init_fn)"} <= by_event[event]
    names = [name for name, _, _ in run["compiled"]["backend_compiles"]]
    assert "jit(train_step)" in names


def test_a_second_identical_call_compiles_nothing(run):
    # the three steps ran after ``aot_compile``: the same program each time
    count = lambda events: sum(1 for name, _, _ in events["backend_compiles"]
                               if name == "jit(train_step)")
    assert count(run["stepped"]) == count(run["compiled"])
    key = (scopes.BACKEND_COMPILE_EVENT, "jit(train_step)")
    assert run["stepped"]["sums"][key] == run["compiled"]["sums"][key]


def test_nested_events_are_counted_once_in_busy(run):
    events = run["compiled"]
    busy = [(a, b) for a, b in events["busy"] if a >= run["mark"]]
    assert busy == sorted(busy) and all(a < b for a, b in busy)
    assert all(b <= a2 for (_, b), (a2, _) in zip(busy, busy[1:]))  # disjoint
    seconds = 1e-9 * sum(b - a for a, b in busy)
    lower = [r for r in run["records"] if r[0] == scopes.STEP_LOWER][0]
    compile_ = [r for r in run["records"] if r[0] == scopes.STEP_COMPILE][0]
    # ``train_step``'s trace holds those of the functions it calls: the plain
    # sum counts them twice, the union cannot pass the wall clock
    assert seconds <= 1e-9 * (compile_[2] - run["mark"]) + 1e-3
    assert seconds >= 0.9e-9 * (lower[2] - lower[1])


def test_cache_events_are_counted():
    before = scopes.compile_events()
    mark = time.perf_counter_ns()
    jax.monitoring.record_event(scopes.CACHE_HIT_EVENT)
    jax.monitoring.record_event(scopes.CACHE_MISS_EVENT)
    jax.monitoring.record_event("/jax/compilation_cache/tasks_using_cache")
    after = scopes.compile_events()
    assert after["cache_hits"] == before["cache_hits"] + 1
    assert len(after["cache_misses"]) == len(before["cache_misses"]) + 1
    assert after["cache_misses"][-1] >= mark


def test_a_span_adds_no_fence(run):
    """By the clock, not by the jaxpr (a span is host code: it is in none): the
    record of a step's dispatch closes when the call returns, long before the
    step's loss is ready. The CPU backend dispatches this step asynchronously,
    as the TPU's does; the better of the last two steps is held to three tenths."""
    steps = [r for r in run["records"] if r[0] == scopes.STEP_DISPATCH]
    ratios = []
    for (_, start, end, _), (returned, ready) in zip(steps, run["ready"]):
        assert end <= returned <= ready
        ratios.append((end - start) / (ready - start))
    assert min(ratios[1:]) < 0.3, ratios  # 0.01-0.02 on an idle host; a fence reads 1


def test_a_span_records_its_arguments_and_an_exception():
    mark = time.perf_counter_ns()
    with pytest.raises(KeyError):
        with scopes.host_span("filler", why="test"):
            raise KeyError("x")
    (record,) = since(mark, "filler")
    assert record[0] == "filler" and record[3] == {"why": "test"}


def test_the_annotations_are_in_a_profile_on_the_records_clock(run, tmp_path):
    """``TraceAnnotation`` is entered: a profile of the tiny run on the CPU holds
    every name, a step's number as the event's ``step``, and each record's
    interval is its annotation's after one constant offset."""
    from jax.profiler import ProfileData

    state, table = run["state"], run["table"]
    params, opt_state = run["carry"]
    mark = time.perf_counter_ns()
    jax.profiler.start_trace(str(tmp_path))
    try:
        state.aot_compile(params, opt_state, table)
        for step in range(4, 7):
            params, opt_state, loss = state.step_fn(params, opt_state, table, step)
        loss.block_until_ready()
    finally:
        jax.profiler.stop_trace()
    run["carry"] = (params, opt_state)
    (path,) = glob.glob(str(tmp_path / "plugins/profile/*/*.xplane.pb"))
    found = {}
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name in scopes.HOST_SPANS:
                    found.setdefault(e.name, []).append(
                        (e.start_ns, e.duration_ns, dict(e.stats)))
    assert set(found) == {"step_lower", "step_compile", "step_dispatch"}
    events = sorted(found["step_dispatch"])
    records = since(mark, scopes.STEP_DISPATCH)
    assert [stats["step"] for _, _, stats in events] == [4, 5, 6] == [
        r[3]["step"] for r in records]
    offsets = [scopes.wall_ns(r[1]) - e[0] for r, e in zip(records, events)]
    assert max(abs(o - statistics.median(offsets)) for o in offsets) < 1e6  # ns
    for record, event in zip(records, events):
        assert abs((record[2] - record[1]) - event[1]) < 1e6


def test_wall_ns_is_the_import_pair():
    perf, wall = scopes.IMPORTED_AT
    assert scopes.wall_ns(perf) == wall
    assert abs(scopes.wall_ns(time.perf_counter_ns()) - time.time_ns()) < 5e9
