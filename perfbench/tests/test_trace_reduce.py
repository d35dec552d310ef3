"""The reduction from a trace to numbers: interval arithmetic on hand-made
events, then the same functions on a small trace recorded on the v5e."""

import gzip
import json
import os

import pytest

from perfbench.harness import trace_reduce as tr
from perfbench.harness.trace_reduce import Event, Trace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def op(name, start, end, text=""):
    return Event(f"%{name} = f32[8,128]{{1,0}} {text}", start, end)


MATMUL = "fusion(%p.1), kind=kOutput, calls=%fused_computation.7"
LOOP = "fusion(%p.1), kind=kLoop, calls=%fused_computation.8"
KERNEL = 'custom-call(%q, %k, %v), custom_call_target="tpu_custom_call"'
HLO_TEXT = """
%fused_computation.7 (param_0: bf16[8,128]) -> f32[8,128] {
  %param_0 = bf16[8,128]{1,0} parameter(0)
  ROOT %convolution.1 = f32[8,128]{1,0} convolution(%param_0, %param_0), dim_labels=bf_io->bf
}

%fused_computation.8 (param_0: f32[8,128]) -> f32[8,128] {
  %param_0 = f32[8,128]{1,0} parameter(0)
  ROOT %add.1 = f32[8,128]{1,0} add(%param_0, %param_0)
}
"""


def hand_made():
    """One chip, one traced window [0, 20]: a while loop [2, 12] holding a
    matmul, a kernel and a gap; then an async all-gather around a fusion."""
    ops = [
        op("while.1", 2.0, 12.0, "while(%tuple.1), condition=%cond, body=%body"),
        op("fusion.1", 2.0, 6.0, MATMUL),
        op("flash.7", 6.0, 9.0, KERNEL),
        op("add_fusion.2", 10.0, 12.0, LOOP),  # 9..10 idle inside the loop
        op("all-gather-start.1", 12.0, 13.0, "all-gather-start(%p.2), dimensions={0}"),
        op("fusion.3", 13.0, 16.0, MATMUL),
        op("all-gather-done.1", 16.0, 18.0, "all-gather-done(%all-gather-start.1)"),
    ]
    host = [Event("dispatch", 0.0, 1.0), Event("dispatch", 1.0, 1.5),
            Event("loss_fetch", 1.5, 20.0)]
    return Trace({
        "/device:TPU:0": {tr.OPS_LINE: ops, tr.MODULES_LINE: [op("jit_train_step", 2.0, 18.0)]},
        tr.HOST_PLANE: {"python3": host},
    })


def test_interval_arithmetic():
    assert tr.merge([(3, 4), (0, 1), (1, 2), (3.5, 5)]) == [(0, 2), (3, 5)]
    assert tr.total([(0, 2), (3, 5)]) == 4
    assert tr.clip([(0, 2), (3, 5)], 1, 4) == [(1, 2), (3, 4)]
    assert tr.subtract([(0, 10)], [(1, 2), (4, 6), (9, 12)]) == [(0, 1), (2, 4), (6, 9)]
    assert tr.subtract([(0, 1), (5, 6)], []) == [(0, 1), (5, 6)]


def test_self_times_of_nested_events():
    times = tr.self_times(hand_made().ops("/device:TPU:0"))
    selfs = {tr.base_name(e): s for e, s, _ in times}
    assert selfs["while"] == pytest.approx(1.0)  # 10 long, children cover 9
    assert selfs["flash"] == pytest.approx(3.0)
    assert sum(s for _, s, _ in times) == pytest.approx(16.0)  # = the busy union


def test_busy_union_and_idle_share():
    trace = hand_made()
    assert tr.window(trace) == (0.0, 20.0)
    busy, window = tr.busy_and_window(trace)
    assert (busy, window) == (pytest.approx(16.0), pytest.approx(20.0))  # the union counts the while
    assert tr.idle_share(trace) == pytest.approx(0.2)


def test_kinds_and_shares():
    matmuls = tr.matmul_computations(HLO_TEXT)
    assert matmuls == {"fused_computation.7"}
    kinds, busy = tr.kind_seconds(hand_made(), "/device:TPU:0", matmuls)
    assert kinds["matmul"] == pytest.approx(7.0)
    assert kinds["attention kernel"] == pytest.approx(3.0)
    assert kinds["collective"] == pytest.approx(3.0)
    assert kinds["other"] == pytest.approx(2.0 + 1.0)  # the loop fusion and the while's own second
    assert busy == pytest.approx(16.0)


def test_exposed_collective_is_what_no_compute_covers():
    trace = hand_made()
    assert tr.exposed_collective_seconds(trace, "/device:TPU:0") == pytest.approx(3.0)
    # a collective that runs under a compute op on another line is hidden
    ops = trace.ops("/device:TPU:0") + [op("add_fusion.9", 16.0, 17.5, LOOP)]
    covered = Trace({"/device:TPU:0": {tr.OPS_LINE: sorted(ops, key=lambda e: e.start)}})
    assert tr.exposed_collective_seconds(covered, "/device:TPU:0") == pytest.approx(1.5)


def test_breakdown_names_gaps_by_the_open_host_span():
    b = tr.breakdown(hand_made(), tr.matmul_computations(HLO_TEXT))
    gaps = dict(b["idle_gaps"])
    assert gaps["dispatch"] == pytest.approx(2.0)  # 0..2, its middle under the first dispatch
    assert gaps["loss_fetch"] == pytest.approx(2.0)  # 18..20
    assert b["device_ops"][0] == ["matmul:fusion", pytest.approx(7.0)]
    assert ["attention kernel:flash", pytest.approx(3.0)] in b["device_ops"]
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10


def test_step_seconds_reads_the_modules_line():
    assert tr.step_seconds(hand_made(), "/device:TPU:0") == [pytest.approx(16.0)]


# --- the small trace recorded on the v5e (tools/record_small_trace.py) ---

RECORDED = os.path.join(DATA, "trace_small.json.gz")


@pytest.fixture(scope="module")
def recorded():
    with gzip.open(RECORDED, "rt") as f:
        record = json.load(f)
    events = lambda rows: [Event(*row) for row in rows]
    trace = Trace({
        record["plane"]: {tr.OPS_LINE: events(record["ops"]),
                          tr.MODULES_LINE: events(record["modules"])},
        tr.HOST_PLANE: {"python3": events(record["host"])},
    })
    return trace, record


def test_recorded_trace_busy_union_against_a_sweep(recorded):
    """The union of op intervals, against a sweep over sorted boundaries that
    shares no code with ``merge``; and against what was recorded."""
    trace, record = recorded
    ops = trace.ops(record["plane"])
    boundaries = sorted([(e.start, 1) for e in ops] + [(e.end, -1) for e in ops])
    busy, depth, since = 0.0, 0, None
    for at, step in boundaries:
        if depth == 0 and step == 1:
            since = at
        depth += step
        if depth == 0:
            busy += at - since
    merged = tr.total(tr.merge((e.start, e.end) for e in ops))
    assert merged == pytest.approx(busy, rel=1e-9)
    assert merged == pytest.approx(record["expected"]["busy_s"], rel=1e-9)


def test_recorded_trace_self_times_add_up_to_the_union(recorded):
    trace, record = recorded
    kinds, self_sum = tr.kind_seconds(trace, record["plane"], set(record["matmul_computations"]))
    # nested ops (the micro-batch loop holds nearly every op) are counted once
    assert self_sum == pytest.approx(record["expected"]["busy_s"], rel=1e-6)
    assert self_sum < sum(e.end - e.start for e in trace.ops(record["plane"]))
    for kind, seconds in record["expected"]["kinds"].items():
        assert kinds[kind] == pytest.approx(seconds, rel=1e-9)


def test_recorded_trace_shares_are_what_the_step_is_known_to_be(recorded):
    """tinygpt-a.seq2048 on the v5e: 64 forward kernels a step (16 layers x 4
    micro-batches) and no backward kernel below 4096; matrix multiplications
    the largest kind; no collective on one chip; the device busy for nearly
    all of the step's run."""
    trace, record = recorded
    plane = record["plane"]
    kernels = [e for e in trace.ops(plane) if tr.kind(e) == "attention kernel"]
    assert len(kernels) == 64
    kinds, busy = tr.kind_seconds(trace, plane, set(record["matmul_computations"]))
    assert kinds["collective"] == 0 and tr.exposed_collective_seconds(trace, plane) == 0
    assert 0.05 < kinds["attention kernel"] / busy < 0.25
    assert kinds["matmul"] / busy > 0.3
    [step] = tr.step_seconds(trace, plane)
    assert step == pytest.approx(record["expected"]["step_s"])
    assert 0.98 < busy / step <= 1.0


def test_recorded_trace_host_and_device_share_a_clock(recorded):
    """The first op starts within a few milliseconds of the first ``dispatch``
    (0.3 ms *before* it in this trace: the two clocks agree to about that)."""
    trace, record = recorded
    first_dispatch = min(e.start for e in trace.host_spans("dispatch"))
    first_op = min(e.start for e in trace.ops(record["plane"]))
    assert abs(first_op - first_dispatch) < 0.005
