"""From the profiler's ``.xplane.pb`` to the numbers the metric readers use.

Read with nothing but jax (``jax.profiler.ProfileData``). What a v5e trace of
this program looks like (looked at by hand, PR 22; PERF.md section 3):

* one plane per chip, ``/device:TPU:<n>``. Its line ``XLA Ops`` holds every
  HLO operation the TensorCore ran, in order, with start and duration on the
  device's clock; a ``while`` is an event that contains its body's events, so
  times are *self* times (an event's duration minus what its children cover).
  An event's name is the instruction's whole HLO text (``%fusion.12 = f32[..]
  fusion(..), kind=kOutput, calls=%fused_computation.7``); this reader sees no
  ``hlo_category``, so an op's kind is read off that text, and whether a
  fusion is a matrix multiplication off the compiled step's HLO text (does
  the computation it calls hold a convolution or dot). A Mosaic kernel is a
  ``custom-call`` whose target is ``tpu_custom_call``; other targets
  (``AllocateBuffer``, ``ConcatBitcast``) take no time. Async copies and
  collectives are a short ``-start`` and a ``-done`` on this line, and a span
  from one to the other on the line ``Async XLA Ops``. The line ``XLA
  Modules`` holds one event per run of a compiled program (one a train step);
* the host's plane ``/host:CPU`` holds the runner's ``TraceAnnotation`` spans
  (``dispatch``, ``loss_fetch``) on the same clock to within about a
  millisecond (in the recorded trace the first op starts 0.3 ms before the
  first ``dispatch`` opens).

All times here are seconds.
"""

import collections
import re

Event = collections.namedtuple("Event", "name start end")

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
HOST_PLANE = "/host:CPU"
HOST_SPANS = ("dispatch", "loss_fetch")
COLLECTIVE = re.compile(
    r" (all-gather|all-reduce|reduce-scatter|collective-permute|all-to-all)(-start|-done)?\("
)
MOSAIC = 'custom_call_target="tpu_custom_call"'
CALLS = re.compile(r"calls=%?([\w.\-]+)")
COMPUTATION = re.compile(r"^%?([\w.\-]+) \(.*\) -> .*\{$")


class Trace:
    """planes[plane name][line name] -> [Event], each line sorted by start."""

    def __init__(self, planes):
        self.planes = planes

    def devices(self):
        return sorted((p for p in self.planes if DEVICE_PLANE.match(p)),
                      key=lambda p: int(DEVICE_PLANE.match(p).group(1)))

    def ops(self, plane):
        return self.planes[plane].get(OPS_LINE, [])

    def modules(self, plane):
        return self.planes[plane].get(MODULES_LINE, [])

    def host_spans(self, name=None):
        return [e for line in self.planes.get(HOST_PLANE, {}).values() for e in line
                if (e.name == name if name else e.name in HOST_SPANS)]


def load(path):
    from jax.profiler import ProfileData

    planes = {}
    for plane in ProfileData.from_file(path).planes:
        device = bool(DEVICE_PLANE.match(plane.name))
        if not device and plane.name != HOST_PLANE:
            continue
        lines = planes.setdefault(plane.name, {})
        for line in plane.lines:
            events = []
            for e in line.events:
                if not device and e.name not in HOST_SPANS:
                    continue
                events.append(Event(e.name, e.start_ns * 1e-9,
                                    (e.start_ns + e.duration_ns) * 1e-9))
            if events:
                lines[line.name] = sorted(events, key=lambda e: (e.start, -e.end))
    return Trace(planes)


def merge(intervals):
    """Union of (start, end) intervals as a sorted list of disjoint ones."""
    out = []
    for start, end in sorted(intervals):
        if out and start <= out[-1][1]:
            out[-1][1] = max(out[-1][1], end)
        else:
            out.append([start, end])
    return [tuple(i) for i in out]


def total(intervals):
    return sum(end - start for start, end in intervals)


def clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals if min(e, hi) > max(s, lo)]


def subtract(intervals, holes):
    """The part of merged ``intervals`` that no interval of merged ``holes`` covers."""
    out = []
    for start, end in intervals:
        at = start
        for h0, h1 in holes:
            if h1 <= at or h0 >= end:
                continue
            if h0 > at:
                out.append((at, h0))
            at = max(at, h1)
        if at < end:
            out.append((at, end))
    return out


def self_times(events):
    """[(event, self seconds, whether it has no children)] for the events of
    one line, which may nest."""
    out, stack = [], []  # stack of [event, seconds covered by children, children]

    def close():
        event, covered, children = stack.pop()
        out.append((event, max(event.end - event.start - covered, 0.0), children == 0))

    for e in events:
        while stack and e.start >= stack[-1][0].end:
            close()
        if stack:
            stack[-1][1] += e.end - e.start
            stack[-1][2] += 1
        stack.append([e, 0.0, 0])
    while stack:
        close()
    return out


def matmul_computations(hlo_text):
    """Names of the computations of a compiled module that hold a convolution
    or a dot: a fusion that calls one of them is a matrix multiplication."""
    found, current = set(), None
    for line in hlo_text.splitlines():
        header = COMPUTATION.match(line.strip())
        if header:
            current = header.group(1)
        elif current and (" convolution(" in line or " dot(" in line):
            found.add(current)
    return found


def kind(event, matmuls=frozenset()):
    """'attention kernel' | 'collective' | 'matmul' | 'other', for one op.
    ``matmuls`` is ``matmul_computations`` of the step that was traced."""
    text = event.name
    if COLLECTIVE.search(text):
        return "collective"
    if MOSAIC in text:
        return "attention kernel"  # today every Mosaic call is a flash-attention kernel
    called = CALLS.search(text)
    if (called and called.group(1) in matmuls) or " convolution(" in text or " dot(" in text:
        return "matmul"
    return "other"


def window(trace):
    """The traced sync window: first ``dispatch`` start to ``loss_fetch`` end
    on the host's spans; the span of the device's ops where there are none."""
    spans = trace.host_spans()
    if spans:
        return min(e.start for e in spans), max(e.end for e in spans)
    ops = [e for p in trace.devices() for e in trace.ops(p)]
    return min(e.start for e in ops), max(e.end for e in ops)


def busy_intervals(trace, plane):
    lo, hi = window(trace)
    return clip(merge((e.start, e.end) for e in trace.ops(plane)), lo, hi)


def busy_and_window(trace):
    """(seconds an op ran, averaged over the chips; seconds of the window)."""
    lo, hi = window(trace)
    busy = [total(busy_intervals(trace, p)) for p in trace.devices()]
    return sum(busy) / len(busy), hi - lo


def idle_share(trace):
    """1 - busy/window on the chip that was idle longest."""
    lo, hi = window(trace)
    return max(1.0 - total(busy_intervals(trace, p)) / (hi - lo) for p in trace.devices())


def kind_seconds(trace, plane, matmuls=frozenset()):
    """{kind: self seconds} over one chip's ops, and the sum."""
    out = collections.Counter()
    for event, seconds, _ in self_times(trace.ops(plane)):
        out[kind(event, matmuls)] += seconds
    return out, sum(out.values())


def exposed_collective_seconds(trace, plane):
    """Seconds in which a collective (or the wait for one) held the chip's op
    line while no other op ran: collective intervals minus compute intervals."""
    ops = self_times(trace.ops(plane))
    coll = merge((e.start, e.end) for e, _, _ in ops if kind(e) == "collective")
    compute = merge((e.start, e.end) for e, _, leaf in ops if leaf and kind(e) != "collective")
    return total(subtract(coll, compute))


def step_seconds(trace, plane):
    """Device seconds of each traced step: the program's runs on ``XLA Modules``."""
    runs = trace.modules(plane)
    if not runs:
        return []
    longest = max(e.end - e.start for e in runs)
    return [e.end - e.start for e in runs if e.end - e.start > 0.5 * longest]


def breakdown(trace, matmuls=frozenset(), top=10):
    """What the ledger keeps: the ops that took most time on the first chip
    (self seconds, summed by kind and instruction name without its number)
    and the longest idle gaps there, named by the host span that was open in
    the middle of the gap."""
    plane = trace.devices()[0]
    by_name = collections.Counter()
    for event, seconds, _ in self_times(trace.ops(plane)):
        by_name[f"{kind(event, matmuls)}:{base_name(event)}"] += seconds
    gaps = collections.Counter()
    lo, hi = window(trace)
    spans = trace.host_spans()
    for start, end in subtract([(lo, hi)], busy_intervals(trace, plane)):
        mid = 0.5 * (start + end)
        open_ = [s.name for s in spans if s.start <= mid <= s.end]
        gaps[open_[0] if open_ else "no_span"] += end - start
    return {
        "device_ops": [[n, s] for n, s in by_name.most_common(top)],
        "idle_gaps": [[n, s] for n, s in gaps.most_common(top)],
    }


def base_name(event):
    """'%select_add_fusion.38 = f32[..] fusion(..)' -> 'select_add_fusion'."""
    return re.sub(r"[.\d]+$", "", event.name.split(" = ")[0].lstrip("%"))[:80]
