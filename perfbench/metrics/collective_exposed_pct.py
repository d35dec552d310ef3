"""Share of the traced window in which a collective, or the wait for one,
held a chip's op line while no other op ran there; worst chip."""
from perfbench.harness import trace_reduce

LAYER, UNIT, MOVES = "strategies and mesh", "%", "tokens_per_s_per_chip"


def read(trace, run):
    if run["chips"] == 1 or not trace.devices():
        return None
    _, seconds = trace_reduce.busy_and_window(trace)
    return 100.0 * max(
        trace_reduce.exposed_collective_seconds(trace, p) for p in trace.devices()
    ) / seconds
