"""Median milliseconds one traced step's program ran on the device (its run
on the ``XLA Modules`` line), slowest chip."""
import statistics

from perfbench.harness import trace_reduce

LAYER, UNIT, MOVES = "train step", "ms", "step_time_p50_ms"


def read(trace, run):
    medians = [statistics.median(s) for p in trace.devices()
               if (s := trace_reduce.step_seconds(trace, p))]
    return 1e3 * max(medians) if medians else None
