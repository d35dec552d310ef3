"""Median milliseconds in the program's ``step_dispatch`` span
(``train/step.py::step_with_mesh``: the jitted step's call, under the mesh)
over the untraced timed stretch: ``host_dispatch_ms`` from inside the program,
on every step of the stretch and with no profiler running."""
from perfbench.harness import host_spans

LAYER, UNIT, MOVES = "timed loop", "ms", "tokens_per_s_per_chip"


def read(trace, run):
    return host_spans.metric("step_dispatch_ms", trace, run)
