"""Seconds in the program's ``step_compile`` span (``train/step.py::aot_compile``):
the compiler's run on the lowered step or, in every run of a checkout but the
first, the read from the compile cache."""
from perfbench.harness import host_spans

LAYER, UNIT, MOVES = "set-up", "s", "setup_s"


def read(trace, run):
    return host_spans.metric("step_compile_s", trace, run)
