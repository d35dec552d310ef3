"""Seconds in the program's ``step_lower`` span (``train/step.py::aot_compile``):
tracing the train step and lowering it to MLIR, which no compile cache
removes. With ``step_compile_s`` it makes up ``compile_s``."""
from perfbench.harness import host_spans

LAYER, UNIT, MOVES = "set-up", "s", "setup_s"


def read(trace, run):
    return host_spans.metric("step_lower_s", trace, run)
