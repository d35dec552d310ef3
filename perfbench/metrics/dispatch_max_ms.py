"""The longest ``step_dispatch`` span of the untraced timed stretch: a stall
inside the step's call (back-pressure, a buffer not yet free, a compilation)."""
from perfbench.harness import host_spans

LAYER, UNIT, MOVES = "timed loop", "ms", "tokens_per_s_per_chip"


def read(trace, run):
    return host_spans.metric("dispatch_max_ms", trace, run)
