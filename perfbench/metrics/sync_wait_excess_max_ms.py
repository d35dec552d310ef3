"""The gap from a window's last ``step_dispatch`` to the next window's first is
where the host waits for the losses. The longest such gap of the untraced
timed stretch minus the median one: a stall outside the step's call (the
device, the transfer, the runner's own code)."""
from perfbench.harness import host_spans

LAYER, UNIT, MOVES = "timed loop", "ms", "tokens_per_s_per_chip"


def read(trace, run):
    return host_spans.metric("sync_wait_excess_max_ms", trace, run)
