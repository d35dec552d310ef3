"""Share of the device's busy time in Mosaic custom calls, first chip. Today
every Mosaic call of the program is a flash-attention kernel."""
from perfbench.harness import trace_reduce

LAYER, UNIT, MOVES = "kernels", "%", "tokens_per_s_per_chip"


def read(trace, run):
    if not trace.devices():
        return None
    kinds, busy = trace_reduce.kind_seconds(trace, trace.devices()[0])
    if not kinds["attention kernel"]:
        return None
    return 100.0 * kinds["attention kernel"] / busy
