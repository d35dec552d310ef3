"""1 - (union of the device's op intervals / the traced sync window), on the
chip that was idle longest. The window runs from the first ``dispatch`` to
the end of ``loss_fetch``, so it holds what every sync window of the timed
loop holds: the launch of the first step onto an idle device."""
from perfbench.harness import trace_reduce

LAYER, UNIT, MOVES = "device", "%", "tokens_per_s_per_chip"


def read(trace, run):
    if not trace.devices():
        return None
    return 100.0 * trace_reduce.idle_share(trace)
