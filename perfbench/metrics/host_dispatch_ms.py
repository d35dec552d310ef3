"""Median milliseconds the host spends in one call of the jitted step (the
runner's ``dispatch`` span). It moves throughput only where the device waits
for the host, that is where device_idle_pct is not near 0."""
import statistics

LAYER, UNIT, MOVES = "timed loop", "ms", "tokens_per_s_per_chip"


def read(trace, run):
    spans = trace.host_spans("dispatch")
    if not spans:
        return None
    return 1e3 * statistics.median(e.end - e.start for e in spans)
