"""The longest collection of Python's collector inside the untraced timed
stretch (the program's ``gc.callbacks`` entry); 0 where none ran."""
from perfbench.harness import host_spans

LAYER, UNIT, MOVES = "timed loop", "ms", "tokens_per_s_per_chip"


def read(trace, run):
    return host_spans.metric("gc_pause_max_ms", trace, run)
