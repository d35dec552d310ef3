"""Sync windows of the untraced timed stretch, cut at every ``sync_every``-th
``step_dispatch`` start: the longest over the median. 1.00 is a steady run;
``tokens_per_s_per_chip`` is a mean over these windows, so one long window
moves it and ``step_time_p50_ms`` not."""
from perfbench.harness import host_spans

LAYER, UNIT, MOVES = "timed loop", "ratio", "tokens_per_s_per_chip"


def read(trace, run):
    return host_spans.metric("window_max_over_p50", trace, run)
