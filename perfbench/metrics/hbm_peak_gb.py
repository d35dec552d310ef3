"""Peak device memory, GB, fullest chip: the larger of the allocator's
``peak_bytes_in_use`` after the window and the compiled step's
buffer-assignment peak. Both are printed on an earlier line of every run;
which one to trust is an open question (PERF.md section 7). The headroom a
remat or micro-batch PR spends; never a gate."""
LAYER, UNIT, MOVES = "device", "GB", "tokens_per_s_per_chip"


def read(trace, run):
    return max(run["memory_allocator_bytes"] or 0, run["memory_assigned_bytes"]) / 1e9
