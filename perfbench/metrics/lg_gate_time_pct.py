"""Share of the device's busy time under the scope ``attn_gate``, every phase,
first chip: the per-head output gate's projection (2048 -> heads), its
sigmoid and the product with the kernel's (B, S, heads, 128) result, in all
five layers (``perfbench/harness/lg_scopes.py``). An overlay: the time is also
inside ``lg_window_time_pct`` / ``lg_global_time_pct``."""
from perfbench.harness import lg_scopes

LAYER, UNIT, MOVES = "model", "%", "tokens_per_s_per_chip"


def read(trace, run):
    return lg_scopes.share(trace, run, lambda reduced: reduced["gate"])
