"""Share of the device's busy time under the scope ``attention``, every phase,
first chip: norm 1, projections, rope, the kernels or einsums, the residual
(``perfbench/harness/scopes.py``). ``attn_kernel_time_pct`` is the part of it
that is Mosaic calls."""
from perfbench.harness import scopes

LAYER, UNIT, MOVES = "model", "%", "tokens_per_s_per_chip"


def read(trace, run):
    return scopes.share(trace, run, lambda s: s.module == "attention")
