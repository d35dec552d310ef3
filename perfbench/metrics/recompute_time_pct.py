"""Share of the device's busy time in ops that remat runs a second time
(``rematted_computation`` in their ``op_name``), first chip: under ``dots`` every
op that is not a dot, the forward flash kernel included
(``perfbench/harness/scopes.py``)."""
from perfbench.harness import scopes

LAYER, UNIT, MOVES = "train step", "%", "tokens_per_s_per_chip"


def read(trace, run):
    return scopes.share(trace, run, lambda s: s.recompute)
