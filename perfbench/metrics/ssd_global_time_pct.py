"""Share of the device's busy time under ``attention`` / ``global``, every
phase, first chip: the attention block's whole sublayer (32 query heads over 2
KV heads, no positions; ``perfbench/harness/ssd_scopes.py``)."""
from perfbench.harness import ssd_scopes

LAYER, UNIT, MOVES = "model", "%", "tokens_per_s_per_chip"


def read(trace, run):
    return ssd_scopes.scope_share(trace, run, "global")
