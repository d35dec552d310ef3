"""Share of the device's busy time under ``attention`` / ``global``, every
phase, first chip: the global layers' whole attention sublayers (the same
leaves as a window layer's, YaRN's table, the flash kernels under causal)
(``perfbench/harness/sw_scopes.py``)."""
from perfbench.harness import sw_scopes

LAYER, UNIT, MOVES = "model", "%", "tokens_per_s_per_chip"


def read(trace, run):
    return sw_scopes.scope_share(trace, run, "global")
