"""Share of the device's busy time under ``attention`` / ``window``, every
phase, first chip: the sliding-window layers' whole attention sublayers
(norm, projections, QK-norm, rotary, the flash kernels on the band, the
output projection), all of them together (``perfbench/harness/sw_scopes.py``)."""
from perfbench.harness import sw_scopes

LAYER, UNIT, MOVES = "model", "%", "tokens_per_s_per_chip"


def read(trace, run):
    return sw_scopes.scope_share(trace, run, "window")
