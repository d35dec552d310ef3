"""Share of the device's busy time under ``attention`` / ``window``, every
phase, first chip: the sliding layers' whole attention sublayers at 64 query
heads (norm, projections, the rotary pass, the flash kernels on the band, the
output gate, the output projection), all of them together
(``perfbench/harness/lg_scopes.py``)."""
from perfbench.harness import lg_scopes

LAYER, UNIT, MOVES = "model", "%", "tokens_per_s_per_chip"


def read(trace, run):
    return lg_scopes.share(trace, run, lambda reduced: reduced["scope"]["window"])
