"""Share of the device's busy time under ``attention`` / ``global``, every
phase, first chip: the full layers' whole attention sublayers at 48 query
heads (half of each head rotated under YaRN, causal flash kernels, the output
gate), both of them together (``perfbench/harness/lg_scopes.py``)."""
from perfbench.harness import lg_scopes

LAYER, UNIT, MOVES = "model", "%", "tokens_per_s_per_chip"


def read(trace, run):
    return lg_scopes.share(trace, run, lambda reduced: reduced["scope"]["global"])
