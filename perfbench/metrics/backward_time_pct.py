"""Share of the device's busy time in the backward pass, first chip: ops whose
``op_name`` holds a ``transpose(...)`` component and that are not the optimizer's
(``perfbench/harness/scopes.py``). What remat runs again counts here too."""
from perfbench.harness import scopes

LAYER, UNIT, MOVES = "train step", "%", "tokens_per_s_per_chip"


def read(trace, run):
    return scopes.share(trace, run, lambda s: s.phase == "backward")
