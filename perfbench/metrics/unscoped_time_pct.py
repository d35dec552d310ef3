"""Share of the device's busy time in ops no module scope names, first chip:
accumulation copies, casts of the weights, the table gather, ops the
partitioner made (``perfbench/harness/scopes.py``, which prints the largest)."""
from perfbench.harness import scopes

LAYER, UNIT, MOVES = "device", "%", "tokens_per_s_per_chip"


def read(trace, run):
    return scopes.share(trace, run, lambda s: s.module is None)
