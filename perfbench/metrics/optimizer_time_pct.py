"""Share of the device's busy time under the scope ``optimizer``, first chip:
clipping, AdamW, the zero2 gather of the updates and their application
(``perfbench/harness/scopes.py``)."""
from perfbench.harness import scopes

LAYER, UNIT, MOVES = "train step", "%", "tokens_per_s_per_chip"


def read(trace, run):
    return scopes.share(trace, run, lambda s: s.module == "optimizer")
