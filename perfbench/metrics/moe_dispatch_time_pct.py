"""Share of the device's busy time under ``mlp`` / ``dispatch`` and ``mlp`` /
``combine``, every phase, first chip: the sort of the assignments by expert,
the gather of the rows into expert order, the gather back, the gate weighting
and the sum over a token's experts: the data movement a dense MLP does not
have (``perfbench/harness/moe_scopes.py``)."""
from perfbench.harness import moe_scopes

LAYER, UNIT, MOVES = "model", "%", "tokens_per_s_per_chip"


def read(trace, run):
    return moe_scopes.share(trace, run, ("dispatch", "combine"))
