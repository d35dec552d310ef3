"""Share of the device's busy time under ``mlp`` / ``router``, ``dispatch``,
``experts`` and ``combine``, every phase, first chip: what this chip's share of
the routed experts costs it: scoring all the experts, sorting the assignments,
gathering the rows of the held ones, the grouped matmuls, the weighted sum
back (``perfbench/harness/mla_scopes.py``)."""
from perfbench.harness import mla_scopes

LAYER, UNIT, MOVES = "model", "%", "tokens_per_s_per_chip"


def read(trace, run):
    return mla_scopes.share(trace, run, "mlp", ("router", "dispatch", "experts", "combine"))
