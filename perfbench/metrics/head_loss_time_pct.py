"""Share of the device's busy time under the scopes ``head`` and ``loss``, every
phase, first chip: final norm, LM-head matmul, log-softmax, gather
(``perfbench/harness/scopes.py``)."""
from perfbench.harness import scopes

LAYER, UNIT, MOVES = "model", "%", "tokens_per_s_per_chip"


def read(trace, run):
    return scopes.share(trace, run, lambda s: s.module in ("head", "loss"))
