"""Share of the device's busy time under ``mlp`` / ``experts``, every phase,
first chip: the grouped matmuls, the activation between them and the casts of
their weights (``perfbench/harness/moe_scopes.py``). Higher is better: it is
where the routed layer's FLOPs are."""
from perfbench.harness import moe_scopes

LAYER, UNIT, MOVES = "model", "%", "tokens_per_s_per_chip"


def read(trace, run):
    return moe_scopes.share(trace, run, ("experts",))
