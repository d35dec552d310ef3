"""Share of the device's busy time under the scope ``mlp``, every phase, first
chip: norm 2, the MLP's matmuls, activation, dropout, residual
(``perfbench/harness/scopes.py``). Higher is better: it is where the model's
matmul FLOPs are."""
from perfbench.harness import scopes

LAYER, UNIT, MOVES = "model", "%", "tokens_per_s_per_chip"


def read(trace, run):
    return scopes.share(trace, run, lambda s: s.module == "mlp")
