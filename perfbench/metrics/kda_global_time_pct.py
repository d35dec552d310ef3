"""Share of the device's busy time under ``attention`` / ``global``, every
phase, first chip: the latent-attention layer's whole sublayer
(``perfbench/harness/kda_scopes.py``)."""
from perfbench.harness import kda_scopes

LAYER, UNIT, MOVES = "model", "%", "tokens_per_s_per_chip"


def read(trace, run):
    return kda_scopes.scope_share(trace, run, "global")
