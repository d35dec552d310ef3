"""Share of the device's busy time under ``attention`` / ``kda`` /
``kda_prep``, every phase, first chip: what XLA makes of the q, k, v
projections, the three depthwise convolutions, SiLU, the l2norms, the decay's
low-rank map with its softplus, and beta (``perfbench/harness/kda_scopes.py``)."""
from perfbench.harness import kda_scopes

LAYER, UNIT, MOVES = "model", "%", "tokens_per_s_per_chip"


def read(trace, run):
    return kda_scopes.scope_share(trace, run, "kda_prep")
