"""Share of the device's busy time in the recurrence's Mosaic calls,
``kda_fwd`` and ``kda_bwd`` (``ops/kda.py``), first chip
(``perfbench/harness/kda_scopes.py``)."""
from perfbench.harness import kda_scopes

LAYER, UNIT, MOVES = "kernels", "%", "tokens_per_s_per_chip"


def read(trace, run):
    return kda_scopes.kernel_share(trace, run, kda_scopes.KDA_KERNELS)
