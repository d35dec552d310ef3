"""Share of the device's busy time under ``attention`` / ``ssd`` /
``ssd_prep``, every phase, first chip: in_proj's three products, the
convolution with its bias and SiLU (its two Mosaic calls included), dt's
softplus and the log-decay (``perfbench/harness/ssd_scopes.py``)."""
from perfbench.harness import ssd_scopes

LAYER, UNIT, MOVES = "model", "%", "tokens_per_s_per_chip"


def read(trace, run):
    return ssd_scopes.scope_share(trace, run, "ssd_prep")
