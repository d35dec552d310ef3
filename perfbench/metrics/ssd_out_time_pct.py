"""Share of the device's busy time under ``attention`` / ``ssd`` /
``ssd_out``, every phase, first chip: the skip, the gate, the RMS over each
group's 512 channels, the scale and out_proj
(``perfbench/harness/ssd_scopes.py``)."""
from perfbench.harness import ssd_scopes

LAYER, UNIT, MOVES = "model", "%", "tokens_per_s_per_chip"


def read(trace, run):
    return ssd_scopes.scope_share(trace, run, "ssd_out")
