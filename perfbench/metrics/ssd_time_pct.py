"""Share of the device's busy time under ``attention`` / ``ssd``, every phase,
first chip: the Mamba-2 blocks' whole mixers (norm, in_proj, the convolution,
the scan's kernels, the gated grouped norm, out_proj), all of them together
(``perfbench/harness/ssd_scopes.py``)."""
from perfbench.harness import ssd_scopes

LAYER, UNIT, MOVES = "model", "%", "tokens_per_s_per_chip"


def read(trace, run):
    return ssd_scopes.scope_share(trace, run, "ssd")
