"""Share of the device's busy time under ``attention`` / ``kda``, every phase,
first chip: the KDA layers' whole mixer sublayers (norm, projections,
convolutions, the recurrence's kernels, the gated head norm, the output
projection), all of them together (``perfbench/harness/kda_scopes.py``)."""
from perfbench.harness import kda_scopes

LAYER, UNIT, MOVES = "model", "%", "tokens_per_s_per_chip"


def read(trace, run):
    return kda_scopes.scope_share(trace, run, "kda")
