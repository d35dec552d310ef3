"""Share of the device's busy time under ``embed`` / ``noise``, first chip:
drawing a noise level a block and a uniform a token, masking, and joining the
noisy copy to the clean one (``perfbench/harness/bd_scopes.py``). The
objective's own work on the device beside the second copy's."""
from perfbench.harness import bd_scopes

LAYER, UNIT, MOVES = "model", "%", "tokens_per_s_per_chip"


def read(trace, run):
    found = bd_scopes.found(trace, run)
    return None if found is None else 100.0 * found[0] / found[2]
