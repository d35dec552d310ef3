"""Share of the device's busy time under ``mlp`` / ``shared``, every phase,
first chip: the shared experts, one dense SwiGLU of two experts' width that
every token passes beside the routed sum
(``perfbench/harness/mla_scopes.py``)."""
from perfbench.harness import mla_scopes

LAYER, UNIT, MOVES = "model", "%", "tokens_per_s_per_chip"


def read(trace, run):
    return mla_scopes.share(trace, run, "mlp", ("shared",))
