"""Share of the device's busy time under ``mlp`` / ``router``, every phase,
first chip: the router's logits, softmax, top-k and the load-balance and
z-loss statistics (``perfbench/harness/moe_scopes.py``)."""
from perfbench.harness import moe_scopes

LAYER, UNIT, MOVES = "model", "%", "tokens_per_s_per_chip"


def read(trace, run):
    return moe_scopes.share(trace, run, ("router",))
