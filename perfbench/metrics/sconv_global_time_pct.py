"""Share of the device's busy time under ``attention`` / ``global``, every
phase, first chip: the attention layer's whole mixer (32 query heads over 8 KV
heads of 64 lanes: the projections, the per-head QK-norm and rotary on the
``jnp`` chain, the flash kernels; ``perfbench/harness/sconv_scopes.py``)."""
from perfbench.harness import sconv_scopes

LAYER, UNIT, MOVES = "model", "%", "tokens_per_s_per_chip"


def read(trace, run):
    return sconv_scopes.scope_share(trace, run, "global")
