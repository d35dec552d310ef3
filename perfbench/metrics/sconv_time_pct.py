"""Share of the device's busy time under ``attention`` / ``conv``, every phase,
first chip: the gated short-convolution layers' whole mixers (norm, the input
projection, the gated convolution's kernels, the output projection), all of
them together (``perfbench/harness/sconv_scopes.py``)."""
from perfbench.harness import sconv_scopes

LAYER, UNIT, MOVES = "model", "%", "tokens_per_s_per_chip"


def read(trace, run):
    return sconv_scopes.scope_share(trace, run, "conv")
