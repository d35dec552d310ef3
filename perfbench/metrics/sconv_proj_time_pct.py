"""Share of the device's busy time under ``attention`` / ``conv`` / ``sconv_in``
and ``sconv_out``, every phase, first chip: the convolution mixers' two
projections, 2048 -> 6144 and 2048 -> 2048, with their gradients and remat's
second run of the first where the policy drops it
(``perfbench/harness/sconv_scopes.py``)."""
from perfbench.harness import sconv_scopes

LAYER, UNIT, MOVES = "model", "%", "tokens_per_s_per_chip"


def read(trace, run):
    return sconv_scopes.scope_share(trace, run, "sconv_in", "sconv_out")
