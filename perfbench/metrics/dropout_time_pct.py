"""Share of the device's busy time under the scope ``dropout`` (mask generation
and select of ``models/tinygpt.py::_dropout``), whatever module it is nested in,
first chip (``perfbench/harness/scopes.py``). The attention-probability mask is
made inside the kernels and is not in it."""
from perfbench.harness import scopes

LAYER, UNIT, MOVES = "model", "%", "tokens_per_s_per_chip"


def read(trace, run):
    return scopes.share(trace, run, lambda s: s.dropout)
