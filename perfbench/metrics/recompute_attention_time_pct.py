"""``recompute_time_pct``'s part under the scope ``attention``: share of the
device's busy time in ops that remat runs a second time
(``rematted_computation`` in their ``op_name``) and whose outermost module is
``attention``, first chip (``perfbench/harness/scopes.py``)."""
from perfbench.harness import scopes

LAYER, UNIT, MOVES = "train step", "%", "tokens_per_s_per_chip"


def read(trace, run):
    return scopes.share(trace, run, lambda s: s.recompute and s.module == "attention")
