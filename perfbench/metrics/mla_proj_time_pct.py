"""Share of the device's busy time under ``attention`` / ``mla_proj``, every
phase, first chip: latent attention's three projections (q whole, down to the
latent and the rotary key, the latent up to k_nope and v), the latent's norm,
the rotary on a part of the head and assembling the 192-wide keys: what
ordinary attention does in one fused projection
(``perfbench/harness/mla_scopes.py``)."""
from perfbench.harness import mla_scopes

LAYER, UNIT, MOVES = "model", "%", "tokens_per_s_per_chip"


def read(trace, run):
    return mla_scopes.share(trace, run, "attention", ("mla_proj",))
