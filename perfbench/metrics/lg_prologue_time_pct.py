"""Share of the device's busy time in ``ops/rotary.py``'s pass over q and k
(the Mosaic calls ``qk_prologue_fwd`` / ``qk_prologue_bwd`` under either kind
of layer: whole heads on the sliding layers, the leading 64 lanes of each head
on the full ones), first chip. No result where no layer took the pass: the
run's ``qk_prologue_stats`` line says which chain ran
(``perfbench/harness/lg_scopes.py``)."""
from perfbench.harness import lg_scopes

LAYER, UNIT, MOVES = "kernels", "%", "tokens_per_s_per_chip"


def read(trace, run):
    moved_took = lg_scopes.prologue(trace, run)
    if moved_took is None:
        return None
    return 100.0 * moved_took[1] / lg_scopes.found(trace, run)["busy"]
