"""The rotary pass's share of its roofline: the bytes its calls that ran need
(``flops_laguna.prologue_call_bytes``: a layer's q and k in and out once a
call, at the kind's head count) over the chip's HBM rate, over those calls'
device time. The pass is memory-bound by construction (a few operations a
byte). No result where no layer took the pass."""
from perfbench.harness import lg_scopes

LAYER, UNIT, MOVES = "kernels", "%", "tokens_per_s_per_chip"


def read(trace, run):
    moved_took = lg_scopes.prologue(trace, run)
    if moved_took is None or run.get("peaks") is None:
        return None
    moved, took = moved_took
    least = moved / run["peaks"]["hbm_bytes_per_s"]
    print(f"perfbench: the rotary pass moves {moved / 1e9:.2f} GB over the traced steps; least "
          f"{least:.4f} s, took {took:.4f} s", flush=True)
    return 100.0 * least / took
