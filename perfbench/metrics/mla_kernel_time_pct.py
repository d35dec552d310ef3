"""Share of the device's busy time in the Mosaic calls named ``flash_fwd`` and
``flash_bwd_fused``, first chip: the attention kernels at 192-wide keys over
128-wide values, and nothing else (``attn_kernel_time_pct`` counts every
Mosaic call, the grouped matmuls too)."""
from perfbench.harness import mla_scopes

LAYER, UNIT, MOVES = "kernels", "%", "tokens_per_s_per_chip"


def read(trace, run):
    found = mla_scopes.kernel_seconds(trace, run)
    return None if found is None or not found[0] else 100.0 * found[0] / found[1]
