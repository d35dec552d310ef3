"""Share of the device's busy time in the Mosaic calls named ``flash_fwd`` and
``flash_bwd_fused``, first chip: the attention kernels under the
block-diffusion rule over the stream of two copies, and nothing else
(``attn_kernel_time_pct`` counts every Mosaic call, the grouped matmuls too)."""
from perfbench.harness import bd_scopes

LAYER, UNIT, MOVES = "kernels", "%", "tokens_per_s_per_chip"


def read(trace, run):
    found = bd_scopes.kernel_seconds(trace, run)
    return None if found is None or not found[0] else 100.0 * found[0] / found[1]
