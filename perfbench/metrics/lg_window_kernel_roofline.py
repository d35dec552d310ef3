"""The sliding layers' attention kernels' share of their roofline: the least
time the chip could take for their forward and fused backward passes over the
rule's TRUE pairs at 64 heads (``flops_laguna.window_kernel_cost``: (4 + 10) x
pairs x head_dim a head, W (W + 1) / 2 + (S - W) W pairs at W 512, operands
once) over the device time of the ``flash_fwd`` and ``flash_bwd_fused`` calls
under ``attention`` / ``window``, first chip. What lowers it besides the
kernels' own pace: what a live tile multiplies beside its true pairs at the
tiles taken (``lg_window_live_fill_pct``)."""
from perfbench.harness import flops_laguna, lg_scopes

LAYER, UNIT, MOVES = "kernels", "%", "tokens_per_s_per_chip"


def read(trace, run):
    return lg_scopes.kernel_roofline(trace, run, "window", flops_laguna.window_kernel_cost)
