"""The full layers' attention kernels' share of their roofline: as
``lg_window_kernel_roofline`` under causal at 48 heads
(``flops_laguna.global_kernel_cost``: S (S + 1) / 2 pairs a head), the calls
under ``attention`` / ``global``."""
from perfbench.harness import flops_laguna, lg_scopes

LAYER, UNIT, MOVES = "kernels", "%", "tokens_per_s_per_chip"


def read(trace, run):
    return lg_scopes.kernel_roofline(trace, run, "global", flops_laguna.global_kernel_cost)
