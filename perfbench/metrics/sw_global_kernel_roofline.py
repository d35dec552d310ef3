"""The global layers' attention kernels' share of their roofline: as
``sw_window_kernel_roofline`` under causal (``flops_mellum.global_kernel_cost``:
S (S + 1) / 2 pairs a head), over the ``flash_fwd`` and ``flash_bwd_fused``
calls under ``attention`` / ``global``."""
from perfbench.harness import flops_mellum, sw_scopes

LAYER, UNIT, MOVES = "kernels", "%", "tokens_per_s_per_chip"


def read(trace, run):
    return sw_scopes.kernel_roofline(trace, run, "global", flops_mellum.global_kernel_cost)
