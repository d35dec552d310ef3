"""The attention block's flash kernels' share of their roofline: the least
time for its forward and fused backward at 32 query heads over 2 KV heads of
128 under causal (``flops_nemotron.global_kernel_cost``: k and v counted at
their own head count), over the device time of the calls ``flash_fwd`` and
``flash_bwd_fused`` under ``attention`` / ``global``, first chip; prints which
bound."""
from perfbench.harness import flops_nemotron, ssd_scopes

LAYER, UNIT, MOVES = "kernels", "%", "tokens_per_s_per_chip"


def read(trace, run):
    return ssd_scopes.kernel_roofline(
        trace, run, ssd_scopes.FLASH_KERNELS, flops_nemotron.global_kernel_cost,
        "the attention block's flash kernels")
