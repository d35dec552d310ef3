"""The attention layer's flash kernels' share of their roofline: the least time
for its forward and fused backward at 32 query heads over 8 KV heads of 64
lanes under causal (``flops_lfm2.global_kernel_cost``: the FlashAttention-2
count over S (S + 1) / 2 pairs, k and v counted at their own head count), over
the device time of the calls ``flash_fwd`` and ``flash_bwd_fused`` under
``attention`` / ``global``, first chip; prints which bound."""
from perfbench.harness import flops_lfm2, sconv_scopes

LAYER, UNIT, MOVES = "kernels", "%", "tokens_per_s_per_chip"


def read(trace, run):
    return sconv_scopes.kernel_roofline(
        trace, run, sconv_scopes.FLASH_KERNELS, flops_lfm2.global_kernel_cost,
        "the attention layer's flash kernels")
