"""The latent-attention layer's flash kernels' share of their roofline: the
least time for its forward and fused backward at 192-wide keys over 128-wide
values, causal (``flops_kda.global_kernel_cost``, which is
``flops_mla.mla_kernel_cost`` over the ``global`` layers alone), over the
device time of the calls ``flash_fwd`` and ``flash_bwd_fused`` under
``attention`` / ``global``, first chip; prints which bound."""
from perfbench.harness import flops_kda, kda_scopes

LAYER, UNIT, MOVES = "kernels", "%", "tokens_per_s_per_chip"


def read(trace, run):
    return kda_scopes.kernel_roofline(
        trace, run, kda_scopes.FLASH_KERNELS, flops_kda.global_kernel_cost,
        "the global layer's flash kernels")
