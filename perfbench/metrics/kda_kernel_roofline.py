"""The recurrence's kernels' share of their roofline: the least time the chip
could take for the KDA layers' forward and backward passes
(``flops_kda.kda_kernel_cost``: the chunkwise form's work at a chunk of 64,
every operand and result once at its stored width; what the forward keeps for
the backward is the kernels' choice and not counted) over the device time of
the calls ``kda_fwd`` and ``kda_bwd``, first chip; prints which bound. A
policy that rematerializes the layer runs ``kda_fwd`` twice; the second run
lowers this share."""
from perfbench.harness import flops_kda, kda_scopes

LAYER, UNIT, MOVES = "kernels", "%", "tokens_per_s_per_chip"


def read(trace, run):
    return kda_scopes.kernel_roofline(
        trace, run, kda_scopes.KDA_KERNELS, flops_kda.kda_kernel_cost, "the kda kernels")
