"""The scan's kernels' share of their roofline: the least time the chip could
take for the Mamba-2 blocks' forward and backward scans
(``flops_nemotron.ssd_kernel_cost``: the chunkwise form's work at a chunk of
128, every operand and result once at its stored width, the greater of the
memory and the MXU time; what the forward keeps for the backward is the
kernels' choice and not counted) over the device time of the calls ``ssd_fwd``
and ``ssd_bwd``, first chip; prints which bound. A policy that rematerializes
the block runs ``ssd_fwd`` twice; the second run lowers this share."""
from perfbench.harness import flops_nemotron, ssd_scopes

LAYER, UNIT, MOVES = "kernels", "%", "tokens_per_s_per_chip"


def read(trace, run):
    return ssd_scopes.kernel_roofline(
        trace, run, ssd_scopes.SSD_KERNELS, flops_nemotron.ssd_kernel_cost, "the ssd kernels")
