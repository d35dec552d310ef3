"""The gated convolution's kernels' share of their roofline: the least time the
chip could take for the conv layers' forward and backward calls
(``flops_lfm2.sconv_kernel_cost``: every operand and result once at its stored
width, forward the (S, 3 D) operand in and (S, D) out, backward the operand, the
cotangent and the (S, 3 D) result; the greater of the memory and the compute
time) over the device time of the calls ``sconv_fwd`` and ``sconv_bwd``, first
chip; prints which bound. The forward is counted as often as the trace shows it
a backward call: twice where the remat policy runs it again, so a second run
does not lower this share."""
from perfbench.harness import flops_lfm2, sconv_scopes

LAYER, UNIT, MOVES = "kernels", "%", "tokens_per_s_per_chip"


def read(trace, run):
    reduced = sconv_scopes.found(trace, run)
    if reduced is None or not reduced["calls"]["sconv_bwd"]:
        return None
    forwards = reduced["calls"]["sconv_fwd"] / reduced["calls"]["sconv_bwd"]
    return sconv_scopes.kernel_roofline(
        trace, run, sconv_scopes.SCONV_KERNELS,
        lambda m, sequences: flops_lfm2.sconv_kernel_cost(m, sequences, forwards),
        f"the gated convolution's kernels ({forwards:g} forward calls a backward call)")
