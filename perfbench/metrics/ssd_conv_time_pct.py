"""Share of the device's busy time in the convolution's Mosaic calls under
``attention`` / ``ssd``, ``kda_conv_fwd`` and ``kda_conv_bwd`` with their bias
and SiLU over the 6144 columns of x | B | C (``ops/kda.py::conv_silu``), first
chip (``perfbench/harness/ssd_scopes.py``). The kernels are the Kimi cell's,
with one more row of taps: they bring no roofline of their own here."""
from perfbench.harness import ssd_scopes

LAYER, UNIT, MOVES = "kernels", "%", "tokens_per_s_per_chip"


def read(trace, run):
    return ssd_scopes.kernel_share(trace, run, ssd_scopes.CONV_KERNELS)
