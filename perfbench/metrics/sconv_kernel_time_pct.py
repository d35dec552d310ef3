"""Share of the device's busy time in the gated convolution's Mosaic calls,
``sconv_fwd`` and ``sconv_bwd`` (``ops/kda.py::gated_conv``), first chip
(``perfbench/harness/sconv_scopes.py``)."""
from perfbench.harness import sconv_scopes

LAYER, UNIT, MOVES = "kernels", "%", "tokens_per_s_per_chip"


def read(trace, run):
    return sconv_scopes.kernel_share(trace, run, sconv_scopes.SCONV_KERNELS)
