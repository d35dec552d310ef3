"""Share of the device's busy time in the scan's Mosaic calls, ``ssd_fwd`` and
``ssd_bwd`` (``ops/ssd.py``), first chip (``perfbench/harness/ssd_scopes.py``)."""
from perfbench.harness import ssd_scopes

LAYER, UNIT, MOVES = "kernels", "%", "tokens_per_s_per_chip"


def read(trace, run):
    return ssd_scopes.kernel_share(trace, run, ssd_scopes.SSD_KERNELS)
