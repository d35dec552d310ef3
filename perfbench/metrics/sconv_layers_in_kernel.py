"""The gated short-convolution layers whose convolution the Mosaic calls
``sconv_fwd`` / ``sconv_bwd`` take, from the program's counter at trace time
(``tinygpt.sconv_stats``), not from the trace: 4 of 4 on the chip at the cell's
widths, 0 where the ``jnp`` chain runs (another backend, or columns that are
not whole 128-lane tiles)."""
LAYER, UNIT, MOVES = "kernels", "layers", "tokens_per_s_per_chip"


def read(trace, run):
    stats = run.get("sconv_stats")
    return None if not stats else stats["layers_in_kernel"]
