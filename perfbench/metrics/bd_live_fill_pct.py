"""The rule's true pairs over the area of the tiles the kernels visit, forward
and backward together, one head: what share of the scores the kernels compute
the mask lets through. From the program's counter at trace time
(``tinygpt.bd_mask_stats``: the mask rule's own tile liveness at the tiles the
flash call picks), not from the trace. 100 would be a kernel that skips every
masked score; a document of 8192 in blocks of 4 at (1024, 1024) tiles reads
80.0 (8 of its 80 live tiles are noisy -> noisy diagonals, 0.4 % live each)."""
LAYER, UNIT, MOVES = "kernels", "%", "tokens_per_s_per_chip"


def read(trace, run):
    stats = run.get("bd_mask_stats")
    if not stats:
        return None
    visited = (stats["fwd_live_tiles"] * stats["fwd_tile_pairs"]
               + stats["bwd_live_tiles"] * stats["bwd_tile_pairs"])
    return 100.0 * 2 * stats["true_pairs"] / visited
