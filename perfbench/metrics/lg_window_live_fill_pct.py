"""The 512-key window rule's true pairs over the pairs the sliding layers' two
kernels multiply, forward and backward together, one head: from the program's
counter at trace time (``tinygpt.attn_mask_stats``: the rule's own tile
liveness and the bodies' piece walks **at the tiles the flash call took**,
which since PR 47 follow a window narrower than the default tile), not from
the trace. 32.1 at the default (1024, 1024) tiles, 59.4 at (512, 512), 69.6 at
(256, 256)."""
LAYER, UNIT, MOVES = "kernels", "%", "tokens_per_s_per_chip"


def read(trace, run):
    stats = (run.get("attn_mask_stats") or {}).get("window")
    if not stats or "fwd_tile" not in stats:  # the counter of a program without tiles by window
        return None
    return 100.0 * 2 * stats["true_pairs"] / (
        stats["fwd_pairs_multiplied"] + stats["bwd_pairs_multiplied"])
