"""The window rule's true pairs over the pairs the window layers' two kernels
multiply, forward and backward together, one head: from the program's counter
at trace time (``tinygpt.attn_mask_stats``: the rule's own tile liveness and
the bodies' piece walks at the tiles the flash call picks), not from the
trace. 50.0 at whole (1024, 1024) tiles under a window of 1024 (a trailing-edge
tile and a diagonal one a query tile, half of each true), 63.3 with the *lower*
body on the diagonal tiles (64.6 forward in pieces of 128, 62.0 backward in
pieces of 256), 89 with a body for the trailing-edge tile too."""
LAYER, UNIT, MOVES = "kernels", "%", "tokens_per_s_per_chip"


def read(trace, run):
    stats = (run.get("attn_mask_stats") or {}).get("window")
    if not stats:
        return None
    return 100.0 * 2 * stats["true_pairs"] / (
        stats["fwd_pairs_multiplied"] + stats["bwd_pairs_multiplied"])
