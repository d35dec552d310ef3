"""Grid steps of the window layers' two kernels that bring (or hold) a tile and
multiply nothing, over the steps on a live tile, one head, forward and backward
together: from the program's counter (``tinygpt.attn_mask_stats``). 7.3 on the
square's grid at S 16,384 and a window of 1024 (225 dead tiles of 256); on the
band 1 of 32, the clipped corner: 0.032."""
LAYER, UNIT, MOVES = "kernels", "ratio", "tokens_per_s_per_chip"


def read(trace, run):
    stats = (run.get("attn_mask_stats") or {}).get("window")
    if not stats:
        return None
    live = stats["fwd_live_tiles"] + stats["bwd_live_tiles"]
    return (stats["fwd_grid_steps"] + stats["bwd_grid_steps"] - live) / live
