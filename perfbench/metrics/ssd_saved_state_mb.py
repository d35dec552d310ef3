"""What one Mamba-2 block's forward keeps for its backward beside its
operands, a sequence, in MB (10^6 bytes): the states entering the chunks, from
the program's counter at trace time (``tinygpt.ssd_stats``: chunks x d_inner x
state at the stored width), not from the trace. Halves as the chunk doubles."""
LAYER, UNIT, MOVES = "kernels", "MB", "tokens_per_s_per_chip"


def read(trace, run):
    stats = run.get("ssd_stats")
    return None if not stats else stats["saved_state_bytes"] / 1e6
