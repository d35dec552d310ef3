"""What one KDA layer's forward keeps for its backward beside its operands, a
sequence, in MB (10^6 bytes): the states entering the chunks, from the
program's counter at trace time (``tinygpt.kda_stats``: heads x chunks x
head_dim^2 at the stored width), not from the trace. Halves as the chunk
doubles."""
LAYER, UNIT, MOVES = "kernels", "MB", "tokens_per_s_per_chip"


def read(trace, run):
    stats = run.get("kda_stats")
    return None if not stats else stats["saved_state_bytes"] / 1e6
