"""Routing imbalance of the step's first micro-batch at the seeded initial
weights: the busiest expert's assignments over the mean (tokens x experts a
token / experts), the worst layer. 1.0 is even; the grouped matmuls take the
same FLOPs either way, but their tiles and an 'expert' axis do not. From the
program's counter (``tinygpt.moe_expert_counts``), not from the trace."""
LAYER, UNIT, MOVES = "model", "ratio", "tokens_per_s_per_chip"


def read(trace, run):
    return run.get("expert_load_max_over_mean")
