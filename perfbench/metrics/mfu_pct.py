"""Model FLOPs utilization: the model's FLOPs a token (the benchmark's own
count; recomputation not counted, a causal mask counted as half) times the
tokens/s/chip of the untraced stretch, over the chip's published bf16 peak."""
LAYER, UNIT, MOVES = "model", "%", "tokens_per_s_per_chip"


def read(trace, run):
    if run["peaks"] is None:
        return None
    return 100.0 * run["flops_per_token"] * run["tokens_per_s_per_chip"] / run["peaks"]["bf16_flops"]
