"""Backend compilations jax reported inside the traced window; must be 0."""
LAYER, UNIT, MOVES = "train step", "count", "tokens_per_s_per_chip"


def read(trace, run):
    return run["compiles_in_window"]
