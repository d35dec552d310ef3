"""Collective operations in the compiled step's HLO text (an async pair
counts once, at its start). A count of the program: it repeats exactly."""
import re

LAYER, UNIT, MOVES = "strategies and mesh", "count", "tokens_per_s_per_chip"

OP = re.compile(
    r"= \S+ (all-gather|all-reduce|reduce-scatter|collective-permute|all-to-all)(-start)?\("
)


def read(trace, run):
    if run["chips"] == 1:
        return None
    return len(OP.findall(run["hlo_text"]))
