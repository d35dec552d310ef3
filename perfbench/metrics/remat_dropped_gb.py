"""The memory remat's second run buys, GB a micro-batch a chip: the sum of the
program's ``saved()["all"]`` (the same loss closure under ``remat="none"``)
minus the sum of ``saved()["kept"]``. ``recompute_time_pct`` is what it costs;
the ``perfbench: memory:`` line has both by module."""
from perfbench.harness import step_memory

LAYER, UNIT, MOVES = "train step", "GB", "tokens_per_s_per_chip"


def read(trace, run):
    return step_memory.metric("remat_dropped_gb", trace, run)
