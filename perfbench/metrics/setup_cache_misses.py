"""Programs jax compiled and wrote to the compile cache before the first timed
step (``/jax/compilation_cache/cache_misses``, the program's listener): 0 in a
warm run; more than 0 says that a slow set-up was a cold one."""
from perfbench.harness import host_spans

LAYER, UNIT, MOVES = "set-up", "count", "setup_s"


def read(trace, run):
    return host_spans.metric("setup_cache_misses", trace, run)
