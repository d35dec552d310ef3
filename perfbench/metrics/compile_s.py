"""Wall seconds of the step's ``aot_compile``: trace, lower, and the compiler
or, in every run of a checkout but the first, the read from the cache."""
LAYER, UNIT, MOVES = "set-up", "s", "setup_s"


def read(trace, run):
    return run["compile_s"]
