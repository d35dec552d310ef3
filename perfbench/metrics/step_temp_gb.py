"""What the compiled step holds at its peak beyond its arguments and outputs,
GB: buffer assignment's ``temp_bytes`` from the program's record
(``utils/scopes.step_memory()["compiled"]``, written where
``train/step.py::aot_compile`` compiles): the gradients, what the forward
keeps for the backward, the kernels' scratch. ``hbm_peak_gb`` holds the state
as well."""
from perfbench.harness import step_memory

LAYER, UNIT, MOVES = "train step", "GB", "tokens_per_s_per_chip"


def read(trace, run):
    return step_memory.metric("step_temp_gb", trace, run)
