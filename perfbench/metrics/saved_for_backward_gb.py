"""What one micro-batch's forward keeps for its backward on one chip under the
cell's remat policy, GB: the sum of the program's ``saved()["kept"]``
(``utils/scopes.step_memory()["saved"]``, a trace of the step's own loss
closure from shapes, made when asked). Parameters, constants and values
computed from the weights alone are not in it (the ``perfbench: memory:``
line gives them apart)."""
from perfbench.harness import step_memory

LAYER, UNIT, MOVES = "train step", "GB", "tokens_per_s_per_chip"


def read(trace, run):
    return step_memory.metric("saved_for_backward_gb", trace, run)
