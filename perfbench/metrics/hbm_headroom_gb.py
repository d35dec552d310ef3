"""What a remat policy or a larger micro-batch may still spend, GB: the
smallest ``bytes_limit`` the mesh's devices' allocators report minus the
compiled step's buffer-assignment peak, both from the program's record
(``utils/scopes.step_memory()``: ``["bytes_limit"]``, ``["compiled"]
["peak_bytes"]``). None where the backend gives no limit."""
from perfbench.harness import step_memory

LAYER, UNIT, MOVES = "device", "GB", "tokens_per_s_per_chip"


def read(trace, run):
    return step_memory.metric("hbm_headroom_gb", trace, run)
