"""Share of the device's busy time in matrix multiplications, first chip: ops
that are a convolution or dot, or a fusion whose computation in the compiled
step's HLO text holds one. The fusion's other work (a bias, a cast, an
activation fused into it) counts with it."""
from perfbench.harness import trace_reduce

LAYER, UNIT, MOVES = "model", "%", "tokens_per_s_per_chip"


def read(trace, run):
    if not trace.devices():
        return None
    matmuls = trace_reduce.matmul_computations(run["hlo_text"])
    kinds, busy = trace_reduce.kind_seconds(trace, trace.devices()[0], matmuls)
    return 100.0 * kinds["matmul"] / busy if matmuls else None
