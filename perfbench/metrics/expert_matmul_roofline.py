"""The expert matmuls' share of their roofline: the least time the chip could
take for one step's six grouped matmuls a layer (operations and bytes from
shapes, ``perfbench/harness/flops_moe.py::expert_matmul_cost``) over the
device time under the scope ``experts``, first chip, per traced step. The
scope also holds the activation and the weights' casts: time the experts
took, not work the matmuls need, so it lowers this share."""
from perfbench.harness import flops, flops_moe, moe_scopes

LAYER, UNIT, MOVES = "kernels", "%", "tokens_per_s_per_chip"


def read(trace, run):
    took = moe_scopes.seconds(trace, run, ("experts",))
    if run["peaks"] is None or not took:
        return None
    w = run["workload"]
    tokens = w["grad_accum"] * w["micro_batch_per_chip"] * w["seq_len"] * run["traced_steps"]
    least, bound = flops.roofline_seconds(
        *flops_moe.expert_matmul_cost(run["shape"], tokens), run["peaks"])
    print(f"perfbench: expert matmuls are {bound}-bound; least {least:.4f} s, took "
          f"{took:.4f} s over the traced steps", flush=True)
    return 100.0 * least / took
