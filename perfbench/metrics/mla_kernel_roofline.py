"""The 192 / 128 attention kernels' share of their roofline: the least time the
chip could take for one step's forward and fused backward passes (operations
and bytes from shapes, ``perfbench/harness/flops_mla.py::mla_kernel_cost``)
over the device time of the calls named ``flash_fwd`` and ``flash_bwd_fused``,
first chip, per traced step. A policy that rematerializes the layer runs the
forward kernel twice; the second run lowers this share."""
from perfbench.harness import flops, flops_mla, mla_scopes

LAYER, UNIT, MOVES = "kernels", "%", "tokens_per_s_per_chip"


def read(trace, run):
    found = mla_scopes.kernel_seconds(trace, run)
    if run["peaks"] is None or found is None or not found[0]:
        return None
    w = run["workload"]
    sequences = w["grad_accum"] * w["micro_batch_per_chip"] * run["traced_steps"]
    least, bound = flops.roofline_seconds(
        *flops_mla.mla_kernel_cost(run["shape"], sequences), run["peaks"])
    print(f"perfbench: mla kernels are {bound}-bound; least {least:.4f} s, took "
          f"{found[0]:.4f} s over the traced steps", flush=True)
    return 100.0 * least / found[0]
