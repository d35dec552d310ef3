"""The attention kernels' share of their roofline under the block-diffusion
rule: the least time the chip could take for one step's forward and fused
backward passes over the rule's TRUE pairs (operations and bytes from shapes,
``perfbench/harness/flops_bd.py::bd_kernel_cost``) over the device time of the
calls named ``flash_fwd`` and ``flash_bwd_fused``, first chip, per traced
step. What lowers it besides the kernels' own pace: the masked part of every
live tile (``bd_live_fill_pct``), which the kernels compute and the count
leaves out, and a policy that rematerializes the layer (the forward kernel
runs twice)."""
from perfbench.harness import bd_scopes, flops, flops_bd

LAYER, UNIT, MOVES = "kernels", "%", "tokens_per_s_per_chip"


def read(trace, run):
    found = bd_scopes.kernel_seconds(trace, run)
    if run.get("peaks") is None or found is None or not found[0]:
        return None
    w = run["workload"]
    documents = w["grad_accum"] * w["micro_batch_per_chip"] * run["traced_steps"]
    least, bound = flops.roofline_seconds(
        *flops_bd.bd_kernel_cost(run["shape"], documents), run["peaks"])
    print(f"perfbench: block-diffusion kernels are {bound}-bound; least {least:.4f} s, took "
          f"{found[0]:.4f} s over the traced steps", flush=True)
    return 100.0 * least / found[0]
