"""The attention kernels' share of their roofline: the least time the chip
could take for the attention passes that run as kernels in this cell, over
the device time the kernels took, first chip, per traced step.

Which passes are kernels: the forward always (attention "flash"); the
backward from S >= 4096, where ``ops/flash_attention.py`` switches from XLA
einsums to its two Pallas kernels. A policy that rematerializes the layer
runs the forward kernel twice; the second run is time the kernels took and
not work the algorithm needs, so it lowers this share.
"""
from perfbench.harness import flops, trace_reduce

LAYER, UNIT, MOVES = "kernels", "%", "tokens_per_s_per_chip"
PALLAS_BACKWARD_FROM = 4096  # ops/flash_attention.py::_PALLAS_BWD_MIN_SEQ


def read(trace, run):
    if run["peaks"] is None or not trace.devices() or run["workload"]["attention"] != "flash":
        return None
    kinds, _ = trace_reduce.kind_seconds(trace, trace.devices()[0])
    if not kinds["attention kernel"]:
        return None
    w = run["workload"]
    passes = ("fwd", "bwd") if w["seq_len"] >= PALLAS_BACKWARD_FROM else ("fwd",)
    sequences = w["grad_accum"] * w["micro_batch_per_chip"] * run["traced_steps"]
    least, bound = flops.roofline_seconds(
        *flops.attention_pass_cost(run["shape"], sequences, passes), run["peaks"]
    )
    print(f"perfbench: attention kernels are {bound}-bound; least {least:.4f} s, "
          f"took {kinds['attention kernel']:.4f} s over the traced steps", flush=True)
    return 100.0 * least / kinds["attention kernel"]
