"""What a stack of Mamba-2 blocks beside attention and routed ones adds to a
step, in a device trace: the scopes ``ssd`` and ``global`` under ``attention``
(the program's ``utils/scopes.LAYER_KIND_SCOPES``), ``ssd_prep`` / ``ssd_core``
/ ``ssd_out`` below ``ssd`` (``SSD_SCOPES``; this is the benchmark's copy), the
scan's Mosaic calls ``ssd_fwd`` / ``ssd_bwd``, the convolution's ``kda_conv_fwd``
/ ``kda_conv_bwd`` under ``ssd`` and the flash calls under ``global`` by the
names the program gives them, and the held experts' scope ``experts`` under
``mlp``.

The join is ``scopes.py``'s: trace event -> instruction name -> ``op_name`` of
the compiled step's text, where a scope is a path component, plain or wrapped
(``jvp(attention)/ssd/ssd_prep/...``, ``transpose(jvp(attention))/global/...``).
Self times of the first chip over the traced steps. A program without the
scope ``ssd`` (any other model, or the parent of the PR that brought it) gives
every reader nothing.
"""

import collections
import functools

from . import mla_scopes, scopes, trace_reduce

KINDS = ("ssd", "global")
PARTS = ("ssd_prep", "ssd_core", "ssd_out")
SSD_KERNELS = ("ssd_fwd", "ssd_bwd")
CONV_KERNELS = ("kda_conv_fwd", "kda_conv_bwd")
FLASH_KERNELS = ("flash_fwd", "flash_bwd_fused")
CALLS = {"ssd": SSD_KERNELS + CONV_KERNELS, "global": FLASH_KERNELS}


def kind_and_part(op_name):
    """-> (``ssd`` | ``global`` | None, ``ssd_prep`` | ``ssd_core`` |
    ``ssd_out`` | None): of the first of the ``;``-joined paths that names
    ``attention`` and, below it, a kind."""
    for path in op_name.split(";"):
        plain = [scopes._unwrap(c) for c in path.split("/")]
        if "attention" in plain:
            below = plain[plain.index("attention") + 1:]
            if kind := next((c for c in below if c in KINDS), None):
                return kind, next((c for c in below if c in PARTS), None)
    return None, None


@functools.lru_cache(maxsize=1)  # the readers of one run share one reduction
def _first_chip(trace, hlo_text):
    names = scopes.op_names(hlo_text)
    if not any(kind_and_part(op_name)[0] == "ssd" for op_name in names.values()):
        return None
    scope, kernels, experts, busy = collections.Counter(), collections.Counter(), 0.0, 0.0
    for event, self_s, _ in trace_reduce.self_times(trace.ops(trace.devices()[0])):
        busy += self_s
        op_name = names.get(scopes.instruction_name(event), "")
        kind, part = kind_and_part(op_name)
        if kind:
            scope[kind] += self_s
            if part:
                scope[part] += self_s
            if trace_reduce.MOSAIC in event.name:
                call = trace_reduce.base_name(event)
                if call in CALLS[kind]:
                    kernels[call] += self_s
        if mla_scopes.part(op_name) == ("mlp", "experts"):
            experts += self_s
    if not busy:
        return None
    by_name = ", ".join(f"{name} {s:.4f}" for name, s in sorted(kernels.items()))
    print(f"perfbench: ssd: self seconds over the traced steps, first chip, busy {busy:.4f} s: "
          f"attention.ssd {scope['ssd']:.4f} (ssd_prep {scope['ssd_prep']:.4f}, ssd_core "
          f"{scope['ssd_core']:.4f}, ssd_out {scope['ssd_out']:.4f}), attention.global "
          f"{scope['global']:.4f}, mlp.experts {experts:.4f}; their kernels: {by_name}", flush=True)
    return {"scope": scope, "kernels": kernels, "experts": experts, "busy": busy}


def found(trace, run):
    """{``scope``: self seconds under attention / kind and under each of the
    Mamba-2 block's three parts, ``kernels``: self seconds of the Mosaic calls
    by name (the scan's and the convolution's under ``ssd``, the flash calls
    under ``global``), ``experts``: self seconds under mlp / experts,
    ``busy``} of the first chip, or None."""
    if not trace.devices() or not run.get("hlo_text"):
        return None
    return _first_chip(trace, run["hlo_text"])


def scope_share(trace, run, name):
    """100 x (self seconds under the scope) / (busy self seconds)."""
    reduced = found(trace, run)
    return None if reduced is None else 100.0 * reduced["scope"][name] / reduced["busy"]


def kernel_share(trace, run, names):
    reduced = found(trace, run)
    if reduced is None:
        return None
    return 100.0 * sum(reduced["kernels"][n] for n in names) / reduced["busy"]


def kernel_roofline(trace, run, names, cost, what):
    """100 x (least time for the named calls over the traced steps, by
    ``cost(shape, sequences)`` and the chip's peaks) / (those calls' device
    time); prints which bound."""
    from . import flops

    reduced = found(trace, run)
    took = reduced and sum(reduced["kernels"][n] for n in names)
    if run.get("peaks") is None or not took:
        return None
    w = run["workload"]
    sequences = w["grad_accum"] * w["micro_batch_per_chip"] * run["traced_steps"]
    least, bound = flops.roofline_seconds(*cost(run["shape"], sequences), run["peaks"])
    print(f"perfbench: {what} are {bound}-bound; least {least:.4f} s, took {took:.4f} s over "
          f"the traced steps", flush=True)
    return 100.0 * least / took
