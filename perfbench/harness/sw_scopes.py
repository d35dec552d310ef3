"""What a stack that mixes sliding-window and global layers adds to a step, in
a device trace: the scopes ``window`` and ``global`` under ``attention`` (the
program's ``utils/scopes.LAYER_KIND_SCOPES``; this is the benchmark's copy),
the attention kernels by the names the program gives their calls *and by the
kind of the layer that calls them*, and the held experts' scope ``experts``
under ``mlp`` (``mla_scopes`` finds it too, but gives nothing to a model
without latent attention).

The join is ``scopes.py``'s: trace event -> instruction name -> ``op_name`` of
the compiled step's text, where a scope is a path component, plain or wrapped
(``jvp(attention)/window/...``, ``transpose(jvp(attention))/global/...``). Self
times of the first chip over the traced steps. A program without the scopes
(any other model, or the parent of the PR that brought them) gives every
reader nothing.
"""

import collections
import functools

from . import mla_scopes, scopes, trace_reduce

KINDS = ("window", "global")
KERNELS = ("flash_fwd", "flash_bwd_fused")


def kind_of(op_name):
    """-> ``window`` | ``global`` | None: the first of the ``;``-joined paths
    that names ``attention`` and, below it, a kind."""
    for path in op_name.split(";"):
        plain = [scopes._unwrap(c) for c in path.split("/")]
        if "attention" in plain:
            below = plain[plain.index("attention") + 1:]
            if found := next((c for c in below if c in KINDS), None):
                return found
    return None


@functools.lru_cache(maxsize=1)  # the readers of one run share one reduction
def _first_chip(trace, hlo_text):
    names = scopes.op_names(hlo_text)
    if not any(kind_of(op_name) for op_name in names.values()):
        return None
    scope, kernels, experts, busy = collections.Counter(), collections.Counter(), 0.0, 0.0
    for event, self_s, _ in trace_reduce.self_times(trace.ops(trace.devices()[0])):
        busy += self_s
        op_name = names.get(scopes.instruction_name(event), "")
        kind = kind_of(op_name)
        if kind:
            scope[kind] += self_s
            if trace_reduce.MOSAIC in event.name and trace_reduce.base_name(event) in KERNELS:
                kernels[kind, trace_reduce.base_name(event)] += self_s
        if mla_scopes.part(op_name) == ("mlp", "experts"):
            experts += self_s
    if not busy:
        return None
    by_name = ", ".join(f"{kind}.{name} {s:.4f}" for (kind, name), s in sorted(kernels.items()))
    print(f"perfbench: layer kinds: self seconds over the traced steps, first chip, busy "
          f"{busy:.4f} s: attention.window {scope['window']:.4f}, attention.global "
          f"{scope['global']:.4f}, mlp.experts {experts:.4f}; their flash kernels: {by_name}",
          flush=True)
    return {"scope": scope, "kernels": kernels, "experts": experts, "busy": busy}


def found(trace, run):
    """{``scope``: self seconds under attention / kind, ``kernels``: self
    seconds of the flash calls by (kind, call name), ``experts``: self
    seconds under mlp / experts, ``busy``} of the first chip, or None."""
    if not trace.devices() or not run.get("hlo_text"):
        return None
    return _first_chip(trace, run["hlo_text"])


def scope_share(trace, run, kind):
    """100 x (self seconds under attention / kind) / (busy self seconds)."""
    reduced = found(trace, run)
    return None if reduced is None else 100.0 * reduced["scope"][kind] / reduced["busy"]


def kernel_roofline(trace, run, kind, cost):
    """100 x (least time for the kind's forward and fused backward calls over
    the traced steps, by ``cost(shape, sequences)`` and the chip's peaks) /
    (those calls' device time); prints which bound."""
    from . import flops

    reduced = found(trace, run)
    took = reduced and sum(s for (k, _), s in reduced["kernels"].items() if k == kind)
    if run.get("peaks") is None or not took:
        return None
    w = run["workload"]
    sequences = w["grad_accum"] * w["micro_batch_per_chip"] * run["traced_steps"]
    least, bound = flops.roofline_seconds(*cost(run["shape"], sequences), run["peaks"])
    print(f"perfbench: the {kind} layers' flash kernels are {bound}-bound over their true pairs; "
          f"least {least:.4f} s, took {took:.4f} s over the traced steps", flush=True)
    return 100.0 * least / took
