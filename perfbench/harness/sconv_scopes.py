"""What a stack of gated short-convolution layers beside attention and routed
ones adds to a step, in a device trace: the scopes ``conv`` and ``global`` under
``attention`` (the program's ``utils/scopes.LAYER_KIND_SCOPES``), ``sconv_in`` /
``sconv_core`` / ``sconv_out`` below ``conv`` (``SCONV_SCOPES``; this is the
benchmark's copy), the gated convolution's Mosaic calls ``sconv_fwd`` /
``sconv_bwd`` under ``conv`` and the flash calls under ``global`` by the names
the program gives them, and the held experts' scope ``experts`` under ``mlp``.

The join is ``scopes.py``'s: trace event -> instruction name -> ``op_name`` of
the compiled step's text, where a scope is a path component, plain or wrapped
(``jvp(attention)/conv/sconv_core/...``, ``transpose(jvp(attention))/global/...``).
Self times of the first chip over the traced steps. A program without the
scope ``conv`` (any other model, or the parent of the PR that brought it) gives
every reader nothing.
"""

import collections
import functools

from . import mla_scopes, scopes, trace_reduce

KINDS = ("conv", "global")
PARTS = ("sconv_in", "sconv_core", "sconv_out")
SCONV_KERNELS = ("sconv_fwd", "sconv_bwd")
FLASH_KERNELS = ("flash_fwd", "flash_bwd_fused")
CALLS = {"conv": SCONV_KERNELS, "global": FLASH_KERNELS}


def kind_and_part(op_name):
    """-> (``conv`` | ``global`` | None, ``sconv_in`` | ``sconv_core`` |
    ``sconv_out`` | None): of the first of the ``;``-joined paths that names
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
    if not any(kind_and_part(op_name)[0] == "conv" for op_name in names.values()):
        return None
    scope, kernels, calls = collections.Counter(), collections.Counter(), collections.Counter()
    experts = busy = 0.0
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
                    calls[call] += 1
        if mla_scopes.part(op_name) == ("mlp", "experts"):
            experts += self_s
    if not busy:
        return None
    by_name = ", ".join(f"{name} {s:.4f} in {calls[name]}" for name, s in sorted(kernels.items()))
    print(f"perfbench: sconv: self seconds over the traced steps, first chip, busy {busy:.4f} s: "
          f"attention.conv {scope['conv']:.4f} (sconv_in {scope['sconv_in']:.4f}, sconv_core "
          f"{scope['sconv_core']:.4f}, sconv_out {scope['sconv_out']:.4f}), attention.global "
          f"{scope['global']:.4f}, mlp.experts {experts:.4f}; their kernels, seconds in calls: "
          f"{by_name}", flush=True)
    return {"scope": scope, "kernels": kernels, "calls": calls, "experts": experts, "busy": busy}


def found(trace, run):
    """{``scope``: self seconds under attention / kind and under each of the
    convolution mixer's three parts, ``kernels``: self seconds of the Mosaic
    calls by name (the gated convolution's under ``conv``, the flash calls
    under ``global``), ``calls``: how many events each name had, ``experts``:
    self seconds under mlp / experts, ``busy``} of the first chip, or None."""
    if not trace.devices() or not run.get("hlo_text"):
        return None
    return _first_chip(trace, run["hlo_text"])


def scope_share(trace, run, *names):
    """100 x (self seconds under the scopes) / (busy self seconds)."""
    reduced = found(trace, run)
    if reduced is None:
        return None
    return 100.0 * sum(reduced["scope"][name] for name in names) / reduced["busy"]


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
