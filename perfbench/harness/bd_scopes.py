"""What block-diffusion training adds to a step, in a device trace: the scope
``noise`` under ``embed`` (the program's ``utils/scopes.NOISE``; this is the
benchmark's copy), the attention kernels by the names the program gives their
calls, and the held experts' scope ``experts`` under ``mlp`` (``mla_scopes``
finds it too, but gives nothing to a model without latent attention).

The join is ``scopes.py``'s: trace event -> instruction name -> ``op_name`` of
the compiled step's text, where a scope is a path component, plain or wrapped.
Self times of the first chip over the traced steps. A program without the
scope (any other model, or the parent of the PR that brought it) gives every
reader nothing.
"""

import collections
import functools

from . import mla_scopes, scopes, trace_reduce

NOISE = ("embed", "noise")
KERNELS = ("flash_fwd", "flash_bwd_fused")


def under_noise(op_name):
    """Whether one of the ``;``-joined paths names ``embed`` and below it ``noise``."""
    for path in op_name.split(";"):
        plain = [scopes._unwrap(c) for c in path.split("/")]
        if NOISE[0] in plain and NOISE[1] in plain[plain.index(NOISE[0]) + 1:]:
            return True
    return False


@functools.lru_cache(maxsize=1)  # the readers of one run share one reduction
def _first_chip(trace, hlo_text):
    names = scopes.op_names(hlo_text)
    if not any(under_noise(op_name) for op_name in names.values()):
        return None
    noise, experts, kernels, busy = 0.0, 0.0, collections.Counter(), 0.0
    for event, self_s, _ in trace_reduce.self_times(trace.ops(trace.devices()[0])):
        busy += self_s
        op_name = names.get(scopes.instruction_name(event), "")
        if under_noise(op_name):
            noise += self_s
        if mla_scopes.part(op_name) == ("mlp", "experts"):
            experts += self_s
        if trace_reduce.MOSAIC in event.name:
            kernels[trace_reduce.base_name(event)] += self_s
    if not busy:
        return None
    calls = ", ".join(f"{n} {s:.4f}" for n, s in sorted(kernels.items()))
    print(f"perfbench: block diffusion: self seconds over the traced steps, first chip, busy "
          f"{busy:.4f} s: embed.noise {noise:.4f}, mlp.experts {experts:.4f}; Mosaic calls by "
          f"name: {calls}", flush=True)
    return noise, kernels, busy, experts


def found(trace, run):
    """(self seconds under ``embed`` / ``noise``, {Mosaic call name: self
    seconds}, busy seconds, self seconds under ``mlp`` / ``experts``) of the
    first chip, or None."""
    if not trace.devices() or not run.get("hlo_text"):
        return None
    return _first_chip(trace, run["hlo_text"])


def kernel_seconds(trace, run):
    """(self seconds in the Mosaic calls named KERNELS, busy seconds) or None."""
    reduced = found(trace, run)
    if reduced is None:
        return None
    return sum(reduced[1][name] for name in KERNELS), reduced[2]
