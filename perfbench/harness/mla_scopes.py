"""The parts of a DeepSeek-V2-class step in a device trace: below ``attention``
the latent attention's ``mla_proj`` / ``mla_core`` / ``mla_out``, below ``mlp``
the routed layer's ``router`` / ``dispatch`` / ``experts`` / ``combine`` and
``shared`` (the program's ``utils/scopes.MLA_SCOPES``, ``MOE_SCOPES`` and
``SHARED``; this is the benchmark's copy), and the attention kernels by the
names the program gives their calls.

The join is ``scopes.py``'s: trace event -> instruction name -> ``op_name`` of
the compiled step's text, where a scope is a path component, plain or wrapped
(``jvp(attention)/mla_core/...``, ``transpose(jvp(mlp))/shared/...``). Self
times of the first chip over the traced steps. A program without these scopes
(any other model, or the parent of the PR that brought them) gives every
reader nothing.
"""

import collections
import functools

from . import scopes, trace_reduce

PARTS = {
    "attention": ("mla_proj", "mla_core", "mla_out"),
    "mlp": ("router", "dispatch", "experts", "combine", "shared"),
}
KERNELS = ("flash_fwd", "flash_bwd_fused")


def part(op_name):
    """-> (module, part) or None: the first of the ``;``-joined paths that
    names a module of PARTS and, below it, one of its parts."""
    for path in op_name.split(";"):
        plain = [scopes._unwrap(c) for c in path.split("/")]
        for module, parts in PARTS.items():
            if module in plain:
                below = plain[plain.index(module) + 1:]
                if found := next((c for c in below if c in parts), None):
                    return module, found
    return None


@functools.lru_cache(maxsize=1)  # the readers of one run share one reduction
def _first_chip(trace, hlo_text):
    names = scopes.op_names(hlo_text)
    seconds, kernels, busy = collections.Counter(), collections.Counter(), 0.0
    for event, self_s, _ in trace_reduce.self_times(trace.ops(trace.devices()[0])):
        busy += self_s
        if found := part(names.get(scopes.instruction_name(event), "")):
            seconds[found] += self_s
        if trace_reduce.MOSAIC in event.name:
            kernels[trace_reduce.base_name(event)] += self_s
    if not any(module == "attention" for module, _ in seconds) or not busy:
        return None
    cells = ", ".join(f"{m}.{p} {s:.4f}" for (m, p), s in sorted(seconds.items()))
    calls = ", ".join(f"{n} {s:.4f}" for n, s in sorted(kernels.items()))
    print(f"perfbench: mla scopes: module.part self seconds over the traced steps, first "
          f"chip, busy {busy:.4f} s: {cells}; Mosaic calls by name: {calls}", flush=True)
    return seconds, kernels, busy


def _found(trace, run):
    return _first_chip(trace, run["hlo_text"]) if trace.devices() else None


def seconds(trace, run, module, parts):
    """Self seconds under the named parts of ``module``; None without them."""
    found = _found(trace, run)
    if found is None:
        return None
    return sum(s for (m, p), s in found[0].items() if m == module and p in parts)


def share(trace, run, module, parts):
    """100 x (self seconds under the named parts) / (busy self seconds)."""
    under = seconds(trace, run, module, parts)
    return None if under is None else 100.0 * under / _found(trace, run)[2]


def kernel_seconds(trace, run):
    """(self seconds in the Mosaic calls named KERNELS, busy seconds) or None."""
    found = _found(trace, run)
    if found is None:
        return None
    return sum(found[1][name] for name in KERNELS), found[2]
