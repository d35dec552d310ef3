"""The routed MLP's parts in a device trace: which of ``router``, ``dispatch``,
``experts``, ``combine`` (the program's ``utils/scopes.MOE_SCOPES``; this is
the benchmark's copy) each device op under ``mlp`` belongs to.

The join is ``scopes.py``'s: trace event -> instruction name -> ``op_name`` of
the compiled step's text, where a scope is a path component, plain or wrapped
(``jvp(mlp)/experts/...``, ``transpose(jvp(mlp))/experts/...``). Self times of
the first chip over the traced steps. A program without these scopes (a dense
model, or the parent of the PR that brought them) gives every reader nothing.
"""

import collections
import functools

from . import scopes, trace_reduce

MOE_SCOPES = ("router", "dispatch", "experts", "combine")


def part(op_name):
    """-> (one of MOE_SCOPES, 'forward' | 'backward') or None: the first of
    the ``;``-joined paths that names ``mlp`` and, below it, a part."""
    for path in op_name.split(";"):
        components = path.split("/")
        plain = [scopes._unwrap(c) for c in components]
        if "mlp" not in plain:
            continue
        found = next((c for c in plain[plain.index("mlp") + 1:] if c in MOE_SCOPES), None)
        if found:
            backward = any(c.startswith("transpose(") for c in components)
            return found, "backward" if backward else "forward"
    return None


@functools.lru_cache(maxsize=1)  # the readers of one run share one reduction
def _first_chip(trace, hlo_text):
    names = scopes.op_names(hlo_text)
    seconds, busy = collections.Counter(), 0.0
    for event, self_s, _ in trace_reduce.self_times(trace.ops(trace.devices()[0])):
        busy += self_s
        if found := part(names.get(scopes.instruction_name(event), "")):
            seconds[found] += self_s
    if not seconds or not busy:
        return None
    cells = ", ".join(f"{p}.{phase} {s:.4f}" for (p, phase), s in sorted(seconds.items()))
    print(f"perfbench: moe scopes: part.phase self seconds over the traced steps, first "
          f"chip, busy {busy:.4f} s: {cells}", flush=True)
    return seconds, busy


def seconds(trace, run, parts):
    """Self seconds under the named parts, both phases; None without them."""
    if not trace.devices():
        return None
    found = _first_chip(trace, run["hlo_text"])
    if found is None:
        return None
    return sum(s for (p, _), s in found[0].items() if p in parts)


def share(trace, run, parts):
    """100 x (self seconds under the named parts) / (busy self seconds)."""
    under = seconds(trace, run, parts)
    return None if under is None else 100.0 * under / _first_chip(trace, run["hlo_text"])[1]
