"""What a Laguna-class stack adds to a step, in a device trace: the scopes
``window`` and ``global`` under ``attention`` (``sw_scopes.kind_of``'s join),
the Mosaic calls by the names the program gives them *and by the kind of the
layer that calls them* (the flash kernels, and ``ops/rotary.py``'s pass
``qk_prologue_fwd`` / ``qk_prologue_bwd``, counted so that the pass's bytes are
those of the calls that ran), and the scope ``attn_gate`` (the per-head output
gate's projection, sigmoid and product, forward, remat's second run and
backward).

Self times of the first chip over the traced steps. A program without the
scopes (any other model, or the parent of the PR that brought them) gives
every reader nothing.
"""

import collections
import functools

from . import scopes, sw_scopes, trace_reduce

FLASH = ("flash_fwd", "flash_bwd_fused")
PROLOGUE = ("qk_prologue_fwd", "qk_prologue_bwd")
GATE = "attn_gate"


def _under_gate(op_name):
    return any(GATE in [scopes._unwrap(c) for c in path.split("/")] for path in op_name.split(";"))


@functools.lru_cache(maxsize=1)  # the readers of one run share one reduction
def _first_chip(trace, hlo_text):
    names = scopes.op_names(hlo_text)
    gated = {name for name, op_name in names.items() if _under_gate(op_name)}
    if not gated:
        return None  # not this model's step
    scope, kernels, calls = collections.Counter(), collections.Counter(), collections.Counter()
    gate = busy = 0.0
    for event, self_s, _ in trace_reduce.self_times(trace.ops(trace.devices()[0])):
        busy += self_s
        instruction = scopes.instruction_name(event)
        kind = sw_scopes.kind_of(names.get(instruction, ""))
        if kind:
            scope[kind] += self_s
            base = trace_reduce.base_name(event)
            if trace_reduce.MOSAIC in event.name and base in FLASH + PROLOGUE:
                kernels[kind, base] += self_s
                calls[kind, base] += 1
        if instruction in gated:
            gate += self_s
    if not busy:
        return None
    by_name = ", ".join(f"{kind}.{name} {s:.4f} ({calls[kind, name]} calls)"
                        for (kind, name), s in sorted(kernels.items()))
    print(f"perfbench: laguna: self seconds over the traced steps, first chip, busy "
          f"{busy:.4f} s: attention.window {scope['window']:.4f}, attention.global "
          f"{scope['global']:.4f}, attn_gate {gate:.4f}; Mosaic calls by kind: {by_name}",
          flush=True)
    return {"scope": scope, "kernels": kernels, "calls": calls, "gate": gate, "busy": busy}


def found(trace, run):
    """{``scope``: self seconds under attention / kind, ``kernels`` and
    ``calls``: self seconds and count of the Mosaic calls by (kind, call name),
    ``gate``: self seconds under ``attn_gate``, ``busy``} of the first chip, or
    None."""
    if not trace.devices() or not run.get("hlo_text"):
        return None
    return _first_chip(trace, run["hlo_text"])


def share(trace, run, of):
    """100 x (``of(reduced)`` self seconds) / (busy self seconds)."""
    reduced = found(trace, run)
    return None if reduced is None else 100.0 * of(reduced) / reduced["busy"]


def kernel_roofline(trace, run, kind, cost):
    """100 x (least time for the kind's forward and fused backward flash calls
    over the traced steps, by ``cost(shape, sequences)`` at the kind's head
    count and the chip's peaks) / (those calls' device time); prints which
    bound."""
    from . import flops

    reduced = found(trace, run)
    took = reduced and sum(s for (k, name), s in reduced["kernels"].items()
                           if k == kind and name in FLASH)
    if run.get("peaks") is None or not took:
        return None
    w = run["workload"]
    sequences = w["grad_accum"] * w["micro_batch_per_chip"] * run["traced_steps"]
    least, bound = flops.roofline_seconds(*cost(run["shape"], sequences), run["peaks"])
    print(f"perfbench: the {kind} layers' flash kernels are {bound}-bound over their true pairs "
          f"at {dict(run['shape']['heads'])[kind]} heads; least {least:.4f} s, took {took:.4f} s "
          f"over the traced steps", flush=True)
    return 100.0 * least / took


def prologue(trace, run):
    """(bytes the rotary pass's calls that ran need, their device seconds), the
    kinds together: a call of a layer of a kind moves that kind's q and k in
    and out once (``flops_laguna.prologue_call_bytes``). None where no layer
    took the pass (the ``jnp`` chain ran: the run's ``qk_prologue_stats`` says
    which)."""
    from . import flops_laguna

    reduced = found(trace, run)
    if reduced is None or not run.get("workload") or not run.get("shape"):
        return None
    sequences = run["workload"]["micro_batch_per_chip"]
    moved = took = 0.0
    for (kind, name), count in reduced["calls"].items():
        if name in PROLOGUE:
            moved += count * flops_laguna.prologue_call_bytes(run["shape"], kind, sequences)
            took += reduced["kernels"][kind, name]
    return (moved, took) if took else None
