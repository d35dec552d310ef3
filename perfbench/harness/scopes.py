"""Which module and phase of the step each device op belongs to, read off the
names the program gives (``jax.named_scope`` in ``models/tinygpt.py`` and
``train/step.py``; the program's tuple is ``utils/scopes.SCOPES``, this is
the benchmark's copy).

The join: a trace event's name starts with its instruction's name; the
compiled step's text (``run["hlo_text"]``) gives every instruction a
``metadata={op_name="..."}``; a scope ``x`` is a component of that path as
``x`` (not differentiated), ``jvp(x)`` (forward) or ``transpose(jvp(x))``
(backward, the rule of a ``custom_vjp`` included). Scopes inside a wrapped
one stay plain (``jvp(mlp)/dropout``); what remat runs a second time carries
``rematted_computation``; one ``op_name`` may join several paths with ``;``.
A fusion counts under the one path its own (else its root's) metadata names,
whatever else was fused into it. A program without the scopes (the parent of
PR 24) gives every op module ``None`` and the readers return nothing.
"""

import collections
import functools
import re

from . import trace_reduce

SCOPES = ("embed", "attention", "mlp", "dropout", "head", "loss", "optimizer")
MODULES = tuple(s for s in SCOPES if s != "dropout")
REMAT = "rematted_computation"

Scope = collections.namedtuple("Scope", "module phase recompute dropout")

COMPUTATION = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\) -> .*\{$")
INSTRUCTION = re.compile(r"^(ROOT )?%?([\w.\-]+) = ")
OP_NAME = re.compile(r'op_name="([^"]*)"')
WRAPPER = re.compile(r"^(?!jit\()\w+\((.*)\)$")  # jit(f) names a function, not a scope


def op_names(hlo_text):
    """{instruction name: op_name} of a compiled module's text. A fusion (or
    call) whose own line has none takes the ``ROOT`` of the computation it
    calls, else the first instruction there that has one."""
    names, calls, roots, firsts, current = {}, {}, {}, {}, None
    for line in hlo_text.splitlines():
        line = line.strip()
        instruction = INSTRUCTION.match(line)
        if not instruction:
            header = COMPUTATION.match(line)
            current = header.group(1) if header else current
            continue
        found = OP_NAME.search(line)
        if found:
            names[instruction.group(2)] = found.group(1)
            firsts.setdefault(current, found.group(1))
            if instruction.group(1):
                roots[current] = found.group(1)
        elif called := trace_reduce.CALLS.search(line):
            calls[instruction.group(2)] = called.group(1)
    for name, computation in calls.items():
        if op_name := roots.get(computation) or firsts.get(computation):
            names[name] = op_name
    return names


def instruction_name(event):
    """'%fusion.12 = f32[..] fusion(..)' -> 'fusion.12'."""
    return event.name.split(" = ")[0].lstrip("%")


def _unwrap(component):
    """'transpose(jvp(attention))' -> 'attention'."""
    while wrapped := WRAPPER.match(component):
        component = wrapped.group(1)
    return component


def classify(op_name):
    """-> Scope(module, phase, recompute, dropout) of one ``op_name``.
    ``module`` is the outermost of MODULES on the path, else None; ``phase``
    is 'optimizer', 'backward' (a ``transpose(`` component) or 'forward'. Of
    several ``;``-joined paths the first that names a module counts."""
    first = None
    for path in op_name.split(";"):
        components = path.split("/")
        plain = [_unwrap(c) for c in components]
        module = next((c for c in plain if c in MODULES), None)
        phase = ("optimizer" if module == "optimizer" else
                 "backward" if any(c.startswith("transpose(") for c in components)
                 else "forward")
        scope = Scope(module, phase, REMAT in plain, "dropout" in plain)
        if module:
            return scope
        first = first or scope
    return first


def scope_seconds(trace, plane, hlo_text):
    """({Scope: self seconds} over one chip's ops, their sum, {instruction
    base name: self seconds} of the ops no module names); None where the text
    holds no ``op_name``. Self times, so a ``while`` does not count its body
    twice; collectives are classified like any op."""
    names = op_names(hlo_text)
    if not names:
        return None
    seconds, unscoped = collections.Counter(), collections.Counter()
    for event, self_s, _ in trace_reduce.self_times(trace.ops(plane)):
        scope = classify(names.get(instruction_name(event), ""))
        seconds[scope] += self_s
        if scope.module is None:
            unscoped[trace_reduce.base_name(event)] += self_s
    return seconds, sum(seconds.values()), unscoped


@functools.lru_cache(maxsize=1)  # the eight readers of one run share one reduction
def _first_chip(trace, hlo_text):
    found = scope_seconds(trace, trace.devices()[0], hlo_text)
    if found is None or not found[1] or all(s.module is None for s in found[0]):
        return None
    seconds, busy, unscoped = found
    table = collections.Counter()
    for scope, s in seconds.items():
        table[scope.module or "unscoped", scope.phase] += s
    cells = ", ".join(f"{m}.{p} {s:.4f}" for (m, p), s in sorted(table.items()))
    recompute = sum(s for scope, s in seconds.items() if scope.recompute)
    dropout = sum(s for scope, s in seconds.items() if scope.dropout)
    print(f"perfbench: scopes: module.phase self seconds over the traced steps, first "
          f"chip, busy {busy:.4f} s: {cells}; overlays: recompute {recompute:.4f} s, "
          f"dropout {dropout:.4f} s; largest unscoped: "
          f"{[[n, round(s, 4)] for n, s in unscoped.most_common(5)]}", flush=True)
    return seconds, busy


def share(trace, run, pick):
    """100 x (self seconds of the scopes ``pick`` accepts) / (busy self
    seconds), first chip; None where the program has no scopes."""
    if not trace.devices():
        return None
    found = _first_chip(trace, run["hlo_text"])
    if found is None:
        return None
    seconds, busy = found
    return 100.0 * sum(s for scope, s in seconds.items() if pick(scope)) / busy
