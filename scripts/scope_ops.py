#!/usr/bin/env python3
"""The device ops under named parts of the step, by self time: which
instructions a scope's milliseconds are, forward, backward and remat's second
run apart. Reads what a traced run of a benchmark cell leaves behind
(``perfbench/.trace/<cell>/``: the newest ``.xplane.pb`` and ``step_hlo.txt``)
with the benchmark's own join (``perfbench/harness/scopes.py``), so its sums
are the readers' sums.

    python3 scripts/scope_ops.py perfbench/.trace/<cell> <steps traced> mlp dispatch combine
    python3 scripts/scope_ops.py perfbench/.trace/<cell> 5 attention qk_prologue -

A part is any scope below the module (``utils/scopes.py``); ``-`` stands for
the module's ops under none of the parts named beside it. Prints one line an
(part, phase, instruction name without its number): ms a step, events a step
and the largest result shape; the 30 largest single instructions; then, for
every instruction name that shows under the parts, how its step total splits
between the parts and the rest of the step (whose ``other:reshape`` is it).
"""

import collections
import glob
import os
import re
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))

from perfbench.harness import scopes, trace_reduce  # noqa: E402

SHAPE = re.compile(r" = (\S+)")
REST = "-"


def part_of(op_name, module, parts):
    """The first of ``parts`` below ``module`` in one of the ``;``-joined paths
    of an ``op_name`` (the join of ``perfbench/harness/mla_scopes.part``, for
    any scope's name), ``REST`` where the module is there, none of them is and
    ``REST`` is asked for, else None."""
    for path in op_name.split(";"):
        plain = [scopes._unwrap(c) for c in path.split("/")]
        if module in plain:
            below = plain[plain.index(module) + 1:]
            if found := next((c for c in below if c in parts), None):
                return found
            if REST in parts:
                return REST
    return None


def phase_of(op_name):
    scope = scopes.classify(op_name)
    return "remat" if scope.recompute else scope.phase


def main(argv):
    trace_dir, steps, module, parts = argv[0], int(argv[1]), argv[2], tuple(argv[3:])
    trace = trace_reduce.load(
        max(glob.glob(os.path.join(trace_dir, "plugins/profile/*/*.xplane.pb"))))
    with open(os.path.join(trace_dir, "step_hlo.txt")) as f:
        names = scopes.op_names(f.read())
    rows = collections.defaultdict(lambda: [0.0, 0, ""])
    single = collections.Counter()
    inside, whole = collections.Counter(), collections.Counter()
    for event, self_s, _ in trace_reduce.self_times(trace.ops(trace.devices()[0])):
        op_name = names.get(scopes.instruction_name(event), "")
        base = trace_reduce.base_name(event)
        whole[base] += self_s
        part = part_of(op_name, module, parts)
        if part is None:
            continue
        phase = phase_of(op_name)
        inside[base, part] += self_s
        shape = SHAPE.search(event.name)
        shape = shape.group(1) if shape else ""
        row = rows[part, phase, base]
        row[0] += self_s
        row[1] += 1
        row[2] = max(row[2], shape, key=len)
        single[part, phase, scopes.instruction_name(event), shape,
               op_name.split("/")[-2][:40] if "/" in op_name else ""] += self_s
    total = collections.Counter()
    print(f"{'part':9s} {'phase':9s} {'ms a step':>10s} {'events':>7s}  instruction, largest result")
    for (part, phase, base), (s, n, shape) in sorted(rows.items(), key=lambda kv: -kv[1][0]):
        total[part, phase] += s
        print(f"{part:9s} {phase:9s} {1e3 * s / steps:10.3f} {n / steps:7.1f}  {base} {shape[:90]}")
    for (part, phase), s in sorted(total.items()):
        print(f"total {part}.{phase}: {1e3 * s / steps:.3f} ms a step")
    print("the 30 largest single instructions (ms a step, the last scope of its op_name):")
    for (part, phase, name, shape, last), s in single.most_common(30):
        print(f"  {part:9s} {phase:9s} {1e3 * s / steps:8.3f}  {name} {shape[:70]} {last}")
    print("instruction name: ms a step in the whole step = " + " + ".join(parts) + " + elsewhere")
    for base in sorted({b for b, _ in inside}, key=lambda b: -whole[b]):
        mine = [inside[base, p] for p in parts]
        print(f"  {base}: {1e3 * whole[base] / steps:.3f} = "
              + " + ".join(f"{1e3 * s / steps:.3f}" for s in mine)
              + f" + {1e3 * (whole[base] - sum(mine)) / steps:.3f}")


if __name__ == "__main__":
    main(sys.argv[1:])
