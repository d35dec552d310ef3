#!/usr/bin/env python3
"""Cut a traced run down to a small recorded trace that keeps its ``op_name``s
and which of its events are Mosaic calls.

    python3 perfbench/tools/record_kernel_trace.py <trace dir of a cell> <out.json.gz>

Beside ``record_scoped_trace.py`` (whose events keep their instruction's name
alone, so that a reader of kernels by name finds none): the first traced step
of the first chip, its ops of at least 200 ns with names cut to the instruction
and, for a Mosaic call, the custom call's target; the step's compiled text cut
to the ``op_name`` of each of those instructions; and the workload's and the
shape's sizes a reader of a roofline needs, for the tests of a cell's readers
to hold later PRs to what they read on exactly these events.
"""

import glob
import gzip
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))


def main(argv):
    from perfbench.harness import scopes
    from perfbench.harness import trace_reduce as tr

    trace_dir, out = argv
    trace = tr.load(max(glob.glob(os.path.join(trace_dir, "plugins/profile/*/*.xplane.pb"))))
    with open(os.path.join(trace_dir, "step_hlo.txt")) as f:
        names = scopes.op_names(f.read())
    plane = trace.devices()[0]
    step = trace.modules(plane)[0]
    cut = lambda e: f"%{scopes.instruction_name(e)} = " + (
        f"custom-call(), {tr.MOSAIC}" if tr.MOSAIC in e.name else "x()")
    ops = [tr.Event(cut(e), e.start, e.end) for e in trace.ops(plane)
           if step.start <= e.start and e.end <= step.end and e.end - e.start >= 200e-9]
    kept = {scopes.instruction_name(e) for e in ops}
    hlo_text = "".join(f'%{name} = x(), metadata={{op_name="{op_name}"}}\n'
                       for name, op_name in sorted(names.items()) if name in kept)
    kernels = {}
    for e in ops:
        if tr.MOSAIC in e.name:
            call = tr.base_name(e)
            kernels[call] = kernels.get(call, 0) + 1
    with gzip.open(out, "wt") as f:
        json.dump({"plane": plane, "ops": ops, "hlo_text": hlo_text,
                   "expected": {"mosaic_calls": kernels,
                                "busy_s": sum(s for _, s, _ in tr.self_times(ops))}}, f)
    print(f"{len(ops)} ops, {sum(kernels.values())} Mosaic calls {kernels} -> {out}")


if __name__ == "__main__":
    main(sys.argv[1:])
