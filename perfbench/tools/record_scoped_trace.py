#!/usr/bin/env python3
"""Cut a traced run down to a small recorded trace with its ``op_name``s kept.

    python3 perfbench/tools/record_scoped_trace.py <trace dir of a cell> <out.json.gz>

Beside ``record_small_trace.py`` (whose record holds no ``op_name``): keeps
the first traced step of the first chip (its ops of at least 200 ns, names cut
to the instruction), the step's compiled text cut to the ``op_name`` of each of
those instructions, and what ``perfbench/harness/scopes.py`` made of exactly
these events, for ``perfbench/tests/test_scopes.py`` to hold later PRs to.
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
    ops = [tr.Event(f"%{scopes.instruction_name(e)} = ", e.start, e.end) for e in trace.ops(plane)
           if step.start <= e.start and e.end <= step.end and e.end - e.start >= 200e-9]
    kept = {scopes.instruction_name(e) for e in ops}
    # the compiled text cut to what the reader needs of it: one line an instruction
    hlo_text = "".join(f'%{name} = x(), metadata={{op_name="{op_name}"}}\n'
                       for name, op_name in sorted(names.items()) if name in kept)
    seconds, busy, unscoped = scopes.scope_seconds(
        tr.Trace({plane: {tr.OPS_LINE: ops}}), plane, hlo_text)
    record = {
        "plane": plane, "ops": ops, "hlo_text": hlo_text,
        "expected": {"busy_s": busy,
                     "scopes": [[*scope, s] for scope, s in sorted(
                         seconds.items(), key=lambda kv: -kv[1])],
                     "unscoped": unscoped.most_common(10)},
    }
    with gzip.open(out, "wt") as f:
        json.dump(record, f)
    print(f"{len(ops)} ops, {hlo_text.count(chr(10))} of {len(kept)} instructions with an "
          f"op_name -> {out}")


if __name__ == "__main__":
    main(sys.argv[1:])
