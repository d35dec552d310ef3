#!/usr/bin/env python3
"""Cut a traced run down to the small recorded trace the tests keep.

    python3 perfbench/tools/record_small_trace.py <trace dir of a cell> <out.json.gz>

Keeps the first traced step of the first chip: its ops of at least 200 ns
(names cut to instruction, opcode, called computation and kernel target), its
run on the modules line, the host's spans over it, the computations of the
step's HLO text that hold a matrix multiplication, and what the reduction gave
on exactly these events, for the tests to hold every later PR to.
"""

import glob
import gzip
import json
import os
import re
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))

OPCODE = re.compile(r" ([a-z][a-z\-]*)\(")


def short(name):
    head, _, rest = name.partition(" = ")
    opcode = OPCODE.search(" " + rest)
    called = re.search(r"calls=%?[\w.\-]+", rest)
    kernel = 'custom_call_target="tpu_custom_call"' if 'target="tpu_custom_call"' in rest else ""
    return f"{head} = {opcode.group(1) if opcode else '?'}(), {called.group(0) if called else ''} {kernel}".strip()


def main(argv):
    from perfbench.harness import trace_reduce as tr

    trace_dir, out = argv
    trace = tr.load(max(glob.glob(os.path.join(trace_dir, "plugins/profile/*/*.xplane.pb"))))
    with open(os.path.join(trace_dir, "step_hlo.txt")) as f:
        matmuls = tr.matmul_computations(f.read())
    plane = trace.devices()[0]
    step = trace.modules(plane)[0]
    ops = [tr.Event(short(e.name), e.start, e.end) for e in trace.ops(plane)
           if step.start <= e.start and e.end <= step.end and e.end - e.start >= 200e-9]
    host = [e for e in trace.host_spans() if e.start < step.end]
    small = tr.Trace({plane: {tr.OPS_LINE: ops, tr.MODULES_LINE: [step]},
                      tr.HOST_PLANE: {"python3": host}})
    called = {c.group(1) for e in ops if (c := tr.CALLS.search(e.name))}
    used = matmuls & called
    kinds, busy = tr.kind_seconds(small, plane, used)
    record = {
        "plane": plane, "ops": ops, "modules": [step], "host": host,
        "matmul_computations": sorted(used),
        "expected": {"busy_s": tr.total(tr.merge((e.start, e.end) for e in ops)),
                     "self_sum_s": busy, "kinds": dict(kinds),
                     "step_s": step.end - step.start},
    }
    with gzip.open(out, "wt") as f:
        json.dump(record, f)
    print(f"{len(ops)} ops, {len(host)} host spans, {len(used)} matmul computations -> {out}")


if __name__ == "__main__":
    main(sys.argv[1:])
