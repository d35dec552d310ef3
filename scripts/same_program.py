#!/usr/bin/env python
"""Did a change move a cell's program? Off the chip: lower each cell's
real-size step for a described (not attached) v5e:2x2 through the benchmark's
own builders and hash the StableHLO text.

    JAX_PLATFORMS=cpu python scripts/same_program.py --write out.jsonl [cell[,key=value...] ...]
    python scripts/same_program.py --compare parent.jsonl change.jsonl

``--write`` hashes the named cells (default: every cell of ``BENCHMARK.json``)
of the tree this file lies in, one cell a process (libtpu's lock: one at a
time), one JSON line a cell. ``,key=value`` overrides a key of the cell's
workload file (``lfm2-8b-a1b.share4-seq16384,remat=dots``), as
``scripts/fsdp_collectives.py`` takes them; its ``compile_cell`` is the
recipe, stopped short of the compile. ``--dump DIR`` also writes each text to
``DIR/<cell>.mlir``, to diff when a hash differs. ``--compare`` exits 1 and
prints the cells whose hashes differ or that one file lacks.

To compare two commits, copy each in turn to ONE path (``git archive <commit>
| tar -x -C /root/scratch/tree``; this file into a parent that has none) and
write there: ``jax_traceback_in_locations_limit`` is set to 0, so no source
line is in the text and a function may change files, but equal paths keep what
is left of a location equal. The hash that counts is ``sha256_renumbered``:
the text with the numeric suffixes of private functions (``@_where_17``)
numbered by first appearance, because jax numbers them by a process-wide
counter that an unrelated earlier trace moves. ``sha256`` is the text as it
came. About 10 s a small cell, two minutes for the nine-megabyte Kimi step.
Nothing runs and nothing compiles: equal text is the same program handed to
the same compiler, so the cell's numbers cannot move; a different text says
nothing yet about speed.
"""

import argparse
import hashlib
import json
import os
import re
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PRIVATE = re.compile(r"@([A-Za-z_][\w.]*?)_(\d+)\b")


def renumbered(text):
    """``text`` with every private function's numeric suffix replaced by its
    order of first appearance."""
    seen = {}

    def by_appearance(found):
        return seen.setdefault(found.group(0), f"@{found.group(1)}_n{len(seen)}")

    return PRIVATE.sub(by_appearance, text)


def lowered_text(cell, overrides):
    """The StableHLO of the cell's step, lowered for the described topology."""
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    import jax
    from fsdp_collectives import compile_cell  # sets TPU_LOG_DIR and the path

    class Lowered(Exception):
        pass

    def stop_short(lowered, *args, **kwargs):
        raise Lowered(lowered.as_text())

    jax.config.update("jax_traceback_in_locations_limit", 0)
    jax.stages.Lowered.compile = stop_short
    try:
        compile_cell(cell, overrides)
    except Lowered as found:
        return found.args[0]
    raise SystemExit(f"same_program: {cell}: the step was never lowered")


def hash_line(spec, text):
    return {"cell": spec, "chars": len(text),
            "sha256": hashlib.sha256(text.encode()).hexdigest(),
            "sha256_renumbered": hashlib.sha256(renumbered(text).encode()).hexdigest(),
            "mosaic_calls": text.count("tpu_custom_call")}


def write(out, specs, dump):
    if not specs:
        with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
            specs = [w["name"] for w in json.load(f)["workloads"]]
    failed = []
    if dump:
        dump = os.path.abspath(dump)  # the cells' processes run from ROOT
        os.makedirs(dump, exist_ok=True)
    with open(out, "w") as f:
        for spec in specs:
            command = [sys.executable, os.path.abspath(__file__), "--one", spec]
            command += ["--dump", dump] if dump else []
            done = subprocess.run(command, cwd=ROOT, stdout=subprocess.PIPE, text=True)
            if done.returncode:
                failed.append(spec)
                continue
            line = done.stdout.strip().splitlines()[-1]
            print(line, flush=True)
            f.write(line + "\n")
    if failed:
        raise SystemExit(f"same_program: not lowered: {' '.join(failed)}")


def read(path):
    with open(path) as f:
        return {row["cell"]: row for row in map(json.loads, filter(str.strip, f))}


def compare(a_path, b_path):
    """The cells of either file whose renumbered hashes differ, or that the
    other file lacks, each with why."""
    a, b = read(a_path), read(b_path)
    differ = []
    for cell in list(a) + [c for c in b if c not in a]:
        if cell not in a or cell not in b:
            differ.append((cell, f"only in {a_path if cell in a else b_path}"))
        elif a[cell]["sha256_renumbered"] != b[cell]["sha256_renumbered"]:
            differ.append((cell, f"{a[cell]['sha256_renumbered'][:12]} ({a[cell]['chars']} chars) != "
                                 f"{b[cell]['sha256_renumbered'][:12]} ({b[cell]['chars']} chars)"))
    return differ, len(a.keys() | b.keys())


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    mode = parser.add_mutually_exclusive_group(required=True)
    mode.add_argument("--write", metavar="OUT.jsonl")
    mode.add_argument("--compare", nargs=2, metavar=("A.jsonl", "B.jsonl"))
    mode.add_argument("--one", metavar="CELL", help=argparse.SUPPRESS)
    parser.add_argument("--dump", metavar="DIR")
    parser.add_argument("cells", nargs="*")
    args = parser.parse_args(argv)
    if args.one:
        cell, *overrides = args.one.split(",")
        text = lowered_text(cell, overrides)
        if args.dump:
            with open(os.path.join(args.dump, args.one + ".mlir"), "w") as f:
                f.write(text)
        print(json.dumps(hash_line(args.one, text)))
    elif args.write:
        write(args.write, args.cells, args.dump)
    else:
        differ, cells = compare(*args.compare)
        for cell, why in differ:
            print(f"{cell}: {why}")
        print(f"same_program: {cells - len(differ)} of {cells} cells equal")
        return 1 if differ else 0
    return 0


if __name__ == "__main__":
    sys.exit(main())
