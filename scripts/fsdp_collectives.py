#!/usr/bin/env python
"""A cell's collectives, off the chip: compile its real-size step for a
described (not attached) v5e:2x2 and print every collective by kind x module
scope x phase, with operand bytes, and the step's peak memory.

    JAX_PLATFORMS=cpu python scripts/fsdp_collectives.py mistral-7b.fsdp4 [key=value ...]
        [--text-out step.hlo.txt] [--shapes]

``key=value`` overrides a key of the cell's workload file for this compile
(``strategy=zero3 depth=4 remat=full``), as ``perfbench/tools/
describe_compile.py`` does; that tool prints the total count only. The module
and phase are read off each instruction's ``op_name`` with the benchmark's own
rule (``perfbench/harness/scopes.py::classify``): the scopes of
``utils/scopes.py`` under ``jvp(...)`` (forward), ``transpose(...)``
(backward) or ``rematted_computation`` (remat's second run, shown as
``forward+remat``). An async pair counts once, at its start; bytes are the
instruction's operands on one chip. A synchronous kind in the entry
computation (all-to-all, all-gather, all-reduce, reduce-scatter without
``-start``) stops the chip's compute for its whole length; one printed inside
a fused computation runs within that fusion's matmul; a ``collective-permute-
start`` / ``-done`` ring runs beside compute. ``--shapes`` adds a line a
distinct (kind, scope, shape).
Nothing runs: no result, no time. One such process at a time (libtpu's lock).
"""

import argparse
import collections
import dataclasses
import json
import math
import os
import re
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

KINDS = ("all-gather", "all-reduce", "reduce-scatter", "collective-permute", "all-to-all")
COLLECTIVE = re.compile(
    r"^(?:ROOT )?%?[\w.\-]+ = .*? (" + "|".join(KINDS) + r")(-start)?\(([^)]*)\)"
)
DEFINITION = re.compile(r"^(?:ROOT )?%?([\w.\-]+) = (\(.*?\)|\S+) [\w\-]+\(")
ARRAY = re.compile(r"\b(pred|[a-z]+\d+)\[([\d,]*)\]")
BYTES = {"pred": 1, "s8": 1, "u8": 1, "bf16": 2, "f16": 2, "s16": 2, "u16": 2,
         "f32": 4, "s32": 4, "u32": 4, "f64": 8, "s64": 8, "u64": 8}

Collective = collections.namedtuple("Collective", "kind sync module phase nbytes shapes")


def array_bytes(type_text):
    """Bytes of every array in an HLO type ('bf16[4,1,8]{2,1,0}' or a tuple)."""
    return sum(math.prod(map(int, filter(None, dims.split(",")))) * BYTES.get(dtype, 4)
               for dtype, dims in ARRAY.findall(type_text))


def shape_of(type_text):
    return " ".join(f"{d}[{dims}]" for d, dims in ARRAY.findall(type_text))


def collectives(hlo_text):
    """The ``Collective`` rows of a compiled module's text: kind, whether it
    is synchronous, module and phase by the benchmark's rule, operand bytes
    and shapes on one chip. An instruction printed inside a fused computation
    counts where the text prints it, as the benchmark's own count does."""
    from perfbench.harness import scopes

    lines = [line.strip() for line in hlo_text.splitlines()]
    types = {found.group(1): found.group(2) for found in map(DEFINITION.match, lines) if found}
    rows = []
    for line in lines:
        found = COLLECTIVE.match(line)
        if not found:
            continue
        kind, start, operands = found.groups()
        operands = [types.get(name.split()[-1].lstrip("%"), "")
                    for name in operands.split(",") if name.strip()]
        op_name = scopes.OP_NAME.search(line)
        scope = scopes.classify(op_name.group(1) if op_name else "")
        rows.append(Collective(
            kind, not start, scope.module or "unscoped",
            scope.phase + ("+remat" if scope.recompute else ""),
            sum(map(array_bytes, operands)), " ".join(filter(None, map(shape_of, operands))),
        ))
    return rows


def compile_cell(cell, overrides):
    """The cell's step, compiled for the described topology (the recipe of
    ``perfbench/tools/describe_compile.py``)."""
    import jax
    from jax.experimental import topologies

    from distributed_llm_training_benchmark_framework_tpu.parallel import get_strategy, make_mesh
    from distributed_llm_training_benchmark_framework_tpu.train.step import abstract_compile_step
    from perfbench.harness import build, manifest

    _, workload, config = manifest.load_cell(cell)
    for override in overrides:
        key, _, value = override.partition("=")
        workload[key] = json.loads(value) if value[:1].isdigit() else value
    jax.config.update("jax_enable_compilation_cache", False)
    jax.config.update("jax_default_prng_impl", "rbg")
    # The program asks jax.default_backend() whether to run its kernels or
    # interpret them; the target here is the described chip.
    jax.default_backend = lambda: "tpu"
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    mesh = make_mesh(tuple(workload["mesh"][a] for a in build.MESH_AXES), build.MESH_AXES,
                     devices=topo.devices[: workload["chips"]])
    strategy = dataclasses.replace(get_strategy(workload["strategy"]), remat=workload["remat"])
    builder = manifest.resolve(config.get("builder", "perfbench.harness.build:tinygpt_config"))
    return abstract_compile_step(
        builder(workload, config), strategy, mesh, grad_accum=workload["grad_accum"],
        global_micro=workload["micro_batch_per_chip"] * workload["mesh"]["data"],
        seq_len=workload["seq_len"], dataset_size=workload["dataset_rows"],
    )


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("cell")
    parser.add_argument("overrides", nargs="*", metavar="key=value")
    parser.add_argument("--text-out", help="write the compiled step's HLO text here")
    parser.add_argument("--shapes", action="store_true",
                        help="one more line a distinct (kind, scope, phase, operand shape)")
    args = parser.parse_args(argv)

    from perfbench.metrics import collective_ops

    t = time.perf_counter()
    compiled = compile_cell(args.cell, args.overrides)
    seconds = time.perf_counter() - t
    text, memory = compiled.as_text(), compiled.memory_analysis()
    if args.text_out:
        with open(args.text_out, "w") as f:
            f.write(text)
    rows = collectives(text)

    table = collections.defaultdict(lambda: [0, 0])
    by_shape, by_kind = collections.Counter(), collections.Counter()
    for row in rows:
        kind = row.kind + ("" if row.sync else " (async)")
        table[kind, row.module, row.phase][0] += 1
        table[kind, row.module, row.phase][1] += row.nbytes
        by_shape[kind, row.module, row.phase, row.shapes] += 1
        by_kind[kind] += 1
    print(f"{args.cell} {' '.join(args.overrides)}: compiled in {seconds:.0f} s for a described v5e:2x2")
    print(f"{'kind':<28}{'module':<11}{'phase':<15}{'count':>6}{'operand GB':>12}")
    for (kind, module, phase), (count, nbytes) in sorted(table.items()):
        print(f"{kind:<28}{module:<11}{phase:<15}{count:>6}{nbytes / 1e9:>12.3f}")
    if args.shapes:
        for (kind, module, phase, shape), count in sorted(by_shape.items()):
            print(f"  {count:>4} x {kind} {module}.{phase}: {shape}")
    print(json.dumps({
        "cell": args.cell, "overrides": args.overrides, "compile_s": round(seconds, 1),
        "collectives": len(rows), "by_kind": dict(sorted(by_kind.items())),
        # the benchmark's `collective_ops`: its pattern skips tuple-typed results,
        # so it counts no async ring and no variadic all-reduce
        "benchmark_collective_ops": len(collective_ops.OP.findall(text)),
        "operand_gb": round(sum(row.nbytes for row in rows) / 1e9, 3),
        "peak_gb": memory.peak_memory_in_bytes / 1e9,
        "arguments_gb": memory.argument_size_in_bytes / 1e9,
        "temporaries_gb": memory.temp_size_in_bytes / 1e9,
        "mosaic_kernels": text.count('custom_call_target="tpu_custom_call"'),
    }))


if __name__ == "__main__":
    main()
