#!/usr/bin/env python
"""A cell's layout copies, off the chip: compile its real-size step for a
described (not attached) v5e:2x2 and print the entry computation's ``copy``
instructions by shape, with bytes and the compiler's ``estimated_cycles``, and
the device layout each leaf of the training state is held in.

    JAX_PLATFORMS=cpu python scripts/state_copies.py mistral-7b.d2 [key=value ...]
        [--text-out step.hlo.txt]

``key=value`` overrides a key of the cell's workload file, as
``scripts/fsdp_collectives.py`` takes them (its ``compile_cell`` is the recipe).
A ``copy`` in the entry computation is a relayout the compiler made between
two fusions, or at the step's boundary: between the layout the step computes
in and the default one, which an argument or a result is held to. The step
pins every gradient to the layout its leaf lives in
(``train/step.py::_in_the_layouts_the_state_lives_in``), so AdamW runs in that
layout and no such copy should have a state leaf's shape on a chip; without
the pin a leaf whose second-to-last axis is 2 (``wgu``, ``wkv``: tiles of two
rows, where the gradient's matmul writes tiles of eight) and its two moments
were copied back through a relayout every step. The last line says how many
such copies there are (``state_copies``: a leaf's dtype and dims and no
``op_name``, which is how the compiler's own boundary copies come; one with a
name is a value of the model's that happens to have the shape) and which. A
copy of a leaf's dims in another dtype (the cast weights) is listed as
``cast``.
Nothing runs: a compile says which copies exist, not what they cost on the
chip. One such process at a time (libtpu's lock).
"""

import argparse
import collections
import json
import math
import os
import re
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from fsdp_collectives import BYTES, compile_cell  # noqa: E402  (sets TPU_LOG_DIR, the path)

COPY = re.compile(
    r"^(?:ROOT )?%?[\w.\-]+ = (pred|[a-z]+\d+)\[([\d,]*)\](\{[^ ]*\})? copy\(")
CYCLES = re.compile(r'"estimated_cycles":"(\d+)"')

Copy = collections.namedtuple("Copy", "dtype dims to_layout nbytes cycles named")


def entry_copies(hlo_text):
    """The ``Copy`` rows of the entry computation of a compiled module's text:
    result dtype and dims (one chip's), the layout it writes, bytes, the
    compiler's estimated cycles (0 where it printed none) and whether the
    instruction carries an ``op_name`` (one the compiler made has none)."""
    rows, inside = [], False
    for line in hlo_text.splitlines():
        if line.startswith("ENTRY "):
            inside = True
        elif inside and line.startswith("}"):
            break
        found = inside and COPY.match(line.strip())
        if not found:
            continue
        dtype, dims, layout = found.groups()
        dims = tuple(map(int, filter(None, dims.split(","))))
        cycles = CYCLES.search(line)
        rows.append(Copy(dtype, dims, layout or "", math.prod(dims) * BYTES.get(dtype, 4),
                         int(cycles.group(1)) if cycles else 0, "op_name=" in line))
    return rows


def state_leaves(compiled):
    """[(path, dtype name, one chip's dims, the chosen Layout)] of the step's
    parameters and optimizer state, from the executable's own formats."""
    import jax

    (params, opt_state, *_), _ = compiled.input_formats
    (params_aval, opt_aval, *_), _ = compiled.in_avals
    rows = []
    for name, formats, avals in (("params", params, params_aval), ("opt_state", opt_state, opt_aval)):
        flat, treedef = jax.tree_util.tree_flatten_with_path(formats)
        for (path, fmt), aval in zip(flat, treedef.flatten_up_to(avals)):
            rows.append((name + jax.tree_util.keystr(path), aval.dtype.name,
                         tuple(fmt.sharding.shard_shape(aval.shape)), fmt.layout))
    return rows


HLO_DTYPE = {"float32": "f32", "bfloat16": "bf16", "float16": "f16", "int32": "s32",
             "uint32": "u32", "int8": "s8", "uint8": "u8", "bool": "pred"}


def state_shaped(copies, leaves):
    """-> (the compiler's own copies with a state leaf's dtype and dims, copies
    with a leaf's dims in another dtype). Scalars and vectors are no relayout
    and are left out."""
    exact = {(HLO_DTYPE.get(dtype, dtype), dims) for _, dtype, dims, _ in leaves if len(dims) > 1}
    dims_only = {dims for _, dims in exact}
    same = [c for c in copies if (c.dtype, c.dims) in exact and not c.named]
    cast = [c for c in copies if c.dims in dims_only and (c.dtype, c.dims) not in exact]
    return same, cast


def shape_text(copy):
    return f"{copy.dtype}[{','.join(map(str, copy.dims))}]"


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("cell")
    parser.add_argument("overrides", nargs="*", metavar="key=value")
    parser.add_argument("--text-out", help="write the compiled step's HLO text here")
    args = parser.parse_args(argv)

    t = time.perf_counter()
    compiled = compile_cell(args.cell, args.overrides)
    seconds = time.perf_counter() - t
    text, memory = compiled.as_text(), compiled.memory_analysis()
    if args.text_out:
        with open(args.text_out, "w") as f:
            f.write(text)
    copies, leaves = entry_copies(text), state_leaves(compiled)
    same, cast = state_shaped(copies, leaves)

    print(f"{args.cell} {' '.join(args.overrides)}: compiled in {seconds:.0f} s for a described v5e:2x2")
    by_shape = collections.defaultdict(lambda: [0, 0, 0, 0])
    for c in copies:
        row = by_shape[shape_text(c), c.to_layout]
        row[0] += 1
        row[1] += c.nbytes
        row[2] += c.cycles
        row[3] += c.named
    print(f"{'copy, entry computation':<34}{'writes layout':<30}{'count':>6}{'named':>6}{'MB':>10}{'est. Mcycles':>14}")
    for (shape, layout), (count, nbytes, cycles, named) in sorted(
            by_shape.items(), key=lambda kv: -kv[1][1]):
        print(f"{shape:<34}{layout:<30}{count:>6}{named:>6}{nbytes / 1e6:>10.1f}{cycles / 1e6:>14.2f}")
    print("state leaves, one chip's shape, the layout the step holds them in:")
    for path, dtype, dims, layout in leaves:
        if len(dims) > 1:
            print(f"  {path:<44}{dtype}{list(dims)}: major_to_minor={layout.major_to_minor} "
                  f"tiling={layout.tiling}")
    print(json.dumps({
        "cell": args.cell, "overrides": args.overrides,
        "compile_s": round(seconds, 1), "entry_copies": len(copies),
        "entry_copy_gb": round(sum(c.nbytes for c in copies) / 1e9, 3),
        "entry_copy_est_mcycles": round(sum(c.cycles for c in copies) / 1e6, 2),
        "state_copies": len(same), "state_copy_shapes": sorted({shape_text(c) for c in same}),
        "cast_copies": len(cast), "cast_copy_shapes": sorted({shape_text(c) for c in cast}),
        "peak_gb": memory.peak_memory_in_bytes / 1e9,
    }))


if __name__ == "__main__":
    main()
