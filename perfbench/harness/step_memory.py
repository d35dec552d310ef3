"""The step's memory, read from the program's own record.

The program (``utils/scopes.py::step_memory``) keeps, for the newest step
``train/step.py::aot_compile`` compiled (the cell's timed step, the last
``step_compile``, as in ``host_spans``): buffer assignment's classes of the
compiled step, the smallest ``bytes_limit`` the mesh's devices' allocators
report, and a callable that lists what a micro-batch's forward keeps for its
backward on one chip, under the cell's remat policy (``kept``) and under none
(``all``), by the program's scope path and ``checkpoint_name``. Nothing traces
until it is called; this module calls it once a traced run, after the traced
window, and joins what the policy drops (``all`` minus ``kept``, by scope) to
the scopes reader's seconds of what remat runs twice, by top-level module
(``scopes.Scope.recompute``; below a module the account has bytes alone).

``metric`` is what the four counter files call; the first call makes the
account and prints one ``perfbench: memory:`` line. On a program without the
record (a parent commit) the account is None and the four read None.
GB is 10^9 bytes throughout, as ``hbm_peak_gb``'s.
"""

import collections
import json
import math
import os
import re
import time

from . import scopes, step_loop, trace_reduce

GB = 1e9
LARGEST = 6
SHOWN = 0.0005 * GB  # a sub-scope under it reads 0.000 / 0.000 and is left off the line


def program():
    """The program's ``utils/scopes`` where it keeps the step's memory, else None."""
    try:
        from distributed_llm_training_benchmark_framework_tpu.utils import scopes as program_scopes
    except ImportError:
        return None
    return program_scopes if hasattr(program_scopes, "step_memory") else None


def by_scope(entries):
    """{scope path: bytes} of a list of (scope_path, name, shape, dtype, bytes)."""
    out = collections.Counter()
    for path, _, _, _, nbytes in entries:
        out[tuple(path)] += nbytes
    return out


def largest(entries, n=LARGEST):
    """The ``n`` largest groups of equal (path, name, shape, dtype):
    [(path, name, dtype, shape, count, bytes together)], largest first."""
    groups = {}
    for path, name, shape, dtype, nbytes in entries:
        group = groups.setdefault((tuple(path), name, dtype, tuple(shape)), [0, 0])
        group[0] += 1
        group[1] += nbytes
    ranked = sorted(groups.items(), key=lambda kv: -kv[1][1])[:n]
    return [(*key, count, nbytes) for key, (count, nbytes) in ranked]


def without(everything, kept):
    """The entries of ``everything`` that ``kept`` does not hold, matched by
    (path, name, shape, dtype) and count: what the policy drops."""
    have = collections.Counter((tuple(p), n, tuple(s), d) for p, n, s, d, _ in kept)
    out = []
    for path, name, shape, dtype, nbytes in everything:
        key = (tuple(path), name, tuple(shape), dtype)
        if have[key]:
            have[key] -= 1
        else:
            out.append((path, name, shape, dtype, nbytes))
    return out


HLO_TYPE = {"float32": "f32", "bfloat16": "bf16", "float16": "f16", "int32": "s32",
            "uint32": "u32", "int8": "s8", "uint8": "u8", "bool": "pred"}
RESULT = re.compile(r" = (.*?) [a-z][\w\-]*\(")  # an instruction's result type, a tuple's too
ARRAY = re.compile(r"\b(pred|[a-z]+\d+)\[([\d,]*)\]")


def produced(hlo_text):
    """{(element type, elements)} of the results of a compiled module's
    instructions outside its fusions' bodies: what the step can hold in a
    buffer of its own (in whatever shape: XLA reshapes freely)."""
    lines = [line.strip() for line in hlo_text.splitlines()]
    fused = {called.group(1) for line in lines if " fusion(" in line
             and (called := trace_reduce.CALLS.search(line))}
    found, current = set(), None
    for line in lines:
        if header := scopes.COMPUTATION.match(line):
            current = header.group(1)
        elif current not in fused and (result := RESULT.search(line)):
            for element, dims in ARRAY.findall(result.group(1)):
                found.add((element, math.prod(int(d) for d in dims.split(",") if d)))
    return found


def never_a_buffer(entries, hlo_text):
    """The entries no instruction of the compiled step has a result of the
    type and size of (XLA fused them into their consumers or makes them
    again): a lower bound of what the list holds and the step does not. An
    entry of a type this table lacks (a key) is taken as held."""
    have = produced(hlo_text)
    return [e for e in entries
            if e[3] in HLO_TYPE and (HLO_TYPE[e[3]], math.prod(e[2])) not in have]


def recompute_ms(trace, run):
    """{module: ms a step in ops remat runs a second time}, first chip, from
    the scopes reader; None where the trace holds no device or no scope."""
    if trace is None or not trace.devices():
        return None
    found = scopes._first_chip(trace, run["hlo_text"])
    if found is None:
        return None
    out = collections.Counter()
    for scope, seconds in found[0].items():
        if scope.recompute:
            out[scope.module or "unscoped"] += 1e3 * seconds / run["traced_steps"]
    return out


def summarize(trace, run):
    """The account of one traced run, or None where the program keeps none."""
    program_scopes = program()
    if program_scopes is None:
        return None
    record = program_scopes.step_memory()
    out = {"compiled": record["compiled"], "limit": record["bytes_limit"],
           "allocator": run.get("memory_allocator_bytes"), "saved": None}
    if record["saved"] is None:
        return out
    t = time.perf_counter()
    saved = record["saved"]()
    write_lists(run, saved)
    kept, everything = by_scope(saved["kept"]), by_scope(saved["all"])
    modules = {}
    for path in sorted(set(kept) | set(everything), key=lambda p: -everything[p]):
        module = modules.setdefault(path[0], {"kept": 0, "all": 0, "below": []})
        module["kept"] += kept[path]
        module["all"] += everything[path]
        if len(path) > 1:
            module["below"].append(("/".join(path[1:]), kept[path], everything[path]))
    out["saved"] = {
        "trace_s": time.perf_counter() - t,
        "kept_bytes": sum(kept.values()), "all_bytes": sum(everything.values()),
        "modules": modules, "left_out": saved["left_out"],
        "largest_kept": largest(saved["kept"]),
        "never_a_buffer": never_a_buffer(saved["kept"], run["hlo_text"]),
        "largest_dropped": largest(without(saved["all"], saved["kept"])),
        "recompute_ms": recompute_ms(trace, run),
    }
    return out


def write_lists(run, saved):
    """Both lists whole, equal entries together, as
    ``perfbench/.trace/<cell>/step_memory.json`` beside the trace and
    ``step_hlo.txt``, for whoever sizes a policy by hand."""
    workload = run.get("workload")
    directory = workload and os.path.join(
        step_loop.TRACE_DIR, f"{workload['config']}.{workload['traffic']}")
    if not directory or not os.path.isdir(directory):
        return
    lists = {which: [{"scope": "/".join(path), "name": name, "dtype": dtype, "shape": shape,
                      "count": count, "bytes": nbytes}
                     for path, name, dtype, shape, count, nbytes in largest(saved[which], None)]
             for which in ("kept", "all")}
    with open(os.path.join(directory, "step_memory.json"), "w") as f:
        json.dump({**lists, "left_out": saved["left_out"]}, f)


def _gb(nbytes):
    return "none" if nbytes is None else f"{nbytes / GB:.3f}"


def _named(groups):
    return ", ".join(f"{'/'.join(path)} {name} {dtype}{list(shape)} x{count} {_gb(nbytes)}"
                     for path, name, dtype, shape, count, nbytes in groups) or "nothing"


def line(a):
    """The one ``perfbench: memory:`` line."""
    c = a["compiled"]
    assigned = ("no analysis from the backend" if c is None else
                f"assigned peak {_gb(c['peak_bytes'])}, the compiler's own, under its classes' sum "
                f"{_gb(c['argument_bytes'] + c['output_bytes'] + c['temp_bytes'] - c['alias_bytes'])} "
                f"= arguments {_gb(c['argument_bytes'])} + outputs {_gb(c['output_bytes'])} + temp "
                f"{_gb(c['temp_bytes'])} - aliased {_gb(c['alias_bytes'])}")
    head = (f"perfbench: memory: GB; limit {_gb(a['limit'])}; {assigned}; allocator's mark "
            f"{_gb(a['allocator'])}")
    s = a["saved"]
    if s is None:
        return head + "; kept for the backward: not listed (the mesh is not data-only)"
    ms = s["recompute_ms"]
    parts = []
    for name, module in s["modules"].items():
        below = ", ".join(f"{path} {_gb(kept)} / {_gb(everything - kept)}"
                          for path, kept, everything in module["below"]
                          if max(kept, abs(everything - kept)) >= SHOWN)
        timed = "" if ms is None else f", {ms[name]:.2f} ms"
        parts.append(f"{name} {_gb(module['kept'])} / {_gb(module['all'] - module['kept'])}{timed}"
                     + (f" ({below})" if below else ""))
    left = s["left_out"]
    fused = s["never_a_buffer"]
    over = c is not None and s["kept_bytes"] > c["temp_bytes"]
    bound = (f"; of the kept, {_gb(sum(e[4] for e in fused))} have no buffer of their type and "
             f"size in the compiled step (a list of the jaxpr's residuals bounds what XLA holds "
             f"from above{', here by more than temp' if over else ''}): {_named(largest(fused, 4))}")
    return (
        f"{head}; kept for the backward {_gb(s['kept_bytes'])} of {_gb(s['all_bytes'])} without "
        f"remat (a micro-batch, a chip; two traces from shapes, {s['trace_s']:.2f} s); by module, "
        f"kept / dropped and remat's ms a step: {'; '.join(parts)}; the largest kept: "
        f"{_named(s['largest_kept'])}; the largest dropped: {_named(s['largest_dropped'])}; left "
        f"out, kept / without remat: constants {_gb(left['kept']['constants'])} / "
        f"{_gb(left['all']['constants'])}, computed from the weights alone "
        f"{_gb(left['kept']['weights'])} / {_gb(left['all']['weights'])}{bound}"
    )


def metric(name, trace, run):
    """One of the four counters for a metric file. The account is made once a
    run, kept in ``run`` and printed when it is made."""
    if "step_memory" not in run:
        run["step_memory"] = summarize(trace, run)
        if run["step_memory"] is not None:
            print(line(run["step_memory"]), flush=True)
    a = run["step_memory"]
    if a is None:
        return None
    compiled, saved = a["compiled"], a["saved"]
    if name == "step_temp_gb":
        return None if compiled is None else compiled["temp_bytes"] / GB
    if name == "hbm_headroom_gb":
        if compiled is None or a["limit"] is None:
            return None
        return (a["limit"] - compiled["peak_bytes"]) / GB
    if saved is None:
        return None
    if name == "saved_for_backward_gb":
        return saved["kept_bytes"] / GB
    if name == "remat_dropped_gb":
        return (saved["all_bytes"] - saved["kept_bytes"]) / GB
    raise KeyError(name)
