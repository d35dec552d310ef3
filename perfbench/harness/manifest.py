"""Find a cell's files by the names in ``BENCHMARK.json``.

A cell is ``perfbench/workloads/<cell>.json`` (the job: layout, lengths,
batch, loop) over ``perfbench/configs/<config>.json`` (the model's sizes). A
per-layer metric is ``perfbench/metrics/<name>.py``. Adding one of them is a
new file plus an entry in ``BENCHMARK.json``; nothing here names a cell.
"""

import importlib
import json
import os

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH_DIR = os.path.join(ROOT, "perfbench")


def _read_json(path):
    with open(path) as f:
        return json.load(f)


def load_manifest(root=ROOT):
    return _read_json(os.path.join(root, "BENCHMARK.json"))


def load_cell(name, root=ROOT):
    """(entry in BENCHMARK.json, workload dict, config dict) for one cell."""
    manifest = load_manifest(root)
    entries = [w for w in manifest["workloads"] if w["name"] == name]
    if len(entries) != 1:
        known = [w["name"] for w in manifest["workloads"]]
        raise SystemExit(f"perfbench: no cell {name!r} in BENCHMARK.json (has {known})")
    entry = entries[0]
    workload = _read_json(os.path.join(root, "perfbench", "workloads", f"{name}.json"))
    configs = {c["name"]: c for c in manifest["configs"]}
    config = _read_json(os.path.join(root, configs[entry["config"]]["file"]))
    for key in ("config", "chips"):
        if workload[key] != entry[key]:
            raise SystemExit(
                f"perfbench: {name}: workload file says {key}={workload[key]!r}, "
                f"BENCHMARK.json says {entry[key]!r}"
            )
    return entry, workload, config


def cell_metrics(manifest, cell, kind):
    """The ``end_to_end`` or ``per_layer`` entries that apply to ``cell``."""
    return [
        m for m in manifest[kind]
        if "workloads" not in m or cell in m["workloads"]
    ]


def resolve(dotted):
    """'package.module:attribute' -> the attribute."""
    module, _, attribute = dotted.partition(":")
    return getattr(importlib.import_module(module), attribute)


def metric_reader(name):
    """The ``read(trace, run)`` of ``perfbench/metrics/<name>.py``."""
    return resolve(f"perfbench.metrics.{name}:read")
