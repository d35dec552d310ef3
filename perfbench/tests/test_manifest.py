"""BENCHMARK.json and the files it names, held to the driver's contract; and
the requirement that a cell, a configuration and a per-layer metric can be
added as files of their own, with no edit to a file that is there."""

import glob
import json
import math
import os
import re
import shutil
import subprocess
import sys

import pytest

from perfbench.harness import build, manifest

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
WIDTHS = re.compile(r"(hidden_size|intermediate|latent|state|proj|head_dim|_dim$|_rank$|experts_per_tok|expansion)")


def test_manifest_meets_the_contract():
    m = manifest.load_manifest()
    assert set(m) == {"command", "paths", "run_seconds", "configs", "workloads",
                      "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(manifest.ROOT, "BENCHMARK.json")) <= 64 * 1024
    assert 1 <= m["run_seconds"] <= 51 and isinstance(m["run_seconds"], int)
    assert 2 <= len(m["workloads"]) <= 24 and 1 <= len(m["configs"]) <= 24
    names = lambda key: [x["name"] for x in m[key]]
    for key in ("configs", "workloads"):
        assert len(set(names(key))) == len(names(key))
    metrics = names("end_to_end") + names("per_layer")
    assert len(set(metrics)) == len(metrics) and "setup_s" in names("end_to_end")
    for c in m["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["file"].startswith("perfbench/")
        assert not any(WIDTHS.search(k) for k in c["reduced"])
        assert c["name"] in {w["config"] for w in m["workloads"]}
    for w in m["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert all(NAME.match(w[k]) for k in ("name", "config", "traffic"))
        assert w["chips"] in (1, 4) and w["config"] in names("configs")
    assert sum(w["chips"] == 4 for w in m["workloads"]) <= max(1, len(m["workloads"]) // 4)
    pairs = [(w["config"], w["traffic"]) for w in m["workloads"]]
    assert len(set(pairs)) == len(pairs)
    for e in m["end_to_end"]:
        assert set(e) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert 0.01 <= e["bound"] <= 0.1 and e["source"] in ("host_clock", "device_trace")
    layers = set()
    for p in m["per_layer"]:
        assert set(p) - {"workloads"} == {"name", "unit", "better", "source", "layer", "moves"}
        assert p["moves"] in names("end_to_end")
        assert p["source"] in ("device_trace", "program_span", "program_counter", "host_clock")
        layers.add(p["layer"])
    for x in m["end_to_end"] + m["per_layer"]:
        assert NAME.match(x["name"]) and UNIT.match(x["unit"])
        assert x["better"] in ("lower", "higher")
        assert set(x.get("workloads", [])) <= set(names("workloads"))
    for text in ([x["why"] for x in m["configs"] + m["workloads"]] + sorted(layers)
                 + [c["source"] for c in m["configs"]] + m["command"]):
        assert 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


@pytest.mark.parametrize(
    "path", sorted(glob.glob(os.path.join(manifest.BENCH_DIR, "workloads", "*.json"))),
    ids=os.path.basename,
)
def test_every_workload_file_names_a_config_that_parses(path):
    with open(path) as f:
        workload = json.load(f)
    name = os.path.basename(path)[: -len(".json")]
    assert name == f"{workload['config']}.{workload['traffic']}"
    with open(os.path.join(manifest.BENCH_DIR, "configs", workload["config"] + ".json")) as f:
        config = json.load(f)
    shape = build.model_shape(workload, config)
    assert shape["hidden"] == shape["heads"] * shape["head_dim"]
    assert shape["layers"] <= config["num_hidden_layers"]
    degrees = [workload["mesh"][a] for a in build.MESH_AXES]
    assert workload["chips"] == math.prod(degrees)
    entry, _, _ = manifest.load_cell(name)  # and BENCHMARK.json agrees with the file
    assert entry["chips"] == workload["chips"]


def test_each_per_layer_metric_has_a_reader_that_agrees_with_the_manifest():
    for p in manifest.load_manifest()["per_layer"]:
        module = __import__(f"perfbench.metrics.{p['name']}", fromlist=["read"])
        assert (module.LAYER, module.UNIT, module.MOVES) == (p["layer"], p["unit"], p["moves"])
        assert callable(module.read)


def test_files_under_paths_are_named_from_a_names_characters():
    tracked = subprocess.run(
        ["git", "ls-files", "--cached", "--others", "--exclude-standard", "perfbench"],
        cwd=manifest.ROOT, capture_output=True, text=True, check=True,
    ).stdout.split()
    assert tracked and all(re.match(r"^[A-Za-z0-9_.\-/]+$", f) for f in tracked)


def test_a_cell_a_config_and_a_metric_are_added_without_editing_a_file(tmp_path):
    """Exactly what a later PR does: new files, new entries, nothing changed;
    then the new cell runs (dry, on the CPU) and the new metric is read."""
    root = str(tmp_path)
    shutil.copy(os.path.join(manifest.ROOT, "BENCHMARK.json"), root)
    shutil.copytree(manifest.BENCH_DIR, os.path.join(root, "perfbench"),
                    ignore=shutil.ignore_patterns(".*", "__pycache__"))
    package = "distributed_llm_training_benchmark_framework_tpu"
    os.symlink(os.path.join(manifest.ROOT, package), os.path.join(root, package))
    before = {f: open(f, "rb").read() for f in glob.glob(root + "/perfbench/**/*.*", recursive=True)}

    bench = os.path.join(root, "perfbench")
    with open(os.path.join(bench, "configs", "tinygpt-a.json")) as f:
        config = json.load(f)
    config.update(name="third", num_hidden_layers=4)
    with open(os.path.join(bench, "configs", "third.json"), "w") as f:
        json.dump(config, f)
    with open(os.path.join(bench, "workloads", "tinygpt-a.seq2048.json")) as f:
        workload = json.load(f)
    workload.update(config="third", traffic="fifth", grad_accum=2)
    with open(os.path.join(bench, "workloads", "third.fifth.json"), "w") as f:
        json.dump(workload, f)
    with open(os.path.join(bench, "metrics", "steps_traced.py"), "w") as f:
        f.write('LAYER, UNIT, MOVES = "train step", "count", "step_time_p50_ms"\n\n\n'
                'def read(trace, run):\n    return run["traced_steps"]\n')
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        m = json.load(f)
    m["configs"].append({"name": "third", "source": "test", "why": "test",
                         "file": "perfbench/configs/third.json", "reduced": ["num_hidden_layers"]})
    m["workloads"].append({"name": "third.fifth", "config": "third", "traffic": "fifth",
                           "chips": 1, "why": "test"})
    m["per_layer"].append({"name": "steps_traced", "unit": "count", "better": "higher",
                           "source": "program_counter", "layer": "train step",
                           "moves": "step_time_p50_ms", "workloads": ["third.fifth"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(m, f)

    run = subprocess.run(
        [sys.executable, "perfbench/run.py", "--workload", "third.fifth", "--seed", "5",
         "--seconds", "1", "--trace", "1", "--allow-cpu"],
        cwd=root, capture_output=True, text=True, timeout=600,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert run.returncode == 0, run.stderr[-2000:]
    last = json.loads(run.stdout.strip().splitlines()[-1])
    assert last["correct"] is True and last["device"]["platform"] == "cpu"
    assert last["metrics"] == {}  # a dry run reports no metric
    assert "not reported: steps_traced = 5" in run.stdout
    assert all(open(f, "rb").read() == data for f, data in before.items())


def test_no_accelerator_is_an_error_and_prints_no_result():
    run = subprocess.run(
        [sys.executable, "perfbench/run.py", "--workload", "tinygpt-a.seq2048", "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        cwd=manifest.ROOT, capture_output=True, text=True, timeout=300,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert run.returncode != 0 and not run.stdout.strip().startswith("{")
    assert "needs 1 TPU chip" in run.stderr
