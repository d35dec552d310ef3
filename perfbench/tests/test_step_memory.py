"""The memory reader (``harness/step_memory.py``) and its six metric files.

The reductions on made-up entries; the metric files through the manifest, on
a made-up record and on a program without one; the six entries' place and
lists in ``BENCHMARK.json``; and two cells' dry runs, which have to print the
``perfbench: memory:`` line. A dry run has no device in its trace and the
CPU's allocator no limit, so there ``hbm_headroom_gb`` and the two recompute
shares read None; the three counters of bytes are "dry run, not reported".
"""

import json
import os
import subprocess
import sys
import types

import pytest

from perfbench.harness import manifest, step_memory

SIX = ("step_temp_gb", "hbm_headroom_gb", "saved_for_backward_gb", "remat_dropped_gb",
       "recompute_attention_time_pct", "recompute_mlp_time_pct")
COUNTERS = SIX[:4]
MB = 1_000_000
KEPT = [
    (("attention",), "flash_out", (1, 32, 4096, 128), "bfloat16", 32 * MB),
    (("attention",), "flash_out", (1, 32, 4096, 128), "bfloat16", 32 * MB),
    (("mlp",), "add", (1, 4096, 4096), "bfloat16", 32 * MB),
    (("head",), "exp", (4096, 32768), "float32", 512 * MB),
]
ALL = KEPT + [
    (("mlp", "experts"), "mul", (32768, 1024), "bfloat16", 64 * MB),
    (("mlp", "experts"), "mul", (32768, 1024), "bfloat16", 64 * MB),
    (("attention", "kda", "kda_prep"), "dot_general", (1, 4096, 12288), "bfloat16", 96 * MB),
]
COMPILED = {"argument_bytes": 7000 * MB, "output_bytes": 7000 * MB, "temp_bytes": 5500 * MB,
            "alias_bytes": 7000 * MB, "peak_bytes": 12000 * MB}


def a_program(saved="listed", limit=15_750 * MB):
    listed = lambda: {"kept": KEPT, "all": ALL, "left_out": {
        which: {"constants": 1 * MB, "weights": 2 * MB} for which in ("kept", "all")}}
    record = {"compiled": COMPILED, "bytes_limit": limit,
              "saved": listed if saved == "listed" else None}
    return types.SimpleNamespace(step_memory=lambda: record)


def a_run():
    return {"memory_allocator_bytes": 7560 * MB, "hlo_text": "", "traced_steps": 5}


def test_what_the_policy_drops_is_all_without_kept():
    dropped = step_memory.without(ALL, KEPT)
    assert dropped == ALL[len(KEPT):]
    assert step_memory.by_scope(dropped) == {("mlp", "experts"): 128 * MB,
                                             ("attention", "kda", "kda_prep"): 96 * MB}
    # matched by count: a value kept once and listed twice is dropped once
    assert step_memory.without(KEPT, KEPT[:1]) == KEPT[1:]
    top = step_memory.largest(KEPT, n=2)
    assert top[0] == (("head",), "exp", "float32", (4096, 32768), 1, 512 * MB)
    assert top[1] == (("attention",), "flash_out", "bfloat16", (1, 32, 4096, 128), 2, 64 * MB)


HLO = """
%fused_computation.1 (p: bf16[1,4096,4096]) -> bf16[1,4096,4096] {
  %p = bf16[1,4096,4096]{2,1,0} parameter(0)
  %e = f32[4096,32768]{1,0} exponential(%p)
  ROOT %m = bf16[1,4096,4096]{2,1,0:T(8,128)(2,1)} multiply(%p, %p)
}

ENTRY %main.1 (a: bf16[4096,4096]) -> (bf16[4096,4096], f32[8]) {
  %a = bf16[4096,4096]{1,0} parameter(0)
  %fusion.1 = bf16[1,4096,4096]{2,1,0:T(8,128)(2,1)} fusion(%a), kind=kLoop, calls=%fused_computation.1
  %call = (bf16[32,4096,128]{2,1,0}, f32[32,4096]{1,0}) custom-call(%fusion.1), custom_call_target="tpu_custom_call"
  ROOT %t = (bf16[4096,4096]{1,0}, f32[8]{0}) tuple(%a, %a)
}
"""


def test_what_the_compiled_step_can_hold_in_a_buffer():
    """Results outside the fusions' bodies, by element type and size: the
    float32 exponential lives inside a fusion and is no buffer; a kernel's
    result is one in whatever shape the list gives it."""
    assert step_memory.produced(HLO) == {("bf16", 4096 * 4096), ("bf16", 32 * 4096 * 128),
                                         ("f32", 32 * 4096), ("f32", 8)}
    fused = step_memory.never_a_buffer(KEPT, HLO)
    assert fused == [KEPT[3]]  # the head's float32 exp; flash_out and the block's input are held
    key = (("unscoped",), "random_fold_in", (), "key<fry>", 8)
    assert step_memory.never_a_buffer([key], HLO) == []


def test_the_account_and_its_line(monkeypatch):
    monkeypatch.setattr(step_memory, "program", a_program)
    run = a_run()
    assert step_memory.metric("step_temp_gb", None, run) == 5.5
    assert step_memory.metric("hbm_headroom_gb", None, run) == pytest.approx(3.75)
    assert step_memory.metric("saved_for_backward_gb", None, run) == pytest.approx(0.608)
    assert step_memory.metric("remat_dropped_gb", None, run) == pytest.approx(0.224)
    account = run["step_memory"]
    assert list(account["saved"]["modules"]) == ["head", "mlp", "attention"]  # by bytes without remat
    assert account["saved"]["modules"]["mlp"] == {
        "kept": 32 * MB, "all": 160 * MB, "below": [("experts", 0, 128 * MB)]}
    assert account["saved"]["recompute_ms"] is None  # no device in the trace
    line = step_memory.line(account)
    for part in ("perfbench: memory: GB; limit 15.750; assigned peak 12.000, the compiler's own, "
                 "under its classes' sum 12.500 = arguments 7.000 + outputs 7.000 + temp 5.500 - "
                 "aliased 7.000; allocator's mark 7.560",
                 "kept for the backward 0.608 of 0.832 without remat",
                 "mlp 0.032 / 0.128 (experts 0.000 / 0.128)",
                 "attention 0.064 / 0.096 (kda/kda_prep 0.000 / 0.096)",
                 "the largest kept: head exp float32[4096, 32768] x1 0.512",
                 "the largest dropped: mlp/experts mul bfloat16[32768, 1024] x2 0.128",
                 "constants 0.001 / 0.001", "weights alone 0.002 / 0.002",
                 # the made-up run's compiled text is empty: nothing in it is a buffer
                 "of the kept, 0.608 have no buffer of their type and size in the compiled step"):
        assert part in line, part


def test_with_the_seconds_of_what_remat_runs_twice(monkeypatch):
    from perfbench.harness import scopes

    monkeypatch.setattr(step_memory, "program", a_program)
    seconds = {scopes.Scope("attention", "backward", True, False): 0.050,
               scopes.Scope("attention", "forward", False, False): 0.200,
               scopes.Scope("mlp", "backward", True, False): 0.025}
    monkeypatch.setattr(scopes, "_first_chip", lambda trace, text: (seconds, 1.0))
    trace = types.SimpleNamespace(devices=lambda: ["/device:TPU:0"])
    run = a_run()
    step_memory.metric("step_temp_gb", trace, run)
    assert dict(run["step_memory"]["saved"]["recompute_ms"]) == {"attention": 10.0, "mlp": 5.0}
    line = step_memory.line(run["step_memory"])
    assert "attention 0.064 / 0.096, 10.00 ms" in line and "head 0.512 / 0.000, 0.00 ms" in line


def test_no_limit_and_a_mesh_that_is_not_data_only(monkeypatch):
    monkeypatch.setattr(step_memory, "program", lambda: a_program(saved=None, limit=None))
    run = a_run()
    assert [step_memory.metric(name, None, run) for name in COUNTERS] == [5.5, None, None, None]
    assert "limit none" in step_memory.line(run["step_memory"])
    assert "not listed (the mesh is not data-only)" in step_memory.line(run["step_memory"])


def test_the_six_metric_files_resolve_and_read_none_without_a_record(monkeypatch):
    entries = {m["name"]: m for m in manifest.load_manifest()["per_layer"]}
    for name in SIX:
        module = __import__(f"perfbench.metrics.{name}", fromlist=["read"])
        assert manifest.metric_reader(name) is module.read
        entry = entries[name]
        assert (module.LAYER, module.UNIT, module.MOVES) == (
            entry["layer"], entry["unit"], entry["moves"])
    # a program without the record (a parent commit): every one reads None, none raises
    monkeypatch.setattr(step_memory, "program", lambda: None)
    trace = types.SimpleNamespace(devices=lambda: [])
    assert [manifest.metric_reader(name)(trace, a_run()) for name in SIX] == [None] * 6


def test_the_six_entries_in_this_order_with_the_recompute_cells():
    per_layer = manifest.load_manifest()["per_layer"]
    names = [m["name"] for m in per_layer]
    at = names.index(SIX[0])
    assert tuple(names[at:at + 6]) == SIX
    entries = dict(zip(SIX, per_layer[at:at + 6]))
    recompute = next(m for m in per_layer if m["name"] == "recompute_time_pct")
    assert at > names.index("recompute_time_pct") and len(recompute["workloads"]) == 6
    for name in SIX[:3]:
        assert "workloads" not in entries[name]
    for name in SIX[3:]:
        assert entries[name]["workloads"] == recompute["workloads"]
    assert [entries[n]["better"] for n in SIX] == ["lower", "higher", "lower", "higher",
                                                   "lower", "lower"]
    assert [entries[n]["source"] for n in SIX] == ["program_counter"] * 4 + ["device_trace"] * 2
    assert [entries[n]["layer"] for n in SIX] == ["train step", "device"] + ["train step"] * 4
    assert {entries[n]["moves"] for n in SIX} == {"tokens_per_s_per_chip"}


@pytest.mark.parametrize("cell,seed", [("mistral-7b.d2", "3000000019"),
                                       ("kimi-linear-48b-a3b.share32-seq16384", "3800000019")])
def test_dry_run_prints_the_memory_line(cell, seed):
    run = subprocess.run(
        [sys.executable, "perfbench/run.py", "--workload", cell, "--seed", seed,
         "--seconds", "1", "--trace", "1", "--allow-cpu"],
        cwd=manifest.ROOT, capture_output=True, text=True, timeout=900,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert run.returncode == 0, run.stderr[-2000:]
    last = json.loads(run.stdout.strip().splitlines()[-1])
    # the Kimi cell's check at its tiny cut is not held to ``correct`` (its own test's rule)
    assert last["failed"] == 0 and last["metrics"] == {}
    assert last["correct"] is True or cell.startswith("kimi")
    (line,) = [l for l in run.stdout.splitlines() if l.startswith("perfbench: memory: ")]
    for part in ("limit none", "assigned peak", "classes' sum", "= arguments", "allocator's mark none",
                 "kept for the backward", "without remat (a micro-batch, a chip; two traces",
                 "by module, kept / dropped", "attention", "mlp", "head", "the largest kept:",
                 "the largest dropped:", "left out"):
        assert part in line, part
    values = {}
    for l in run.stdout.splitlines():
        if l.startswith("perfbench: dry run, not reported: "):
            name, _, value = l[len("perfbench: dry run, not reported: "):].partition(" = ")
            values[name] = float(value)
    kimi = cell.startswith("kimi")
    assert set(SIX) & set(values) == (
        {"step_temp_gb", "saved_for_backward_gb"} | ({"remat_dropped_gb"} if kimi else set()))
    assert 0 < values["saved_for_backward_gb"] and 0 < values["step_temp_gb"]
    if kimi:
        assert values["remat_dropped_gb"] > 0
        assert "attention/kda/kda_core kda_out" in line
