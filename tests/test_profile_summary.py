"""Profile-trace analyzer tests (hermetic: synthetic Chrome trace)."""

import gzip
import json
import os

from distributed_llm_training_benchmark_framework_tpu.analysis import (
    profile_summary as ps,
)


def make_trace(tmp_path):
    rundir = tmp_path / "plugins" / "profile" / "2026_01_01_00_00_00"
    rundir.mkdir(parents=True)
    events = [
        {"ph": "M", "pid": 1, "name": "process_name",
         "args": {"name": "/device:TPU:0"}},
        {"ph": "M", "pid": 1, "tid": 10, "name": "thread_name",
         "args": {"name": "XLA Ops"}},
        {"ph": "M", "pid": 1, "tid": 11, "name": "thread_name",
         "args": {"name": "Steps"}},
        {"ph": "M", "pid": 2, "name": "process_name",
         "args": {"name": "/host:CPU"}},
        {"ph": "M", "pid": 2, "tid": 20, "name": "thread_name",
         "args": {"name": "python"}},
        # device ops: two fusions, one flash kernel, one while
        {"ph": "X", "pid": 1, "tid": 10, "name": "fusion.12", "ts": 0,
         "dur": 300, "args": {"long_name": "%fusion.12 = f32[8,8] fusion(...)"}},
        {"ph": "X", "pid": 1, "tid": 10, "name": "fusion.13", "ts": 300, "dur": 100},
        {"ph": "X", "pid": 1, "tid": 10,
         "name": "jvp_jit_flash_attention__.3", "ts": 400, "dur": 200},
        {"ph": "X", "pid": 1, "tid": 10, "name": "while.7", "ts": 600, "dur": 400},
        # steps lane
        {"ph": "X", "pid": 1, "tid": 11, "name": "1", "ts": 0, "dur": 500},
        {"ph": "X", "pid": 1, "tid": 11, "name": "2", "ts": 500, "dur": 500},
        # host noise (must not land in op classes)
        {"ph": "X", "pid": 2, "tid": 20, "name": "python_thing", "ts": 0, "dur": 9000},
    ]
    f = rundir / "host.trace.json.gz"
    with gzip.open(f, "wt") as fh:
        json.dump({"traceEvents": events}, fh)
    return str(tmp_path), str(f)


def test_find_and_summarize(tmp_path):
    profile_dir, trace_file = make_trace(tmp_path)
    assert ps.find_trace_file(profile_dir) == trace_file
    s = ps.summarize(ps.load_events(trace_file), top=3)
    assert s["op_classes"]["fusion"] == 400
    assert s["op_classes"]["flash_kernel"] == 200
    assert s["op_classes"]["while"] == 400
    assert "python_thing" not in s["op_classes"]
    assert s["step_durs_us"] == [500, 500]
    top_names = [n for n, _, _ in s["top_ops"]]
    assert top_names[0] in ("while.7",)  # largest single op
    text = ps.format_summary(s, top=3)
    assert "flash_kernel" in text and "Device steps: 2 traced" in text
    assert "%fusion.12" in text  # provenance surfaced


def test_op_class_knows_the_flash_kernels_by_name():
    """The forward and the fused backward carry their pallas_call's name in
    a trace; the ring block kernels their jit's."""
    for name in ("flash_fwd.3", "flash_bwd_fused.1", "jvp_jit_flash_attention__.3"):
        assert ps.op_class(name) == "flash_kernel"
    assert ps.op_class("fusion.1234") == "fusion"


def test_cli_missing_trace_errors_to_stderr(tmp_path, capsys):
    """ERROR lines belong on stderr: a scripted `$(...)` capture of the
    summary must not swallow the failure into the captured variable."""
    rc = ps.main(["--profile-dir", str(tmp_path)])
    assert rc == 1
    captured = capsys.readouterr()
    assert "no *.trace.json.gz" in captured.err
    assert captured.out == ""


def test_cli_bad_run_selector_errors_to_stderr(tmp_path, capsys):
    make_trace(tmp_path)
    rc = ps.main(["--profile-dir", str(tmp_path), "--run", "no-such-run"])
    assert rc == 1
    captured = capsys.readouterr()
    assert "ERROR" in captured.err
    assert captured.out == ""


def test_cli_end_to_end(tmp_path, capsys):
    profile_dir, _ = make_trace(tmp_path)
    rc = ps.main(["--profile-dir", profile_dir])
    assert rc == 0
    out = capsys.readouterr().out
    assert "XLA op classes" in out and "fusion" in out
