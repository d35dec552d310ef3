"""Flight-recorder telemetry tests (docs/OBSERVABILITY.md).

Four layers, cheapest first:

- recorder unit behavior: phase accounting, step-window events, anomaly
  screening (NaN loss, step-time spikes + resolution), heartbeat cadence;
- the frozen-fixture JSONL round-trip (``tests/fixtures/
  telemetry_frozen.jsonl``): the on-disk event schema is a contract —
  readers of old telemetry must keep working, so the fixture never
  changes and these assertions pin what the reader extracts from it;
- crash resilience in real subprocesses: a SIGKILL'd recorder leaves
  every event up to its last sync on disk (line-buffered writes), the
  excepthook turns an uncaught crash into ``run_aborted``, and
  ``scripts/collect_results.sh`` salvages the last heartbeat into
  ``partial_<arm>.json`` — recorder and scraper parse the SAME marker
  shape (pinned against the script text, so they cannot drift apart);
- an e2e CPU benchmark run (tier S) asserting phase events bracket
  correctly and the phase durations sum to the measured wall time.
"""

import json
import os
import signal
import subprocess
import sys
import textwrap

import pytest

from distributed_llm_training_benchmark_framework_tpu import telemetry
from distributed_llm_training_benchmark_framework_tpu.analysis import (
    telemetry_report as tr,
)
from distributed_llm_training_benchmark_framework_tpu.telemetry import (
    TelemetryRecorder,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FROZEN = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                      "fixtures", "telemetry_frozen.jsonl")


def make_recorder(tmp_path, **kw):
    kw.setdefault("results_dir", str(tmp_path))
    kw.setdefault("heartbeat_every_sec", 0.0)
    kw.setdefault("tokens_per_step", 100)
    kw.setdefault("total_steps", 10)
    return TelemetryRecorder("arm_ws1_seq8_tierS", **kw)


def read(tmp_path):
    return telemetry.read_events(
        str(tmp_path / "telemetry_arm_ws1_seq8_tierS.jsonl")
    )


# ---------------------------------------------------------------------------
# Recorder unit behavior
# ---------------------------------------------------------------------------


def test_recorder_event_stream_and_phase_accounting(tmp_path, capsys):
    rec = make_recorder(tmp_path, meta={"strategy": "ddp", "world_size": 1})
    rec.begin_phase("init")
    rec.begin_phase("compile")
    rec.step_window(last_step=0, losses=[6.0],
                    window_mean_step_time_sec=0.5)
    rec.begin_phase("timed")
    rec.step_window(last_step=4, losses=[5.9, 5.8, 5.7, 5.6],
                    window_mean_step_time_sec=0.1)
    phases = rec.close("ok")
    events = read(tmp_path)
    kinds = [e["event"] for e in events]
    assert kinds[0] == "run_meta" and kinds[-1] == "run_end"
    assert kinds.count("step_window") == 2
    # run_meta carries schema version + identity for the scrape path.
    assert events[0]["schema_version"] == telemetry.SCHEMA_VERSION
    assert events[0]["strategy"] == "ddp"
    # Cumulative throughput: 5 steps x 100 tokens over 0.5 + 4*0.1 sec.
    w = [e for e in events if e["event"] == "step_window"][-1]
    assert w["cum_tokens"] == 500
    assert w["tokens_per_sec"] == pytest.approx(500 / 0.9, rel=1e-3)
    assert w["phase"] == "timed"
    # Phases are disjoint: their sum never exceeds the run's wall time.
    end = events[-1]
    assert end["status"] == "ok" and end["last_step"] == 4
    assert sum(phases.values()) <= end["wall_time_total_sec"] + 1e-6
    assert set(phases) == {"init", "compile", "timed"}


def test_recorder_rejects_unknown_phase(tmp_path):
    rec = make_recorder(tmp_path)
    with pytest.raises(ValueError, match="unknown telemetry phase"):
        rec.begin_phase("cmopile")
    rec.close()


def test_heartbeat_cadence_and_shape(tmp_path, capsys):
    rec = make_recorder(tmp_path, heartbeat_every_sec=3600.0,
                        meta={"strategy": "zero2", "world_size": 4})
    rec.begin_phase("timed")
    for w in range(5):
        rec.step_window(last_step=w, losses=[5.0],
                        window_mean_step_time_sec=0.01)
    rec.close()
    lines = [l for l in capsys.readouterr().out.splitlines()
             if l.startswith(telemetry.HEARTBEAT_MARKER)]
    # First window always heartbeats (a run killed in window 2 must have
    # left one); the 3600s interval suppresses the rest.
    assert len(lines) == 1
    hb = telemetry.parse_heartbeat_line(lines[0])
    assert hb is not None
    for key in ("arm", "step", "total_steps", "loss", "tokens_per_sec",
                "window_mean_step_time_sec", "phase", "ts", "strategy",
                "world_size"):
        assert key in hb, key
    assert hb["step"] == 0 and hb["strategy"] == "zero2"


def test_heartbeat_silent_off_main_rank(tmp_path, capsys):
    rec = make_recorder(tmp_path, is_main=False)
    rec.begin_phase("timed")
    rec.step_window(last_step=0, losses=[5.0], window_mean_step_time_sec=0.1)
    rec.close()
    assert telemetry.HEARTBEAT_MARKER not in capsys.readouterr().out
    # ...and no file either: rank 0 owns the JSONL.
    assert not (tmp_path / "telemetry_arm_ws1_seq8_tierS.jsonl").exists()


def test_nan_loss_anomaly_is_unresolved(tmp_path, capsys):
    rec = make_recorder(tmp_path)
    rec.begin_phase("timed")
    rec.step_window(last_step=0, losses=[float("nan")],
                    window_mean_step_time_sec=0.1)
    rec.step_window(last_step=1, losses=[float("inf")],
                    window_mean_step_time_sec=0.1)
    rec.close()
    events = read(tmp_path)
    anomalies = [e for e in events if e["event"] == "anomaly"]
    assert [a["kind"] for a in anomalies] == ["nan_loss", "nan_loss"]
    end = events[-1]
    assert end["n_anomalies"] == 2 and end["n_unresolved_anomalies"] == 2
    # Non-finite losses serialize as null — json.dumps would otherwise
    # write the non-spec NaN/Infinity tokens and break strict consumers
    # (jq-based probes, non-python scrapers) of both channels.
    for w in (e for e in events if e["event"] == "step_window"):
        assert w["loss"] is None
    for line in capsys.readouterr().out.splitlines():
        hb = telemetry.parse_heartbeat_line(line)
        if hb is not None:
            assert hb["loss"] is None
    assert "Infinity" not in open(
        tmp_path / "telemetry_arm_ws1_seq8_tierS.jsonl"
    ).read()


def test_step_time_spike_opens_and_resolves(tmp_path):
    rec = make_recorder(tmp_path)
    rec.begin_phase("timed")
    for w in range(4):  # build median history at 0.1s
        rec.step_window(last_step=w, losses=[5.0],
                        window_mean_step_time_sec=0.1)
    rec.step_window(last_step=4, losses=[5.0],
                    window_mean_step_time_sec=1.0)  # 10x spike
    assert rec.n_unresolved_anomalies == 1
    rec.step_window(last_step=5, losses=[5.0],
                    window_mean_step_time_sec=0.1)  # back to normal
    assert rec.n_unresolved_anomalies == 0
    rec.close()
    events = read(tmp_path)
    kinds = [(e["event"], e.get("kind")) for e in events
             if e["event"].startswith("anomaly")]
    assert kinds == [("anomaly", "step_time_spike"),
                     ("anomaly_resolved", "step_time_spike")]
    assert events[-1]["n_anomalies"] == 1
    assert events[-1]["n_unresolved_anomalies"] == 0


@pytest.mark.parametrize("planted", ["dispatch", "wait"])
def test_step_time_spike_says_where_the_window_went(tmp_path, planted):
    """The spike event carries the long window's split from the program's own
    host record: a stall inside the step's call reads in ``dispatch_max_ms``,
    one after it (the wait for the losses) in ``wait_excess_ms``, and each
    leaves the other still."""
    import time

    from distributed_llm_training_benchmark_framework_tpu.utils import scopes

    def window(first, inside=0.0, after=0.0):
        for step in range(first, first + 2):
            with scopes.host_span(scopes.STEP_DISPATCH, step=step):
                time.sleep(inside if step == first else 0.0)
        time.sleep(after)

    rec = make_recorder(tmp_path)
    rec.begin_phase("timed")
    for w in range(4):
        window(2 * w)
        rec.step_window(last_step=2 * w + 1, losses=[5.0, 5.0],
                        window_mean_step_time_sec=0.1)
    # 80 ms against the limit of 50: the wait is read over the median wait of the
    # process's earlier windows, which a loaded host puts at several ms
    window(8, **{"dispatch": {"inside": 0.08}, "wait": {"after": 0.08}}[planted])
    rec.step_window(last_step=9, losses=[5.0, 5.0],
                    window_mean_step_time_sec=1.0)
    rec.close()
    (spike,) = [e for e in read(tmp_path) if e["event"] == "anomaly"]
    split = spike["host_split"]
    long, still = (("dispatch_max_ms", "wait_excess_ms") if planted == "dispatch"
                   else ("wait_excess_ms", "dispatch_max_ms"))
    assert split[long] >= 50 and abs(split[still]) < 25
    assert split["gc_max_ms"] < 25


def test_sustained_slowdown_rebaselines_instead_of_staying_open(tmp_path):
    """A spike that persists becomes the new baseline: a thermally
    throttled (but completed) run must not be rejected by the validator
    as an eternally-open anomaly, and the NEXT stall on top of the new
    level is still caught."""
    rec = make_recorder(tmp_path)
    rec.begin_phase("timed")
    for w in range(4):
        rec.step_window(last_step=w, losses=[5.0],
                        window_mean_step_time_sec=0.1)
    for w in range(4, 4 + telemetry.recorder.SPIKE_REBASELINE_WINDOWS):
        rec.step_window(last_step=w, losses=[5.0],
                        window_mean_step_time_sec=0.4)  # sustained 4x
    assert rec.n_unresolved_anomalies == 0  # rebaselined
    # A fresh 3x stall relative to the NEW level still opens.
    rec.step_window(last_step=20, losses=[5.0],
                    window_mean_step_time_sec=2.0)
    assert rec.n_unresolved_anomalies == 1
    rec.close()
    events = read(tmp_path)
    resolved = [e for e in events if e["event"] == "anomaly_resolved"]
    assert any("rebaselined" in (e.get("detail") or "") for e in resolved)


def test_spike_open_at_run_end_stays_unresolved(tmp_path):
    rec = make_recorder(tmp_path)
    rec.begin_phase("timed")
    for w in range(4):
        rec.step_window(last_step=w, losses=[5.0],
                        window_mean_step_time_sec=0.1)
    rec.step_window(last_step=4, losses=[5.0],
                    window_mean_step_time_sec=2.0)
    rec.close()
    assert read(tmp_path)[-1]["n_unresolved_anomalies"] == 1


def test_abort_emits_run_aborted_with_phase_and_step(tmp_path):
    rec = make_recorder(tmp_path)
    rec.begin_phase("timed")
    rec.step_window(last_step=7, losses=[5.0], window_mean_step_time_sec=0.1)
    rec.abort("exception:ValueError: boom")
    events = read(tmp_path)
    end = events[-1]
    assert end["event"] == "run_aborted"
    assert end["phase"] == "timed" and end["last_step"] == 7
    assert "ValueError" in end["reason"]
    # abort/close are idempotent — a second shutdown adds nothing.
    rec.close()
    assert len(read(tmp_path)) == len(events)


def test_disabled_recorder_writes_nothing_but_tracks_phases(tmp_path):
    rec = make_recorder(tmp_path, enabled=False)
    rec.begin_phase("init")
    rec.begin_phase("timed")
    phases = rec.close("ok")
    assert not (tmp_path / "telemetry_arm_ws1_seq8_tierS.jsonl").exists()
    assert set(phases) == {"init", "timed"}


# ---------------------------------------------------------------------------
# Frozen-fixture round trip (on-disk schema contract)
# ---------------------------------------------------------------------------


def test_frozen_fixture_round_trip():
    events = telemetry.read_events(FROZEN)
    assert events[0]["event"] == "run_meta"
    assert events[0]["schema_version"] == 1
    tl = tr.build_timeline(events)
    assert tl["meta"]["arm"] == "zero2_ws4_seq128_tierS"
    assert tl["end"]["event"] == "run_end"
    # Phase attribution reconstructed from the intervals matches the
    # run_end summary the recorder wrote.
    assert tl["phase_times"]["compile"] == pytest.approx(6.001, abs=1e-3)
    assert tl["phase_times"]["timed"] == pytest.approx(3.0, abs=1e-3)
    assert tl["phase_times"]["checkpoint"] == pytest.approx(0.5, abs=1e-3)
    assert sum(tl["phase_times"].values()) == pytest.approx(
        tl["wall"], rel=0.05
    )
    assert [w["step"] for w in tl["windows"]] == [0, 4, 9, 14, 19]
    report = tr.format_report(tl)
    assert "completed (ok), last step 19" in report
    assert "compile" in report and "Phase attribution" in report
    assert "loss: first 6.2500 -> last 4.7300" in report


def test_frozen_fixture_schema_keys_are_pinned():
    """The event schema is a contract: these keys must never disappear
    (consumers of archived telemetry depend on them)."""
    events = telemetry.read_events(FROZEN)
    by_kind = {}
    for e in events:
        by_kind.setdefault(e["event"], e)
    assert {"arm", "schema_version", "tokens_per_step",
            "total_steps"} <= set(by_kind["run_meta"])
    assert {"phase", "ts", "rel"} <= set(by_kind["phase_begin"])
    assert {"phase", "dur_sec"} <= set(by_kind["phase_end"])
    assert {"step", "steps_in_window", "loss", "window_mean_step_time_sec",
            "cum_tokens", "tokens_per_sec", "peak_hbm_bytes",
            "phase"} <= set(by_kind["step_window"])
    assert {"status", "last_step", "phase_times", "wall_time_total_sec",
            "n_anomalies",
            "n_unresolved_anomalies"} <= set(by_kind["run_end"])


def test_read_events_tolerates_torn_tail(tmp_path):
    path = tmp_path / "t.jsonl"
    path.write_text('{"event": "run_meta", "ts": 1, "rel": 0}\n'
                    '{"event": "step_window", "st')  # killed mid-write
    events = telemetry.read_events(str(path))
    assert [e["event"] for e in events] == ["run_meta"]
    # Corruption anywhere else is NOT a crash artifact and must raise.
    path.write_text('garbage\n{"event": "run_meta", "ts": 1, "rel": 0}\n')
    with pytest.raises(json.JSONDecodeError):
        telemetry.read_events(str(path))


# ---------------------------------------------------------------------------
# Heartbeat marker contract: recorder <-> collect script
# ---------------------------------------------------------------------------


def test_collect_script_and_recorder_share_the_marker():
    script = open(os.path.join(REPO, "scripts", "collect_results.sh")).read()
    # The scraper greps this exact anchored shape; the recorder prints
    # MARKER + space + JSON object. Either side drifting breaks salvage.
    assert f"^{telemetry.HEARTBEAT_MARKER} {{" in script
    line = f'{telemetry.HEARTBEAT_MARKER} {{"arm": "a", "step": 3}}'
    assert telemetry.parse_heartbeat_line(line) == {"arm": "a", "step": 3}
    assert telemetry.parse_heartbeat_line("unrelated") is None
    assert telemetry.parse_heartbeat_line(
        telemetry.HEARTBEAT_MARKER + " not-json"
    ) is None


# ---------------------------------------------------------------------------
# Crash resilience (real subprocesses)
# ---------------------------------------------------------------------------

DRIVER = textwrap.dedent("""\
    import sys, time
    sys.path.insert(0, {repo!r})
    from distributed_llm_training_benchmark_framework_tpu.telemetry import (
        TelemetryRecorder,
    )
    rec = TelemetryRecorder(
        "crash_ws1_seq8_tierS", results_dir=sys.argv[1],
        heartbeat_every_sec=0.0, tokens_per_step=8, total_steps=1000,
        meta={{"strategy": "ddp", "world_size": 1, "seq_len": 8,
              "tier": "S"}},
    )
    rec.begin_phase("init")
    rec.begin_phase("timed")
    for w in range(1000):
        rec.step_window(last_step=w * 2 + 1, losses=[5.0, 4.9],
                        window_mean_step_time_sec=0.05)
        time.sleep(0.05)
""").format(repo=REPO)


@pytest.fixture()
def killed_run(tmp_path):
    """Drive a recorder in a subprocess, SIGKILL it after 3 heartbeats."""
    driver = tmp_path / "driver.py"
    driver.write_text(DRIVER)
    proc = subprocess.Popen(
        [sys.executable, str(driver), str(tmp_path)],
        stdout=subprocess.PIPE, text=True,
    )
    heartbeats = []
    try:
        for line in proc.stdout:
            if line.startswith(telemetry.HEARTBEAT_MARKER):
                heartbeats.append(line)
                if len(heartbeats) >= 3:
                    break
    finally:
        proc.send_signal(signal.SIGKILL)
        proc.wait(timeout=30)
    return tmp_path, heartbeats


def test_sigkill_preserves_events_to_last_sync(killed_run):
    tmp_path, heartbeats = killed_run
    assert len(heartbeats) == 3
    events = telemetry.read_events(
        str(tmp_path / "telemetry_crash_ws1_seq8_tierS.jsonl")
    )
    kinds = [e["event"] for e in events]
    # Line-buffered writes: every window up to the kill survived; no
    # run_end/run_aborted — SIGKILL gives no chance to say goodbye.
    assert kinds[0] == "run_meta"
    assert kinds.count("step_window") >= 3
    assert "run_end" not in kinds and "run_aborted" not in kinds
    # The report renders the partial timeline anyway.
    tl = tr.build_timeline(events)
    assert tl["intervals"][-1]["phase"] == "timed"
    assert "no run_end" in tr.format_report(tl)


def test_collect_script_salvages_partial_from_heartbeats(killed_run):
    tmp_path, heartbeats = killed_run
    log = tmp_path / "run.log"
    log.write_text("boot noise\n" + "".join(heartbeats))
    out = tmp_path / "collected"
    proc = subprocess.run(
        ["bash", os.path.join(REPO, "scripts", "collect_results.sh"),
         "--log", str(log), str(out)],
        capture_output=True, text=True, timeout=60,
    )
    assert proc.returncode == 0, proc.stderr
    p = json.loads((out / "partial_crash_ws1_seq8_tierS.json").read_text())
    assert p["partial"] is True
    assert p["n_heartbeats"] == 3
    assert p["step"] == 5 and p["strategy"] == "ddp"
    assert p["tokens_per_sec"] > 0
    # A log with neither markers nor heartbeats stays an error.
    empty = tmp_path / "empty.log"
    empty.write_text("nothing here\n")
    proc = subprocess.run(
        ["bash", os.path.join(REPO, "scripts", "collect_results.sh"),
         "--log", str(empty), str(out)],
        capture_output=True, text=True, timeout=60,
    )
    assert proc.returncode == 1
    assert "nothing to salvage" in proc.stderr
    # A later SUCCESSFUL scrape into the same outdir supersedes the stale
    # partial — otherwise a rerun arm would surface twice in metrics.csv
    # (once as a phantom "died mid-run" row).
    good = tmp_path / "good.log"
    good.write_text(
        "BENCHMARK_RESULT_JSON_START\n"
        + json.dumps({"strategy": "ddp", "world_size": 1})
        + "\nBENCHMARK_RESULT_JSON_END\n"
    )
    proc = subprocess.run(
        ["bash", os.path.join(REPO, "scripts", "collect_results.sh"),
         "--log", str(good), str(out)],
        capture_output=True, text=True, timeout=60,
    )
    assert proc.returncode == 0, proc.stderr
    assert (out / "result.json").exists()
    assert not (out / "partial_crash_ws1_seq8_tierS.json").exists()


def test_uncaught_exception_emits_run_aborted(tmp_path):
    driver = tmp_path / "driver.py"
    driver.write_text(textwrap.dedent(f"""\
        import sys
        sys.path.insert(0, {REPO!r})
        from distributed_llm_training_benchmark_framework_tpu.telemetry import (
            TelemetryRecorder,
        )
        rec = TelemetryRecorder(
            "boom_ws1_seq8_tierS", results_dir=sys.argv[1],
            heartbeat_every_sec=0.0,
        )
        rec.begin_phase("compile")
        rec.step_window(last_step=0, losses=[6.0],
                        window_mean_step_time_sec=0.4)
        raise RuntimeError("simulated OOM")
    """))
    proc = subprocess.run(
        [sys.executable, str(driver), str(tmp_path)],
        capture_output=True, text=True, timeout=60,
    )
    assert proc.returncode != 0
    events = telemetry.read_events(
        str(tmp_path / "telemetry_boom_ws1_seq8_tierS.jsonl")
    )
    end = events[-1]
    assert end["event"] == "run_aborted"
    assert "RuntimeError" in end["reason"] and "simulated OOM" in end["reason"]
    assert end["phase"] == "compile" and end["last_step"] == 0


# ---------------------------------------------------------------------------
# Partial rows flow into the analysis pipeline
# ---------------------------------------------------------------------------


def test_partial_rows_surface_in_metrics_and_report(tmp_path):
    from distributed_llm_training_benchmark_framework_tpu.analysis import (
        make_report,
        parse_metrics,
    )

    full_dir = tmp_path / "ok_results"
    full_dir.mkdir()
    (full_dir / "result.json").write_text(json.dumps({
        "strategy": "ddp", "world_size": 4, "rank": 0, "seq_len": 128,
        "tier": "S", "steps": 20, "per_device_batch": 2, "grad_accum": 1,
        "tokens_per_sec": 4000.0, "mean_step_time_sec": 0.25,
        "mean_loss": 5.5, "peak_vram_gb": 1.0, "h2d_gbps_per_gpu": 1e-5,
    }))
    dead_dir = tmp_path / "dead_results"
    dead_dir.mkdir()
    (dead_dir / "partial_zero2_ws4_seq128_tierS.json").write_text(json.dumps({
        "arm": "zero2_ws4_seq128_tierS", "step": 11, "total_steps": 20,
        "loss": 5.9, "tokens_per_sec": 3100.0,
        "window_mean_step_time_sec": 0.33, "phase": "timed",
        "strategy": "zero2", "world_size": 4, "rank": 0, "seq_len": 128,
        "tier": "S", "model_family": "tinygpt", "per_device_batch": 2,
        "grad_accum": 1, "partial": True, "n_heartbeats": 6,
    }))
    df = parse_metrics.add_scaling_efficiency(
        parse_metrics.load_results(str(tmp_path))
    )
    assert len(df) == 2
    partial = df[df["partial"] == True]  # noqa: E712
    assert len(partial) == 1
    row = partial.iloc[0]
    assert row["strategy"] == "zero2" and row["last_step"] == 11
    assert row["mean_step_time_sec"] == pytest.approx(0.33)
    report = make_report.build_report(df)
    assert "Partial rows:" in report
    assert "zero2" in report
    # The dead arm must not win a superlative.
    assert "**Best throughput:** ddp" in report
    # ...and must not mint a fabricated efficiency number (a partial row's
    # last-window rate is not a run mean, and alone in its group it would
    # otherwise be its own 100/ws baseline).
    eff = partial.iloc[0]["scaling_efficiency_pct"]
    assert eff != eff  # NaN


def test_partial_rows_from_colliding_arms_stay_distinct(tmp_path):
    """The zigzag A/B pair shares (strategy, ws, seq, tier, batch): the
    composition axes carried in the heartbeat meta are what keep two dead
    arms from deduping into one."""
    from distributed_llm_training_benchmark_framework_tpu.analysis import (
        parse_metrics,
    )

    base = {
        "arm": "zero2_ws4_seq128_tierS", "step": 7, "total_steps": 20,
        "loss": 5.9, "tokens_per_sec": 900.0,
        "window_mean_step_time_sec": 0.4, "phase": "timed",
        "strategy": "zero2", "world_size": 4, "rank": 0, "seq_len": 128,
        "tier": "S", "model_family": "tinygpt", "per_device_batch": 2,
        "grad_accum": 1, "attention_impl": "ring", "tensor_parallel": 1,
        "sequence_parallel": 2, "pipeline_parallel": 1,
        "pipeline_schedule": "gpipe", "expert_parallel": 1, "n_experts": 0,
        "causal": True, "ring_zigzag": "auto", "partial": True,
        "n_heartbeats": 3,
    }
    d = tmp_path / "dead_results"
    d.mkdir()
    (d / "partial_a.json").write_text(json.dumps(base))
    (d / "partial_b.json").write_text(
        json.dumps(dict(base, ring_zigzag="off", tokens_per_sec=850.0))
    )
    df = parse_metrics.load_results(str(tmp_path))
    assert len(df) == 2
    assert set(df["ring_zigzag"]) == {"auto", "off"}


def test_no_partials_means_no_partial_column(tmp_path):
    """Pure-success suites keep the pre-round-8 metrics.csv column set."""
    from distributed_llm_training_benchmark_framework_tpu.analysis import (
        parse_metrics,
    )

    d = tmp_path / "ok_results"
    d.mkdir()
    (d / "result.json").write_text(json.dumps({
        "strategy": "ddp", "world_size": 1, "rank": 0, "seq_len": 128,
        "tier": "S", "steps": 20, "per_device_batch": 2, "grad_accum": 1,
        "tokens_per_sec": 1000.0, "mean_step_time_sec": 0.25,
        "mean_loss": 5.5, "peak_vram_gb": 1.0, "h2d_gbps_per_gpu": 1e-5,
    }))
    df = parse_metrics.load_results(str(tmp_path))
    assert "partial" not in df.columns


# ---------------------------------------------------------------------------
# validate_results: phase envelope + telemetry cross-check
# ---------------------------------------------------------------------------


def _result_row(**kw):
    r = {
        "strategy": "ddp", "world_size": 1, "rank": 0, "seq_len": 128,
        "tier": "A", "steps": 20, "per_device_batch": 1, "grad_accum": 4,
        "tokens_per_sec": 1000.0, "mean_step_time_sec": 0.5,
        "mean_loss": 6.1, "peak_vram_gb": 10.0, "h2d_gbps_per_gpu": 1e-5,
    }
    r.update(kw)
    return r


def test_validate_phase_time_envelope():
    from distributed_llm_training_benchmark_framework_tpu.analysis import (
        validate_results as vr,
    )

    ok = _result_row(wall_time_total_sec=10.0, time_in_init_sec=2.0,
                     time_in_compile_sec=5.0, time_in_timed_sec=2.5)
    assert vr.validate_result(ok, "ok") == []
    neg = _result_row(wall_time_total_sec=10.0, time_in_compile_sec=-1.0)
    assert any("negative" in f for f in vr.validate_result(neg, "neg"))
    oversum = _result_row(wall_time_total_sec=5.0, time_in_init_sec=3.0,
                          time_in_compile_sec=3.0, time_in_timed_sec=3.0)
    assert any("disjoint" in f for f in vr.validate_result(oversum, "over"))
    # Pre-telemetry artifacts (no wall time field) skip the envelope.
    legacy = _result_row()
    assert vr.validate_result(legacy, "legacy") == []


def test_validate_telemetry_cross_check(tmp_path):
    from distributed_llm_training_benchmark_framework_tpu.analysis import (
        validate_results as vr,
    )

    rpath = tmp_path / "result_ddp_ws1_seq128_tierA.json"
    row = _result_row()
    rpath.write_text(json.dumps(row))
    tpath = tmp_path / "telemetry_ddp_ws1_seq128_tierA.jsonl"

    # No sibling telemetry (scraped result.json): check skipped.
    assert vr.validate_telemetry(str(rpath), row, "r") == []

    def write_events(events):
        tpath.write_text("".join(json.dumps(e) + "\n" for e in events))

    meta = {"event": "run_meta", "ts": 1.0, "rel": 0.0, "arm": "x"}
    end = {"event": "run_end", "ts": 2.0, "rel": 1.0, "status": "ok",
           "n_unresolved_anomalies": 0}
    write_events([meta, end])
    assert vr.validate_telemetry(str(rpath), row, "r") == []

    # A result row whose telemetry never reached run_end is rejected.
    write_events([meta])
    f = vr.validate_telemetry(str(rpath), row, "r")
    assert any("run_end" in v for v in f)

    # Unresolved anomalies reject the row.
    write_events([meta, dict(end, n_unresolved_anomalies=2)])
    f = vr.validate_telemetry(str(rpath), row, "r")
    assert any("unresolved anomaly" in v for v in f)

    # The full collect() path wires the cross-check in.
    write_events([meta])
    failures, n = vr.collect(str(tmp_path), None)
    assert n == 1 and any("run_end" in v for v in failures)


# ---------------------------------------------------------------------------
# profile_summary multi-run selection (satellite fix)
# ---------------------------------------------------------------------------


def _write_trace(profile_dir, run, mtime):
    import gzip

    d = profile_dir / "plugins" / "profile" / run
    d.mkdir(parents=True)
    f = d / "host.trace.json.gz"
    with gzip.open(f, "wt") as fh:
        json.dump({"traceEvents": []}, fh)
    os.utime(f, (mtime, mtime))
    return str(f)


def test_find_trace_file_multi_run_warns_and_selects(tmp_path, capsys):
    from distributed_llm_training_benchmark_framework_tpu.analysis import (
        profile_summary as ps,
    )

    old = _write_trace(tmp_path, "2026_01_01_00_00_00", 1000.0)
    new = _write_trace(tmp_path, "2026_02_02_00_00_00", 2000.0)
    # Ambiguity: newest wins, but the candidates are named on stderr.
    assert ps.find_trace_file(str(tmp_path)) == new
    err = capsys.readouterr().err
    assert "2 profile runs" in err and "2026_01_01_00_00_00" in err
    # --run selects exactly (and by unique substring).
    assert ps.find_trace_file(str(tmp_path), run="2026_01_01_00_00_00") == old
    assert ps.find_trace_file(str(tmp_path), run="01_01") == old
    with pytest.raises(ValueError, match="candidates"):
        ps.find_trace_file(str(tmp_path), run="2026")
    with pytest.raises(ValueError, match="candidates"):
        ps.find_trace_file(str(tmp_path), run="no-such-run")


def test_find_trace_file_single_run_stays_quiet(tmp_path, capsys):
    from distributed_llm_training_benchmark_framework_tpu.analysis import (
        profile_summary as ps,
    )

    only = _write_trace(tmp_path, "2026_01_01_00_00_00", 1000.0)
    assert ps.find_trace_file(str(tmp_path)) == only
    assert capsys.readouterr().err == ""


# ---------------------------------------------------------------------------
# telemetry_report CLI + profiler join
# ---------------------------------------------------------------------------


def test_report_cli_on_frozen_fixture(capsys):
    rc = tr.main(["--telemetry", FROZEN])
    assert rc == 0
    out = capsys.readouterr().out
    assert "Phase attribution" in out and "zero2_ws4_seq128_tierS" in out


def test_report_cli_discovers_results_dir(tmp_path, capsys):
    import shutil

    d = tmp_path / "run_results"
    d.mkdir()
    shutil.copy(FROZEN, d / "telemetry_zero2_ws4_seq128_tierS.jsonl")
    rc = tr.main(["--results-dir", str(tmp_path)])
    assert rc == 0
    assert "Timeline" in capsys.readouterr().out
    rc = tr.main(["--results-dir", str(tmp_path / "empty")])
    assert rc == 1


def test_report_joins_profiler_step_lane(tmp_path, capsys):
    import gzip

    d = tmp_path / "plugins" / "profile" / "run1"
    d.mkdir(parents=True)
    events = [
        {"ph": "M", "pid": 1, "name": "process_name",
         "args": {"name": "/device:TPU:0"}},
        {"ph": "M", "pid": 1, "tid": 11, "name": "thread_name",
         "args": {"name": "Steps"}},
        {"ph": "X", "pid": 1, "tid": 11, "name": "1", "ts": 0,
         "dur": 180000},
        {"ph": "X", "pid": 1, "tid": 11, "name": "2", "ts": 180000,
         "dur": 190000},
    ]
    with gzip.open(d / "host.trace.json.gz", "wt") as fh:
        json.dump({"traceEvents": events}, fh)
    rc = tr.main(["--telemetry", FROZEN, "--profile-dir", str(tmp_path)])
    assert rc == 0
    out = capsys.readouterr().out
    assert "Profiler join" in out
    assert "device steps traced: 2" in out
    # JSONL timed windows median 0.2s vs device 0.19s -> +0.01s host-side.
    assert "host-side overhead:  +0.0100s/step" in out


def test_report_writes_trajectory_plots(tmp_path, capsys):
    rc = tr.main(["--telemetry", FROZEN, "--plots-out", str(tmp_path)])
    assert rc == 0
    names = sorted(os.listdir(tmp_path))
    assert "telemetry_loss.png" in names
    assert "telemetry_step_time.png" in names
    assert "telemetry_hbm.png" in names
