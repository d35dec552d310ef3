"""Analysis pipeline tests: parse -> metrics.csv -> plots -> report.

Golden checks for the scaling-efficiency formula (reference
``scripts/parse_metrics.py:50-63``) including the published-quirk case where
the baseline world size is 2 (rows pinned at 50%) and the honest ws=1 case.
"""

import json
import os

import pandas as pd
import pytest

from distributed_llm_training_benchmark_framework_tpu.analysis import (
    parse_metrics,
    make_report,
)
from distributed_llm_training_benchmark_framework_tpu.analysis import plot as plot_mod


def result(strategy="ddp", ws=1, tps=1000.0, seq=2048, **kw):
    r = {
        "strategy": strategy, "world_size": ws, "rank": 0, "seq_len": seq,
        "tier": "A", "steps": 100, "per_device_batch": 1, "grad_accum": 4,
        "tokens_per_sec": tps, "mean_step_time_sec": 0.5, "mean_loss": 6.1,
        "peak_vram_gb": 10.0, "h2d_gbps_per_gpu": 1e-5,
    }
    r.update(kw)
    return r


def write_results(tmp_path, results):
    for i, r in enumerate(results):
        d = tmp_path / f"run{i}_results"
        d.mkdir(exist_ok=True)
        (d / "result.json").write_text(json.dumps(r))


def test_scaling_efficiency_with_ws1_baseline(tmp_path):
    write_results(tmp_path, [
        result(ws=1, tps=1000.0),
        result(ws=4, tps=3600.0),
        result(ws=8, tps=7200.0),
    ])
    df = parse_metrics.add_scaling_efficiency(parse_metrics.load_results(str(tmp_path)))
    by_ws = df.set_index("world_size")["scaling_efficiency_pct"]
    assert by_ws[1] == pytest.approx(100.0)
    assert by_ws[4] == pytest.approx(90.0)
    assert by_ws[8] == pytest.approx(90.0)


def test_scaling_efficiency_reference_quirk_ws2_baseline(tmp_path):
    """With min world size 2 the formula pins baseline rows at 50% — exactly
    the published reference behavior (README.md:216-223)."""
    write_results(tmp_path, [
        result(ws=2, tps=8369.0),
        result(ws=4, tps=12220.0),
    ])
    df = parse_metrics.add_scaling_efficiency(parse_metrics.load_results(str(tmp_path)))
    by_ws = df.set_index("world_size")["scaling_efficiency_pct"]
    assert by_ws[2] == pytest.approx(50.0)
    assert by_ws[4] == pytest.approx(12220.0 / (8369.0 * 4) * 100, rel=1e-6)


def test_groups_are_independent(tmp_path):
    write_results(tmp_path, [
        result("ddp", ws=1, tps=1000.0),
        result("ddp", ws=8, tps=4000.0),
        result("zero2", ws=1, tps=2000.0),
        result("zero2", ws=8, tps=16000.0),
    ])
    df = parse_metrics.add_scaling_efficiency(parse_metrics.load_results(str(tmp_path)))
    z2 = df[(df.strategy == "zero2") & (df.world_size == 8)]
    assert z2["scaling_efficiency_pct"].iloc[0] == pytest.approx(100.0)
    ddp = df[(df.strategy == "ddp") & (df.world_size == 8)]
    assert ddp["scaling_efficiency_pct"].iloc[0] == pytest.approx(50.0)


def test_csv_column_contract(tmp_path):
    write_results(tmp_path, [result()])
    df = parse_metrics.add_scaling_efficiency(parse_metrics.load_results(str(tmp_path)))
    out = tmp_path / "summary" / "metrics.csv"
    parse_metrics.to_csv(df, str(out))
    got = pd.read_csv(out)
    # Reference columns lead, in reference order; efficiency column last.
    assert list(got.columns[:13]) == parse_metrics.REFERENCE_COLUMNS
    assert got.columns[-1] == "scaling_efficiency_pct"


def test_cli_end_to_end(tmp_path, capsys):
    write_results(tmp_path, [result(ws=1), result(ws=4, tps=3500.0)])
    out = tmp_path / "summary"
    rc = parse_metrics.main(["--results-dir", str(tmp_path), "--out", str(out)])
    assert rc == 0
    assert (out / "metrics.csv").exists()


def test_plots_written(tmp_path):
    write_results(tmp_path, [
        result(ws=1), result(ws=4, tps=3500.0),
        result("zero2", ws=1, tps=1200.0), result("zero2", ws=4, tps=4500.0),
    ])
    df = parse_metrics.add_scaling_efficiency(parse_metrics.load_results(str(tmp_path)))
    plots = tmp_path / "plots"
    written = plot_mod.make_plots(df, str(plots))
    assert "tokens_per_sec_vs_gpu.png" in written
    assert "scaling_efficiency.png" in written
    for name in written:
        assert (plots / name).stat().st_size > 1000


def test_plot_seqlen_figure_only_with_multiple_seqlens(tmp_path):
    write_results(tmp_path, [result(seq=2048), result(seq=4096, ws=1)])
    df = parse_metrics.add_scaling_efficiency(parse_metrics.load_results(str(tmp_path)))
    written = plot_mod.make_plots(df, str(tmp_path / "plots"))
    assert "vram_vs_seqlen.png" in written


def test_report_generation(tmp_path):
    write_results(tmp_path, [
        result(ws=1), result(ws=4, tps=3500.0),
        result("zero2", ws=4, tps=4500.0, peak_vram_gb=8.0),
    ])
    df = parse_metrics.add_scaling_efficiency(parse_metrics.load_results(str(tmp_path)))
    report = make_report.build_report(df)
    assert "# TPU Distributed Training Benchmark Report" in report
    assert "Best throughput:" in report and "zero2" in report
    assert "scaling_efficiency.png" in report


def test_duplicate_results_deduped(tmp_path):
    """The harness-written and log-scraped copies of one run count once."""
    write_results(tmp_path, [result(ws=4, tps=3500.0)])
    d = tmp_path / "scraped"
    d.mkdir()
    (d / "result.json").write_text(json.dumps(result(ws=4, tps=3500.0)))
    df = parse_metrics.load_results(str(tmp_path))
    assert len(df) == 1


def test_old_rows_offload_fields_mean_nothing(tmp_path):
    """Rows written before the host-offloaded optimizer was deleted carry
    three ``offload_*`` fields (results/example_output/, the registry's
    records). Both readers take such a row, and the fields are no part of a
    run's identity: the old row and the same run without them are one row of
    metrics.csv and one lineage of the registry."""
    from distributed_llm_training_benchmark_framework_tpu.regress import store

    new = result(ws=4, tps=3500.0)
    old = dict(new, offload_opt_state=True, offload_delayed_update=True,
               offload_dpu_start_step=5)
    write_results(tmp_path, [old, new])
    df = parse_metrics.load_results(str(tmp_path))
    assert len(df) == 1
    assert store.config_key({"result": old}) == store.config_key({"result": new})
    assert vr.validate_result(old, "run") == vr.validate_result(new, "run")


def test_empty_results_dir_errors(tmp_path):
    with pytest.raises(SystemExit):
        parse_metrics.load_results(str(tmp_path))


# --- validate_results: the sanity envelopes as executable checks ---

from distributed_llm_training_benchmark_framework_tpu.analysis import (  # noqa: E402
    validate_results as vr,
)


def test_validate_results_pass(tmp_path):
    write_results(tmp_path, [
        result(ws=1, tps=1000.0, sync_every=1, step_time_cv_pct=3.0,
               peak_hbm_gb=8.0, peak_hbm_method="xla_buffer_assignment",
               est_hbm_gb=7.0, device_kind="TPU v5 lite"),
    ])
    failures, n = vr.collect(str(tmp_path), None)
    assert n == 1
    assert failures == []


def test_validate_results_loss_envelope(tmp_path):
    write_results(tmp_path, [result(mean_loss=float(11.5))])
    failures, _ = vr.collect(str(tmp_path), None)
    assert any("mean_loss" in f for f in failures)


def test_validate_results_step_variance_envelope(tmp_path):
    write_results(tmp_path, [
        result(sync_every=1, step_time_cv_pct=25.0),
    ])
    failures, _ = vr.collect(str(tmp_path), None)
    assert any("cv" in f for f in failures)
    # Windowed timing: per-step variance unobservable, envelope not applied.
    write_results(tmp_path, [
        result(sync_every=10, step_time_cv_pct=25.0),
    ])
    failures, _ = vr.collect(str(tmp_path), None)
    assert not any("cv" in f for f in failures)


def test_validate_results_memory_envelopes(tmp_path):
    # est vs measured disagreement beyond tolerance
    write_results(tmp_path, [
        result(peak_hbm_gb=10.0, peak_hbm_method="allocator", est_hbm_gb=2.0,
               device_kind="TPU v5 lite"),
    ])
    failures, _ = vr.collect(str(tmp_path), None)
    assert any("analytic est" in f for f in failures)
    # capacity violation
    write_results(tmp_path, [
        result(peak_hbm_gb=99.0, peak_hbm_method="allocator", est_hbm_gb=99.0,
               device_kind="TPU v5 lite"),
    ])
    failures, _ = vr.collect(str(tmp_path), None)
    assert any("exceeds" in f for f in failures)


def test_validate_results_mfu_floor(tmp_path):
    """A published-geometry row whose MFU regressed below the floor fails;
    the same MFU on a non-published geometry (reference attention) passes."""
    degraded = result(
        strategy="zero2", ws=1, seq=4096, attention_impl="flash",
        device_kind="TPU v5 lite", mfu_pct=24.0, sync_every=10,
    )
    write_results(tmp_path, [degraded])
    failures, _ = vr.collect(str(tmp_path), None)
    assert any("below the 31.0% floor" in f for f in failures)
    # Same number under reference attention: exploratory, no floor.
    write_results(tmp_path, [dict(degraded, attention_impl="reference")])
    failures, _ = vr.collect(str(tmp_path), None)
    assert not any("floor" in f for f in failures)
    # Healthy published row passes.
    write_results(tmp_path, [dict(degraded, mfu_pct=33.6)])
    failures, _ = vr.collect(str(tmp_path), None)
    assert not any("floor" in f for f in failures)


def test_validate_results_llama_mfu_floor(tmp_path):
    """The llama-family 2K row has its own floor (42%), keyed on
    model_family — a degraded llama row fails; the same MFU is fine for a
    tinygpt row (whose 2K floor is 36%) and a tinygpt row never trips the
    llama floor."""
    degraded = result(
        strategy="zero2", ws=1, seq=2048, attention_impl="flash",
        device_kind="TPU v5 lite", mfu_pct=39.0, sync_every=10,
    )
    write_results(tmp_path, [dict(degraded, model_family="llama", causal=True)])
    failures, _ = vr.collect(str(tmp_path), None)
    assert any("llama-family floor" in f for f in failures)
    write_results(tmp_path, [dict(degraded, model_family="tinygpt")])
    failures, _ = vr.collect(str(tmp_path), None)
    assert not any("floor" in f for f in failures)
    write_results(tmp_path, [dict(degraded, model_family="llama",
                                  causal=True, mfu_pct=45.2)])
    failures, _ = vr.collect(str(tmp_path), None)
    assert not any("floor" in f for f in failures)


def test_validate_results_loss_descent_envelope(tmp_path):
    """The deliberately-FROZEN llama fixture must fail: 100 steps whose
    first and last loss windows are identical (a plausible mean, zero
    descent) is a run that did not train. A descending row passes, a short
    smoke row (< 50 steps) and a pre-envelope row (no window keys) are
    exempt."""
    frozen = result(
        strategy="zero2", steps=100, model_family="llama", mean_loss=6.3,
        loss_first_window=6.31, loss_last_window=6.31, loss_window_steps=10,
    )
    write_results(tmp_path, [frozen])
    failures, _ = vr.collect(str(tmp_path), None)
    assert any("did not train" in f for f in failures), failures
    # Healthy descent (llama's measured slow trajectory: 10.58 -> 10.09).
    write_results(tmp_path, [dict(
        frozen, mean_loss=10.3, loss_first_window=10.55,
        loss_last_window=10.09,
    )])
    failures, _ = vr.collect(str(tmp_path), None)
    assert not any("did not train" in f for f in failures), failures
    # Short smoke runs are exempt (steps < 50)...
    write_results(tmp_path, [dict(frozen, steps=8)])
    failures, _ = vr.collect(str(tmp_path), None)
    assert not any("did not train" in f for f in failures), failures
    # ...and so are rows without the window keys (pre-round-6 artifacts)...
    legacy = result(strategy="zero2", steps=100, model_family="llama")
    write_results(tmp_path, [legacy])
    failures, _ = vr.collect(str(tmp_path), None)
    assert not any("did not train" in f for f in failures), failures
    # ...and resumed rows, which legitimately start near converged loss.
    write_results(tmp_path, [dict(frozen, resumed=True)])
    failures, _ = vr.collect(str(tmp_path), None)
    assert not any("did not train" in f for f in failures), failures
    # The tinygpt envelope is stricter: a 100-step tinygpt row descending
    # only 0.2 nats fails where a llama row would pass.
    write_results(tmp_path, [dict(
        frozen, model_family="tinygpt", mean_loss=6.2,
        loss_first_window=6.31, loss_last_window=6.11,
    )])
    failures, _ = vr.collect(str(tmp_path), None)
    assert any("did not train" in f for f in failures), failures


def test_validate_results_published_artifacts_pass():
    """The committed example_output must satisfy its own envelopes —
    including the new MFU floors against the published rows."""
    root = os.path.join(os.path.dirname(os.path.dirname(__file__)),
                        "results", "example_output")
    failures, n = vr.collect(root, None)
    assert n > 0
    assert failures == [], failures


def test_validate_results_marker_contract(tmp_path):
    write_results(tmp_path, [result()])
    good = tmp_path / "good.log"
    good.write_text(
        "noise\nBENCHMARK_RESULT_JSON_START\n{\"a\": 1}\nBENCHMARK_RESULT_JSON_END\n"
    )
    bad = tmp_path / "bad.log"
    bad.write_text("no markers here\n")
    failures, n = vr.collect(str(tmp_path), str(tmp_path))
    assert any("bad.log" in f for f in failures)
    assert not any("good.log" in f for f in failures)


def test_validate_results_cli_exit_codes(tmp_path):
    write_results(tmp_path, [result()])
    assert vr.main(["--results-dir", str(tmp_path)]) == 0
    write_results(tmp_path, [result(tokens_per_sec=0.0)])
    assert vr.main(["--results-dir", str(tmp_path)]) == 1
    empty = tmp_path / "empty"
    empty.mkdir()
    assert vr.main(["--results-dir", str(empty)]) == 1


def test_report_cost_efficiency_finding(tmp_path):
    df = pd.DataFrame([
        result(ws=1, tps=42000.0, tokens_per_dollar=1.26e8,
               usd_per_chip_hour=1.20, scaling_efficiency_pct=100.0),
    ])
    text = make_report.build_report(df)
    assert "Best cost efficiency" in text
    assert "tokens/$" in text
