"""benchreg tests: registry, statistics engine, gate, and integrations.

Five layers, cheapest first (docs/REGRESSION.md):

- **store**: ingest round-trips, content-addressed dedup (including the
  result_<arm>.json vs scraped result.json pair of one run), partial
  records stored-but-never-baseline (the satellite contract: a salvaged
  ``partial_<arm>.json`` must never anchor a gate verdict), schema-drift
  refusal for both a single newer record and a newer registry meta, and
  the legacy BENCH_r*/MULTICHIP_r* seed path;
- **stats**: seeded-bootstrap determinism (same inputs -> bit-identical
  CI), Mann-Whitney sanity at window sizes, and the verdict classifier's
  A/A no-false-positive + minimum-effect behavior;
- **frozen-fixture gate proof** (the ISSUE-4 acceptance contract): on
  ``tests/fixtures/registry_frozen/``, ``regress gate`` exits 0 for the
  A/A pair and exits 1 once the frozen -10% tokens/sec candidate is
  ingested — naming the arm, metric, delta and confidence interval. The
  fixture files never change; these assertions pin the record schema the
  same way telemetry_frozen.jsonl pins the event schema;
- **integrations**: telemetry_report --compare delegates to the shared
  stats engine (per-phase + per-window tables), make_report's registry
  trend section, bench.py's scalar verdict line;
- **scripts**: regress_gate.sh mirrors graftcheck.sh, the suite finish
  path gates behind SKIP_REGRESS, and the k8s liveness probe
  (fresh/stale/absent heartbeat) with its template/launcher wiring.
"""

import json
import os
import shutil
import subprocess
import sys

import pytest

from distributed_llm_training_benchmark_framework_tpu.regress import (
    compare as rcompare,
)
from distributed_llm_training_benchmark_framework_tpu.regress import (
    stats as rstats,
)
from distributed_llm_training_benchmark_framework_tpu.regress import (
    store as rstore,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXTURES = os.path.join(REPO, "tests", "fixtures")
FROZEN_REGISTRY = os.path.join(FIXTURES, "registry_frozen")
FROZEN_CANDIDATES = os.path.join(FIXTURES, "registry_frozen_candidates")
FROZEN_ARM = "zero2_ws4_seq128_tierS"
COMPARE_A = os.path.join(FIXTURES, "telemetry_compare_a.jsonl")
COMPARE_B = os.path.join(FIXTURES, "telemetry_compare_b_slow.jsonl")

BASE_DTS = [0.2, 0.201, 0.199, 0.2, 0.202, 0.198, 0.2, 0.201, 0.199, 0.2]
AA_DTS = [0.201, 0.199, 0.2, 0.2, 0.201, 0.2, 0.199, 0.202, 0.198, 0.2]
SLOW_DTS = [round(d * 10 / 9, 6) for d in BASE_DTS]


def result_row(**over):
    row = {
        "strategy": "zero2", "world_size": 4, "rank": 0, "seq_len": 128,
        "tier": "S", "steps": 50, "per_device_batch": 2, "grad_accum": 1,
        "tokens_per_sec": 5120.0, "mean_step_time_sec": 0.2,
        "mean_loss": 5.1, "peak_vram_gb": 1.2, "h2d_gbps_per_gpu": 1e-4,
        "attention_impl": "flash", "model_family": "tinygpt",
    }
    row.update(over)
    return row


def windows(dts):
    return [{"step": 9 + 5 * i, "steps_in_window": 5, "dt": dt,
             "loss": 5.5} for i, dt in enumerate(dts)]


# ---------------------------------------------------------------------------
# store
# ---------------------------------------------------------------------------


def test_ingest_round_trip_and_dedup(tmp_path):
    reg = rstore.Registry(str(tmp_path / "reg"))
    rec = rstore.make_record(
        arm="a_ws1_seq8_tierS", result_row=result_row(),
        windows=windows(BASE_DTS), tokens_per_step=1024, source="x.json",
    )
    stored, created = reg.ingest(rec)
    assert created
    again, created2 = reg.ingest(rec)
    assert not created2 and again["record_id"] == stored["record_id"]
    assert len(reg.index_lines()) == 1  # append-only index not re-appended
    loaded = reg.latest("a_ws1_seq8_tierS")
    assert loaded["metric"]["value"] == 5120.0
    assert loaded["windows"][0]["dt"] == 0.2
    # Content addressing ignores source: the harness file and the
    # log-scraped copy of the SAME run dedupe to one record.
    dup = rstore.make_record(
        arm="a_ws1_seq8_tierS", result_row=result_row(),
        windows=windows(BASE_DTS), tokens_per_step=1024,
        source="scraped/result.json",
    )
    assert dup["record_id"] == stored["record_id"]


def test_partial_records_never_baseline(tmp_path):
    """Satellite contract: a salvaged partial_<arm>.json is stored (it
    shows in trend) but can never become the gate's baseline."""
    reg = rstore.Registry(str(tmp_path / "reg"))
    ok = rstore.make_record(
        arm="arm1", result_row=result_row(), windows=windows(BASE_DTS),
        tokens_per_step=1024, status="ok", source="result_arm1.json",
    )
    reg.ingest(ok)
    partial = rstore.make_record(
        arm="arm1",
        result_row=result_row(tokens_per_sec=9000.0, partial=True),
        status="partial", source="partial_arm1.json",
    )
    reg.ingest(partial)
    base = reg.baseline("arm1")
    assert base is not None and base["status"] == "ok"
    assert base["record_id"] == ok["record_id"]
    # ...even when the partial is the newest record and the only one left
    # after excluding the candidate itself.
    only_partial = rstore.Registry(str(tmp_path / "reg2"))
    only_partial.ingest(partial)
    assert only_partial.baseline("arm1") is None
    # And the noise-floor history never samples a partial's rate.
    vals = reg.history_values("arm1", metric_name="tokens_per_sec")
    assert 9000.0 not in vals


def test_resumed_records_never_baseline(tmp_path):
    """Chaos-round satellite: a stitched run (resumed=true) joins partials
    in the never-baseline-eligible set — its first window folds in the
    restore recompile, so it is an honest record but a dishonest anchor."""
    reg = rstore.Registry(str(tmp_path / "reg"))
    clean = rstore.make_record(
        arm="arm1", result_row=result_row(), windows=windows(BASE_DTS),
        tokens_per_step=1024, status="ok", source="result_arm1.json",
    )
    reg.ingest(clean)
    stitched = rstore.make_record(
        arm="arm1",
        result_row=result_row(tokens_per_sec=4000.0, resumed=True,
                              n_restarts=1, resume_step=25),
        status="ok", source="resumed/result_arm1.json",
    )
    reg.ingest(stitched)
    base = reg.baseline("arm1")
    assert base is not None and base["record_id"] == clean["record_id"]
    vals = reg.history_values("arm1", metric_name="tokens_per_sec")
    assert 4000.0 not in vals
    # The gate never verdicts a resumed candidate either: recovery noise
    # must not mint a regression.
    verdict, line = rcompare.gate_arm(reg, "arm1")
    assert verdict == rstats.VERDICT_INSUFFICIENT
    assert "resumed (stitched) run" in line


def test_banked_regression_skipped_by_last_good(tmp_path):
    """ROADMAP benchreg follow-up (b): a banked regression is never
    adopted as last-good; unbank lifts it. The banked ledger is
    append-only action lines."""
    reg = rstore.Registry(str(tmp_path / "reg"))
    good = rstore.make_record(
        arm="arm1", result_row=result_row(), status="ok", source="r1.json",
    )
    reg.ingest(good)
    regressed = rstore.make_record(
        arm="arm1", result_row=result_row(tokens_per_sec=4600.0),
        status="ok", source="r2.json",
    )
    reg.ingest(regressed)
    # Un-banked, the newer record would be the baseline.
    assert reg.baseline("arm1")["record_id"] == regressed["record_id"]
    assert reg.bank(regressed["record_id"], reason="gate: REGRESSION ...")
    assert not reg.bank(regressed["record_id"])  # idempotent
    assert reg.baseline("arm1")["record_id"] == good["record_id"]
    assert 4600.0 not in reg.history_values(
        "arm1", metric_name="tokens_per_sec"
    )
    # Trend still shows it, flagged.
    rows = rcompare.trend_rows(reg, "arm1")
    assert [r["banked"] for r in rows] == [False, True]
    assert reg.unbank(regressed["record_id"])
    assert reg.baseline("arm1")["record_id"] == regressed["record_id"]
    # A torn trailing append (SIGKILL mid-write — the environment this
    # ledger serves) must not wedge every read path with a traceback.
    with open(reg.banked_path, "a") as f:
        f.write('{"record_id": "deadbeef", "acti')
    assert reg.banked_ids() == set()
    assert reg.baseline("arm1") is not None


def test_gate_banks_regressed_candidate(frozen_registry, capsys):
    """A REGRESSION verdict on the default last-good/latest path banks
    the candidate, so the NEXT run's last-good skips it instead of
    adopting the regressed number as the new normal."""
    reg0 = rstore.Registry(frozen_registry)
    slow = json.load(
        open(os.path.join(FROZEN_CANDIDATES, "record_slow.json"))
    )
    _, created = reg0.ingest(slow)
    assert created
    rc = rcompare.main(["--registry", frozen_registry, "gate", "--all"])
    out = capsys.readouterr().out
    assert rc == 1
    assert "banked candidate" in out
    reg = rstore.Registry(frozen_registry)
    banked = reg.banked_ids()
    assert len(banked) == 1
    # The regressed record is no longer anyone's last-good...
    bad_id = next(iter(banked))
    base = reg.baseline(FROZEN_ARM)
    assert base is not None and base["record_id"] != bad_id
    # ...and the CLI can lift the bank.
    rc = rcompare.main(
        ["--registry", frozen_registry, "unbank", bad_id,
         "--reason", "accepted as the new normal"]
    )
    assert rc == 0
    assert rstore.Registry(frozen_registry).banked_ids() == set()


def test_partial_result_file_ingests_as_partial(tmp_path):
    """End-to-end satellite proof: collect_results.sh's salvage file ->
    status partial -> gate SKIPs rather than verdicts."""
    rdir = tmp_path / "results"
    rdir.mkdir()
    hb = {
        "arm": "zero2_ws2_seq128_tierS", "step": 37, "total_steps": 50,
        "loss": 5.4, "tokens_per_sec": 4100.0,
        "window_mean_step_time_sec": 0.25, "strategy": "zero2",
        "world_size": 2, "rank": 0, "seq_len": 128, "tier": "S",
        "partial": True, "n_heartbeats": 7,
    }
    (rdir / "partial_zero2_ws2_seq128_tierS.json").write_text(json.dumps(hb))
    reg = rstore.Registry(str(tmp_path / "reg"))
    ingested = rstore.ingest_results_dir(reg, str(rdir))
    assert len(ingested) == 1
    rec, created = ingested[0]
    assert created and rec["status"] == "partial"
    verdict, line = rcompare.gate_arm(reg, "zero2_ws2_seq128_tierS")
    assert verdict == rstats.VERDICT_INSUFFICIENT
    assert "partial" in line and "SKIP" in line


def test_results_dir_ingest_pairs_telemetry_windows(tmp_path):
    rdir = tmp_path / "results"
    rdir.mkdir()
    arm = "zero2_ws4_seq128_tierS"
    (rdir / f"result_{arm}.json").write_text(json.dumps(result_row()))
    events = [
        {"event": "run_meta", "ts": 0, "rel": 0, "arm": arm,
         "schema_version": 1, "tokens_per_step": 1024},
        {"event": "step_window", "ts": 1, "rel": 1, "step": 4,
         "steps_in_window": 5, "loss": 6.0,
         "window_mean_step_time_sec": 0.3, "cum_tokens": 5120,
         "tokens_per_sec": 3413.3, "phase": "warmup"},
    ] + [
        {"event": "step_window", "ts": 2 + i, "rel": 2 + i,
         "step": 9 + 5 * i, "steps_in_window": 5, "loss": 5.5,
         "window_mean_step_time_sec": dt, "cum_tokens": 10240,
         "tokens_per_sec": 5000.0, "phase": "timed"}
        for i, dt in enumerate(BASE_DTS)
    ]
    with open(rdir / f"telemetry_{arm}.jsonl", "w") as f:
        for e in events:
            f.write(json.dumps(e) + "\n")
    reg = rstore.Registry(str(tmp_path / "reg"))
    (rec, created), = rstore.ingest_results_dir(reg, str(rdir))
    assert created
    # Only the TIMED windows become the comparison sample — the warmup
    # window's 0.3s must not pollute the distribution.
    assert [w["dt"] for w in rec["windows"]] == BASE_DTS
    assert rec["tokens_per_step"] == 1024


def test_schema_drift_refused_for_record_and_registry(tmp_path):
    reg = rstore.Registry(str(tmp_path / "reg"))
    future = json.load(
        open(os.path.join(FROZEN_CANDIDATES, "record_future.json"))
    )
    with pytest.raises(rstore.SchemaDrift):
        reg.ingest(future)
    # A whole registry written by a newer tool refuses at open.
    newer = tmp_path / "newer"
    newer.mkdir()
    (newer / "registry_meta.json").write_text(
        json.dumps({"schema_version": rstore.REGISTRY_SCHEMA_VERSION + 1})
    )
    with pytest.raises(rstore.SchemaDrift):
        rstore.Registry(str(newer))
    # CLI surface: exit code 2, graftcheck-style.
    rc = rcompare.main(["--registry", str(newer), "list"])
    assert rc == 2


# The five driver rounds the committed registry seed was ingested from. The
# BENCH_r0N.json snapshots left the tree with the runtime they were taken
# on; these carry the same shape (n / cmd / rc / tail / parsed) and the same
# parsed rows — which is all a record is built from — with the log tail cut.
_R_FLASH = {"attention_impl": "flash", "dropout": 0.1}
_R_HBM = {"peak_hbm_gb": 5.95, "peak_hbm_method": "xla_buffer_assignment"}
_LEGACY_BENCH_ROUNDS = [
    {"value": 23563.68, "vs_baseline": 5.194},
    {"value": 41890.94, "vs_baseline": 9.234, **_R_FLASH,
     "model_tflops_per_sec_per_chip": 75.71, "mfu_pct": 38.43},
    {"value": 41578.75, "vs_baseline": 9.165, **_R_FLASH,
     "model_tflops_per_sec_per_chip": 75.14, "mfu_pct": 38.14, **_R_HBM,
     "tokens_per_dollar": 124736250},
    {"value": 41670.33, "vs_baseline": 9.185, **_R_FLASH,
     "model_tflops_per_sec_per_chip": 75.31, "mfu_pct": 38.23, **_R_HBM,
     "tokens_per_dollar": 125011004},
    {"value": 41483.37, "vs_baseline": 9.144, **_R_FLASH,
     "model_tflops_per_sec_per_chip": 74.97, "mfu_pct": 38.06, **_R_HBM,
     "tokens_per_dollar": 124450116},
]


def legacy_snapshot_root(tmp_path):
    """A directory holding BENCH_r01-05 (written here) beside the repo's own
    MULTICHIP_r01-05 — what ``ingest_legacy`` expects of a repo root."""
    root = tmp_path / "legacy_root"
    root.mkdir()
    for n, extra in enumerate(_LEGACY_BENCH_ROUNDS, start=1):
        parsed = {
            "metric": "tinygpt_tierA_seq2048_tokens_per_sec_per_chip",
            "value": extra["value"], "unit": "tokens/sec/chip",
            **{k: v for k, v in extra.items() if k != "value"},
        }
        (root / f"BENCH_r{n:02d}.json").write_text(json.dumps({
            "n": n, "cmd": "python bench.py", "rc": 0,
            "tail": json.dumps(parsed) + "\n", "parsed": parsed,
        }))
        name = f"MULTICHIP_r{n:02d}.json"
        shutil.copy(os.path.join(REPO, name), root / name)
    return str(root)


def test_legacy_seed_ingest(tmp_path):
    """BENCH_r*/MULTICHIP_r* snapshots -> day-one trend history."""
    reg = rstore.Registry(str(tmp_path / "reg"))
    root = legacy_snapshot_root(tmp_path)
    ingested = rstore.ingest_legacy(reg, root)
    created = [r for r, c in ingested if c]
    assert len(created) == 10  # 5 bench rounds + 5 multichip rounds
    assert "bench_tinygpt_tierA_seq2048" in reg.arms()
    vals = reg.history_values(
        "bench_tinygpt_tierA_seq2048", metric_name="tokens_per_sec_per_chip",
    )
    assert vals[-1] == pytest.approx(41483.37)
    # Re-seeding is a no-op (content-addressed).
    assert sum(1 for _, c in rstore.ingest_legacy(reg, root) if c) == 0
    # The committed registry seed matches what --legacy produces.
    committed = rstore.Registry(os.path.join(REPO, "results", "registry"))
    if committed.exists():
        want = {r["record_id"] for r, _ in ingested}
        have = {l["record_id"] for l in committed.index_lines()}
        assert want <= have


# ---------------------------------------------------------------------------
# stats
# ---------------------------------------------------------------------------


def test_bootstrap_is_deterministic():
    a = [1024 / d for d in BASE_DTS]
    b = [1024 / d for d in SLOW_DTS]
    ci1 = rstats.bootstrap_delta_ci_pct(a, b)
    ci2 = rstats.bootstrap_delta_ci_pct(a, b)
    assert ci1 == ci2  # bit-identical: the seed is fixed
    lo, hi = ci1
    assert lo < -9.0 and hi > -11.0  # brackets the true -10%


def test_mann_whitney_sanity():
    assert rstats.mann_whitney_p(BASE_DTS, SLOW_DTS) < 0.001
    assert rstats.mann_whitney_p(BASE_DTS, AA_DTS) > 0.5
    assert rstats.mann_whitney_p([1.0] * 6, [1.0] * 6) == 1.0


def test_aa_comparison_is_neutral():
    """No false positives on a same-distribution rerun."""
    c = rstats.compare_distributions(
        [1024 / d for d in BASE_DTS], [1024 / d for d in AA_DTS],
        metric="tokens_per_sec", higher_is_better=True,
    )
    assert c.verdict == rstats.VERDICT_NEUTRAL
    assert abs(c.delta_pct) < 0.5


def test_significant_but_tiny_delta_stays_neutral():
    """The minimum-effect threshold: a perfectly separated 1% delta is
    statistically significant yet below the 2% floor -> neutral."""
    base = [1024 / d for d in BASE_DTS]
    cand = [v * 0.99 for v in base]
    c = rstats.compare_distributions(
        base, cand, metric="tokens_per_sec", higher_is_better=True,
    )
    assert c.p_value < 0.05
    assert c.verdict == rstats.VERDICT_NEUTRAL


def test_ten_percent_drop_is_regression_and_improvement_mirror():
    base = [1024 / d for d in BASE_DTS]
    slow = [1024 / d for d in SLOW_DTS]
    c = rstats.compare_distributions(
        base, slow, metric="tokens_per_sec", higher_is_better=True,
    )
    assert c.verdict == rstats.VERDICT_REGRESSION
    assert c.delta_pct == pytest.approx(-10.0, abs=0.1)
    up = rstats.compare_distributions(
        slow, base, metric="tokens_per_sec", higher_is_better=True,
    )
    assert up.verdict == rstats.VERDICT_IMPROVEMENT
    # Step time is a lower-is-better metric: the same slowdown flags.
    st = rstats.compare_distributions(
        BASE_DTS, SLOW_DTS, metric="window_mean_step_time_sec",
        higher_is_better=False,
    )
    assert st.verdict == rstats.VERDICT_REGRESSION


def test_too_few_windows_is_insufficient():
    c = rstats.compare_distributions(
        BASE_DTS[:3], SLOW_DTS[:3], metric="t", higher_is_better=True,
    )
    assert c.verdict == rstats.VERDICT_INSUFFICIENT


def test_scalar_verdict_needs_learned_noise_floor():
    """Scalar mode with thin history must not verdict: one prior run
    cannot distinguish platform jitter from a real regression (the
    second-ever suite run on a noisy host would otherwise flake)."""
    c = rstats.compare_scalars(
        5000.0, 4000.0, metric="tokens_per_sec", higher_is_better=True,
        history=[5000.0],
    )
    assert c.verdict == rstats.VERDICT_INSUFFICIENT
    assert c.delta_pct == pytest.approx(-20.0)  # delta still reported
    # With the floor learned (>= 3 history runs) the same drop verdicts.
    c = rstats.compare_scalars(
        5000.0, 4000.0, metric="tokens_per_sec", higher_is_better=True,
        history=[5000.0, 5010.0, 4990.0],
    )
    assert c.verdict == rstats.VERDICT_REGRESSION


def test_noise_floor_widens_threshold():
    noisy_history = [40000, 44000, 38000, 42000, 41000]
    noise = rstats.noise_floor_pct(noisy_history)
    assert noise > rstats.DEFAULT_MIN_EFFECT_PCT
    c = rstats.compare_scalars(
        41000.0, 41000.0 * 0.96, metric="tokens_per_sec_per_chip",
        higher_is_better=True, history=noisy_history,
    )
    # A 4% drop inside a ~10% noise band must NOT verdict.
    assert c.verdict == rstats.VERDICT_NEUTRAL
    assert c.threshold_pct == pytest.approx(noise)


# ---------------------------------------------------------------------------
# Frozen-fixture gate proof (acceptance contract)
# ---------------------------------------------------------------------------


@pytest.fixture()
def frozen_registry(tmp_path):
    root = str(tmp_path / "reg")
    shutil.copytree(FROZEN_REGISTRY, root)
    return root


def test_frozen_record_schema_is_pinned():
    """The on-disk record schema is a contract: readers of old registries
    must keep working, so the frozen fixture never changes and this pins
    exactly what it carries (and that its content hash still verifies)."""
    reg = rstore.Registry(FROZEN_REGISTRY)
    recs = reg.records(FROZEN_ARM)
    assert len(recs) == 2
    for rec in recs:
        assert sorted(rec.keys()) == [
            "arm", "env", "ingested_at", "metric", "record_id", "result",
            "schema_version", "source", "status", "tokens_per_step",
            "windows",
        ]
        assert rec["schema_version"] == 1
        assert rstore.record_id_for(rec) == rec["record_id"]
        assert sorted(rec["metric"].keys()) == [
            "higher_is_better", "name", "value",
        ]
        assert sorted(rec["windows"][0].keys()) == [
            "dt", "loss", "step", "steps_in_window",
        ]
    lines = reg.index_lines()
    assert sorted(lines[0].keys()) == [
        "arm", "ingested_at", "metric_name", "metric_value", "record_id",
        "seq", "source", "status",
    ]


def test_gate_aa_exits_zero(frozen_registry, capsys):
    rc = rcompare.main(["--registry", frozen_registry, "gate", "--all"])
    out = capsys.readouterr().out
    assert rc == 0, out
    assert "NEUTRAL" in out and "0 regression(s)" in out


def test_gate_flags_injected_ten_percent_regression(frozen_registry, capsys):
    """The end-to-end proof: ingest the frozen -10% candidate, and the
    gate exits 1 naming the arm, metric, delta and CI."""
    reg = rstore.Registry(frozen_registry)
    slow = json.load(
        open(os.path.join(FROZEN_CANDIDATES, "record_slow.json"))
    )
    _, created = reg.ingest(slow)
    assert created
    rc = rcompare.main(["--registry", frozen_registry, "gate", "--all"])
    out = capsys.readouterr().out
    assert rc == 1, out
    line = next(l for l in out.splitlines() if "REGRESSION" in l)
    assert f"arm={FROZEN_ARM}" in line
    assert "metric=tokens_per_sec" in line
    assert "delta=-10.0" in line
    assert "CI95=[" in line and "p=" in line
    # Deterministic: the same records verdict identically on a rerun.
    rc2 = rcompare.main(["--registry", frozen_registry, "gate", "--all"])
    out2 = capsys.readouterr().out
    assert rc2 == 1
    assert next(l for l in out2.splitlines() if "REGRESSION" in l) == line


def test_gate_fresh_arm_is_not_a_failure(frozen_registry, capsys):
    """First-ever record on an arm: insufficient-data, exit 0 — a fresh
    registry must not block the first suite run."""
    reg = rstore.Registry(frozen_registry)
    reg.ingest(rstore.make_record(
        arm="new_arm", result_row=result_row(), windows=windows(BASE_DTS),
        tokens_per_step=1024, source="result_new_arm.json",
    ))
    rc = rcompare.main(
        ["--registry", frozen_registry, "gate", "--arm", "new_arm"]
    )
    out = capsys.readouterr().out
    assert rc == 0
    assert "SKIP" in out and "no prior ok record" in out


def test_compare_cli_and_trend(frozen_registry, tmp_path, capsys):
    rc = rcompare.main([
        "--registry", frozen_registry, "compare", "last-good", "latest",
        "--arm", FROZEN_ARM,
    ])
    out = capsys.readouterr().out
    assert rc == 0 and "VERDICT: neutral" in out
    png = str(tmp_path / "trend.png")
    rc = rcompare.main(
        ["--registry", frozen_registry, "trend", FROZEN_ARM, "--png", png]
    )
    out = capsys.readouterr().out
    assert rc == 0
    assert "regress trend" in out and os.path.exists(png)


def test_trend_superlatives_exclude_partials(tmp_path):
    reg = rstore.Registry(str(tmp_path / "reg"))
    reg.ingest(rstore.make_record(
        arm="arm1", result_row=result_row(tokens_per_sec=5000.0),
        source="r1",
    ))
    # The partial's (bogus, higher) last-window rate must not be "best",
    # nor anchor the next delta.
    reg.ingest(rstore.make_record(
        arm="arm1", result_row=result_row(tokens_per_sec=9999.0, partial=True),
        status="partial", source="partial_arm1.json",
    ))
    reg.ingest(rstore.make_record(
        arm="arm1", result_row=result_row(tokens_per_sec=5100.0),
        source="r2",
    ))
    rows = rcompare.trend_rows(reg, "arm1")
    assert [r["best"] for r in rows] == [False, False, True]
    assert rows[1]["status"] == "partial"
    assert rows[2]["delta_pct_vs_prev"] == pytest.approx(2.0)


# ---------------------------------------------------------------------------
# Integrations
# ---------------------------------------------------------------------------


def test_telemetry_report_compare_tables(capsys):
    """Acceptance: --compare A B produces per-phase + per-window delta
    tables via the shared stats engine, and flags the frozen -10% pair."""
    from distributed_llm_training_benchmark_framework_tpu.analysis import (
        telemetry_report as tr,
    )

    rc = tr.main(["--compare", COMPARE_A, COMPARE_B])
    out = capsys.readouterr().out
    # Exit codes agree with `regress compare`: a regression exits 1.
    assert rc == 1
    assert "Phase delta" in out
    for phase in ("init", "compile", "warmup", "timed", "finalize"):
        assert phase in out
    assert "Timed-window distributions (regress.stats)" in out
    assert "metric=tokens_per_sec delta=-10.0" in out
    assert "metric=window_mean_step_time_sec delta=+11.1" in out
    assert "VERDICT: regression" in out
    # A/A self-compare: neutral, zero phase deltas, exit 0.
    rc = tr.main(["--compare", COMPARE_A, COMPARE_A])
    out = capsys.readouterr().out
    assert rc == 0 and "VERDICT: neutral" in out
    # Unreadable input is operational (2), distinct from a regression.
    rc = tr.main(["--compare", COMPARE_A, "/nonexistent.jsonl"])
    capsys.readouterr()
    assert rc == 2


def test_make_report_trend_section(frozen_registry, tmp_path):
    import pandas as pd

    from distributed_llm_training_benchmark_framework_tpu.analysis import (
        make_report,
    )

    df = pd.DataFrame([result_row()])
    md = make_report.build_report(df, registry_root=frozen_registry)
    assert "## Per-arm trend (registry)" in md
    assert FROZEN_ARM in md
    # Without a registry the section is absent (old callers unchanged).
    assert "Per-arm trend" not in make_report.build_report(df)


FROZEN_REMAT = os.path.join(FIXTURES, "registry_frozen_remat")


@pytest.fixture()
def remat_registry(tmp_path):
    """A scratch registry holding the frozen --remat-sweep records (one
    per policy; regenerate with tests/fixtures/make_remat_frozen.py)."""
    reg = rstore.Registry(str(tmp_path / "reg"))
    for pol in ("none", "dots", "full", "auto"):
        rec = json.load(
            open(os.path.join(FROZEN_REMAT, f"record_remat_{pol}.json"))
        )
        reg.ingest(rec)
    return reg


def test_make_report_remat_frontier_from_frozen_fixture(remat_registry):
    """The ISSUE-8 acceptance pin: make_report renders the HBM-vs-
    recompute frontier table from the frozen sweep records — one row per
    policy in recompute order, resolved policy, delta vs the no-remat
    point, peak HBM + per-chip headroom."""
    import pandas as pd

    from distributed_llm_training_benchmark_framework_tpu.analysis import (
        make_report,
    )

    md = make_report.build_report(
        pd.DataFrame([result_row()]), registry_root=remat_registry.root,
    )
    assert "## Remat/HBM frontier (`bench.py --remat-sweep`)" in md
    assert "### bench_llama_tierA_seq2048" in md
    lines = [l for l in md.splitlines() if l.startswith("|") and
             any(f"| {p} |" in l for p in ("none", "dots", "full", "auto"))]
    # Recompute order none -> dots -> full, the auto probe last.
    assert [l.split("|")[1].strip() for l in lines] == [
        "none", "dots", "full", "auto",
    ]
    assert "| none | none | 41,900.00 | +0.0% | 12.40 | 3.60 | 38.40 |" \
        in md
    assert "| full | full | 36,400.00 | -13.1% | 7.10 | 8.90 | 33.40 |" \
        in md
    assert "| auto | dots | 40,050.00 | -4.4% |" in md
    # Registries without sweep records render no frontier section.
    md_plain = make_report.build_report(
        pd.DataFrame([result_row()]),
        registry_root=os.path.join(FIXTURES, "registry_frozen"),
    )
    assert "Remat/HBM frontier" not in md_plain


def test_remat_frontier_never_mixes_lineages(remat_registry):
    """A later smoke-length sweep must not lend rows to (or borrow the
    'none' base from) an older full-length sweep: the table renders the
    NEWEST lineage only, counting omitted older-lineage records in a
    visible note."""
    import pandas as pd

    from distributed_llm_training_benchmark_framework_tpu.analysis import (
        make_report,
    )

    smoke = json.load(
        open(os.path.join(FROZEN_REMAT, "record_remat_none.json"))
    )
    smoke["result"] = dict(smoke["result"], steps=12, value=9000.0)
    smoke["metric"] = dict(smoke["metric"], value=9000.0)
    smoke["record_id"] = rstore.record_id_for(smoke)
    remat_registry.ingest(smoke)
    md = make_report.build_report(
        pd.DataFrame([result_row()]), registry_root=remat_registry.root,
    )
    # Only the smoke lineage's single row renders in the FRONTIER
    # section (the registry trend section still lists every record)…
    section = md.split("## Remat/HBM frontier")[1].split("\n## ")[0]
    assert "| none | none | 9,000.00 |" in section
    assert "41,900.00" not in section and "| full |" not in section
    # …and the omission is named, never silent.
    assert "4 older-lineage sweep record(s)" in section


def test_remat_sweep_records_stay_separate_lineages(remat_registry):
    """One record per policy, each its own config-key lineage (the
    acceptance contract: a 'full' run can never gate against the 'none'
    history), and the ordinary bench lineage excludes them all."""
    reg = remat_registry
    recs = reg.records("bench_llama_tierA_seq2048")
    assert len(recs) == 4
    keys = {r["result"]["remat_policy"]: rstore.config_key(r) for r in recs}
    assert len(set(keys.values())) == 4
    for rec in recs:
        base = reg.baseline(
            "bench_llama_tierA_seq2048",
            exclude_record_id=rec["record_id"], match_config_of=rec,
        )
        assert base is None, (
            f"{rec['result']['remat_policy']} found a cross-policy baseline"
        )


def test_bench_registry_rows_emit_one_row_per_sweep_policy():
    """bench.registry_rows fans the remat_sweep sub-object into one
    record per policy, tagged with its source and the flagship geometry,
    while the headline row stays sweep-free."""
    import bench

    args = bench.build_parser().parse_args(["--remat-sweep"])
    sweep_row = {
        "metric": "llama_tierA_seq2048_tokens_per_sec_per_chip",
        "value": 40000.0, "remat_policy": "none",
        "remat_policy_resolved": "none", "hbm_headroom_gb": 3.6,
    }
    payload = {
        "metric": "tinygpt_tierA_seq2048_tokens_per_sec_per_chip",
        "value": 41500.0,
        "remat_sweep": {
            pol: dict(sweep_row, remat_policy=pol)
            for pol in bench.REMAT_SWEEP_POLICIES
        },
    }
    rows = bench.registry_rows(args, payload)
    sources = [src for src, _row, _extra in rows]
    assert sources[0] == "bench.py"
    assert sorted(sources[1:]) == sorted(
        f"bench.py:remat-sweep:{p}" for p in bench.REMAT_SWEEP_POLICIES
    )
    # The headline row never carries the sweep payload…
    assert "remat_sweep" not in rows[0][1]
    # …and each sweep row keeps its policy + gets the flagship geometry.
    for src, row, extra in rows[1:]:
        assert row["remat_policy"] == src.rsplit(":", 1)[1]
        assert extra["model_family"] == bench.FLAGSHIP_FAMILY
        assert extra["grad_accum"] == bench.FLAGSHIP_GRAD_ACCUM


def test_bench_style_scalar_verdict(tmp_path):
    """bench.py's lineage: legacy seed -> a -10% headline run flags."""
    import bench

    reg = rstore.Registry(str(tmp_path / "reg"))
    rstore.ingest_legacy(reg, legacy_snapshot_root(tmp_path))
    row = {
        "metric": "tinygpt_tierA_seq2048_tokens_per_sec_per_chip",
        "value": 37335.03, "unit": "tokens/sec/chip", "vs_baseline": 8.2,
        "attention_impl": "flash", "dropout": 0.1,
    }
    # Build the record exactly the way a default bench.py invocation does
    # so it joins the legacy snapshots' config lineage.
    args = bench.build_parser().parse_args([])
    (source, brow, extra), = bench.registry_rows(args, row)
    rec, _ = reg.ingest(rstore.record_from_bench_row(
        brow, source=source, extra_result=extra,
    ))
    line = rcompare.verdict_line_for_bench(reg, rec)
    assert "REGRESSION" in line
    assert "arm=bench_tinygpt_tierA_seq2048" in line
    assert "delta=-10.0" in line and "CI95=[" in line
    # The pre-flash r01 outlier is a config change, not noise: the floor
    # stays tight enough to catch the drop.
    c = rcompare.compare_pair(
        reg, reg.baseline("bench_tinygpt_tierA_seq2048",
                          exclude_record_id=rec["record_id"],
                          match_config_of=rec),
        rec,
    )["comparisons"][0]
    assert c.threshold_pct < 3.0


def test_default_bench_invocation_joins_committed_seed_lineage(tmp_path):
    """The committed seed's whole point is that a fresh checkout's first
    `python bench.py` already has a baseline and noise floor. That only
    holds if the config_key of a record built EXACTLY the way bench.py
    builds it matches the legacy rows' — this pins the two construction
    paths (bench.registry_rows vs store.ingest_legacy) together."""
    import bench

    reg = rstore.Registry(str(tmp_path / "reg"))
    rstore.ingest_legacy(reg, legacy_snapshot_root(tmp_path))
    args = bench.build_parser().parse_args([])  # a default invocation
    payload = {
        "metric": "tinygpt_tierA_seq2048_tokens_per_sec_per_chip",
        "value": 41500.0, "unit": "tokens/sec/chip", "vs_baseline": 9.1,
        "attention_impl": "flash", "dropout": 0.1,
    }
    (source, row, extra), = bench.registry_rows(args, payload)
    rec, _ = reg.ingest(rstore.record_from_bench_row(
        row, source=source, extra_result=extra,
    ))
    base = reg.baseline(
        "bench_tinygpt_tierA_seq2048",
        exclude_record_id=rec["record_id"], match_config_of=rec,
    )
    assert base is not None, (
        "live default-invocation record found no config-matching baseline "
        "in the legacy seed — config_key drifted between bench.py and "
        "ingest_legacy"
    )
    assert base["source"] == "legacy:BENCH_r05.json"
    line = rcompare.verdict_line_for_bench(reg, rec)
    assert "vs last-good" in line  # a real verdict, not 'first record'
    # A smoke-length run must NOT join the 100-step lineage.
    smoke = bench.build_parser().parse_args(["--steps", "12"])
    (_, srow, sextra), = bench.registry_rows(smoke, payload)
    srec = rstore.record_from_bench_row(srow, source="bench.py",
                                        extra_result=sextra)
    assert rstore.config_key(srec) != rstore.config_key(rec)


def test_ingest_self_heals_missing_index_line(tmp_path):
    """A crash between the record write and the index append must not
    hide the record forever: the next ingest of the same content repairs
    the index instead of short-circuiting on file existence."""
    reg = rstore.Registry(str(tmp_path / "reg"))
    rec = rstore.make_record(
        arm="arm1", result_row=result_row(), windows=windows(BASE_DTS),
        tokens_per_step=1024, source="r1",
    )
    reg.ingest(rec)
    # Simulate the torn ingest: file present, index line gone.
    idx = tmp_path / "reg" / "index.jsonl"
    idx.write_text("")
    reg2 = rstore.Registry(str(tmp_path / "reg"))
    assert reg2.records("arm1") == []  # invisible, as the crash left it
    _, created = reg2.ingest(rec)
    assert not created  # still a dedupe hit...
    assert len(reg2.records("arm1")) == 1  # ...but the index healed
    assert reg2.baseline("arm1") is not None


# ---------------------------------------------------------------------------
# Scripts / wiring pins
# ---------------------------------------------------------------------------


def test_regress_gate_script_mirrors_graftcheck():
    text = open(os.path.join(REPO, "scripts", "regress_gate.sh")).read()
    assert "set -euo pipefail" in text
    assert ("exec python -m "
            "distributed_llm_training_benchmark_framework_tpu.regress"
            in text)
    assert "gate --all" in text  # the no-args default
    assert os.access(os.path.join(REPO, "scripts", "regress_gate.sh"),
                     os.X_OK)


def test_suite_finish_path_has_gate_with_escape_hatch():
    text = open(
        os.path.join(REPO, "scripts", "run_all_benchmarks.sh")
    ).read()
    assert 'SKIP_REGRESS="${SKIP_REGRESS:-0}"' in text
    assert "distributed_llm_training_benchmark_framework_tpu.regress" in text
    assert "ingest --results-dir" in text
    assert "gate --all" in text
    assert "REGRESSION GATE FAILED" in text


def test_suite_remat_sweep_opt_in_wiring():
    """REMAT_SWEEP=1 appends the frontier sweep after the matrix: the
    flagship-off bench.py sweep invocation, registry ingestion via
    --regress on, and a report refresh so the frontier table lands in
    BENCHMARK_REPORT.md (local mode only — the sweep is in-process)."""
    text = open(
        os.path.join(REPO, "scripts", "run_all_benchmarks.sh")
    ).read()
    assert 'REMAT_SWEEP="${REMAT_SWEEP:-0}"' in text
    assert "--remat-sweep --flagship off" in text
    assert '"$REMAT_SWEEP" = "1" ] && [ "$MODE" = "local"' in text
    assert "REMAT SWEEP FAILED" in text
    # The sweep block refreshes the report AFTER ingesting its records.
    assert text.index("--remat-sweep") < text.rindex("make_report")


def test_gate_script_end_to_end(frozen_registry):
    """The wrapper really gates: 0 on the A/A registry, 1 after the slow
    candidate lands (subprocess — the run_all finish-path contract)."""
    env = dict(os.environ, REGRESS_REGISTRY=frozen_registry,
               JAX_PLATFORMS="cpu")
    r = subprocess.run(
        ["bash", os.path.join(REPO, "scripts", "regress_gate.sh")],
        capture_output=True, text=True, env=env, timeout=120,
    )
    assert r.returncode == 0, r.stdout + r.stderr
    reg = rstore.Registry(frozen_registry)
    reg.ingest(json.load(
        open(os.path.join(FROZEN_CANDIDATES, "record_slow.json"))
    ))
    r = subprocess.run(
        ["bash", os.path.join(REPO, "scripts", "regress_gate.sh")],
        capture_output=True, text=True, env=env, timeout=120,
    )
    assert r.returncode == 1, r.stdout + r.stderr
    assert f"REGRESSION arm={FROZEN_ARM}" in r.stdout


# ---------------------------------------------------------------------------
# k8s liveness probe (ROADMAP telemetry follow-up (b))
# ---------------------------------------------------------------------------

PROBE = os.path.join(REPO, "scripts", "liveness_probe.sh")


def run_probe(log_path, **env_over):
    env = dict(os.environ, BENCH_LOG=str(log_path))
    env.update({k: str(v) for k, v in env_over.items()})
    return subprocess.run(
        ["bash", PROBE], capture_output=True, text=True, env=env,
        timeout=60,
    )


def heartbeat_line(ts):
    return "BENCHMARK_HEARTBEAT " + json.dumps(
        {"arm": "zero2_ws4_seq128_tierS", "step": 20, "loss": 5.2,
         "tokens_per_sec": 5000.0, "ts": ts}
    )


def test_probe_passes_before_first_signal(tmp_path):
    # No mirror file, no telemetry dir (container just started)...
    assert run_probe(tmp_path / "absent.log",
                     RESULTS_DIR=str(tmp_path / "none")).returncode == 0
    # ...and a results dir with no telemetry yet (init/compile): killing
    # a pod mid-compile would turn cold starts into CrashLoops.
    rdir = tmp_path / "results"
    rdir.mkdir()
    assert run_probe(tmp_path / "absent.log",
                     RESULTS_DIR=str(rdir)).returncode == 0


def test_probe_reads_telemetry_jsonl_channel(tmp_path):
    """The k8s path: no stdout mirror exists — liveness comes from the
    newest telemetry JSONL's last event timestamp."""
    import time as _time

    rdir = tmp_path / "results"
    rdir.mkdir()
    tfile = rdir / "telemetry_zero2_ws4_seq128_tierS.jsonl"
    tfile.write_text(json.dumps(
        {"event": "step_window", "ts": _time.time(), "rel": 5.0, "step": 9}
    ) + "\n")
    absent = tmp_path / "absent.log"
    assert run_probe(absent, RESULTS_DIR=str(rdir),
                     HEARTBEAT_SEC=30).returncode == 0
    tfile.write_text(json.dumps(
        {"event": "step_window", "ts": _time.time() - 1000, "rel": 5.0,
         "step": 9}
    ) + "\n")
    r = run_probe(absent, RESULTS_DIR=str(rdir), HEARTBEAT_SEC=30)
    assert r.returncode == 1
    assert "grace" in r.stderr


def test_probe_fresh_vs_stale_heartbeat(tmp_path):
    """The mirror channel (non-k8s supervisors): heartbeat lines in
    $BENCH_LOG win over the telemetry dir when present."""
    import time as _time

    log = tmp_path / "bench.log"
    log.write_text(heartbeat_line(_time.time()) + "\n")
    assert run_probe(log, HEARTBEAT_SEC=30).returncode == 0
    # Stale beyond the derived grace (10 x 30s = 300s): stalled.
    log.write_text(heartbeat_line(_time.time() - 1000) + "\n")
    r = run_probe(log, HEARTBEAT_SEC=30)
    assert r.returncode == 1
    assert "grace" in r.stderr
    # The grace window derives from the cadence knob: a cadence large
    # enough to cover the same age passes.
    assert run_probe(log, HEARTBEAT_SEC=200).returncode == 0
    # An explicit override wins.
    assert run_probe(log, HEARTBEAT_SEC=30,
                     LIVENESS_GRACE_SEC=2000).returncode == 0


def test_probe_tolerates_torn_lines(tmp_path):
    # Mid-write kills are not evidence of a hang, on either channel.
    log = tmp_path / "bench.log"
    log.write_text('BENCHMARK_HEARTBEAT {"arm": "x", "ts": 17')
    assert run_probe(log).returncode == 0
    rdir = tmp_path / "results"
    rdir.mkdir()
    (rdir / "telemetry_x.jsonl").write_text('{"event": "step_window", "ts')
    assert run_probe(tmp_path / "absent.log",
                     RESULTS_DIR=str(rdir)).returncode == 0


def test_template_and_launcher_wire_the_probe():
    tpl = open(
        os.path.join(REPO, "k8s", "job-benchmark.template.yaml")
    ).read()
    assert "livenessProbe:" in tpl
    assert "liveness_probe.sh" in tpl
    assert "{{LIVENESS_PERIOD}}" in tpl
    assert "{{HEARTBEAT_SEC}}" in tpl
    launcher = open(
        os.path.join(REPO, "scripts", "launch_multi.sh")
    ).read()
    for var in ("{{HEARTBEAT_SEC}}", "{{LIVENESS_PERIOD}}"):
        assert var in launcher, f"launch_multi.sh must substitute {var}"
    assert "--heartbeat-sec" in launcher
    # The probe reads the recorder's telemetry JSONL (the stdout stream
    # stays untouched — no tee interposed on PID 1; the Dockerfile
    # contract's plain `exec python -u` covers the entrypoint side).
    probe = open(PROBE).read()
    assert "telemetry_" in probe and "BENCHMARK_HEARTBEAT" in probe


@pytest.mark.slow
def test_bench_auto_ingest_and_verdict(tmp_path):
    """bench.py --regress on: records land in the registry and the
    verdict line goes to stderr (stdout stays one JSON line — the
    contract test covers that side)."""
    registry = str(tmp_path / "reg")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=1")
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py"),
         "--tier", "S", "--seq-len", "64", "--steps", "3",
         "--warmup-steps", "1", "--world-size", "1", "--flagship", "off",
         "--regress", "on", "--registry", registry],
        capture_output=True, text=True, env=env, timeout=900, cwd=REPO,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    lines = [l for l in proc.stdout.splitlines() if l.strip()]
    assert len(lines) == 1  # stdout contract untouched
    assert "regress: recorded bench_tinygpt_tierS_seq64" in proc.stderr
    assert "first record with this configuration" in proc.stderr
    reg = rstore.Registry(registry)
    assert reg.arms() == ["bench_tinygpt_tierS_seq64"]
    # Second run: now there IS a baseline; a verdict line appears.
    proc2 = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py"),
         "--tier", "S", "--seq-len", "64", "--steps", "3",
         "--warmup-steps", "1", "--world-size", "1", "--flagship", "off",
         "--regress", "on", "--registry", registry],
        capture_output=True, text=True, env=env, timeout=900, cwd=REPO,
    )
    assert proc2.returncode == 0, proc2.stderr[-3000:]
    assert "vs last-good arm=bench_tinygpt_tierS_seq64" in proc2.stderr
