"""Elastic-resilience matrix: geometry-change resume, coordinated
multi-host preemption, async delta checkpointing, per-rank telemetry.

The ISSUE-6 acceptance contracts pinned here:

- a checkpoint saved under dp4 restores and trains onward under dp2 in a
  REAL subprocess round trip, passing validate_results resume-continuity
  with ``resume_geometry_changed=true``;
- a SIGTERM delivered to a NON-ZERO rank of a real two-process
  ``jax.distributed`` rendezvous (the multihost dryrun shape) produces a
  coherent all-host emergency checkpoint and a unanimous exit 75 — the
  preempt-soon flag crosses hosts on the coordination-service KV store,
  not on a signal;
- ``--checkpoint-async`` keeps periodic saves off the timed path and the
  emergency path only FLUSHES the in-flight delta.

Plus the satellite edge cases: same-geometry round trips take the exact
pre-elastic path (no stitch recorded), dp regrow/shrink reshard, a tp
change against GQA kv heads lands on the PR 1 replication rule, an
incompatible geometry (different global shapes) refuses loudly, and a
torn resharded checkpoint falls back through quarantine.
"""

import json
import os
import signal
import socket
import subprocess
import sys
import time

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P  # noqa: E402

from distributed_llm_training_benchmark_framework_tpu import faults  # noqa: E402
from distributed_llm_training_benchmark_framework_tpu.analysis import (  # noqa: E402
    validate_results as vr,
)
from distributed_llm_training_benchmark_framework_tpu.faults import (  # noqa: E402
    injection as finj,
)
from distributed_llm_training_benchmark_framework_tpu.parallel import (  # noqa: E402
    strategies as strat,
)
from distributed_llm_training_benchmark_framework_tpu.parallel.mesh import (  # noqa: E402
    jsonable_to_spec,
    mesh_axes_dict,
    spec_to_jsonable,
)
from distributed_llm_training_benchmark_framework_tpu.runtime.checkpoint import (  # noqa: E402
    BenchmarkCheckpointer,
)


def _mesh(n, axis="data"):
    return Mesh(np.asarray(jax.devices()[:n]).reshape(n), (axis,))


def _sharded(mesh, vals, spec):
    return jax.device_put(jnp.asarray(vals), NamedSharding(mesh, spec))


def _ck(tmp_path, mesh, world_size, **kw):
    return BenchmarkCheckpointer(
        str(tmp_path / "ck"),
        geometry={"mesh_axes": mesh_axes_dict(mesh),
                  "world_size": world_size},
        **kw,
    )


# ---------------------------------------------------------------------------
# Spec (de)serialization + geometry sidecar
# ---------------------------------------------------------------------------


def test_spec_jsonable_round_trip():
    for spec in (P(), P("data"), P(None, "model"), P(("data", "expert"), None)):
        assert jsonable_to_spec(spec_to_jsonable(spec)) == spec


def test_geometry_sidecar_written_with_abstract_trees(tmp_path):
    mesh = _mesh(4)
    ck = _ck(tmp_path, mesh, 4)
    params = {"w": _sharded(mesh, np.arange(16, dtype=np.float32), P("data"))}
    opt = {"m": _sharded(mesh, np.zeros(16, dtype=np.float32), P("data"))}
    assert ck.save(2, params, opt, force=True)
    geom = ck.read_geometry(2)
    assert geom["mesh_axes"] == {"data": 4} and geom["world_size"] == 4
    # The abstract-tree entries carry the restore-compat contract: the
    # key set must stay stable for older checkpoints to keep restoring.
    (entry,) = geom["params"]
    assert sorted(entry) == ["dtype", "path", "shape", "spec"]
    assert entry["shape"] == [16] and entry["spec"] == ["data"]
    ck.close()


# ---------------------------------------------------------------------------
# Geometry-change restore: same / shrink / regrow / GQA kv / refuse / torn
# ---------------------------------------------------------------------------


def test_same_geometry_round_trip_records_no_stitch(tmp_path):
    mesh = _mesh(4)
    ck = _ck(tmp_path, mesh, 4)
    params = {"w": _sharded(mesh, np.arange(16, dtype=np.float32), P("data"))}
    opt = {"m": _sharded(mesh, np.zeros(16, dtype=np.float32), P("data"))}
    ck.save(2, params, opt, force=True)
    p, _o, step = ck.restore(params, opt)
    assert step == 2
    assert ck.last_resume_geometry_changed is False
    assert ck.last_resume_source_geometry is None
    np.testing.assert_array_equal(np.asarray(p["w"]), np.arange(16))
    ck.close()


@pytest.mark.parametrize("src,dst", [(4, 2), (2, 8)])
def test_dp_shrink_and_regrow_resharded(tmp_path, src, dst):
    """dp4 -> dp2 (shrink) and dp2 -> dp8 (regrow): values identical,
    placement follows the TARGET mesh, the stitch is recorded."""
    mesh_a, mesh_b = _mesh(src), _mesh(dst)
    vals = np.arange(16, dtype=np.float32)
    ck = _ck(tmp_path, mesh_a, src)
    ck.save(3, {"w": _sharded(mesh_a, vals, P("data"))},
            {"m": _sharded(mesh_a, vals * 0, P("data"))}, force=True)
    ck.close()
    ck2 = _ck(tmp_path, mesh_b, dst)
    p, o, step = ck2.restore(
        {"w": _sharded(mesh_b, vals * 0, P("data"))},
        {"m": _sharded(mesh_b, vals * 0, P("data"))},
    )
    assert step == 3 and ck2.last_resume_geometry_changed is True
    assert ck2.last_resume_source_geometry["mesh_axes"] == {"data": src}
    np.testing.assert_array_equal(np.asarray(p["w"]), vals)
    assert p["w"].sharding.mesh.shape["data"] == dst
    ck2.close()


def test_tp_change_with_gqa_kv_replication(tmp_path):
    """tp2 -> tp3 with kv_heads=2: the target specs come from the PR 1
    kv-head-aligned rule, so wkv lands REPLICATED over 'model' instead of
    split inside a kv head — and the reshard restore honors that."""
    mesh2, mesh3 = _mesh(2, axis="model"), _mesh(3, axis="model")
    # wkv layout: (layers, d_model, 2, kv_dim) — the stacked GQA k/v
    # projection the PR 1 rule governs (axis 3 is the column split).
    w = np.arange(2 * 4 * 2 * 6, dtype=np.float32).reshape(2, 4, 2, 6)
    params_shape = {
        "blocks": {"wkv": jax.ShapeDtypeStruct((2, 4, 2, 6), jnp.float32)}
    }
    spec2 = strat.param_partition_specs(
        params_shape, mesh2, shard=False, kv_heads=2
    )["blocks"]["wkv"]
    assert tuple(spec2)[3] == "model"  # tp2 divides kv_heads=2: sharded
    spec3 = strat.param_partition_specs(
        params_shape, mesh3, shard=False, kv_heads=2
    )["blocks"]["wkv"]
    # tp3 does not divide kv_heads=2 -> the PR 1 rule replicates.
    assert "model" not in tuple(spec3)
    ck = _ck(tmp_path, mesh2, 2)
    ck.save(1, {"blocks": {"wkv": _sharded(mesh2, w, spec2)}},
            {"m": _sharded(mesh2, np.zeros(4, np.float32), P())}, force=True)
    ck.close()
    ck2 = _ck(tmp_path, mesh3, 3)
    p, _o, _s = ck2.restore(
        {"blocks": {"wkv": _sharded(mesh3, w * 0, spec3)}},
        {"m": _sharded(mesh3, np.zeros(4, np.float32), P())},
    )
    assert ck2.last_resume_geometry_changed is True
    np.testing.assert_array_equal(np.asarray(p["blocks"]["wkv"]), w)
    assert "model" not in tuple(p["blocks"]["wkv"].sharding.spec)
    ck2.close()


def test_refused_incompatible_geometry_names_the_leaf(tmp_path):
    """A geometry change with DIFFERENT global shapes (another model/tier/
    seq) must refuse loudly, not hand orbax mismatched templates."""
    mesh4, mesh2 = _mesh(4), _mesh(2)
    ck = _ck(tmp_path, mesh4, 4)
    ck.save(2, {"w": _sharded(mesh4, np.zeros(16, np.float32), P("data"))},
            {"m": _sharded(mesh4, np.zeros(16, np.float32), P("data"))},
            force=True)
    ck.close()
    ck2 = _ck(tmp_path, mesh2, 2)
    with pytest.raises(ValueError, match="shape-incompatible") as e:
        ck2.restore(
            {"w": _sharded(mesh2, np.zeros(8, np.float32), P("data"))},
            {"m": _sharded(mesh2, np.zeros(8, np.float32), P("data"))},
        )
    assert "['w']" in str(e.value) and "[16]" in str(e.value)
    ck2.close()


def test_torn_resharded_checkpoint_falls_back_to_quarantine(tmp_path):
    """Digest validation runs BEFORE the reshard: a torn newest step is
    quarantined (geometry sidecar traveling with it) and the restore
    falls back to the previous good step — still resharded."""
    mesh4, mesh2 = _mesh(4), _mesh(2)
    vals = np.arange(16, dtype=np.float32)
    ck = _ck(tmp_path, mesh4, 4)
    opt = {"m": _sharded(mesh4, vals * 0, P("data"))}
    ck.save(2, {"w": _sharded(mesh4, vals, P("data"))}, opt, force=True)
    ck.save(4, {"w": _sharded(mesh4, vals + 1, P("data"))}, opt, force=True)
    finj._tear_newest_file(ck.step_dir(4))
    ck.close()
    ck2 = _ck(tmp_path, mesh2, 2)
    p, _o, step = ck2.restore(
        {"w": _sharded(mesh2, vals * 0, P("data"))},
        {"m": _sharded(mesh2, vals * 0, P("data"))},
    )
    assert step == 2 and ck2.last_resume_geometry_changed is True
    np.testing.assert_array_equal(np.asarray(p["w"]), vals)
    qdir = os.path.join(ck2.quarantine_dir, "step_4")
    assert os.path.isdir(qdir)
    assert os.path.exists(os.path.join(qdir, "geometry_4.json"))
    ck2.close()


def test_restart_ledger_counts_geometry_changes(tmp_path):
    mesh = _mesh(2)
    ck = _ck(tmp_path, mesh, 2)
    assert ck.note_restart() == 1
    ck.last_resume_source_geometry = {"mesh_axes": {"data": 4}}
    assert ck.note_restart(geometry_changed=True) == 2
    assert ck.n_restarts() == 2 and ck.n_geometry_changes() == 1
    ledger = json.load(open(os.path.join(ck.directory, "restarts.json")))
    assert ledger["last_geometry_change"]["from_mesh_axes"] == {"data": 4}
    assert ledger["last_geometry_change"]["to_mesh_axes"] == {"data": 2}
    ck.close()


# ---------------------------------------------------------------------------
# Async delta checkpointing (unit level)
# ---------------------------------------------------------------------------


def test_async_save_defers_digest_until_finalize(tmp_path):
    mesh = _mesh(2)
    ck = _ck(tmp_path, mesh, 2, async_save=True)
    params = {"w": _sharded(mesh, np.arange(4, dtype=np.float32), P("data"))}
    opt = {"m": _sharded(mesh, np.zeros(4, dtype=np.float32), P("data"))}
    assert ck.save(2, params, opt, meta={"last_loss": 5.0})
    assert ck.pending_async_step() == 2
    assert not os.path.exists(ck._digest_path(2))  # not yet certified
    # The geometry sidecar lands at DISPATCH: a commit that finishes in
    # the background before any finalize must not be restorable onto a
    # different mesh unstitched.
    assert os.path.exists(ck._geometry_path(2))
    assert ck.finalize_pending() == 2
    assert ck.pending_async_step() is None
    assert ck.validate_step(2) == ("ok", "digest verified")
    assert ck.step_meta(2) == {"last_loss": 5.0}
    assert ck.read_geometry(2)["mesh_axes"] == {"data": 2}
    ck.close()


def test_async_pending_finalized_by_close_and_next_save(tmp_path):
    mesh = _mesh(2)
    ck = _ck(tmp_path, mesh, 2, async_save=True)
    params = {"w": _sharded(mesh, np.arange(4, dtype=np.float32), P("data"))}
    opt = {"m": _sharded(mesh, np.zeros(4, dtype=np.float32), P("data"))}
    ck.save(2, params, opt)
    ck.save(4, params, opt)  # finalizes step 2 first
    assert ck.validate_step(2)[0] == "ok"
    assert ck.pending_async_step() == 4
    ck.close()  # finalizes step 4
    ck2 = _ck(tmp_path, mesh, 2)
    assert ck2.validate_step(4)[0] == "ok"
    assert ck2.restore_latest(params, opt)[2] == 4
    ck2.close()


# ---------------------------------------------------------------------------
# sigterm-rank fault spec + coordinated guard
# ---------------------------------------------------------------------------


def test_parse_sigterm_rank_spec():
    s = faults.parse_fault_spec("sigterm-rank@9:1")
    assert (s.kind, s.step, s.rank) == ("sigterm-rank", 9, 1)
    assert str(s) == "sigterm-rank@9:1"  # chaos-trail identity round trip


@pytest.mark.parametrize("bad", [
    "sigterm-rank",        # no step
    "sigterm-rank@9",      # no rank — which rank dies is the point
    "sigterm-rank@9:x",    # non-integer rank
    "sigterm-rank@9:-1",   # negative rank
])
def test_parse_sigterm_rank_rejects(bad):
    with pytest.raises(ValueError):
        faults.parse_fault_spec(bad)


def test_sigterm_rank_fires_only_on_matching_rank(monkeypatch):
    fired = []
    monkeypatch.setattr(finj.os, "kill",
                        lambda pid, sig: fired.append(sig))
    other = faults.FaultInjector(
        faults.parse_fault_spec("sigterm-rank@5:1"), is_main=False, rank=0
    )
    other.at_boundary(5)
    other.at_boundary(7)
    assert fired == [] and other.fired  # armed once, never signals rank 0
    target = faults.FaultInjector(
        faults.parse_fault_spec("sigterm-rank@5:1"), is_main=False, rank=1
    )
    target.at_boundary(5)
    assert fired == [signal.SIGTERM]


def test_coordinate_single_process_reduces_to_local_flag():
    guard = faults.PreemptionGuard(enabled=False)
    assert guard.coordinate(7) is None
    guard._requested = True
    assert guard.coordinate(7) == 7


# ---------------------------------------------------------------------------
# Per-rank telemetry
# ---------------------------------------------------------------------------


def test_rank_recorder_writes_rank_file_without_heartbeats(tmp_path, capsys):
    from distributed_llm_training_benchmark_framework_tpu.telemetry import (
        TelemetryRecorder,
        read_events,
    )

    rec = TelemetryRecorder(
        "arm_ws2_seq8_tierS", results_dir=str(tmp_path), is_main=False,
        rank=1, heartbeat_every_sec=0.0, tokens_per_step=8, total_steps=4,
    )
    rec.begin_phase("init")
    rec.begin_phase("timed")
    rec.step_window(last_step=3, losses=[5.0], window_mean_step_time_sec=0.1)
    rec.close("ok")
    path = tmp_path / "telemetry_arm_ws2_seq8_tierS.rank1.jsonl"
    assert path.exists()
    events = read_events(str(path))
    assert [e["event"] for e in events][-1] == "run_end"
    assert "BENCHMARK_HEARTBEAT" not in capsys.readouterr().out  # rank 0 only


def test_rank_merge_flags_straggler(tmp_path):
    from distributed_llm_training_benchmark_framework_tpu.analysis import (
        telemetry_report as tr,
    )
    from distributed_llm_training_benchmark_framework_tpu.telemetry import (
        TelemetryRecorder,
        rank_telemetry_files,
    )

    for rank, last in ((0, 30), (1, 10)):
        rec = TelemetryRecorder(
            "arm_ws2_seq8_tierS", results_dir=str(tmp_path),
            is_main=rank == 0, rank=rank, heartbeat_every_sec=1e9,
        )
        rec.begin_phase("timed")
        rec.step_window(last_step=last, losses=[5.0],
                        window_mean_step_time_sec=0.1)
        if rank == 0:
            rec.close("ok")
        else:
            rec.abort("preempted")
    canonical = str(tmp_path / "telemetry_arm_ws2_seq8_tierS.jsonl")
    files = rank_telemetry_files(canonical)
    assert sorted(files) == [0, 1] and files[1].endswith(".rank1.jsonl")
    merged = tr.merge_rank_timelines(canonical)
    text = tr.format_rank_merge(merged)
    assert "rank 0" in text and "rank 1" in text
    assert "straggler (20 steps behind)" in text
    assert "aborted: preempted" in text
    # The report discovery treats rank files as siblings, not runs.
    assert [canonical] == tr._discover(str(tmp_path))


# ---------------------------------------------------------------------------
# Validator + regress never-baseline coherence
# ---------------------------------------------------------------------------


def _resharded_row(**over):
    row = {
        "strategy": "fsdp", "world_size": 2, "seq_len": 64, "tier": "S",
        "steps": 100, "per_device_batch": 1, "grad_accum": 1,
        "tokens_per_sec": 1000.0, "mean_step_time_sec": 0.1,
        "mean_loss": 4.0, "peak_vram_gb": 0.5, "h2d_gbps_per_gpu": 0.01,
        "resumed": True, "n_restarts": 1, "resume_step": 50,
        "resume_baseline_loss": 4.2, "resume_geometry_changed": True,
        "loss_first_window": 4.3, "loss_last_window": 3.9,
        "loss_window_steps": 10,
    }
    row.update(over)
    return row


def test_validator_accepts_geometry_changed_resume():
    assert vr.validate_result(_resharded_row(), "r") == []


def test_validator_rejects_geometry_flag_without_resumed():
    fails = vr.validate_result(
        _resharded_row(resumed=False, n_restarts=0, loss_first_window=0.0,
                       loss_last_window=0.0), "r",
    )
    assert any("resume_geometry_changed" in f for f in fails)


def test_geometry_changed_records_never_baseline(tmp_path):
    from distributed_llm_training_benchmark_framework_tpu.regress import (
        store as rstore,
    )

    reg = rstore.Registry(str(tmp_path / "reg"))
    clean = rstore.make_record(
        arm="arm1", result_row=_resharded_row(
            resumed=False, n_restarts=0, resume_geometry_changed=False,
            resume_step=-1, resume_baseline_loss=0.0,
        ),
        status="ok", source="result_arm1.json",
    )
    reg.ingest(clean)
    # Defense in depth: even a row with BROKEN accounting (geometry flag
    # without resumed=true) stays out of the baseline set.
    stitched = rstore.make_record(
        arm="arm1", result_row=_resharded_row(
            tokens_per_sec=4000.0, resumed=False, n_restarts=0,
        ),
        status="ok", source="resharded/result_arm1.json",
    )
    reg.ingest(stitched)
    base = reg.baseline("arm1")
    assert base is not None and base["record_id"] == clean["record_id"]
    assert 4000.0 not in reg.history_values(
        "arm1", metric_name="tokens_per_sec"
    )


# ---------------------------------------------------------------------------
# Real-subprocess acceptance proofs
# ---------------------------------------------------------------------------


def _env():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env.pop("INJECT_FAULT", None)
    return env


def _harness(results, ckpt_dir, *, strategy="fsdp", world_size=4, extra=()):
    return [
        sys.executable, "-u",
        os.path.join(REPO, "benchmarking", "train_harness.py"),
        "--strategy", strategy, "--world-size", str(world_size),
        "--rank", "0", "--tier", "S", "--seq-len", "32", "--steps", "14",
        "--warmup-steps", "2", "--per-device-batch", "1",
        "--grad-accum", "1", "--dataset-size", "64",
        "--sync-every", "2", "--heartbeat-sec", "0",
        "--results-dir", str(results),
        "--checkpoint-dir", str(ckpt_dir), "--checkpoint-every", "4",
        *extra,
    ]


@pytest.fixture(scope="module")
def elastic_round_trip(tmp_path_factory):
    """ISSUE-6 acceptance: die under dp4, resume + train onward under dp2."""
    base = tmp_path_factory.mktemp("elastic_rt")
    results, ckpt_dir = base / "results", base / "ckpt"
    p1 = subprocess.run(
        _harness(results, ckpt_dir, world_size=4,
                 extra=("--inject-fault", "sigkill@9")),
        capture_output=True, text=True, env=_env(), timeout=300,
    )
    p2 = subprocess.run(
        _harness(results, ckpt_dir, world_size=2, extra=("--resume",)),
        capture_output=True, text=True, env=_env(), timeout=300,
    )
    return {"base": base, "p1": p1, "p2": p2}


def test_elastic_resume_trains_onward_under_new_geometry(elastic_round_trip):
    p1, p2 = elastic_round_trip["p1"], elastic_round_trip["p2"]
    results = elastic_round_trip["base"] / "results"
    assert p1.returncode != 0  # SIGKILL'd as injected
    assert p2.returncode == 0, p2.stdout[-3000:] + p2.stderr[-2000:]
    assert "Elastic resume" in p2.stdout  # the reshard path announced itself
    row = json.load(open(results / "result_fsdp_ws2_seq32_tierS.json"))
    assert row["resumed"] is True
    assert row["resume_geometry_changed"] is True
    assert row["n_restarts"] == 1 and row["resume_step"] >= 8
    assert row["world_size"] == 2 and row["tokens_per_sec"] > 0
    path = str(results / "result_fsdp_ws2_seq32_tierS.json")
    failures = vr.validate_result(row, "elastic-row")
    failures += vr.validate_telemetry(path, row, "elastic-row")
    assert failures == [], failures


def test_elastic_resume_telemetry_and_ledger_record_stitch(elastic_round_trip):
    from distributed_llm_training_benchmark_framework_tpu.telemetry import (
        read_events,
    )

    base = elastic_round_trip["base"]
    events = read_events(
        str(base / "results" / "telemetry_fsdp_ws2_seq32_tierS.jsonl")
    )
    (resume,) = [e for e in events if e["event"] == "resume"]
    assert resume["geometry_changed"] is True
    assert resume["source_geometry"]["mesh_axes"]["data"] == 4
    end = [e for e in events if e["event"] == "run_end"]
    assert end and end[0]["resume_geometry_changed"] is True
    ledger = json.load(open(base / "ckpt" / "restarts.json"))
    assert ledger["n_geometry_changes"] == 1
    assert ledger["last_geometry_change"]["from_mesh_axes"]["data"] == 4
    assert ledger["last_geometry_change"]["to_mesh_axes"]["data"] == 2


@pytest.fixture(scope="module")
def multihost_preemption(tmp_path_factory):
    """The multihost dryrun: two ranks rendezvous for real over
    jax.distributed on localhost (each driving its own local mesh);
    SIGTERM is injected on rank 1 ONLY.

    Each rank steps its own mesh, so nothing keeps the two in step the way
    a pod's collectives do, and the protocol assumes that (``coordinate``:
    "boundary-aligned across hosts"). Fourteen steps take a third of a
    second: beside other jobs one rank compiled two seconds later than the
    other, rank 0 had finished before rank 1 reached step 9, and it timed
    out alone at the end-of-run barrier (exit 1). So the run is made longer
    than the fixture's own limit: whichever rank is ahead is still stepping
    when rank 1's SIGTERM fires, the agreed stop is the boundary of the rank
    ahead, and the other walks up to it. Only this fixture's limit decides."""
    base = tmp_path_factory.mktemp("mh_preempt")
    limit_sec = 180
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    env = _env()
    env["PREEMPT_ACK_TIMEOUT_SEC"] = str(limit_sec)
    procs, logs = [], []
    for rank in (0, 1):
        results = base / f"results{rank}"
        ckpt = base / f"ckpt{rank}"
        # To a file: a rank that waits for its peer must never also wait
        # for this fixture to drain a pipe.
        logs.append(open(base / f"rank{rank}.log", "w"))
        procs.append(subprocess.Popen(
            _harness(results, ckpt, strategy="ddp", world_size=1, extra=(
                "--rank", str(rank), "--num-processes", "2",
                "--master-addr", "127.0.0.1", "--master-port", str(port),
                "--inject-fault", "sigterm-rank@9:1",
                "--steps", "40000", "--checkpoint-every", "10000",
                "--heartbeat-sec", "30",
            )),
            stdout=logs[-1], stderr=subprocess.STDOUT, env=env,
        ))
    # Alone, both ranks exit 75 inside half a minute. One shared limit, and
    # no rank left running when it expires.
    deadline = time.monotonic() + limit_sec
    try:
        for p in procs:
            try:
                p.wait(timeout=max(deadline - time.monotonic(), 1))
            except subprocess.TimeoutExpired:
                pass
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
                p.wait()
        for f in logs:
            f.close()
    outs = [(base / f"rank{rank}.log").read_text() for rank in (0, 1)]
    return {"base": base, "rcs": [p.returncode for p in procs], "outs": outs}


def test_nonzero_rank_sigterm_stops_all_hosts_unanimous_75(
    multihost_preemption,
):
    rcs = multihost_preemption["rcs"]
    assert rcs == [faults.EXIT_PREEMPTED, faults.EXIT_PREEMPTED], (
        rcs, multihost_preemption["outs"][0][-2000:],
        multihost_preemption["outs"][1][-2000:],
    )


def test_rank0_commits_coherent_emergency_checkpoint(multihost_preemption):
    from distributed_llm_training_benchmark_framework_tpu.telemetry import (
        read_events,
    )

    base = multihost_preemption["base"]
    events0 = read_events(
        str(base / "results0" / "telemetry_ddp_ws1_seq32_tierS.jsonl")
    )
    (aborted0,) = [e for e in events0 if e["event"] == "run_aborted"]
    assert aborted0["reason"] == "preempted"
    # Rank 0 never received a signal — the broadcast stopped it — and its
    # emergency checkpoint committed at the agreed boundary.
    steps0 = [int(d) for d in os.listdir(base / "ckpt0") if d.isdigit()]
    assert steps0, "rank 0 committed no emergency checkpoint"
    events1 = read_events(
        str(base / "results1" / "telemetry_ddp_ws1_seq32_tierS.rank1.jsonl")
    )
    (aborted1,) = [e for e in events1 if e["event"] == "run_aborted"]
    assert aborted1["reason"] == "preempted"
    # Coherence: both ranks stopped at the SAME agreed boundary step.
    assert aborted0["last_step"] == aborted1["last_step"]


def test_preempted_nonzero_rank_visible_in_rank_telemetry(
    multihost_preemption,
):
    from distributed_llm_training_benchmark_framework_tpu.telemetry import (
        read_events,
    )

    base = multihost_preemption["base"]
    events1 = read_events(
        str(base / "results1" / "telemetry_ddp_ws1_seq32_tierS.rank1.jsonl")
    )
    injected = [e for e in events1 if e["event"] == "fault_injected"]
    assert injected and injected[0]["fault"] == "sigterm-rank@9:1"
    meta = [e for e in events1 if e["event"] == "run_meta"]
    assert meta and meta[0]["rank"] == 1


@pytest.fixture(scope="module")
def async_preemption(tmp_path_factory):
    """--checkpoint-async + sigterm: the emergency path flushes the
    in-flight delta instead of writing a fresh full save."""
    base = tmp_path_factory.mktemp("async_rt")
    results, ckpt_dir = base / "results", base / "ckpt"
    p1 = subprocess.run(
        _harness(results, ckpt_dir, strategy="ddp", world_size=1,
                 extra=("--checkpoint-async", "--inject-fault", "sigterm@9")),
        capture_output=True, text=True, env=_env(), timeout=300,
    )
    return {"base": base, "p1": p1}


def test_async_emergency_flushes_delta_only(async_preemption):
    from distributed_llm_training_benchmark_framework_tpu.telemetry import (
        read_events,
    )

    p1 = async_preemption["p1"]
    base = async_preemption["base"]
    assert p1.returncode == faults.EXIT_PREEMPTED, p1.stdout[-3000:]
    assert "async dispatch" in p1.stdout  # periodic saves left the timed path
    assert "Emergency flush" in p1.stdout
    events = read_events(
        str(base / "results" / "telemetry_ddp_ws1_seq32_tierS.jsonl")
    )
    (flush,) = [e for e in events if e["event"] == "emergency_flush"]
    assert flush["mode"] == "async-delta"
    assert flush["committed_step"] is not None
    assert flush["committed_step"] <= flush["step"]
    (aborted,) = [e for e in events if e["event"] == "run_aborted"]
    assert aborted["reason"] == "preempted"
    # The flushed step is digest-certified and resumable.
    from distributed_llm_training_benchmark_framework_tpu.runtime.checkpoint import (
        BenchmarkCheckpointer,
    )

    ck = BenchmarkCheckpointer(str(base / "ckpt"))
    assert ck.validate_step(flush["committed_step"])[0] == "ok"
    ck.close()


# ---------------------------------------------------------------------------
# Wiring pins: chaos suite, suite gate, k8s knobs, bench flags
# ---------------------------------------------------------------------------


def test_chaos_suite_gains_elastic_and_multihost_arms():
    text = open(os.path.join(REPO, "scripts", "chaos_suite.sh")).read()
    assert "--elastic" in text and "elastic)" in text
    assert "sigterm-rank" in text
    assert "--k8s-chaos" in text and "k8s-coordinator)" in text
    assert "resume_geometry_changed" in text


def test_run_all_smoke_gate_includes_elastic():
    text = open(os.path.join(REPO, "scripts", "run_all_benchmarks.sh")).read()
    assert "chaos_suite.sh --smoke --elastic" in text
    assert "SKIP_CHAOS" in text  # the escape hatch survives


def test_k8s_template_and_launcher_carry_checkpoint_knobs():
    tpl = open(os.path.join(REPO, "k8s", "job-benchmark.template.yaml")).read()
    for var in ("{{CHECKPOINT_DIR}}", "{{CHECKPOINT_EVERY}}",
                "{{CHECKPOINT_ASYNC}}"):
        assert var in tpl
    launch = open(os.path.join(REPO, "scripts", "launch_multi.sh")).read()
    for flag in ("--checkpoint-dir", "--checkpoint-every",
                 "--checkpoint-async"):
        assert flag in launch
    for var in ("{{CHECKPOINT_DIR}}", "{{CHECKPOINT_EVERY}}",
                "{{CHECKPOINT_ASYNC}}"):
        assert var in launch  # sed fill — no live {{VAR}} left in manifests


def test_bench_and_harness_expose_checkpoint_async():
    from distributed_llm_training_benchmark_framework_tpu.train.harness import (
        build_parser,
    )

    flags = set()
    for action in build_parser()._actions:
        flags.update(action.option_strings)
    assert "--checkpoint-async" in flags
    bench = open(os.path.join(REPO, "bench.py")).read()
    assert "--checkpoint-async" in bench
