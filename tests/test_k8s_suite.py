"""Hermetic L4 test: the k8s suite path with a fake kubectl.

The reference's suite logic was only ever exercised against a live cluster;
here a stub ``kubectl`` on PATH records every invocation and plays back
canned pod logs (with the stdout marker protocol), so the launch -> wait ->
collect -> delete -> analyze flow runs end to end with no cluster.

Regression anchor: round-1 verdict found the k8s mode collected every run as
job ``tpu-bench`` into the same ``tpu-bench_results/result.json`` — each
matrix run overwrote the previous one. Unique job names per (strategy, ws)
fix it; these tests pin that.
"""

import json
import os
import stat
import subprocess
import textwrap

import pytest

pytestmark = pytest.mark.slow

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

FAKE_KUBECTL = r'''#!/usr/bin/env python3
"""Stub kubectl: records argv; plays back canned logs per job name."""
import json, os, re, sys

argv = sys.argv[1:]
logdir = os.environ["FAKE_KUBECTL_DIR"]
with open(os.path.join(logdir, "calls.log"), "a") as f:
    f.write(json.dumps(argv) + "\n")

def arg_after(flag):
    return argv[argv.index(flag) + 1] if flag in argv else None

if "apply" in argv:
    if "-" in argv:  # manifest on stdin: keep it for assertions
        manifest = sys.stdin.read()
        m = re.search(r"name: (tpu-bench[\w-]*)", manifest)
        name = m.group(1) if m else "unknown"
        with open(os.path.join(logdir, f"manifest_{name}.yaml"), "w") as f:
            f.write(manifest)
    print("applied")
    sys.exit(0)

if "wait" in argv:
    sys.exit(0)  # job "completed"

if "get" in argv and "pods" in argv:
    sel = arg_after("-l") or ""
    job = sel.split("=", 1)[1]
    print(f"{job}-0", end="")
    sys.exit(0)

if "get" in argv and "pod" in argv:
    print("Succeeded", end="")
    sys.exit(0)

if "logs" in argv:
    pod = argv[-1]
    m = re.match(r"tpu-bench-(\w+)-ws(\d+)(?:-([\w-]+?))?-0$", pod)
    if m is None:
        # e.g. the failure-diagnostic call `kubectl logs -l job-name=... --tail=100`
        sys.exit(0)
    strategy, ws, comp = m.group(1), int(m.group(2)), m.group(3) or ""
    result = {
        "strategy": strategy, "world_size": ws, "rank": 0, "seq_len": 128,
        "tier": "S", "steps": 6, "per_device_batch": 1, "grad_accum": 1,
        "tokens_per_sec": 1000.0 * ws, "mean_step_time_sec": 0.128,
        "mean_loss": 6.0, "peak_vram_gb": 1.0, "h2d_gbps_per_gpu": 1e-5,
    }
    # Composition jobs carry their axes in result.json (the harness writes
    # them; the analyzer keys run identity on them).
    if comp == "tp2":
        result["tensor_parallel"] = 2
    elif comp.startswith("pp2-"):
        result.update(pipeline_parallel=2, pipeline_schedule=comp[4:])
    elif comp.startswith("sp2-"):
        att = comp[4:]
        if att.endswith("-nozz"):
            att = att[:-len("-nozz")]
            result["ring_zigzag"] = "off"
        if att.endswith("-causal"):
            att = att[:-len("-causal")]
            result["causal"] = True
        result.update(sequence_parallel=2, attention_impl=att)
    elif comp == "moe-ep2":
        result.update(expert_parallel=2, n_experts=4)
    elif comp == "moe8-ep2":
        result.update(expert_parallel=2, n_experts=8)
    elif comp == "llama-tp2":
        result.update(tensor_parallel=2, model_family="llama", causal=True)
    elif comp == "llama-tp2-ddp":
        result.update(tensor_parallel=2, model_family="llama", causal=True)
    elif comp == "llama-tp2-cmm":
        # The A/B partner differs from llama-tp2-ddp ONLY in the fusion
        # knob — exactly the axis parse_metrics' dedup key must keep.
        result.update(tensor_parallel=2, model_family="llama", causal=True,
                      tp_collective_matmul=True)
    elif comp == "llama-flagship":
        result.update(model_family="llama", causal=True, per_device_batch=2,
                      grad_accum=2, attention_impl="flash")
    print("boot log line")
    print("BENCHMARK_RESULT_JSON_START")
    print(json.dumps(result, indent=2))
    print("BENCHMARK_RESULT_JSON_END")
    sys.exit(0)

if "delete" in argv:
    print("deleted")
    sys.exit(0)

sys.exit(0)
'''


@pytest.fixture(scope="module")
def suite_run(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("k8s_suite")
    bindir = tmp / "bin"
    bindir.mkdir()
    kubectl = bindir / "kubectl"
    kubectl.write_text(FAKE_KUBECTL)
    kubectl.chmod(kubectl.stat().st_mode | stat.S_IEXEC)
    results = tmp / "results"
    env = dict(os.environ)
    env["PATH"] = f"{bindir}:{env['PATH']}"
    env["FAKE_KUBECTL_DIR"] = str(tmp)
    env["RESULTS_DIR"] = str(results)
    env["STRATEGIES"] = "ddp zero2"
    env["WORLD_SIZES"] = "2 4"
    env["TIER"] = "S"
    env["SEQ_LEN"] = "128"
    env["STEPS"] = "6"
    # These tests pin the PURE-matrix contract (4 jobs, exact names); the
    # auto-appended composition roster has its own fixture below.
    env["COMPOSITIONS"] = "off"
    proc = subprocess.run(
        ["bash", os.path.join(REPO, "scripts", "run_all_benchmarks.sh"), "--k8s"],
        capture_output=True, text=True, env=env, timeout=300,
    )
    return proc, tmp, results


def test_suite_exits_zero(suite_run):
    proc, _, _ = suite_run
    assert proc.returncode == 0, proc.stdout[-4000:] + proc.stderr[-2000:]
    assert "4 passed, 0 failed" in proc.stdout


def test_every_run_collected_distinctly(suite_run):
    _, _, results = suite_run
    # The round-1 bug: all four runs collapsed into one tpu-bench_results dir.
    dirs = sorted(d for d in os.listdir(results) if d.endswith("_results"))
    assert dirs == [
        "tpu-bench-ddp-ws2_results", "tpu-bench-ddp-ws4_results",
        "tpu-bench-zero2-ws2_results", "tpu-bench-zero2-ws4_results",
    ]
    seen = set()
    for d in dirs:
        r = json.loads((results / d / "result.json").read_text())
        seen.add((r["strategy"], r["world_size"]))
    assert seen == {("ddp", 2), ("ddp", 4), ("zero2", 2), ("zero2", 4)}


def test_manifests_have_unique_job_names_and_dns(suite_run):
    _, tmp, _ = suite_run
    manifests = sorted(f for f in os.listdir(tmp) if f.startswith("manifest_"))
    assert len(manifests) == 4, manifests
    m = (tmp / "manifest_tpu-bench-zero2-ws4.yaml").read_text()
    assert "name: tpu-bench-zero2-ws4" in m
    # Coordinator DNS follows the job name; subdomain stays on the one
    # headless service.
    assert "tpu-bench-zero2-ws4-0.tpu-bench.bench.svc.cluster.local" in m
    assert "subdomain: tpu-bench" in m
    # Every placeholder substituted (comment lines mention "{{VAR}}" legally).
    live = "\n".join(l for l in m.splitlines() if not l.lstrip().startswith("#"))
    assert "{{" not in live


def test_jobs_waited_and_deleted_by_name(suite_run):
    _, tmp, _ = suite_run
    calls = [json.loads(l) for l in (tmp / "calls.log").read_text().splitlines()]
    waits = [c for c in calls if "wait" in c]
    deletes = [c for c in calls if "delete" in c and "job" in c]
    wait_jobs = {a for c in waits for a in c if a.startswith("job/")}
    assert wait_jobs == {
        "job/tpu-bench-ddp-ws2", "job/tpu-bench-ddp-ws4",
        "job/tpu-bench-zero2-ws2", "job/tpu-bench-zero2-ws4",
    }
    deleted = {c[c.index("job") + 1] for c in deletes}
    assert deleted == {
        "tpu-bench-ddp-ws2", "tpu-bench-ddp-ws4",
        "tpu-bench-zero2-ws2", "tpu-bench-zero2-ws4",
    }


def test_metrics_csv_has_one_row_per_run(suite_run):
    _, _, results = suite_run
    import pandas as pd

    df = pd.read_csv(results / "summary" / "metrics.csv")
    assert len(df) == 4
    assert set(zip(df.strategy, df.world_size)) == {
        ("ddp", 2), ("ddp", 4), ("zero2", 2), ("zero2", 4),
    }


COMP_JOBS = {
    "tpu-bench-ddp-ws4-tp2",
    "tpu-bench-ddp-ws4-pp2-gpipe",
    "tpu-bench-ddp-ws4-pp2-1f1b",
    "tpu-bench-ddp-ws4-pp2-interleaved",
    "tpu-bench-zero2-ws4-sp2-ring",
    "tpu-bench-zero2-ws4-sp2-ring-causal",
    "tpu-bench-zero2-ws4-sp2-ring-causal-nozz",
    "tpu-bench-zero2-ws4-sp2-ulysses",
    "tpu-bench-zero2-ws4-moe-ep2",
    "tpu-bench-zero2-ws4-moe8-ep2",
    "tpu-bench-fsdp-ws4-llama-tp2",
    "tpu-bench-ddp-ws4-llama-tp2-ddp",
    "tpu-bench-ddp-ws4-llama-tp2-cmm",
    "tpu-bench-zero2-ws4-llama-flagship",
}


@pytest.fixture(scope="module")
def roster_run(tmp_path_factory):
    """k8s suite with COMPOSITIONS=only: the auto-appended extended-axis
    roster (reference parity: its suite hard-codes the complete matrix;
    ours extends it with tp/pp/sp/ep arms at the widest world size)."""
    tmp = tmp_path_factory.mktemp("k8s_roster")
    bindir = tmp / "bin"
    bindir.mkdir()
    kubectl = bindir / "kubectl"
    kubectl.write_text(FAKE_KUBECTL)
    kubectl.chmod(kubectl.stat().st_mode | stat.S_IEXEC)
    results = tmp / "results"
    env = dict(os.environ)
    env["PATH"] = f"{bindir}:{env['PATH']}"
    env["FAKE_KUBECTL_DIR"] = str(tmp)
    env["RESULTS_DIR"] = str(results)
    env["STRATEGIES"] = "ddp zero2"
    env["WORLD_SIZES"] = "4"
    env["TIER"] = "S"
    env["SEQ_LEN"] = "128"
    env["STEPS"] = "6"
    env["COMPOSITIONS"] = "only"
    proc = subprocess.run(
        ["bash", os.path.join(REPO, "scripts", "run_all_benchmarks.sh"), "--k8s"],
        capture_output=True, text=True, env=env, timeout=300,
    )
    return proc, tmp, results


def test_roster_exits_zero_with_fourteen_arms(roster_run):
    proc, _, _ = roster_run
    assert proc.returncode == 0, proc.stdout[-4000:] + proc.stderr[-2000:]
    assert "14 passed, 0 failed" in proc.stdout


def test_roster_job_names_and_manifest_env(roster_run):
    _, tmp, _ = roster_run
    manifests = {
        f[len("manifest_"):-len(".yaml")]
        for f in os.listdir(tmp) if f.startswith("manifest_")
    }
    assert manifests == COMP_JOBS, manifests
    # Extended-axis env vars reach the pod spec substituted, so
    # docker/entrypoint.sh turns them into harness flags.
    tp = (tmp / "manifest_tpu-bench-ddp-ws4-tp2.yaml").read_text()
    assert 'name: TENSOR_PARALLEL\n              value: "2"' in tp
    il = (tmp / "manifest_tpu-bench-ddp-ws4-pp2-interleaved.yaml").read_text()
    assert 'name: PIPELINE_PARALLEL\n              value: "2"' in il
    assert 'name: PIPELINE_SCHEDULE\n              value: "interleaved"' in il
    assert 'name: VIRTUAL_STAGES\n              value: "1"' in il  # tier S
    ring = (tmp / "manifest_tpu-bench-zero2-ws4-sp2-ring.yaml").read_text()
    assert 'name: SEQUENCE_PARALLEL\n              value: "2"' in ring
    assert 'name: ATTENTION\n              value: "ring"' in ring
    assert 'name: CAUSAL\n              value: "0"' in ring
    lm = (tmp / "manifest_tpu-bench-fsdp-ws4-llama-tp2.yaml").read_text()
    assert 'name: MODEL_FAMILY\n              value: "llama"' in lm
    assert 'name: TENSOR_PARALLEL\n              value: "2"' in lm
    zz = (tmp / "manifest_tpu-bench-zero2-ws4-sp2-ring-causal.yaml").read_text()
    assert 'name: CAUSAL\n              value: "1"' in zz
    assert 'name: RING_ZIGZAG\n              value: "auto"' in zz
    nozz = (tmp / "manifest_tpu-bench-zero2-ws4-sp2-ring-causal-nozz.yaml").read_text()
    assert 'name: RING_ZIGZAG\n              value: "off"' in nozz
    # The llama-flagship arm carries its swept geometry (bench.py flagship
    # sub-object config, docs/PERFORMANCE.md §16) into the pod env.
    cmm = (tmp / "manifest_tpu-bench-ddp-ws4-llama-tp2-cmm.yaml").read_text()
    assert 'name: MODEL_FAMILY\n              value: "llama"' in cmm
    assert 'name: TENSOR_PARALLEL\n              value: "2"' in cmm
    assert 'name: TP_COLLECTIVE_MATMUL\n              value: "1"' in cmm
    # ...and its A/B partner — same ddp strategy, same llama tp2 geometry,
    # fusion OFF — so the pair differs ONLY in --tp-collective-matmul.
    ab = (tmp / "manifest_tpu-bench-ddp-ws4-llama-tp2-ddp.yaml").read_text()
    assert 'name: MODEL_FAMILY\n              value: "llama"' in ab
    assert 'name: TENSOR_PARALLEL\n              value: "2"' in ab
    assert 'name: TP_COLLECTIVE_MATMUL\n              value: "0"' in ab
    assert 'name: TP_COLLECTIVE_MATMUL\n              value: "0"' in lm
    fl = (tmp / "manifest_tpu-bench-zero2-ws4-llama-flagship.yaml").read_text()
    assert 'name: MODEL_FAMILY\n              value: "llama"' in fl
    assert 'name: PER_DEVICE_BATCH\n              value: "2"' in fl
    assert 'name: GRAD_ACCUM\n              value: "2"' in fl
    assert 'name: LAYER_LOOP\n              value: "unrolled"' in fl
    assert 'name: ATTENTION\n              value: "flash"' in fl
    moe = (tmp / "manifest_tpu-bench-zero2-ws4-moe-ep2.yaml").read_text()
    assert 'name: NUM_EXPERTS\n              value: "4"' in moe
    assert 'name: EXPERT_PARALLEL\n              value: "2"' in moe
    for f in manifests:
        live = "\n".join(
            l for l in (tmp / f"manifest_{f}.yaml").read_text().splitlines()
            if not l.lstrip().startswith("#")
        )
        assert "{{" not in live, f


def test_roster_rows_survive_dedup(roster_run):
    _, _, results = roster_run
    import pandas as pd

    df = pd.read_csv(results / "summary" / "metrics.csv")
    # 14 composition runs, all (strategy, ws)-colliding pairs kept distinct
    # by the composition axes in the identity key (sp2-ring vs
    # sp2-ring-causal collide on everything except the causal column; the
    # zigzag A/B pair only on ring_zigzag; the two MoE arms only on
    # n_experts; the llama arms on model_family + tensor_parallel and on
    # the flagship's batch geometry + attention impl).
    assert len(df) == 14, df
