"""The bench.py stdout contract: one JSON line, legacy keys + flagship.

``bench.py`` is the repo's headline emitter — the one line outside tooling
parses. Round 6 added the flagship sub-object (the llama arm measured at
its swept b2 x accum2 geometry, docs/PERFORMANCE.md §16) to the default
invocation; these CPU smoke runs (tier S, 3 steps) pin the contract shape:

- exactly ONE line on stdout, valid JSON (progress goes to stderr);
- the legacy contract keys (metric/value/unit/vs_baseline) unchanged in
  name and semantics;
- the additive ``flagship`` sub-object present by default, carrying the
  llama arm's throughput/MFU/peak-HBM with run-identity provenance;
- ``--model-family llama`` promotes the family to the top-level metric
  (and, being the flagship family itself, emits no duplicate sub-object);
- every row names the platform, device kind and device count it ran on;
- the process starts no child (one process per chip: a child that needed
  the chip its parent holds would fail or hang), and refuses to run when
  no TPU came up and the CPU was not asked for by name.
"""

import json
import os
import subprocess
import sys
import tempfile

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "bench.py")

SMOKE_ARGS = [
    "--tier", "S", "--seq-len", "64", "--steps", "3",
    "--warmup-steps", "1", "--world-size", "1",
]


# Runs bench.py as __main__ under an audit hook that turns any attempt to
# start a child process, anywhere in the process, into a failure.
NO_CHILD_RUNNER = """
import runpy, sys

def _refuse_children(event, args):
    if event in ("subprocess.Popen", "os.fork", "os.forkpty",
                 "os.posix_spawn", "os.system", "os.exec", "os.spawn"):
        raise RuntimeError(f"bench.py started a child process: {event}")

sys.addaudithook(_refuse_children)
sys.argv = sys.argv[1:]
runpy.run_path(sys.argv[0], run_name="__main__")
"""


def run_bench(*extra, platforms="cpu", check=True):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = platforms
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
    # Hermeticity: bench.py auto-ingests its rows into the regress
    # registry when one exists (the repo ships a seeded results/registry)
    # — point it at a throwaway root so smoke runs never append test
    # records to the committed history. The registry behavior itself is
    # covered by tests/test_regress.py.
    env["REGRESS_REGISTRY"] = tempfile.mkdtemp(prefix="bench_registry_")
    proc = subprocess.run(
        [sys.executable, "-c", NO_CHILD_RUNNER, BENCH, *SMOKE_ARGS, *extra],
        capture_output=True, text=True, env=env, timeout=900, cwd=REPO,
    )
    if check:
        assert proc.returncode == 0, proc.stderr[-4000:]
    return proc


@pytest.fixture(scope="module")
def default_run():
    return run_bench()


def test_stdout_is_exactly_one_json_line(default_run):
    lines = [l for l in default_run.stdout.splitlines() if l.strip()]
    assert len(lines) == 1, default_run.stdout
    json.loads(lines[0])  # must parse


def test_legacy_contract_keys_unchanged(default_run):
    r = json.loads(default_run.stdout)
    # Names AND semantics: the metric string scheme, a positive per-chip
    # throughput, the unit literal, and vs_baseline = value / the
    # reference's best per-GPU number.
    assert r["metric"] == "tinygpt_tierS_seq64_tokens_per_sec_per_chip"
    assert r["unit"] == "tokens/sec/chip"
    assert r["value"] > 0
    assert r["vs_baseline"] == pytest.approx(r["value"] / 4536.75, rel=1e-2)


def test_flagship_subobject_present_with_expected_keys(default_run):
    r = json.loads(default_run.stdout)
    f = r["flagship"]
    for key in (
        "metric", "value", "unit", "vs_baseline", "model_family", "strategy",
        "tier", "seq_len", "per_device_batch", "grad_accum", "layer_loop",
        "attention_impl", "dropout", "mfu_pct", "peak_hbm_gb",
        "peak_hbm_method",
    ):
        assert key in f, key
    assert f["metric"] == "llama_tierS_seq64_tokens_per_sec_per_chip"
    assert f["value"] > 0
    # The flagship arm's swept run-identity (docs/PERFORMANCE.md §16):
    # llama family, per-device batch 2 x grad-accum 2, unrolled layers,
    # the family's native dropout-free semantics.
    assert f["model_family"] == "llama"
    assert f["per_device_batch"] == 2
    assert f["grad_accum"] == 2
    assert f["layer_loop"] == "unrolled"
    assert f["dropout"] == 0.0


def test_llama_as_top_level_family():
    proc = run_bench("--model-family", "llama")
    lines = [l for l in proc.stdout.splitlines() if l.strip()]
    assert len(lines) == 1
    r = json.loads(lines[0])
    assert r["metric"] == "llama_tierS_seq64_tokens_per_sec_per_chip"
    assert r["value"] > 0
    # The top-level row IS the flagship family: no duplicate sub-object
    # under --flagship auto.
    assert "flagship" not in r


def test_every_row_names_platform_device_kind_and_count(default_run):
    r = json.loads(default_run.stdout)
    for row in (r, r["flagship"]):
        # This run asked for the CPU by name; on the chip the same keys
        # read "tpu" / "TPU v5 lite" / the chip count.
        assert row["platform"] == "cpu"
        assert row["device_kind"] == "cpu"
        assert row["device_count"] == 1


def test_bench_starts_no_child_process(default_run):
    """default_run ran both arms under NO_CHILD_RUNNER's audit hook, which
    raises on any spawn/fork/exec: exit 0 means none was attempted. The
    graftcheck preflight that used to be bench.py's first act is gone."""
    assert default_run.returncode == 0
    src = open(BENCH).read()
    assert "subprocess" not in src and "preflight" not in src


def test_no_tpu_and_cpu_not_asked_for_is_an_error():
    """The silent case: no chip found, run carries on and prints a CPU
    number. With JAX_PLATFORMS unset jax falls back to the CPU here, and
    bench.py must fail before measuring, printing no row."""
    proc = run_bench(platforms="", check=False)
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr
    assert not proc.stdout.strip()
