"""chip_smoke.py at tier S on the CPU: its control flow, its last line, and
that it reports nothing off a TPU.

The script proves the TPU path and has no option to run anywhere else, so
what has to differ here is steered from this side: the module constants that
say what a passing run is held to (platform, kernel mode, sizes, output
directory), and a table row giving the CPU a peak so an MFU exists. This is
rehearsal 1 and 2 of the on-chip-measurement guide (tiny CPU run; virtual
devices for the four-chip phase) — it says nothing about the chip.
"""

import importlib.util
import json
import os
import shutil
import subprocess
import sys

import pytest

from distributed_llm_training_benchmark_framework_tpu.utils import (
    platform as platform_mod,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


@pytest.fixture
def smoke(monkeypatch, tmp_path):
    spec = importlib.util.spec_from_file_location("chip_smoke", SMOKE)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    for name, value in dict(
        PLATFORM="cpu", INTERPRET=True, TIER="S", SEQ_LEN=64,
        KERNEL_SHAPE=(1, 64, 2, 16), STEPS=12, WARMUP_STEPS=2,
        OUT_DIR=str(tmp_path / "out"),
    ).items():
        monkeypatch.setattr(mod, name, value)
    monkeypatch.setitem(
        platform_mod.CHIP_SPECS, "cpu",
        platform_mod.ChipSpec(1.0, 100.0, 64.0, None),
    )
    return mod


@pytest.mark.parametrize("flags", [[], ["--four-chips"]], ids=["one", "four"])
def test_tiny_run_ends_in_the_contract_line(smoke, capsys, flags):
    assert smoke.main(flags) == 0
    lines = capsys.readouterr().out.strip().splitlines()
    last = json.loads(lines[-1])
    # Exactly the contract's keys, with the device as jax reports it.
    assert last == {
        "ok": True,
        "device": {"platform": "cpu", "kind": "cpu", "count": 8},
    }
    assert sum('"ok"' in line for line in lines) == 1
    body = "\n".join(lines[:-1])
    for needle in ("tokens/s/chip", "step time median", "MFU", "peak HBM",
                   "compile", "cache dir", "stopped after the loss fetch"):
        assert needle in body, needle
    if flags:
        assert "four-chips[fsdp_dp4] parity mean_loss" in body
        assert "four-chips[ring_sp4] parity mean_loss" in body
        assert "kernels[" not in body  # that phase and no other
    else:
        assert "kernels[pallas-bwd]" in body
    # It wrote under its output directory only.
    assert os.listdir(smoke.OUT_DIR)


def test_reports_nothing_off_a_tpu(tmp_path):
    """Unsteered, on this CPU: non-zero exit and no result line — from the
    checkout, and from a directory holding chip_smoke.py and nothing else of
    the repo."""
    alone = tmp_path / "alone"
    alone.mkdir()
    shutil.copy(SMOKE, alone / "chip_smoke.py")
    for cwd in (REPO, str(alone)):
        proc = subprocess.run(
            [sys.executable, "chip_smoke.py"], cwd=cwd, timeout=300,
            capture_output=True, text=True,
            env=dict(os.environ, JAX_PLATFORMS="cpu"),
        )
        assert proc.returncode != 0, cwd
        assert proc.stdout.strip() == "", proc.stdout
