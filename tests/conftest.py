"""Test harness: every test runs on a virtual 8-device CPU mesh.

This is the affordance the reference lacks entirely (SURVEY §4: no tests, and
multi-node behavior is untestable without a GPU cluster). With JAX,
``--xla_force_host_platform_device_count=8`` makes every parallelism arm a
real multi-device program on CPU, so DDP/FSDP/ZeRO sharding, collectives and
loss parity are all unit-testable hermetically.

Must run before ``import jax`` — hence module-level os.environ mutation here.
"""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ.setdefault("JAX_ENABLE_X64", "0")
# Keep tests (and the subprocesses they start, which inherit this) off the
# persistent compile cache the entry points place (utils.platform
# .enable_compile_cache): CPU test programs would fill it, and compiles for
# a described chip (tests/test_tpu_compile.py) write entries that cannot be
# read back without one.
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "0"

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO_ROOT not in sys.path:
    sys.path.insert(0, REPO_ROOT)

import jax  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture(scope="session")
def eight_devices():
    devs = jax.devices()
    assert len(devs) == 8, f"expected 8 virtual CPU devices, got {len(devs)}"
    return devs


@pytest.fixture(scope="module", autouse=True)
def _clear_jax_caches_per_module():
    """Drop JAX's in-process compilation caches after each test module.

    The full suite compiles hundreds of multi-device CPU executables in one
    process; without this, accumulation eventually aborts XLA:CPU deep into
    the run (observed as a message-less ``Fatal Python error: Aborted``
    inside an array fetch around test ~230 of 234 — the same tests pass in
    any smaller grouping). Clearing per module bounds the growth; the cost
    is only cross-module recompiles, which are rare (modules share little
    beyond tiny helpers).
    """
    yield
    try:
        jax.clear_caches()
    except Exception:
        pass
