"""The device a run is on: the TPU requirement, the per-chip hardware table,
the compile cache's place, and the XLA scheduler flag set.

jax reads ``JAX_PLATFORMS`` itself; nothing here selects a platform. What
this module adds is the other direction: a measured entry point
(``bench.py``, ``train/harness.py``) calls :func:`require_tpu` so that a run
which found no chip fails instead of measuring the CPU; every row they
print names its platform, device kind and device count.

It is also the one place hardware numbers live (:data:`CHIP_SPECS`): bf16
peak, HBM bandwidth and capacity, list price — keyed by the exact
``device_kind`` jax reports. ``utils.flops`` (MFU, tokens/$) and
``utils.memory`` (capacity checks) read it through :func:`chip_spec`; the
step-anatomy roofline reads :func:`device_peak_flops` and
:func:`device_peak_hbm_gbps`. A TPU kind that is not in the table is an
error, never a neighbouring generation's number.
"""

from __future__ import annotations

import dataclasses
import os
import re
from typing import Dict, Optional


@dataclasses.dataclass(frozen=True)
class ChipSpec:
    """Public per-chip numbers for one TPU generation."""

    bf16_tflops: float  # peak dense bf16 TFLOP/s
    hbm_gbps: float  # HBM bandwidth, GB/s (decimal)
    hbm_gib: float  # HBM capacity, GiB
    usd_per_chip_hour: Optional[float]  # on-demand US list price (mid-2025)


_V5E = ChipSpec(197.0, 819.0, 16.0, 1.20)
_V5P = ChipSpec(459.0, 2765.0, 95.0, 4.20)
_V6E = ChipSpec(918.0, 1640.0, 32.0, 2.70)

#: Keyed by ``jax.Device.device_kind``, exactly as the runtime spells it
#: (jax's own list: ``jax/_src/pallas/mosaic/tpu_info.py``; one generation
#: can report under two names). Peaks and capacities are Google Cloud's
#: published per-chip numbers ("TPU v5e": 197 TFLOP/s bf16, 16 GB HBM at
#: 819 GB/s); prices are the Cloud TPU on-demand page.
CHIP_SPECS: Dict[str, ChipSpec] = {
    "TPU v2": ChipSpec(45.0, 700.0, 8.0, 1.125),
    "TPU v3": ChipSpec(123.0, 900.0, 16.0, 2.00),
    "TPU v4": ChipSpec(275.0, 1228.0, 32.0, 3.22),
    "TPU v4 lite": ChipSpec(138.0, 614.0, 8.0, None),
    "TPU v5 lite": _V5E,  # what a v5e chip reports
    "TPU v5e": _V5E,
    "TPU v5": _V5P,
    "TPU v5p": _V5P,
    "TPU v6 lite": _V6E,
    "TPU v6e": _V6E,
}


def chip_spec(device_kind: str) -> Optional[ChipSpec]:
    """The table row for ``device_kind``; None off a TPU (CPU hosts).

    A kind that names a TPU but is not in the table raises: a peak guessed
    from a neighbouring generation turns into a wrong MFU nobody questions.
    """
    spec = CHIP_SPECS.get(device_kind)
    if spec is None and device_kind.upper().startswith("TPU"):
        raise ValueError(
            f"unknown TPU device_kind {device_kind!r}: add its published "
            "peaks to utils.platform.CHIP_SPECS (known: "
            f"{', '.join(sorted(CHIP_SPECS))})"
        )
    return spec


def device_peak_hbm_gbps(device_kind: str) -> Optional[float]:
    """HBM GB/s peak (the roofline's memory axis), or None off a TPU."""
    spec = chip_spec(device_kind)
    return spec.hbm_gbps if spec else None


def device_peak_flops(device_kind: str) -> Optional[float]:
    """bf16 peak FLOP/s per chip (the roofline's compute axis), or None."""
    spec = chip_spec(device_kind)
    return spec.bf16_tflops * 1e12 if spec else None


def require_tpu() -> None:
    """Fail unless the backend that came up is a TPU — or the CPU was asked
    for by name.

    The measured entry points call this before any work. ``JAX_PLATFORMS``
    naming ``cpu`` is the tests' mode (``tests/conftest.py``) and a stated
    choice; what this refuses is the silent case, where no chip was found
    and the run would carry on and print a CPU number in a TPU benchmark.
    """
    import jax

    platform = jax.devices()[0].platform
    asked = os.environ.get("JAX_PLATFORMS", "").lower().split(",")
    if platform == "tpu" or "cpu" in asked:
        return
    raise RuntimeError(
        f"no TPU: jax came up on {platform!r}. This program measures a TPU; "
        "run it on the chip, or set JAX_PLATFORMS=cpu to run on the CPU on "
        "purpose (tests and dry runs — never a device measurement)"
    )


#: Where the persistent compile cache goes when the environment does not
#: say: a fixed path inside the checkout (the path is part of the cache key
#: on some runtimes, so never a temp name, pid or time). Git-ignored.
_DEFAULT_COMPILE_CACHE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache",
)


def enable_compile_cache() -> str:
    """Place jax's persistent compilation cache; return its directory.

    Called by the entry points before the first compile. Where
    ``JAX_COMPILATION_CACHE_DIR`` is set jax already reads it, and no
    directory is set in code; otherwise the cache lives at
    :data:`_DEFAULT_COMPILE_CACHE`. jax keeps programs that took over a
    second to compile, which is the train step (tens of seconds cold at
    tier A) and not the small set-up programs.
    """
    import jax

    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not env_dir:
        jax.config.update("jax_compilation_cache_dir", _DEFAULT_COMPILE_CACHE)
    return env_dir or _DEFAULT_COMPILE_CACHE


# ---------------------------------------------------------------------------
# Latency-hiding scheduler flags (round 8, overlap work)
# ---------------------------------------------------------------------------

#: XLA flags that turn on the latency-hiding scheduler + async collectives
#: on TPU — the compiler half of the zero2 per-block reduce-scatter overlap
#: (the model half is tinygpt.block_grad_spec). One canonical tuple so the
#: harness (--xla-latency-hiding), the entrypoint (XLA_LATENCY_HIDING=1)
#: and the docs all name the same set. TPU-only: XLA aborts on unknown
#: flags, so :func:`apply_latency_hiding_flags` gates the append on
#: :func:`tpu_xla_plausible` (a CPU dryrun warns and no-ops).
LATENCY_HIDING_XLA_FLAGS = (
    "--xla_tpu_enable_latency_hiding_scheduler=true",
    "--xla_tpu_enable_async_collective_fusion=true",
    "--xla_tpu_enable_async_collective_fusion_fuse_all_gather=true",
    "--xla_tpu_overlap_compute_collective_tc=true",
)

#: XLA_FLAGS tokens that change the collective schedule: these join the
#: run's env fingerprint AND the registry config key (a flagged run is a
#: different measurement lineage than an unflagged one — regress.store).
_SCHEDULER_FLAG_RE = re.compile(
    r"--xla\S*(?:latency_hiding|async_collective|overlap_compute"
    r"|collective_scheduler|scheduling)\S*"
)


def tpu_xla_plausible() -> bool:
    """True when the process can plausibly parse TPU-targeting XLA flags.

    XLA ABORTS the process on unknown flags in ``XLA_FLAGS`` (a fatal
    check in parse_flags_from_env.cc, not a warning), and the
    latency-hiding set is ``--xla_tpu_*`` — unknown to a CPU/GPU-only
    jaxlib. So: apply only when ``JAX_PLATFORMS``/``JAX_PLATFORM_NAME``
    names a tpu-like platform, or (platform unforced) a TPU plugin is
    importable. A forced-CPU env (the dryrun/test path) always skips.
    """
    env = (os.environ.get("JAX_PLATFORMS")
           or os.environ.get("JAX_PLATFORM_NAME") or "").lower()
    if "tpu" in env:
        return True
    if env:  # explicitly forced to another platform — not our flag set
        return False
    import importlib.util

    try:
        return (importlib.util.find_spec("libtpu") is not None
                or importlib.util.find_spec("jax_plugins.libtpu")
                is not None)
    except (ImportError, ValueError):
        return False


def apply_latency_hiding_flags() -> str:
    """Append :data:`LATENCY_HIDING_XLA_FLAGS` to ``XLA_FLAGS`` (idempotent).

    Must run BEFORE jax initializes its backend — callers are the harness
    and bench.py flag handlers. Returns the resulting ``XLA_FLAGS``.

    On a host whose XLA cannot know the TPU flag set
    (:func:`tpu_xla_plausible` False) this warns and no-ops instead of
    letting XLA's unknown-flag check abort the process — the run then
    records an empty ``xla_scheduler_flags`` fingerprint and stays in
    the unflagged regress lineage, so the degrade is never silent in
    the registry.
    """
    flags = os.environ.get("XLA_FLAGS", "")
    if not tpu_xla_plausible():
        import sys

        print(
            "WARNING: --xla-latency-hiding skipped: no TPU platform/plugin "
            "visible, and XLA aborts on unknown --xla_tpu_* flags "
            "(xla_scheduler_flags stays empty for this run)",
            file=sys.stderr,
        )
        return flags
    present = set(flags.split())
    missing = [f for f in LATENCY_HIDING_XLA_FLAGS if f not in present]
    if missing:
        flags = (flags + " " + " ".join(missing)).strip()
        os.environ["XLA_FLAGS"] = flags
    return flags


def scheduler_flags_fingerprint(flags: Optional[str] = None) -> str:
    """The scheduling-relevant subset of ``XLA_FLAGS``, sorted and joined.

    Empty string when none are set — the default lineage. Recorded into
    every result row (``xla_scheduler_flags``) so the regress registry can
    keep flagged and unflagged lineages apart (store.config_key).
    """
    if flags is None:
        flags = os.environ.get("XLA_FLAGS", "")
    return " ".join(sorted(set(_SCHEDULER_FLAG_RE.findall(flags))))


def allreduce_promotion_disabled(flags: str) -> bool:
    """True iff an ``--xla_disable_hlo_passes`` list in ``flags`` names the
    all-reduce-promotion pass.

    A plain substring test would be satisfied by the string appearing inside
    any unrelated flag value; this parses the actual pass list (last
    occurrence wins, matching XLA's flag parsing).
    """
    disabled = False
    for tok in flags.split():
        if tok.startswith("--xla_disable_hlo_passes="):
            passes = tok.split("=", 1)[1].split(",")
            disabled = "all-reduce-promotion" in (p.strip() for p in passes)
    return disabled
