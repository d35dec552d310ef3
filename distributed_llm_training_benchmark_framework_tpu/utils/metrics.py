"""Benchmark metrics + the result.json / stdout-marker export protocol.

This is the reference's core subsystem (SURVEY §5.5), reproduced
contract-for-contract so downstream tooling (collect scripts, parsers,
plotters) works unchanged against TPU pod logs:

- result schema: identical keys to reference ``train_harness.py:415-429`` /
  ``results/example_output/README.md:26-41`` (``peak_vram_gb`` keeps its name
  for schema compatibility — on TPU it reports peak HBM bytes in use), plus
  additive TPU fields (``peak_hbm_gb``, ``device_kind``, ``backend``,
  ``n_params``) that no reference consumer needs to read;
- file name: ``result_{strategy}_ws{N}_seq{L}_tier{T}.json``
  (reference ``train_harness.py:443-446``);
- stdout markers: ``BENCHMARK_RESULT_JSON_START`` / ``_END`` delimit the JSON
  on stdout (reference ``train_harness.py:452-456``) — the load-bearing export
  channel, because pod filesystems are ephemeral and results get scraped from
  ``kubectl logs`` (reference ``scripts/collect_results.sh:50-52``).

Metric formulas (parity, reference ``train_harness.py:399-413``):
- ``tokens_per_sec = tokens_per_step / mean_step_time`` — with the one honest
  correction that ``tokens_per_step`` includes ``grad_accum``, because our
  accumulation is real (the reference's is inert for DDP/FSDP yet it still
  reports per-microbatch tokens);
- ``h2d_gbps_per_gpu = batch*seq*4 bytes / step_time / 1e9`` — the reference's
  admitted FP32-equivalent transfer proxy, kept for comparability;
- warmup steps are excluded from timing (reference ``train_harness.py:388-390``).
"""

from __future__ import annotations

import dataclasses
import json
import os
from typing import Any, Dict, List, Optional

MARKER_START = "BENCHMARK_RESULT_JSON_START"
MARKER_END = "BENCHMARK_RESULT_JSON_END"


def arm_slug(
    strategy: str, world_size: int, seq_len: int, tier: str,
    model_family: str = "tinygpt",
) -> str:
    """The run's artifact stem: ``result_<slug>.json`` pairs with
    ``telemetry_<slug>.jsonl`` (the flight recorder's file), and
    validate_results cross-checks them purely by this slug — so there is
    exactly one place that builds it. Non-default families suffix the
    name; the tinygpt form stays bit-compatible with the reference scheme
    (train_harness.py:443-446)."""
    fam = "" if model_family == "tinygpt" else f"_{model_family}"
    return f"{strategy}_ws{world_size}_seq{seq_len}_tier{tier}{fam}"


def tokens_per_step(
    per_device_batch: int, grad_accum: int, seq_len: int, dp: int,
    expert_parallel: int = 1,
) -> int:
    """Global tokens one optimizer step consumes (see compute_result's
    honest-accounting note) — shared with the telemetry recorder so
    heartbeat tokens/sec can never drift from the published formula."""
    return per_device_batch * grad_accum * seq_len * dp * expert_parallel


def peak_hbm_bytes() -> Optional[int]:
    """Peak device-memory bytes in use, or None when the backend can't say.

    TPU runtimes expose ``memory_stats()['peak_bytes_in_use']`` per device
    (the HBM analogue of ``torch.cuda.max_memory_allocated``, reference
    ``train_harness.py:406-408``); CPU backends typically return None.
    """
    import jax

    peaks = []
    for d in jax.local_devices():
        try:
            stats = d.memory_stats()
        except Exception:
            stats = None
        if stats and "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


def hbm_bytes_in_use() -> Optional[int]:
    """Current device-memory bytes in use, or None when the backend
    can't say — the live sibling of :func:`peak_hbm_bytes`, sampled per
    sync window by the flight recorder so the HBM high-water timeline is
    reconstructible from telemetry alone (docs/OBSERVABILITY.md memory
    anatomy)."""
    import jax

    vals = []
    for d in jax.local_devices():
        try:
            stats = d.memory_stats()
        except Exception:
            stats = None
        if stats and "bytes_in_use" in stats:
            vals.append(int(stats["bytes_in_use"]))
    return max(vals) if vals else None


def buffer_assignment_peak_bytes(ma) -> int:
    """XLA's buffer-assignment peak from a ``memory_analysis()`` result.

    Current jaxlib exposes ``peak_memory_in_bytes`` directly; older
    ``CompiledMemoryStats`` (pre-0.4.38) only carries the component sizes,
    whose sum (arguments + outputs + temporaries, donation-aliased bytes
    counted once) is the same buffer-assignment quantity. Returns 0 when
    neither form is available.
    """
    peak = int(getattr(ma, "peak_memory_in_bytes", 0) or 0)
    if peak > 0:
        return peak
    try:
        parts = (
            int(getattr(ma, "argument_size_in_bytes", 0) or 0)
            + int(getattr(ma, "output_size_in_bytes", 0) or 0)
            + int(getattr(ma, "temp_size_in_bytes", 0) or 0)
            - int(getattr(ma, "alias_size_in_bytes", 0) or 0)
        )
        return max(parts, 0)
    except Exception:
        return 0


def measure_peak_hbm(
    compiled_step=None, prior_peak_bytes: Optional[int] = None,
) -> tuple[float, str]:
    """Measured per-device peak memory in GB, with provenance.

    Fallback chain (first rung that yields a number wins):

    1. ``allocator`` — per-device ``memory_stats()['peak_bytes_in_use']``,
       the runtime allocator's true high-water mark (reference parity:
       ``torch.cuda.max_memory_allocated``, ``train_harness.py:406-408``).
       The TPU runtime reports it; the CPU backend does not. The
       high-water mark is PROCESS-lifetime and
       has no reset API, so when several arms run in one process (bench.py
       measures parity then flagship) a later arm would silently inherit
       an earlier, larger arm's peak: callers pass ``prior_peak_bytes``
       (the mark observed before their run) and this rung only claims the
       number when the run actually raised it; otherwise the chain falls
       through to the per-executable rung 2.
    2. ``xla_buffer_assignment`` — ``compiled_step.memory_analysis()``
       ``.peak_memory_in_bytes``: the XLA compiler's buffer-assignment peak
       for the train-step executable (arguments + outputs + temporaries,
       donation-aliased). This is what the device allocator actually
       reserves to run the step, i.e. a *measured* property of the compiled
       program, not an analytic estimate.
    3. ``live_arrays`` — sum of bytes of all live ``jax.Array``s on the
       largest-resident device: a floor (params + opt state + dataset, no
       in-step temporaries).
    4. ``unavailable`` — 0.0: no allocator statistic, no executable, no
       live array. ``chip_smoke.py`` fails on it.

    Returns (peak_gb, method).
    """
    peak = peak_hbm_bytes()
    if peak and (prior_peak_bytes is None or peak > prior_peak_bytes):
        return peak / 1e9, "allocator"
    if compiled_step is not None:
        try:
            ma = compiled_step.memory_analysis()
            peak_bytes = buffer_assignment_peak_bytes(ma)
            if peak_bytes > 0:
                return peak_bytes / 1e9, "xla_buffer_assignment"
        except Exception:
            pass
    try:
        import jax

        per_device: Dict[Any, int] = {}
        for a in jax.live_arrays():
            for shard in a.addressable_shards:
                per_device[shard.device] = per_device.get(shard.device, 0) + int(
                    shard.data.nbytes
                )
        if per_device:
            return max(per_device.values()) / 1e9, "live_arrays"
    except Exception:
        pass
    return 0.0, "unavailable"


@dataclasses.dataclass
class BenchmarkResult:
    strategy: str
    world_size: int
    rank: int
    seq_len: int
    tier: str
    steps: int
    per_device_batch: int
    grad_accum: int
    tokens_per_sec: float
    mean_step_time_sec: float
    mean_loss: float
    peak_vram_gb: float  # schema-compat name; peak HBM GB on TPU
    h2d_gbps_per_gpu: float
    # --- additive TPU-native fields (ignored by reference-era consumers) ---
    peak_hbm_gb: float = 0.0
    # Provenance of peak_hbm_gb — see measure_peak_hbm():
    # allocator | xla_buffer_assignment | live_arrays | unavailable
    peak_hbm_method: str = "unavailable"
    # Pre-flight analytic estimate (utils.memory), published alongside the
    # measurement so the model's accuracy is auditable (docs/PERFORMANCE.md).
    est_hbm_gb: float = 0.0
    device_kind: str = ""
    backend: str = ""
    # Where the row ran, as jax reports it (jax.devices()[0], device count):
    # a number is a device measurement only when platform says "tpu".
    platform: str = ""
    device_count: int = 0
    n_params: int = 0
    attention_impl: str = "reference"
    dropout: float = 0.0
    # Analytic model-FLOPs accounting (utils.flops); the reference has no
    # FLOPs metric at all (train_harness.py:399-413 is its whole surface).
    flops_per_token: float = 0.0
    model_tflops_per_sec_per_chip: float = 0.0
    mfu_pct: float = 0.0  # 0.0 off a TPU (CPU runs have no peak)
    # Cost efficiency at public on-demand $/chip-hr (utils.platform table);
    # 0.0 off a TPU. Reference parity: README.md:270-276.
    usd_per_chip_hour: float = 0.0
    tokens_per_dollar: float = 0.0
    # Per-step wall-time distribution over the timed (post-warmup) steps.
    # Individually meaningful when sync_every == 1 (each step fenced, the
    # reference's per-step loss.item() discipline); with sync_every > 1 each
    # step carries its window's mean, so the spread understates true variance
    # — consumers must check sync_every before using these.
    sync_every: int = 1
    step_time_p50_sec: float = 0.0
    step_time_p95_sec: float = 0.0
    step_time_max_sec: float = 0.0
    step_time_cv_pct: float = 0.0  # stddev / mean * 100
    tensor_parallel: int = 1
    sequence_parallel: int = 1
    pipeline_parallel: int = 1
    pipeline_schedule: str = "gpipe"  # meaningful when pipeline_parallel > 1
    virtual_stages: int = 1  # interleaved schedule: layer chunks per stage
    expert_parallel: int = 1
    n_experts: int = 0
    # The remat policy the run actually executed with ("none"/"dots"/"full")
    # — provenance for strategies whose "auto" resolves per-geometry.
    remat_policy: str = "none"
    # Parameter storage dtype ('f32'/'bf16') — run identity for arms
    # sharing (strategy, tier, seq) geometry.
    param_dtype: str = "f32"
    # Causal (autoregressive) masking — False is reference parity
    # (train_harness.py:127 applies no mask); True halves attention FLOPs
    # and, on causal rings, turns on the zigzag load-balanced layout.
    causal: bool = False
    # Ring-attention zigzag layout mode ('auto'/'on'/'off') — run identity
    # for the scaling-day zigzag A/B arms, which differ in nothing else.
    ring_zigzag: str = "auto"
    # Collective-matmul tp fusion (round 15, ops/collective_matmul.py) —
    # run identity: the ppermute-ring projection schedule is a different
    # measurement than the plain tp lowering, so cmm and non-cmm runs
    # must never cross-gate (store.config_key includes this field,
    # mirroring xla_scheduler_flags).
    tp_collective_matmul: bool = False
    # MoE runs: measured fraction (%) of (token, choice) expert assignments
    # dropped by the capacity limit on the trained params (models.tinygpt
    # .moe_overflow_fraction diagnostic); None for dense runs or when the
    # diagnostic could not run under the run's sharding.
    expert_overflow_pct: Optional[float] = None
    # Model family ('tinygpt' = reference parity architecture; 'llama' =
    # the RMSNorm/RoPE/SwiGLU/GQA family, models.llama) — run identity: a
    # llama tier-A row is a different model than a tinygpt tier-A row.
    model_family: str = "tinygpt"
    # Loss-descent endpoints: means of the first/last ``loss_window_steps``
    # timed (post-warmup) per-step losses. mean_loss alone cannot distinguish
    # a training run from a frozen one (a flat line and a descent can share a
    # mean); the validator's descent envelope
    # (analysis.validate_results) compares these. 0.0 when no losses.
    loss_first_window: float = 0.0
    loss_last_window: float = 0.0
    loss_window_steps: int = 0
    # True when the run restored a checkpoint and continued (--resume): its
    # loss starts wherever the checkpoint left off, so the from-scratch
    # descent envelope does not apply.
    resumed: bool = False
    # Honest stitched-run accounting (chaos round, docs/FAULT_TOLERANCE.md):
    # how many times this arm resumed (the checkpoint dir's restart
    # ledger), which step it restored, and the loss recorded at that
    # checkpoint's save boundary. validate_results checks pre/post loss
    # continuity across the stitch, and the regress registry refuses
    # resumed rows as baselines — a stitched run must never pollute the
    # noise floor or pose as a clean measurement. All defaults for
    # non-resumed runs and pre-chaos artifacts.
    n_restarts: int = 0
    resume_step: int = -1
    resume_baseline_loss: float = 0.0
    # Numerics-sentinel accounting (self-healing round, docs/
    # FAULT_TOLERANCE.md): how many times the run rolled back in-process
    # to its last validated checkpoint after a sentinel trip (NaN/loss
    # envelope/grad explosion/parameter-checksum SDC), and how many steps
    # those rollbacks replayed. Replayed steps are EXCLUDED from the
    # timed step-time distribution (their windows fold the restore);
    # validate_results checks the two fields cohere, and the regress
    # registry keeps rolled-back rows out of the baseline set exactly
    # like resumed/partial ones — a healed run is an honest record but
    # not a clean measurement.
    n_rollbacks: int = 0
    rollback_steps_replayed: int = 0
    # True when the resume crossed a mesh-geometry change (elastic resume:
    # the checkpoint was saved under a different dp/tp/sp/pp/ep mesh and
    # was reshard-restored against this run's PartitionSpecs). Implies
    # resumed=true (validate_results enforces the coherence); such rows
    # join plain resumed rows in the regress never-baseline set.
    resume_geometry_changed: bool = False
    # --- flight-recorder phase attribution (telemetry.TelemetryRecorder,
    # round 8) — where the run's wall time actually went. Measured from
    # recorder start to result computation; the run's telemetry JSONL
    # (telemetry_<arm>.jsonl, run_end event) carries the final total
    # including emission itself. The phase fields are disjoint by
    # construction, so their sum never exceeds wall_time_total_sec
    # (validate_results enforces it). All 0.0 for pre-round-8 artifacts.
    wall_time_total_sec: float = 0.0
    time_in_init_sec: float = 0.0
    time_in_compile_sec: float = 0.0
    time_in_warmup_sec: float = 0.0
    time_in_timed_sec: float = 0.0
    time_in_checkpoint_sec: float = 0.0
    time_in_trace_sec: float = 0.0
    # Count of anomaly events (NaN loss, step-time spikes) the recorder
    # screened over the run's sync windows; validate_results rejects rows
    # whose telemetry shows them unresolved.
    n_anomalies: int = 0
    # --- step-anatomy attribution (analysis/step_anatomy.py) — the
    # trace-derived decomposition of the timed device steps, published
    # only when the run captured a --profile-dir trace (None otherwise /
    # for pre-anatomy artifacts). The three step components are additive:
    # anatomy_compute_frac + comms_exposed_frac + anatomy_idle_frac == 1
    # (overlapped collective time is accounted inside compute;
    # comms_overlap_frac reports it as a fraction OF collective time).
    # comms_exposed_frac is a first-class secondary metric in the regress
    # gate (stats.SECONDARY_METRICS); validate_results envelopes all of
    # them (fractions in [0,1], components summing to <= 1).
    anatomy_compute_frac: Optional[float] = None
    comms_exposed_frac: Optional[float] = None
    comms_overlap_frac: Optional[float] = None
    anatomy_idle_frac: Optional[float] = None
    # Pipeline arms only: the device-idle fraction inside the step IS the
    # schedule's bubble (ROADMAP direction 3's per-schedule metric).
    bubble_frac: Optional[float] = None
    # Roofline position: achieved vs peak FLOP/s and HBM GB/s (peaks from
    # utils/platform.py; achieved from the jitted step's cost_analysis()
    # over the traced median step). None on unknown device kinds (CPU).
    roofline_flops_pct_of_peak: Optional[float] = None
    roofline_hbm_pct_of_peak: Optional[float] = None
    # Across rank-sibling traces / device lanes: how far the slowest
    # lane's median step sits above the fastest's (percent).
    straggler_skew_pct: Optional[float] = None
    # Scheduling-relevant XLA_FLAGS subset (utils.platform
    # .scheduler_flags_fingerprint) — "" for the default lineage. Run
    # identity: the latency-hiding scheduler changes the collective
    # schedule, so flagged and unflagged runs must never cross-gate in the
    # regress registry (store.config_key includes this field).
    xla_scheduler_flags: str = ""
    # --- memory-anatomy reconciliation (analysis/memory_anatomy.py) —
    # the per-chip HBM peak, attributed. ``hbm_estimate`` persists the
    # pre-flight analytic breakdown (utils.memory.HBMEstimate.breakdown,
    # GiB keys — previously print-only); ``hbm_measured`` is the
    # allocator's peak in GiB or None-with-reason when the backend lacks
    # memory_stats(); ``hbm_attribution`` splits the reference peak
    # (source in ``hbm_attribution_source``, total in
    # ``hbm_reference_gib``) across params/grads/opt_state/activations/
    # dataset/xla_temp plus a SIGNED unattributed residual that closes
    # the books exactly. ``hbm_model_drift_frac`` — |reference −
    # analytic| / analytic — is a gated secondary metric
    # (regress.stats.SECONDARY_METRICS): the estimator's ±20% disclaimer
    # as a tested invariant. All None for pre-memory-anatomy artifacts.
    hbm_estimate: Optional[Dict[str, float]] = None
    hbm_measured: Optional[float] = None
    hbm_measured_reason: str = ""
    hbm_attribution: Optional[Dict[str, float]] = None
    hbm_attribution_source: str = ""
    hbm_reference_gib: Optional[float] = None
    hbm_model_drift_frac: Optional[float] = None
    # --- streaming-data-path accounting (data/stream.py +
    # data/prefetch.py, docs/FAULT_TOLERANCE.md) — run identity plus the
    # input-path honesty ledger. ``data_mode`` is 'synthetic' (the
    # default zero-IO table; all fields below stay at their inert
    # defaults) or 'stream' (--data-path). ``data_stall_frac`` — fraction
    # of timed step wall spent starved for input — is a gated secondary
    # metric (regress.stats.SECONDARY_METRICS, abs-pp, lower-better) so
    # an input-bound regression fails `regress gate --all` by name.
    # ``records_skipped`` counts corrupt records healed by substitution
    # (one quarantine-ledger entry + data_corrupt_record telemetry event
    # each; validate_results cross-checks the counts). The cursor pair
    # makes resume stream-position continuity closed-form: cursor_end -
    # cursor_start == records_consumed == steps_run x records/step, and a
    # same-geometry resume must start exactly where the checkpoint's
    # sidecar left off (no replayed or skipped records across a stitch).
    data_mode: str = "synthetic"
    data_stall_frac: Optional[float] = None
    data_stall_sec: float = 0.0
    records_consumed: int = 0
    records_skipped: int = 0
    stream_cursor_start: int = -1
    stream_cursor_end: int = -1

    def to_dict(self) -> Dict[str, Any]:
        return dataclasses.asdict(self)

    def result_filename(self) -> str:
        return "result_" + arm_slug(
            self.strategy, self.world_size, self.seq_len, self.tier,
            self.model_family,
        ) + ".json"


def compute_result(
    *,
    strategy: str,
    world_size: int,
    rank: int,
    seq_len: int,
    tier: str,
    steps: int,
    per_device_batch: int,
    grad_accum: int,
    step_times: List[float],
    losses: List[float],
    device_kind: str = "",
    backend: str = "",
    platform: str = "",
    device_count: int = 0,
    n_params: int = 0,
    attention_impl: str = "reference",
    dropout: float = 0.0,
    flops_per_token: float = 0.0,
    est_hbm_gb: float = 0.0,
    compiled_step=None,
    sync_every: int = 1,
    tensor_parallel: int = 1,
    sequence_parallel: int = 1,
    pipeline_parallel: int = 1,
    pipeline_schedule: str = "gpipe",
    virtual_stages: int = 1,
    expert_parallel: int = 1,
    n_experts: int = 0,
    remat_policy: str = "none",
    param_dtype: str = "f32",
    causal: bool = False,
    ring_zigzag: str = "auto",
    tp_collective_matmul: bool = False,
    expert_overflow_pct: Optional[float] = None,
    model_family: str = "tinygpt",
    resumed: bool = False,
    n_restarts: int = 0,
    resume_step: int = -1,
    resume_baseline_loss: float = 0.0,
    resume_geometry_changed: bool = False,
    n_rollbacks: int = 0,
    rollback_steps_replayed: int = 0,
    prior_peak_bytes: Optional[int] = None,
    wall_time_total_sec: float = 0.0,
    phase_times: Optional[Dict[str, float]] = None,
    n_anomalies: int = 0,
    step_anatomy: Optional[Dict[str, Any]] = None,
    memory_anatomy: Optional[Dict[str, Any]] = None,
    data_mode: str = "synthetic",
    data_stall_frac: Optional[float] = None,
    data_stall_sec: float = 0.0,
    records_consumed: int = 0,
    records_skipped: int = 0,
    stream_cursor_start: int = -1,
    stream_cursor_end: int = -1,
) -> BenchmarkResult:
    def _scheduler_flags() -> str:
        from . import platform as platform_mod

        return platform_mod.scheduler_flags_fingerprint()

    mean_step = sum(step_times) / len(step_times) if step_times else 0.0
    mean_loss = sum(losses) / len(losses) if losses else 0.0
    # Descent endpoints: window of up to 10 steps, at most a fifth of the
    # timed run each so the two windows never overlap at benchmark lengths.
    if losses:
        lw = max(1, min(10, len(losses) // 5))
        loss_first = sum(losses[:lw]) / lw
        loss_last = sum(losses[-lw:]) / lw
    else:
        lw, loss_first, loss_last = 0, 0.0, 0.0
    # Honest accounting: a step consumes per_device_batch * grad_accum
    # sequences per *data-parallel replica* (our accumulation is real, and
    # tensor/sequence-parallel groups jointly compute one example rather than
    # multiplying throughput; see module docstring). With tp=sp=1 this is the
    # reference's formula (train_harness.py:403). Expert-parallel groups DO
    # multiply throughput: the batch is sharded over ('data', 'expert')
    # (strategies.batch_partition_spec), so each expert-axis member consumes
    # its own per_device_batch sequences.
    dp = world_size // (
        tensor_parallel * sequence_parallel * pipeline_parallel * expert_parallel
    )
    step_tokens = tokens_per_step(
        per_device_batch, grad_accum, seq_len, dp, expert_parallel
    )
    tps = step_tokens / mean_step if mean_step > 0 else 0.0
    bytes_per_step = per_device_batch * grad_accum * seq_len * 4
    h2d = (bytes_per_step / mean_step) / 1e9 if mean_step > 0 else 0.0
    peak_gb, peak_method = measure_peak_hbm(
        compiled_step, prior_peak_bytes=prior_peak_bytes,
    )
    from . import flops as flops_mod

    tps_per_chip = tps / world_size if world_size else 0.0
    tflops_per_chip = flops_mod.achieved_tflops_per_sec(tps_per_chip, flops_per_token)
    mfu = flops_mod.mfu_pct(tps_per_chip, flops_per_token, device_kind)
    price = flops_mod.device_usd_per_chip_hour(device_kind)
    tok_per_usd = flops_mod.tokens_per_dollar(tps_per_chip, device_kind)
    if step_times:
        ts = sorted(step_times)
        n = len(ts)
        p50 = ts[n // 2]
        p95 = ts[min(n - 1, int(0.95 * (n - 1) + 0.5))]
        t_max = ts[-1]
        var = sum((t - mean_step) ** 2 for t in step_times) / n
        cv = 100.0 * var**0.5 / mean_step if mean_step > 0 else 0.0
    else:
        p50 = p95 = t_max = cv = 0.0
    pt = phase_times or {}
    # Step-anatomy fields (analysis.step_anatomy.result_fields keys):
    # unknown keys are refused rather than silently dropped — the engine
    # and the result schema must not drift apart.
    anatomy = dict(step_anatomy or {})
    anatomy_fields = {
        k: anatomy.pop(k, None) for k in (
            "anatomy_compute_frac", "comms_exposed_frac",
            "comms_overlap_frac", "anatomy_idle_frac", "bubble_frac",
            "roofline_flops_pct_of_peak", "roofline_hbm_pct_of_peak",
            "straggler_skew_pct",
        )
    }
    if anatomy:
        raise ValueError(
            f"unknown step_anatomy keys {sorted(anatomy)} (the engine's "
            "result_fields and BenchmarkResult must agree)"
        )
    # Memory-anatomy fields (analysis.memory_anatomy.result_fields keys):
    # same refusal contract as step_anatomy — the engine and the result
    # schema must not drift apart.
    mem = dict(memory_anatomy or {})
    mem_fields = {
        k: mem.pop(k, None if k not in (
            "hbm_measured_reason", "hbm_attribution_source",
        ) else "") for k in (
            "hbm_estimate", "hbm_measured", "hbm_measured_reason",
            "hbm_attribution", "hbm_attribution_source",
            "hbm_reference_gib", "hbm_model_drift_frac",
        )
    }
    if mem_fields["hbm_measured_reason"] is None:
        mem_fields["hbm_measured_reason"] = ""
    if mem_fields["hbm_attribution_source"] is None:
        mem_fields["hbm_attribution_source"] = ""
    if mem:
        raise ValueError(
            f"unknown memory_anatomy keys {sorted(mem)} (the engine's "
            "result_fields and BenchmarkResult must agree)"
        )
    return BenchmarkResult(
        strategy=strategy,
        world_size=world_size,
        rank=rank,
        seq_len=seq_len,
        tier=tier,
        steps=steps,
        per_device_batch=per_device_batch,
        grad_accum=grad_accum,
        tokens_per_sec=tps,
        mean_step_time_sec=mean_step,
        mean_loss=mean_loss,
        peak_vram_gb=peak_gb,
        h2d_gbps_per_gpu=h2d,
        peak_hbm_gb=peak_gb,
        peak_hbm_method=peak_method,
        est_hbm_gb=est_hbm_gb,
        device_kind=device_kind,
        backend=backend,
        platform=platform,
        device_count=device_count,
        n_params=n_params,
        attention_impl=attention_impl,
        dropout=dropout,
        flops_per_token=flops_per_token,
        model_tflops_per_sec_per_chip=tflops_per_chip,
        mfu_pct=mfu if mfu is not None else 0.0,
        usd_per_chip_hour=price if price is not None else 0.0,
        tokens_per_dollar=tok_per_usd if tok_per_usd is not None else 0.0,
        sync_every=sync_every,
        step_time_p50_sec=p50,
        step_time_p95_sec=p95,
        step_time_max_sec=t_max,
        step_time_cv_pct=cv,
        tensor_parallel=tensor_parallel,
        sequence_parallel=sequence_parallel,
        pipeline_parallel=pipeline_parallel,
        pipeline_schedule=pipeline_schedule,
        virtual_stages=virtual_stages,
        expert_parallel=expert_parallel,
        n_experts=n_experts,
        remat_policy=remat_policy,
        param_dtype=param_dtype,
        causal=causal,
        ring_zigzag=ring_zigzag,
        tp_collective_matmul=tp_collective_matmul,
        expert_overflow_pct=expert_overflow_pct,
        model_family=model_family,
        loss_first_window=loss_first,
        loss_last_window=loss_last,
        loss_window_steps=lw,
        resumed=resumed,
        n_restarts=n_restarts,
        resume_step=resume_step,
        resume_baseline_loss=round(resume_baseline_loss, 6),
        resume_geometry_changed=resume_geometry_changed,
        n_rollbacks=n_rollbacks,
        rollback_steps_replayed=rollback_steps_replayed,
        wall_time_total_sec=round(wall_time_total_sec, 4),
        time_in_init_sec=round(pt.get("init", 0.0), 4),
        time_in_compile_sec=round(pt.get("compile", 0.0), 4),
        time_in_warmup_sec=round(pt.get("warmup", 0.0), 4),
        time_in_timed_sec=round(pt.get("timed", 0.0), 4),
        time_in_checkpoint_sec=round(pt.get("checkpoint", 0.0), 4),
        time_in_trace_sec=round(pt.get("trace", 0.0), 4),
        n_anomalies=n_anomalies,
        xla_scheduler_flags=_scheduler_flags(),
        data_mode=data_mode,
        data_stall_frac=data_stall_frac,
        data_stall_sec=data_stall_sec,
        records_consumed=records_consumed,
        records_skipped=records_skipped,
        stream_cursor_start=stream_cursor_start,
        stream_cursor_end=stream_cursor_end,
        **anatomy_fields,
        **mem_fields,
    )


def emit_result(result: BenchmarkResult, results_dir: str, is_main: bool = True) -> Optional[str]:
    """Write result.json + print the marker-delimited JSON block (rank 0 only).

    Console format parity: reference ``train_harness.py:431-456``.
    """
    if not is_main:
        return None
    payload = json.dumps(result.to_dict(), indent=2)

    print("\n" + "=" * 80)
    print("Benchmark Results:")
    print(
        f"  Device:           {result.platform} {result.device_kind!r}"
        f" x{result.device_count}"
    )
    print(f"  Tokens/sec:       {result.tokens_per_sec:,.0f}")
    if result.mfu_pct > 0:
        print(
            f"  Model TFLOP/s/chip: {result.model_tflops_per_sec_per_chip:.1f}"
            f"  (MFU {result.mfu_pct:.1f}%)"
        )
    print(f"  Mean step time:   {result.mean_step_time_sec:.4f}s")
    if result.sync_every == 1 and result.step_time_p95_sec > 0:
        print(
            f"  Step time p50/p95/max: {result.step_time_p50_sec:.4f}s /"
            f" {result.step_time_p95_sec:.4f}s / {result.step_time_max_sec:.4f}s"
            f"  (cv {result.step_time_cv_pct:.1f}%)"
        )
    if result.tokens_per_dollar > 0:
        print(
            f"  Tokens/$:         {result.tokens_per_dollar:,.0f}"
            f"  (at ${result.usd_per_chip_hour:.2f}/chip-hr on-demand)"
        )
    print(
        f"  Peak HBM/chip:    {result.peak_hbm_gb:.2f} GB"
        f" ({result.peak_hbm_method})"
    )
    if result.hbm_attribution is not None:
        attr = result.hbm_attribution
        measured = (
            f"{result.hbm_measured:.2f} GiB measured"
            if result.hbm_measured is not None
            else f"measured n/a ({result.hbm_measured_reason})"
        )
        drift = (
            f", model drift {100.0 * result.hbm_model_drift_frac:.1f}%"
            if result.hbm_model_drift_frac is not None else ""
        )
        print(
            f"  HBM anatomy:      {measured}; "
            f"{result.hbm_attribution_source} peak "
            f"{result.hbm_reference_gib or 0:.2f} GiB = params "
            f"{attr.get('params', 0):.2f} + grads {attr.get('grads', 0):.2f}"
            f" + opt {attr.get('opt_state', 0):.2f} + act "
            f"{attr.get('activations', 0):.2f} + data "
            f"{attr.get('dataset', 0):.2f} + xla-temp "
            f"{attr.get('xla_temp', 0):.2f} "
            f"{attr.get('unattributed', 0):+.2f} residual{drift}"
        )
    print(f"  H2D GB/s/chip:    {result.h2d_gbps_per_gpu:.3f}")
    print(f"  Mean loss:        {result.mean_loss:.4f}")
    if result.data_mode == "stream":
        print(
            f"  Data path:        stream — stall "
            f"{100.0 * (result.data_stall_frac or 0.0):.1f}% of timed wall "
            f"({result.data_stall_sec:.2f}s), {result.records_consumed} "
            f"records consumed (cursor {result.stream_cursor_start} -> "
            f"{result.stream_cursor_end}), {result.records_skipped} "
            "skipped/quarantined"
        )
    if result.wall_time_total_sec > 0:
        print(
            f"  Wall time:        {result.wall_time_total_sec:.1f}s"
            f"  (compile {result.time_in_compile_sec:.1f}s,"
            f" warmup {result.time_in_warmup_sec:.1f}s,"
            f" timed {result.time_in_timed_sec:.1f}s,"
            f" checkpoint {result.time_in_checkpoint_sec:.1f}s)"
        )
    if result.comms_exposed_frac is not None:
        anatomy = (
            f"  Step anatomy:     compute "
            f"{100.0 * (result.anatomy_compute_frac or 0):.1f}% / exposed "
            f"comms {100.0 * result.comms_exposed_frac:.1f}% / idle "
            f"{100.0 * (result.anatomy_idle_frac or 0):.1f}%"
        )
        if result.comms_overlap_frac is not None:
            anatomy += (f"  (overlap {100.0 * result.comms_overlap_frac:.1f}%"
                        " of collective time)")
        if result.bubble_frac is not None:
            anatomy += f"  bubble {100.0 * result.bubble_frac:.1f}%"
        print(anatomy)
    if result.n_anomalies > 0:
        print(f"  ANOMALIES:        {result.n_anomalies} (see telemetry JSONL)")
    if result.resumed:
        stitch = (
            ", geometry changed" if result.resume_geometry_changed else ""
        )
        print(
            f"  RESUMED:          from step {result.resume_step} "
            f"(restart #{result.n_restarts}{stitch}) — stitched run, "
            "never a regression baseline"
        )
    if result.n_rollbacks > 0:
        print(
            f"  ROLLBACKS:        {result.n_rollbacks} sentinel "
            f"rollback(s), {result.rollback_steps_replayed} step(s) "
            "replayed — healed run, never a regression baseline"
        )
    print("=" * 80 + "\n")

    os.makedirs(results_dir, exist_ok=True)
    path = os.path.join(results_dir, result.result_filename())
    with open(path, "w") as f:
        f.write(payload)
    print(f"Results saved to: {path}")

    print("\n" + "=" * 80)
    print(MARKER_START)
    print(payload)
    print(MARKER_END)
    print("=" * 80 + "\n")
    return path
