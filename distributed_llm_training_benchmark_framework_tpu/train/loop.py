"""The timed benchmark loop.

Hot-loop parity with the reference (``benchmarking/train_harness.py:278-458``)
with TPU-honest timing:

- per-step wall-clock via ``time.perf_counter`` around the whole step;
- JAX dispatch is asynchronous, so each timed step ends with
  ``jax.block_until_ready(loss)`` — the explicit equivalent of the device
  sync the reference gets implicitly from ``loss.item()`` (``:390``);
- warmup steps excluded from the averages (``:388-390``);
- rank-0 progress print every 10 steps (``:392-393``);
- cross-host barrier before final metrics (``:396-397``).

One loop serves every strategy arm — the arm only changes the shardings baked
into ``state.step_fn``.

Flight-recorder telemetry (round 8, docs/OBSERVABILITY.md): a
``telemetry.TelemetryRecorder`` rides along for the whole run — JSONL
events + ``BENCHMARK_HEARTBEAT`` stdout markers at every sync-window
boundary, phase-time attribution (init/compile/warmup/timed/checkpoint/
trace/finalize) into the result row, and a ``run_aborted`` event on any
crash. All recorder call sites sit at sync boundaries (graftcheck rule
GC105 pins this), so telemetry never adds a device sync to a timed window.

Chaos harness (docs/FAULT_TOLERANCE.md): the loop is preemption-safe — a
SIGTERM sets a flag (``faults.PreemptionGuard``, installed OUTSIDE the
timed loop per graftcheck GC106) that the loop polls at sync-window
boundaries; on preemption it emergency-checkpoints, emits ``run_aborted
reason=preempted`` plus a final heartbeat, and exits with the distinct
``EXIT_PREEMPTED`` code the retrying orchestration resumes on. The same
boundaries host the deterministic fault injector (``--inject-fault`` /
``INJECT_FAULT``) the chaos suite uses to prove all of this works.

Streaming data path (docs/FAULT_TOLERANCE.md, ROADMAP direction 5):
``--data-path`` swaps the device-resident synthetic table for the
fault-tolerant sharded record stream (``data/stream.py``) behind a
bounded double-buffered host prefetcher (``data/prefetch.py``) — the
default synthetic path is untouched. The prefetcher's ``get()`` is the
ONE sanctioned blocking pull on the input path inside the timed loop
(graftcheck GC111); its measured waits accumulate into the published
``data_stall_frac`` (a gated secondary metric), a window that starved
past half its wall emits a ``data_stall`` telemetry event, and a wait
past ``--data-stall-timeout-sec`` aborts the run as ``reason=data_stall``
(exit ``EXIT_DATA_STALL`` 78, retryable-with-resume) — distinct from the
watchdog's ``hang``: the device was healthy, the INPUT path starved it.
Every checkpoint save carries the stream's exact-resume cursor in a
``stream_<step>.json`` sidecar, so a killed run resumes consuming
precisely the un-consumed records, including across geometry changes.

Self-healing round (docs/FAULT_TOLERANCE.md): two more boundary-cadence
guards ride the same discipline. The **hang watchdog**
(``faults.HangWatchdog``, ``--hang-timeout-sec``) is beaten at every
sync-window boundary; when a boundary fails to arrive in time it dumps
all-thread stacks into a ``hang_dump`` telemetry event, broadcasts a hang
flag over the coordination-service KV store so every rank aborts
coherently, and exits the distinct ``EXIT_HUNG`` (76,
retryable-with-resume). The **numerics sentinel**
(``faults.NumericsSentinel``, ``--sentinel on``) screens each synced
window's loss + in-step global grad-norm (and a per-N-steps parameter
checksum) and on trip does NOT kill the run: it rolls back in-process to
the last validated checkpoint, reseeds the data stream past the poisoned
region, and replays — with ``n_rollbacks``/``rollback_steps_replayed``
accounting on the result row and replayed windows excluded from the
timed distributions.
"""

from __future__ import annotations

import os
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..data import DataStalled, DataStallTimeout, SyntheticDataset
from ..data.stream import STREAM_STATE_SCHEMA_VERSION
from ..faults import (
    DATA_KINDS,
    FaultInjector,
    HangWatchdog,
    NothingToResume,
    NumericsSentinel,
    Preempted,
    PreemptionGuard,
    SentinelTripped,
    parse_fault_spec,
)
from ..faults.watchdog import abort_on_peer_hang
from ..models import get_model_config
from ..parallel import make_mesh, StrategyConfig
from ..runtime import distributed as dist
from ..telemetry import TelemetryRecorder
from ..utils import flops as flops_mod
from ..utils import metrics as metrics_mod
from .step import create_train_state

# A "[Step NNNN] Loss:" line every this many steps (rank 0).
_LOG_EVERY = 10


class _StepCursor:
    """The loop's step iterator, with in-run rollback support.

    Yields ``start .. stop-1`` like the plain ``range`` it replaces, but
    the numerics sentinel's rollback handler can rewind it
    (:meth:`rollback`) so the loop replays from the restored checkpoint —
    keeping the ``for step in ...`` shape the graftcheck timed-loop rules
    (GC102/GC105/GC106) police. ``replay_until`` marks the highest step
    already measured once: replayed steps at or below it are excluded
    from the timed step-time distribution (their windows fold the
    restore; the original, poisoned measurements were truncated).
    """

    def __init__(self, start: int, stop: int):
        self.next_step = start
        self.stop = stop
        self.replay_until = -1

    def __iter__(self) -> "_StepCursor":
        return self

    def __next__(self) -> int:
        if self.next_step >= self.stop:
            raise StopIteration
        s = self.next_step
        self.next_step = s + 1
        return s

    def rollback(self, to_step: int, tripped_at: int) -> None:
        self.next_step = to_step + 1
        self.replay_until = max(self.replay_until, tripped_at)


def _make_recorder(kwargs: dict) -> TelemetryRecorder:
    """Build the run's flight recorder from run_benchmark's kwargs.

    Created BEFORE any validation or device work so that even a refused or
    crashed-at-startup run leaves a ``run_aborted`` trail. Must therefore
    never raise itself: any surprise in the kwargs degrades to a disabled
    recorder rather than masking the real error the impl is about to
    report properly.
    """
    try:
        strategy = kwargs["strategy"]
        world_size = int(kwargs["world_size"])
        seq_len = int(kwargs["seq_len"])
        tier = kwargs["tier"]
        family = kwargs.get("model_family", "tinygpt")
        # Shared slug/formula (utils.metrics): the telemetry filename must
        # pair with result_filename, and heartbeat tokens/sec must match
        # the published accounting — neither may drift independently.
        arm = metrics_mod.arm_slug(
            strategy.name, world_size, seq_len, tier, family
        )
        denom = (
            int(kwargs.get("tensor_parallel", 1))
            * int(kwargs.get("sequence_parallel", 1))
            * int(kwargs.get("pipeline_parallel", 1))
            * int(kwargs.get("expert_parallel", 1))
        )
        dp = max(world_size // max(denom, 1), 1)
        step_tokens = metrics_mod.tokens_per_step(
            int(kwargs["per_device_batch"]), int(kwargs["grad_accum"]),
            seq_len, dp, int(kwargs.get("expert_parallel", 1)),
        )
        rank = int(kwargs.get("rank", 0))
        meta = {
            "strategy": strategy.name,
            "world_size": world_size,
            "rank": rank,
            "seq_len": seq_len,
            "tier": tier,
            "model_family": family,
            "per_device_batch": int(kwargs["per_device_batch"]),
            "grad_accum": int(kwargs["grad_accum"]),
            # Composition axes: arms sharing (strategy, ws, seq, tier)
            # geometry — the zigzag A/B pair, tp vs pp arms — must stay
            # distinguishable in a salvaged partial row, or the
            # metrics-dedup collapses two dead arms into one.
            "attention_impl": kwargs.get("attention_impl", "reference"),
            "tensor_parallel": int(kwargs.get("tensor_parallel", 1)),
            "sequence_parallel": int(kwargs.get("sequence_parallel", 1)),
            "pipeline_parallel": int(kwargs.get("pipeline_parallel", 1)),
            "pipeline_schedule": kwargs.get("pipeline_schedule", "gpipe"),
            # The step-anatomy bubble cross-check needs V to derive the
            # interleaved schedule's structural bound from the trace;
            # effective value (only interleaved runs virtual chunks).
            # The omitted-kwarg default MUST match _run_benchmark_impl's
            # signature default (2) or the recorded V lies about the
            # compiled schedule and the bound goes silently loose.
            "virtual_stages": (
                int(kwargs.get("virtual_stages", 2))
                if int(kwargs.get("pipeline_parallel", 1)) > 1
                and kwargs.get("pipeline_schedule") == "interleaved"
                else 1
            ),
            "expert_parallel": int(kwargs.get("expert_parallel", 1)),
            "n_experts": int(kwargs.get("n_experts", 0)),
            "causal": bool(kwargs.get("causal", False)),
            "ring_zigzag": {None: "auto", True: "on", False: "off"}[
                kwargs.get("ring_zigzag")
            ],
        }
        if kwargs.get("data_path"):
            # Stream identity in every heartbeat: a salvaged partial row
            # must land in the STREAM regress lineage (store.config_key
            # reads data_mode off the row), never the synthetic one.
            # Synthetic runs omit the key so their heartbeat/telemetry
            # bytes stay unchanged.
            meta["data_mode"] = "stream"
        if kwargs.get("tp_collective_matmul"):
            # Collective-matmul identity (round 15), same posture as
            # data_mode: a dead cmm arm's salvaged partial row must stay
            # distinct from its llama-tp2-ddp A/B partner in the metrics
            # dedup AND land in the cmm regress lineage (store.config_key
            # reads the field off the row). Plain runs omit the key so
            # their heartbeat bytes stay unchanged.
            meta["tp_collective_matmul"] = True
        sup_attempt = os.environ.get("BENCH_SUPERVISED_ATTEMPT", "")
        if sup_attempt.isdigit() and int(sup_attempt) > 1:
            # Fleet-supervisor recovery attempt: the attempt number rides
            # run_meta and every heartbeat, so a salvaged trail from a
            # supervised retry is attributable to its leg of the
            # supervision.json ledger. First attempts (and unsupervised
            # runs) omit the key — their telemetry bytes stay unchanged.
            meta["supervised_attempt"] = int(sup_attempt)
        rec = TelemetryRecorder(
            arm,
            results_dir=kwargs.get("results_dir"),
            is_main=dist.is_main_process() and rank == 0,
            enabled=bool(kwargs.get("telemetry", True)),
            heartbeat_every_sec=float(kwargs.get("heartbeat_sec", 30.0)),
            tokens_per_step=step_tokens,
            total_steps=int(kwargs["steps"]),
            rank=rank,
            meta=meta,
        )
        rec.begin_phase("init")
        return rec
    except Exception:
        return TelemetryRecorder(
            "unknown", results_dir=None, is_main=False, enabled=False
        )


def run_benchmark(*, prng_impl: str = "rbg", **kwargs) -> metrics_mod.BenchmarkResult:
    """Run one benchmark arm end-to-end and (on rank 0) emit its result.

    Thin wrapper that (a) owns the run's flight recorder — any exception
    that escapes the arm is recorded as a ``run_aborted`` telemetry event
    with its phase and last step before propagating — and (b) scopes the
    dropout-key PRNG choice: 'rbg' (XLA RngBitGenerator) measures ~6%
    faster end-to-end than the default threefry on v5e — threefry lowers
    to a long VPU integer chain per bernoulli draw. No cross-framework RNG
    parity is at stake (the reference uses torch's RNG); 'threefry'
    remains available for bit-exact reproducibility across jax
    versions/backends. The process default is restored on exit so
    embedding callers / later tests keep theirs.

    See ``_run_benchmark_impl`` for the full parameter list.
    """
    recorder = _make_recorder(kwargs)
    # SIGTERM guard installed here — before any device work, outside the
    # timed loop (graftcheck GC106) — so even a preemption landing during
    # init/compile is caught at the first boundary poll; the finally
    # restores the previous handler for embedding callers (bench.py runs
    # several arms in one process).
    guard = PreemptionGuard()
    # Hang watchdog created beside the guard (same outside-the-loop
    # discipline; faults/watchdog.py): its deadline only arms at the
    # first sync-window beat, so init/XLA-compile time never trips it,
    # and the finally disarms it for embedding callers.
    _rank = int(kwargs.get("rank", 0) or 0)
    watchdog = HangWatchdog(
        float(kwargs.get("hang_timeout_sec") or 0.0),
        recorder=recorder,
        is_main=dist.is_main_process() and _rank == 0,
        rank=_rank,
    )
    try:
        if not prng_impl:
            return _run_benchmark_impl(
                recorder=recorder, preempt_guard=guard,
                hang_watchdog=watchdog, **kwargs
            )
        prev_impl = jax.config.jax_default_prng_impl
        try:
            jax.config.update("jax_default_prng_impl", prng_impl)
        except ValueError:
            # Older jax spells the threefry enum value 'threefry2x32'; the
            # CLI name stays 'threefry' (bit-identical generator either way).
            alias = {"threefry": "threefry2x32"}.get(prng_impl)
            if alias is None:
                raise
            jax.config.update("jax_default_prng_impl", alias)
        try:
            return _run_benchmark_impl(
                recorder=recorder, preempt_guard=guard,
                hang_watchdog=watchdog, **kwargs
            )
        finally:
            jax.config.update("jax_default_prng_impl", prev_impl)
    except BaseException as e:
        # Idempotent: the preemption path already aborted with
        # reason=preempted; any other escape records its exception here.
        recorder.abort(f"exception:{type(e).__name__}: {e}")
        raise
    finally:
        watchdog.disarm()
        guard.uninstall()


def _run_benchmark_impl(
    *,
    strategy: StrategyConfig,
    tier: str,
    seq_len: int,
    model_family: str = "tinygpt",
    steps: int,
    warmup_steps: int,
    per_device_batch: int,
    grad_accum: int,
    world_size: int,
    rank: int = 0,
    tensor_parallel: int = 1,
    sequence_parallel: int = 1,
    pipeline_parallel: int = 1,
    pipeline_schedule: str = "gpipe",
    virtual_stages: int = 2,
    expert_parallel: int = 1,
    n_experts: int = 0,
    results_dir: Optional[str] = None,
    seed: int = 42,
    attention_impl: str = "reference",
    dropout: Optional[float] = None,
    causal: bool = False,
    ring_zigzag: Optional[bool] = None,
    layer_loop: str = "scan",
    tp_collective_matmul: bool = False,
    dataset_size: int = 1000,
    sync_every: int = 1,
    skip_memory_check: bool = False,
    profile_dir: Optional[str] = None,
    checkpoint_dir: Optional[str] = None,
    checkpoint_every: int = 0,
    checkpoint_async: bool = False,
    resume: bool = False,
    telemetry: bool = True,
    heartbeat_sec: float = 30.0,
    inject_fault: Optional[str] = None,
    hang_timeout_sec: float = 0.0,
    sentinel: bool = False,
    sentinel_checksum_every: int = 0,
    data_path: Optional[str] = None,
    data_stall_timeout_sec: float = 60.0,
    recorder: Optional[TelemetryRecorder] = None,
    preempt_guard: Optional[PreemptionGuard] = None,
    hang_watchdog: Optional[HangWatchdog] = None,
) -> metrics_mod.BenchmarkResult:
    """Benchmark body (see run_benchmark).

    ``telemetry``/``heartbeat_sec`` configure the flight recorder (already
    consumed by ``_make_recorder`` when entering via run_benchmark);
    ``recorder`` is injected by the wrapper so the crash guard outlives
    this frame, and ``preempt_guard`` so the SIGTERM handler is installed
    before (and survives past) this frame. ``inject_fault`` arms one
    deterministic chaos fault (faults.parse_fault_spec grammar; the
    ``INJECT_FAULT`` env var is the flagless fallback).
    ``hang_timeout_sec`` arms the hang watchdog (``hang_watchdog`` is the
    wrapper-owned instance so its disarm outlives this frame); ``sentinel``
    arms the numerics sentinel with ``sentinel_checksum_every`` as the
    parameter-checksum cadence (0 = checksum guard off). ``data_path``
    selects the streaming input path (a directory of tokenized record
    shards — see the module docstring) and ``data_stall_timeout_sec`` is
    the starvation bound past which the run aborts as
    ``reason=data_stall``.
    """
    if recorder is None:
        # Direct-impl callers (tests) still get phase accounting.
        recorder = TelemetryRecorder(
            "direct", results_dir=None, is_main=False, enabled=False
        )
        recorder.begin_phase("init")
    is_main = dist.is_main_process() and rank == 0
    preempt = preempt_guard or PreemptionGuard(enabled=False)
    watchdog = hang_watchdog or HangWatchdog(
        hang_timeout_sec, recorder=recorder, is_main=is_main, rank=rank,
    )
    use_stream = data_path is not None
    # sentinel x stream composes since the fleet-supervisor round: a
    # rollback on the streaming path rewinds the record cursor to the
    # restored checkpoint's stream sidecar (closed-form fallback) and
    # rebuilds the prefetcher — see _roll_back_if_tripped. The replay
    # re-consumes the SAME records (unlike the synthetic path's
    # step-fold reseed): the records were never the poison — a corrupt
    # record is healed by the stream's own CRC quarantine — the device
    # state was, and that is what the restore replaces.
    if use_stream and data_stall_timeout_sec <= 0:
        # A non-positive timeout would classify every normal batch wait
        # as a fatal stall (or disable the classification entirely,
        # depending on sign) while the result row still recorded the
        # streaming identity — the silent-misconfiguration class the
        # other refusals exist for.
        raise ValueError(
            f"--data-stall-timeout-sec must be > 0, got "
            f"{data_stall_timeout_sec}"
        )
    numerics = (
        NumericsSentinel(recorder=recorder, is_main=is_main)
        if sentinel else None
    )
    # In-step grad-norm output: SPMD arms only. The pipelined arms run
    # their loss/backward inside a partially-manual shard_map whose
    # outputs trip XLA's tile-assignment validation when a replicated
    # reduction is appended after them (the same u32[4] lowering bug
    # class as the known interleaved-sharding issue — ROADMAP direction
    # 3); those arms keep the sentinel's loss-envelope and
    # parameter-checksum guards, with the grad-norm guard disabled and
    # announced rather than silently absent.
    sentinel_in_step = sentinel and pipeline_parallel == 1
    chaos = FaultInjector(
        parse_fault_spec(
            inject_fault if inject_fault is not None
            else os.environ.get("INJECT_FAULT")
        ),
        recorder=recorder, is_main=is_main, rank=rank,
    )
    if chaos.spec is not None and chaos.spec.kind in DATA_KINDS and not use_stream:
        # A data fault without the stream has no consumer: the run would
        # train normally and exit 0 while the chaos report claimed the
        # fault was survived — a silently inert injection proves nothing.
        raise ValueError(
            f"--inject-fault {chaos.spec} is a streaming-data fault and "
            "requires --data-path (without the stream the injector's "
            "data hooks have no consumer and the chaos run is inert)"
        )
    devices = jax.devices()
    # Multihost dryrun shape: a jax.distributed rendezvous exists (the
    # cross-host preempt-soon broadcast rides it) but each host drives its
    # OWN local mesh — the global device list leads with process 0's
    # chips, which other ranks cannot address. CPU-backend only (plus a
    # BENCH_PROCESS_LOCAL=1/0 override): on real accelerators a small
    # world_size must keep the global list and fail loudly rather than
    # silently training N independent replicas that publish as one
    # distributed measurement.
    _pl = os.environ.get("BENCH_PROCESS_LOCAL", "auto")
    process_local_world = (
        jax.process_count() > 1
        and world_size <= len(jax.local_devices())
        and (_pl == "1"
             or (_pl == "auto" and jax.default_backend() == "cpu"))
    )
    if process_local_world:
        devices = jax.local_devices()
    if world_size > len(devices):
        raise ValueError(
            f"world_size={world_size} but only {len(devices)} devices visible"
        )
    tp, sp, pp, ep = (
        tensor_parallel, sequence_parallel, pipeline_parallel, expert_parallel
    )
    if ep > 1 and n_experts == 0:
        raise ValueError("expert_parallel > 1 requires --num-experts > 0")
    if n_experts > 0 and ep > 1 and n_experts % ep != 0:
        raise ValueError(f"n_experts={n_experts} not divisible by expert_parallel={ep}")
    if world_size % (tp * sp * pp * ep) != 0:
        raise ValueError(
            f"world_size={world_size} not divisible by "
            f"tensor*sequence*pipeline*expert parallel={tp * sp * pp * ep}"
        )
    dp = world_size // (tp * sp * pp * ep)
    mesh = make_mesh(
        (dp, sp, tp, pp, ep),
        ("data", "seq", "model", "pipe", "expert"),
        devices=devices[:world_size],
    )
    if sp > 1 and attention_impl not in ("ring", "ulysses"):
        raise ValueError(
            "sequence_parallel > 1 requires --attention ring or ulysses"
        )
    if pp > 1 and tp > 1 and jax.default_backend() == "cpu":
        # XLA's CPU-only AllReducePromotion pass aborts the process compiling
        # the partially-manual pipeline with tensor-parallel collectives
        # inside ("Invalid binary instruction opcode copy"). Workaround:
        # XLA_FLAGS=--xla_disable_hlo_passes=all-reduce-promotion compiles and
        # runs tp x pp — including dp>1 x tp x pp now that pipeline runs keep
        # wte replicated over 'model' (the vocab-sharded embedding gather was
        # what tripped the SPMD partitioner CHECK; see
        # parallel/strategies.py param_partition_specs). TPU needs no flag.
        import os as _os

        from ..utils.platform import allreduce_promotion_disabled

        if not allreduce_promotion_disabled(_os.environ.get("XLA_FLAGS", "")):
            raise ValueError(
                "pipeline_parallel x tensor_parallel on the CPU backend needs "
                "XLA_FLAGS=--xla_disable_hlo_passes=all-reduce-promotion (XLA "
                "CPU compiler bug); TPU runs this composition without flags"
            )

    overrides = {} if dropout is None else {"dropout": dropout}
    if causal:
        # Causal masking is an explicit opt-in (reference parity keeps it
        # off, train_harness.py:127); causal rings auto-enable the zigzag
        # load-balanced layout (ops/ring_attention.py).
        overrides["causal"] = True
    if ring_zigzag is not None:
        # The knob only has a consumer on a real ring: without --attention
        # ring (or, for 'on', without a >1 seq axis) the model would fall
        # back to flash and silently drop the setting while the result row
        # still recorded it as run identity — a misconfigured A/B pair
        # would publish a legitimate-looking zero delta. Refuse instead.
        if attention_impl != "ring":
            raise ValueError(
                f"--ring-zigzag {'on' if ring_zigzag else 'off'} requires "
                "--attention ring (the zigzag layout is a ring-attention "
                f"property; got --attention {attention_impl})"
            )
        if ring_zigzag and sp <= 1:
            raise ValueError(
                "--ring-zigzag on requires --sequence-parallel > 1: with "
                "one sequence shard there is no ring to balance (use "
                "'auto', or add --sequence-parallel N)"
            )
        overrides["ring_zigzag"] = ring_zigzag
    if n_experts > 0:
        overrides["n_experts"] = n_experts
    if tp_collective_matmul:
        # Collective-matmul tp fusion (round 15, ops/collective_matmul.py):
        # the residual stream rides sequence-sharded over 'model' between
        # ppermute-ring projections. Compositions that already own the
        # sequence layout are refused loudly rather than silently
        # double-sharding: pipeline schedules run the stream manually over
        # 'seq', sequence-parallel attention shards S over 'seq', and the
        # MoE dispatch owns the token layout through the expert all-to-all.
        if pp > 1:
            raise ValueError(
                "--tp-collective-matmul cannot compose with pipeline "
                "parallelism (the pipeline runs the residual stream "
                "manually over 'seq'; drop one of the two)"
            )
        if sp > 1:
            raise ValueError(
                "--tp-collective-matmul cannot compose with sequence "
                "parallelism (both want to own the sequence axis; the "
                "ring/ulysses arms already overlap their comms)"
            )
        if n_experts > 0:
            raise ValueError(
                "--tp-collective-matmul does not support MoE models (the "
                "expert dispatch owns the token layout; dense MLPs only)"
            )
        overrides["tp_collective_matmul"] = True
    if layer_loop == "unrolled":
        # Unrolled layer loop: ~15% faster single-chip (activations save as
        # distinct buffers, no dynamic-update-slice stacking) at the cost of
        # 16x the HLO and slower compiles. scan stays the default.
        overrides["scan_layers"] = False
    elif layer_loop != "scan":
        raise ValueError(f"unknown layer_loop {layer_loop!r}")
    if model_family == "llama":
        from ..models.llama import get_llama_config

        # The family is causal by construction; --causal is redundant but
        # harmless (same value), and every other override applies on top.
        model_config = get_llama_config(
            tier, seq_len, attention_impl=attention_impl, **overrides
        )
    elif model_family == "tinygpt":
        model_config = get_model_config(
            tier, seq_len, attention_impl=attention_impl, **overrides
        )
    else:
        raise ValueError(
            f"unknown model_family {model_family!r} (expected 'tinygpt' or 'llama')"
        )
    if is_main:
        print(f"Strategy: {strategy.describe()}")
        print(
            f"Mesh: {dict(mesh.shape)} over {devices[0].device_kind!r} devices"
        )

    # Data-parallel width sets the global microbatch; tp/sp groups share
    # replicas of each example (matching how the reference's world_size
    # multiplies per-device batch for pure DP, reference train_harness.py:403).
    # Expert-parallel members hold distinct batch shards (the batch dim is
    # sharded over ('data', 'expert') — strategies.batch_partition_spec), so
    # the global microbatch scales with dp * ep.
    global_micro = per_device_batch * dp * ep

    # Fail fast on arms that cannot fit (e.g. tier B replicated on a 16 GiB
    # v5e chip) — refuse with a breakdown instead of an allocator OOM mid-run.
    from ..utils import memory as memory_mod
    from .step import _resolve_model_config

    if strategy.remat == "auto":
        import dataclasses as _dc

        from .step import abstract_step_peak_bytes

        def _aot_probe(pol: str):
            # Measured near-capacity decision: compile the REAL step for
            # this policy abstractly (no allocation) and return XLA's
            # buffer-assignment peak. ~one compile of startup cost, paid
            # only when the analytic margin is inconclusive.
            if is_main:
                print(f"Auto remat: probing '{pol}' via abstract AOT compile...")
            return abstract_step_peak_bytes(
                model_config, _dc.replace(strategy, remat=pol), mesh,
                grad_accum=grad_accum, seed=seed, from_table=True,
                global_micro=global_micro, seq_len=seq_len,
                dataset_size=dataset_size,
                pipeline_schedule=pipeline_schedule,
                virtual_stages=virtual_stages,
            )

        strategy = memory_mod.resolve_auto_remat(
            _resolve_model_config(model_config, strategy, mesh), strategy, mesh,
            per_device_batch, seq_len, dataset_size=dataset_size,
            device_kind=devices[0].device_kind,
            aot_probe=_aot_probe,
        )
        if is_main:
            print(f"Auto remat: resolved to '{strategy.remat}' for this arm")

    est = memory_mod.estimate_hbm(
        _resolve_model_config(model_config, strategy, mesh), strategy, mesh,
        per_device_batch, seq_len, dataset_size=dataset_size,
    )
    if is_main:
        print(memory_mod.format_breakdown(est, devices[0].device_kind))
    refusal = memory_mod.check_fits(est, devices[0].device_kind)
    if refusal is not None:
        if skip_memory_check:
            if is_main:
                print(f"WARNING (--skip-memory-check): {refusal}")
        else:
            raise ValueError(
                f"{refusal}\nPass --skip-memory-check to attempt the run anyway."
            )

    t_init = time.perf_counter()
    # Snapshot the allocator's process-lifetime high-water mark BEFORE this
    # arm allocates anything: when several arms share one process (bench.py
    # parity + flagship) the mark has no reset, and a later arm must not
    # publish an earlier arm's peak as its own (metrics.measure_peak_hbm
    # falls to the per-executable rung when the run didn't raise the mark).
    prior_peak_bytes = metrics_mod.peak_hbm_bytes()
    state = create_train_state(
        model_config, strategy, mesh, seed=seed, grad_accum=grad_accum,
        # Streaming runs feed per-step batches from the host prefetcher;
        # the synthetic path keeps the in-jit table gather (zero per-step
        # host->device transfers), byte-identical to every prior round.
        from_table=not use_stream, global_micro=global_micro, seq_len=seq_len,
        pipeline_schedule=pipeline_schedule, virtual_stages=virtual_stages,
        sentinel=sentinel_in_step,
    )
    if numerics is not None and not sentinel_in_step and is_main:
        print("SENTINEL: grad-norm guard unavailable on pipelined arms "
              "(shard_map lowering); loss-envelope and checksum guards "
              "remain active")
    if is_main:
        print(f"Model initialized: {state.n_params/1e6:.2f}M parameters")
        print(f"Init time: {time.perf_counter() - t_init:.1f}s")

    from jax.sharding import NamedSharding, PartitionSpec as P

    # Streaming-data-path state (None/inert on the default synthetic
    # path): the shard stream, its prefetcher, the per-window and
    # timed-phase starvation accumulators, and the consumed-batch resume
    # snapshot the checkpoint sidecars persist.
    ds = None
    table = None
    stream = None
    prefetch = None
    batch_sharding = None
    data_meta_box: list = [None]    # resume meta of the last CONSUMED batch
    data_wait_win = [0.0]           # input wait inside the open window
    data_wait_timed = [0.0]         # input wait over timed (post-warmup) steps
    records_per_step = grad_accum * global_micro
    cursor_start = 0
    if use_stream:
        from ..data import HostPrefetcher, ShardedTokenStream
        from ..parallel import strategies as strat_mod

        # Stream open validates the shard set (checksummed headers,
        # completeness) BEFORE any device work: a missing shard refuses
        # loudly here, naming the hole, instead of wasting compile time.
        stream = ShardedTokenStream(data_path, seq_len=seq_len, injector=chaos)
        batch_sharding = NamedSharding(
            mesh, P(None, *strat_mod.batch_partition_spec(mesh))
        )
        if is_main:
            print(f"ShardedTokenStream: {stream.describe()}")
    else:
        ds = SyntheticDataset(
            vocab_size=model_config.vocab_size, seq_len=seq_len, size=dataset_size, seed=seed
        )
        if is_main:
            print(f"SyntheticDataset: {dataset_size} samples, seq_len={seq_len}")

        # The dataset table lives on-device for the whole run (8 MB at
        # reference scale): per-step batches are gathered inside the jitted
        # step from the step index, so the hot loop performs zero
        # host->device transfers.
        replicated = NamedSharding(mesh, P())
        if jax.process_count() > 1:
            table = jax.make_array_from_callback(
                ds.data.shape, replicated, lambda idx: ds.data[idx]
            )
        else:
            table = jax.device_put(ds.data, replicated)
    params, opt_state = state.params, state.opt_state
    # Timed stats keyed by step so the sentinel's rollback can truncate
    # a poisoned tail and the replay can re-measure honestly (replayed
    # step TIMES stay excluded — their windows fold the restore; the
    # values are extracted into plain lists for compute_result below).
    # (step, window-mean step time, the same stopped after the loss fetch)
    timed_times: list = []
    timed_losses: list = []  # (step, loss)
    trace_started = False

    ckpt = None
    start_step = 0
    n_restarts = 0
    resume_step = -1
    resume_baseline_loss = 0.0
    resume_geometry_changed = False
    if checkpoint_dir:
        from ..parallel.mesh import mesh_axes_dict
        from ..runtime.checkpoint import BenchmarkCheckpointer

        # Tag the PHYSICAL parameter layout: interleaved permutes the stacked
        # layer axis (per virtual-stage count); gpipe/1f1b/no-pipeline share
        # the contiguous layout and may resume each other freely.
        interleaved = pp > 1 and pipeline_schedule == "interleaved"
        ckpt = BenchmarkCheckpointer(
            checkpoint_dir, save_every=checkpoint_every,
            layout={
                "layer_layout": (
                    f"interleaved:pp={pp}:v={virtual_stages}" if interleaved
                    else "contiguous"
                ),
            },
            # Geometry identity for the elastic-resume sidecars: a later
            # run on a different mesh reshard-restores against its OWN
            # templates and records the stitch (docs/FAULT_TOLERANCE.md).
            geometry={
                "mesh_axes": mesh_axes_dict(mesh),
                "world_size": world_size,
            },
            async_save=checkpoint_async,
            process_local=process_local_world,
        )
        if resume:
            # restore_latest validates digests newest-first, quarantining
            # torn steps and falling back — a corrupted tail never
            # surfaces as an orbax traceback, and an empty/all-torn
            # directory degrades to a cold start (the retrying
            # orchestration passes --resume unconditionally on retries).
            restored = ckpt.restore_latest(params, opt_state)
            if restored is not None:
                params, opt_state, resume_step = restored
                start_step = resume_step + 1
                if start_step >= steps:
                    # Nothing left to run: a "resumed" row here would have
                    # ZERO timed steps and publish 0 tokens/sec over the
                    # real result (observed when a retry loop re-resumes a
                    # run whose final step already checkpointed). Refuse —
                    # the orchestration's salvage path (heartbeat partial)
                    # is the honest record of the dead attempt. The
                    # dedicated exception maps to EXIT_NOTHING_TO_RESUME
                    # (77) in the harness, which the retry wrappers treat
                    # as terminal: the refusal is deterministic. The
                    # recorder already truncated telemetry_<arm>.jsonl at
                    # construction — discard it, or the refusal's
                    # run_aborted trail would sit beside the completed
                    # run's published row and make validate_results
                    # reject a perfectly good result.
                    recorder.discard()
                    raise NothingToResume(
                        f"--resume found checkpoint step {resume_step} but "
                        f"--steps {steps} leaves no steps to run: the run "
                        "already completed (or the checkpoint belongs to a "
                        "longer configuration). Nothing to measure — not "
                        "publishing a zero-step row."
                    )
                resume_geometry_changed = ckpt.last_resume_geometry_changed
                n_restarts = ckpt.note_restart(
                    geometry_changed=resume_geometry_changed
                )
                resume_baseline_loss = float(
                    ckpt.step_meta(resume_step).get("last_loss") or 0.0
                )
                recorder.note_resume(
                    step=resume_step, n_restarts=n_restarts,
                    baseline_loss=resume_baseline_loss or None,
                    geometry_changed=resume_geometry_changed,
                    source_geometry=ckpt.last_resume_source_geometry,
                )
                if is_main:
                    stitch = (
                        ", geometry changed" if resume_geometry_changed else ""
                    )
                    print(f"Resumed from checkpoint at step {resume_step} "
                          f"(restart #{n_restarts}{stitch})")
            elif is_main:
                print("Resume requested but no valid checkpoint found — "
                      "cold start")

    if use_stream:
        # Exact-resume seek: the authoritative position is the restored
        # step's stream sidecar (its cursor is geometry-independent, so a
        # geometry-change resume carries it over unchanged while per-host
        # shard ownership is recomputed from the new batch sharding). A
        # checkpoint without one (synthetic-path directory, failed
        # sidecar write) falls back to the closed-form cursor — exact for
        # same-geometry resumes, where records_per_step is unchanged.
        cursor_start = start_step * records_per_step
        if ckpt is not None and resume_step >= 0:
            side = ckpt.read_stream_state(resume_step)
            if side is not None:
                cursor_start = int(side.get("cursor", cursor_start))
            elif is_main:
                print("WARNING: resumed checkpoint has no stream-state "
                      f"sidecar; using the closed-form cursor {cursor_start} "
                      "(exact only for same-geometry resumes)")
        stream.seek(cursor_start)
        prefetch = HostPrefetcher(
            stream, sharding=batch_sharding, grad_accum=grad_accum,
            global_micro=global_micro, seq_len=seq_len,
            start_step=start_step, stop_step=steps,
            injector=chaos, multi_process=jax.process_count() > 1,
        ).start()
        if is_main:
            print(f"Streaming data path: cursor {cursor_start}, "
                  f"{records_per_step} records/step, stall timeout "
                  f"{data_stall_timeout_sec:g}s")

    # Sentinel cheap-rollback target (self-healing follow-up (b)): a run
    # with no checkpoint cadence used to REFUSE to heal — correct for
    # benchmarks (which always checkpoint) but it made every short smoke
    # run un-healable. Snapshot the pristine host-side params/opt-state
    # once, before the first dispatch (the "first boundary": the state is
    # validated by construction and the copy sits entirely off the timed
    # path), and _prepare_rollback falls back to it when no durable
    # checkpoint exists. Single-process only (device_get needs every
    # shard addressable; a one-host-only rollback on a multi-host run
    # would diverge the replicas). Accounting is unchanged: the heal flows
    # through the same note_rollback ledger.
    mem_snapshot = None
    if (
        numerics is not None
        and jax.process_count() == 1
        and (ckpt is None or checkpoint_every <= 0)
    ):
        mem_snapshot = (
            jax.device_get(params),
            jax.device_get(opt_state),
            start_step - 1,
        )
        if is_main:
            print("SENTINEL: no checkpoint cadence — holding an in-memory "
                  "params/opt-state snapshot as the rollback target")

    # Timing discipline. Steps are data-dependent (params chain through the
    # jitted step), so the device necessarily executes them back-to-back;
    # blocking on a step's loss therefore fences every step dispatched before
    # it. With sync_every=1 (default — the reference's per-step loss.item()
    # discipline, train_harness.py:390) each step is timed individually;
    # with sync_every=N the loop hard-syncs every N steps and each step in
    # the window is assigned the window's mean — the totals are identical,
    # but N>1 keeps host round-trip latency (dispatch + sync RPCs) out of
    # the hot loop, which matters when the host link is slow.
    pending: list = []  # (step, loss_handle, gnorm_handle|None) since last sync
    last_loss_box = [None]  # last synced loss — emergency-checkpoint meta

    def sync_window(t_start):
        """Block on the window's last loss; distribute wall time evenly.

        Also the telemetry boundary: with the device already fenced, the
        recorder logs the window (step/loss/mean time/HBM sample) and may
        print a heartbeat — the only sanctioned place for telemetry IO in
        the loop (graftcheck GC105). The numerics sentinel judges each
        synced step here (host floats only; a trip is handled at the top
        of the next loop iteration, before anything dispatches on the
        poisoned state), the hang watchdog is beaten, and the chaos
        injector's boundary hook fires LAST, after the window's telemetry
        committed: a fault's trail always records the window it killed —
        and an injected hang stalls with the beat already recorded, so
        the watchdog measures the stall itself.
        """
        if not pending:
            return
        jax.block_until_ready(pending[-1][1])
        dt = (time.perf_counter() - t_start) / len(pending)
        last = pending[-1][0]
        window_losses = [float(l) for _s, l, _g in pending]
        # The same window read again once the losses are on the host. The
        # published time is dt; the two are printed side by side at the end
        # of the run, because a runtime whose block_until_ready returned
        # early would show up as a gap between them.
        dt_fetched = (time.perf_counter() - t_start) / len(pending)
        for (s, _l, g), lf in zip(pending, window_losses):
            if s >= warmup_steps:
                if s > cursor.replay_until:
                    timed_times.append((s, dt, dt_fetched))
                timed_losses.append((s, lf))
            if is_main and s % _LOG_EVERY == 0:
                print(f"[Step {s:04d}] Loss: {lf:.4f}, Time: {dt:.3f}s")
            if numerics is not None:
                numerics.observe(
                    s, lf, float(g) if g is not None else None
                )
        recorder.step_window(
            last_step=last, losses=window_losses,
            window_mean_step_time_sec=dt,
            data_wait_sec=(
                round(data_wait_win[0], 6) if prefetch is not None else None
            ),
            records_skipped=(
                (data_meta_box[0] or {}).get("records_skipped")
                if prefetch is not None else None
            ),
        )
        if prefetch is not None:
            # Streaming-data boundary work, at the sanctioned GC105
            # cadence: the quarantine ledger drains into one
            # data_corrupt_record event per healed record, and a window
            # that spent more than half its wall starved for input opens
            # a (non-fatal) data_stall event — the telemetry sibling of
            # the published data_stall_frac.
            for entry in stream.drain_quarantine():
                recorder.note("data_corrupt_record", step=last, **entry)
            window_wall = dt * len(window_losses)
            if data_wait_win[0] > max(0.5 * window_wall, 0.05):
                recorder.note(
                    "data_stall", step=last, fatal=False,
                    wait_sec=round(data_wait_win[0], 6),
                    window_sec=round(window_wall, 6),
                )
            data_wait_win[0] = 0.0
        last_loss_box[0] = window_losses[-1]
        pending.clear()
        watchdog.beat(last)
        chaos.at_boundary(last)

    param_norm_fn = None
    last_checksum_box = [start_step]

    def _observe_checksum(at_step):
        """Sentinel parameter-tree checksum at one fenced boundary.

        One jitted global-norm reduction + a scalar host read — device
        work, but off the timed path (the caller restarts the window
        clock after). The jit is built lazily on first use and cache-hits
        thereafter.
        """
        nonlocal param_norm_fn
        if param_norm_fn is None:
            from .step import make_param_norm_fn

            param_norm_fn = make_param_norm_fn(mesh)
        numerics.observe_param_checksum(at_step, float(param_norm_fn(params)))

    def _prepare_rollback():
        """Restore the last validated checkpoint for an open sentinel trip.

        Returns ``((params, opt_state, restored_step), trip_step)``; when
        healing is impossible — no checkpointer, no validated step behind
        the run, or MAX_ROLLBACKS exhausted — raises
        :class:`faults.SentinelTripped` so the run fails LOUDLY instead of
        publishing (or endlessly replaying) a poisoned measurement.
        """
        trip = numerics.trip
        if not numerics.rollback_allowed:
            raise SentinelTripped(
                trip["kind"], trip["step"],
                f"{trip['detail']}; {numerics.n_rollbacks} rollback(s) "
                "already spent — persistent numerics failure, not a "
                "transient",
            )
        if ckpt is not None:
            recorder.begin_phase("checkpoint")
            restored = ckpt.restore_latest(params, opt_state)
            if restored is not None:
                return restored, trip["step"]
        if mem_snapshot is not None:
            # Cheap-rollback fallback: rebuild the device state from the
            # pre-dispatch host snapshot (the run has no durable
            # checkpoint to offer). The current params/opt_state arrays
            # carry the target shardings — the poisoned VALUES are about
            # to be overwritten, their placement is exactly right.
            recorder.begin_phase("checkpoint")
            snap_params, snap_opt, snap_step = mem_snapshot
            rb_params = jax.tree.map(
                lambda h, cur: jax.device_put(h, cur.sharding),
                snap_params, params,
            )
            rb_opt = jax.tree.map(
                lambda h, cur: jax.device_put(h, cur.sharding),
                snap_opt, opt_state,
            )
            if is_main:
                print("SENTINEL: rolling back to the in-memory snapshot "
                      "(no checkpoint cadence)")
            return (rb_params, rb_opt, snap_step), trip["step"]
        raise SentinelTripped(
            trip["kind"], trip["step"],
            f"{trip['detail']}; "
            + ("no validated checkpoint committed yet"
               if ckpt is not None else
               "no --checkpoint-dir (and no in-memory snapshot on this "
               "run shape) to roll back to"),
        )

    def _after_rollback(rb_step, tripped_at):
        """Bookkeeping half of a rollback: truncate the poisoned tail out
        of the timed stats, record the ledger + telemetry event, and
        re-open the right phase for the replay."""
        timed_times[:] = [e for e in timed_times if e[0] <= rb_step]
        timed_losses[:] = [e for e in timed_losses if e[0] <= rb_step]
        numerics.note_rollback(from_step=tripped_at, to_step=rb_step)
        recorder.begin_phase(
            "timed" if rb_step + 1 >= warmup_steps else "warmup"
        )

    def _rewind_stream(rb_step):
        """Rewind the streaming input path for a rollback replay.

        The restored checkpoint's ``stream_<step>.json`` sidecar is the
        authoritative cursor (records delivered THROUGH ``rb_step``);
        a restore without one — the in-memory-snapshot fallback, or a
        failed sidecar write — uses the closed-form cursor, exact
        because records_per_step is constant within a run. The old
        prefetcher is stopped WITH a join first: its producer thread
        advances ``stream.cursor`` as it reads ahead, and a seek issued
        under a live producer could be overwritten by an in-flight
        batch. Then a fresh prefetcher restarts production at
        ``rb_step + 1`` — the replay re-consumes the same records (the
        poison was the device state, not the stream; corrupt records
        are the CRC quarantine's job, and a re-quarantined record
        increments the skip ledger and its telemetry event in step).
        """
        nonlocal prefetch
        prefetch.stop(join=True)
        rewind = (
            cursor_start + max(rb_step + 1 - start_step, 0) * records_per_step
        )
        if ckpt is not None and rb_step >= 0:
            side = ckpt.read_stream_state(rb_step)
            if side is not None:
                rewind = int(side.get("cursor", rewind))
        stream.seek(rewind)
        data_meta_box[0] = None
        prefetch = HostPrefetcher(
            stream, sharding=batch_sharding, grad_accum=grad_accum,
            global_micro=global_micro, seq_len=seq_len,
            start_step=rb_step + 1, stop_step=steps,
            injector=chaos, multi_process=jax.process_count() > 1,
        ).start()
        if is_main:
            print(f"SENTINEL: stream rewound to cursor {rewind} — "
                  f"replaying records from step {rb_step + 1}", flush=True)

    def _roll_back_if_tripped():
        """The whole heal for an open trip: restore + bookkeeping +
        cursor rewind (both the HBM cursor and, on the streaming path,
        the record cursor). Returns the restored ``(params, opt_state)``
        (the caller rebinds its locals and restarts the window clock),
        or None when no trip is open. ONE implementation for both trip
        sources — the window observation and the checksum — so the two
        paths can never diverge."""
        if numerics.trip is None:
            return None
        restored, tripped_at = _prepare_rollback()
        rb_params, rb_opt, rb_step = restored
        _after_rollback(rb_step, tripped_at)
        cursor.rollback(rb_step, tripped_at)
        if prefetch is not None:
            _rewind_stream(rb_step)
        return rb_params, rb_opt

    def _stream_state_for(at_step):
        """The exact-resume sidecar payload for a fenced boundary at
        ``at_step`` (None on the synthetic path). The cursor is the
        records DELIVERED to training through that step — closed form
        from the run's own consumption, never the prefetcher's
        read-ahead position (which may sit a buffer depth ahead)."""
        if stream is None:
            return None
        delivered = (
            cursor_start + max(at_step + 1 - start_step, 0) * records_per_step
        )
        return {
            "schema_version": STREAM_STATE_SCHEMA_VERSION,
            "cursor": delivered,
            "records_skipped": (data_meta_box[0] or {}).get(
                "records_skipped", stream.records_skipped
            ),
            "total_records": stream.total_records,
        }

    def _data_stall_stop(at_step, waited_sec):
        """The input path starved the loop past --data-stall-timeout-sec.

        Called at a fenced boundary (the caller synced first): the device
        state is healthy and coherent — it is the INPUT that died — so
        this checkpoints at ``at_step`` with the stream sidecar, emits
        the fatal ``data_stall`` event + a final ``reason=data_stall``
        heartbeat (the partial-row classification, beside
        preempted|crash|hang), records ``run_aborted reason=data_stall``
        and raises :class:`DataStalled` — the harness maps it to
        ``EXIT_DATA_STALL`` (78, retryable-with-resume: the sidecar makes
        the retry consume exactly the un-consumed records).
        """
        saved = None
        if ckpt is not None and at_step >= max(start_step, 0):
            if ckpt.latest_step() == at_step:
                saved = at_step
            else:
                recorder.begin_phase("checkpoint")
                try:
                    ckpt.save(at_step, params, opt_state, force=True,
                              meta={"last_loss": last_loss_box[0],
                                    "emergency": True,
                                    "reason": "data_stall"},
                              stream_state=_stream_state_for(at_step))
                    saved = at_step
                    if is_main:
                        print(f"Emergency checkpoint saved at step "
                              f"{at_step} (data stall)")
                except Exception as e:
                    recorder.note("checkpoint_failed", step=at_step,
                                  error=str(e), emergency=True)
                    if is_main:
                        print(f"WARNING: emergency checkpoint at step "
                              f"{at_step} failed ({e}); aborting as a "
                              "plain data-stall partial")
        recorder.note(
            "data_stall", step=at_step + 1, fatal=True,
            wait_sec=round(waited_sec, 3),
            timeout_sec=data_stall_timeout_sec,
        )
        recorder.emergency_heartbeat(
            reason="data_stall",
            extra={"emergency_checkpoint_step": saved},
        )
        recorder.abort("data_stall")
        raise DataStalled(at_step + 1, waited_sec, saved_step=saved)

    def _emergency_stop(at_step):
        """SIGTERM landed: checkpoint at this fenced boundary and stop.

        Called only where the device is already fenced and ``pending``
        is empty, so params/opt_state are exactly the post-``at_step``
        state. Saves (when a checkpointer exists and at least one new
        step ran), prints the final heartbeat carrying the emergency
        checkpoint's metadata, emits ``run_aborted reason=preempted``,
        and raises Preempted — the harness maps it to EXIT_PREEMPTED.
        """
        saved = None
        if (
            ckpt is not None and ckpt.async_save
            and at_step >= max(start_step, 0)
            and (ckpt.pending_async_step() is not None
                 or ckpt.latest_step() is not None)
        ):
            # Async-delta emergency path (docs/FAULT_TOLERANCE.md): the
            # periodic async saves already streamed (or committed) the
            # state — only FLUSH the in-flight delta instead of writing a
            # fresh full checkpoint inside the grace window. The steps
            # since that save are bounded recompute on resume, recorded
            # honestly below.
            recorder.begin_phase("checkpoint")
            try:
                flushed = ckpt.finalize_pending()
                saved = ckpt.latest_step() if flushed is None else flushed
                recorder.note(
                    "emergency_flush", mode="async-delta", step=at_step,
                    committed_step=saved,
                    steps_delta=(at_step - saved if saved is not None
                                 else None),
                )
                if is_main:
                    print(f"Emergency flush: async checkpoint at step "
                          f"{saved} committed (preempted at boundary "
                          f"{at_step}; {at_step - saved} step(s) of "
                          "recompute on resume)")
            except Exception as e:
                recorder.note("checkpoint_failed", step=at_step,
                              error=str(e), emergency=True)
                saved = None
                if is_main:
                    print(f"WARNING: emergency async flush at step "
                          f"{at_step} failed ({e}); aborting as a plain "
                          "partial")
        elif ckpt is not None and at_step >= max(start_step, 0):
            if ckpt.latest_step() == at_step:
                # The periodic save already committed this exact boundary
                # (orbax refuses same-step overwrites even with force) —
                # the state is durable, which is all the resume needs.
                saved = at_step
            else:
                recorder.begin_phase("checkpoint")
                try:
                    ckpt.save(
                        at_step, params, opt_state, force=True,
                        meta={"last_loss": last_loss_box[0],
                              "emergency": True, "reason": "preempted"},
                        stream_state=_stream_state_for(at_step),
                    )
                    saved = at_step
                    if is_main:
                        print(f"Emergency checkpoint saved at step {at_step} "
                              "(preempted)")
                except Exception as e:
                    # Broadest net of any save site: whatever went wrong,
                    # the run must still abort AS PREEMPTED (clean trail,
                    # exit 75) rather than degrade to a generic crash.
                    recorder.note("checkpoint_failed", step=at_step,
                                  error=str(e), emergency=True)
                    if is_main:
                        print(f"WARNING: emergency checkpoint at step "
                              f"{at_step} failed ({e}); aborting as a "
                              "plain partial")
        recorder.emergency_heartbeat(
            reason="preempted",
            extra={"emergency_checkpoint_step": saved},
        )
        recorder.abort("preempted")
        raise Preempted(at_step, saved)

    if preempt.requested and jax.process_count() <= 1:
        # Preempted before the first dispatch (init/compile): nothing new
        # to save, but the abort trail still records the clean reason.
        # Multi-host runs defer to the first boundary poll instead — the
        # peers are still compiling, so the cross-host agreement cannot
        # complete yet (and stopping alone would wedge their collectives).
        _emergency_stop(start_step - 1)

    watchdog.start()
    recorder.begin_phase("compile")
    t_window = time.perf_counter()
    cursor = _StepCursor(start_step, steps)
    for step in cursor:
        # Sentinel boundary work FIRST (pending empty == the previous
        # iteration ended at a fenced boundary): an open trip must be
        # rolled back before anything dispatches on the poisoned state —
        # in particular before a periodic checkpoint could persist it.
        if numerics is not None and not pending:
            rolled = _roll_back_if_tripped()
            if rolled is None and (
                sentinel_checksum_every > 0
                and step - last_checksum_box[0] >= sentinel_checksum_every
            ):
                last_checksum_box[0] = step
                _observe_checksum(step - 1)
                t_window = time.perf_counter()
                rolled = _roll_back_if_tripped()
            if rolled is not None:
                params, opt_state = rolled
                t_window = time.perf_counter()
                continue
        if profile_dir and step == warmup_steps and is_main and not trace_started:
            sync_window(t_window)
            recorder.begin_phase("trace")
            jax.profiler.start_trace(profile_dir)
            trace_started = True
            t_window = time.perf_counter()
        if step == warmup_steps and step > start_step:
            if sync_every > 1:
                # Warmup excluded from averages; fence so its tail doesn't
                # leak into the first timed window.
                sync_window(t_window)
            recorder.begin_phase("timed")
            t_window = time.perf_counter()
        # Chaos param corruption (bitflip/grad-explode): poisons the
        # pre-dispatch handle exactly once at its armed step — the
        # sentinel-proof injection point. Inert (one attribute check)
        # when not armed. opt-moments poisons the OPTIMIZER state
        # instead (collapsed Adam second moments -> step N's update
        # explodes -> step N+1's grad-norm guard must trip FIRST).
        params = chaos.corrupt_params(step, params)
        opt_state = chaos.corrupt_opt_state(step, opt_state)
        if prefetch is not None:
            # The prefetch fence (graftcheck GC111): the one sanctioned
            # blocking pull on the input path inside the timed loop. The
            # measured wait feeds data_stall_frac; starving past the
            # timeout classifies the run as reason=data_stall at the
            # fenced boundary below — never as the watchdog's hang.
            try:
                stream_batch, data_meta, waited = prefetch.get(
                    step, timeout=data_stall_timeout_sec
                )
            except DataStallTimeout as e:
                sync_window(t_window)
                _data_stall_stop(step - 1, e.waited_sec)
            data_meta_box[0] = data_meta
            data_wait_win[0] += waited
            if step >= warmup_steps:
                data_wait_timed[0] += waited
            if sentinel_in_step:
                # Sentinel x stream: same in-step grad-norm guard as the
                # synthetic path, but the step index is NOT reseed-folded
                # — a rollback replay re-consumes the same records (the
                # stream rewind in _roll_back_if_tripped repositions the
                # cursor), so the step index must address the same rows.
                params, opt_state, loss, gnorm = state.step_fn(
                    params, opt_state, stream_batch, step
                )
            else:
                params, opt_state, loss = state.step_fn(
                    params, opt_state, stream_batch, step
                )
                gnorm = None
        elif numerics is None:
            params, opt_state, loss = state.step_fn(
                params, opt_state, table, step
            )
            gnorm = None
        elif sentinel_in_step:
            # Sentinel-armed step: fourth output is the in-step global
            # grad-norm. The step index is shifted by whole-run strides
            # per rollback (data_reseeds) so a replay draws fresh batch
            # rows and dropout keys instead of re-consuming the poisoned
            # sequence.
            params, opt_state, loss, gnorm = state.step_fn(
                params, opt_state, table,
                step + numerics.data_reseeds * steps,
            )
        else:
            # Pipelined sentinel arm: no in-step grad-norm (see the
            # sentinel_in_step note above) — same reseeded step fold.
            params, opt_state, loss = state.step_fn(
                params, opt_state, table,
                step + numerics.data_reseeds * steps,
            )
            gnorm = None
        loss = chaos.corrupt_loss(step, loss)
        pending.append((step, loss, gnorm))
        if step == start_step and step < warmup_steps:
            # Fence the first dispatched step on its own: its wall time is
            # dominated by the XLA compile, and attributing it to the
            # 'compile' phase (begun just before the loop) is what lets
            # telemetry_report answer "where did startup go". Only when the
            # first step is UNTIMED warmup: a timed first step (warmup 0,
            # or resume past warmup) keeps the pre-telemetry window shape —
            # a solo fence there would concentrate the whole compile into
            # step 0's published time and distort the p95/max/cv columns.
            sync_window(t_window)
            recorder.begin_phase("warmup")
            t_window = time.perf_counter()
        if len(pending) >= sync_every or step == steps - 1:
            sync_window(t_window)
            if recorder.phase in ("compile", "trace"):
                # Timed-first-step runs (warmup 0 / resume past warmup)
                # reach here still in 'compile' (or 'trace', when a warmup-0
                # run also profiles): the first window carries compile + its
                # steps inseparably (exactly as it is timed), and everything
                # after is honest 'timed'.
                recorder.begin_phase("timed")
            t_window = time.perf_counter()
        # Checkpointing happens at a sync boundary, outside the next timed
        # window, so benchmark step times stay honest.
        if ckpt is not None and ckpt.should_save(step):
            sync_window(t_window)
            if numerics is not None and numerics.trip is None:
                # Pre-save checksum, unconditional under the sentinel
                # (independent of the --sentinel-checksum-every cadence):
                # "roll back to the last VALIDATED checkpoint" is only
                # true if no save can ever persist a state the checksum
                # guard would reject — without this, an SDC that slips
                # between cadence points gets checkpointed and the
                # rollback would faithfully restore the poison. Also
                # advances the cadence clock: with aligned cadences the
                # periodic branch would otherwise recompute the identical
                # norm at the very next boundary.
                last_checksum_box[0] = step
                _observe_checksum(step)
            if numerics is not None and numerics.trip is not None:
                # A sentinel guard tripped in the window this boundary just
                # closed (or the pre-save checksum just failed): persisting
                # the state now would CHECKPOINT THE POISON and make every
                # future rollback restore it. Skip the save; the rollback
                # handler runs at the top of the next iteration, before
                # anything else dispatches.
                if is_main:
                    print(f"SENTINEL: skipping checkpoint save at step "
                          f"{step} (open {numerics.trip['kind']} trip)")
            else:
                recorder.begin_phase("checkpoint")
                try:
                    chaos.maybe_fail_save()
                    ckpt.save(step, params, opt_state,
                              meta={"last_loss": last_loss_box[0]},
                              stream_state=_stream_state_for(step))
                    if is_main:
                        mode = " (async dispatch)" if checkpoint_async else ""
                        print(f"Checkpoint saved at step {step}{mode}")
                    chaos.after_save(ckpt, step)
                except OSError as e:
                    # A full disk (ENOSPC et al.) must degrade the checkpoint
                    # cadence, never kill the benchmark: the run finishes on
                    # its older checkpoints, and the telemetry trail says why
                    # the cadence has a hole.
                    recorder.note("checkpoint_failed", step=step, error=str(e))
                    if is_main:
                        print(f"WARNING: checkpoint save at step {step} failed "
                              f"({e}); continuing without")
            recorder.begin_phase("timed" if step >= warmup_steps else "warmup")
            t_window = time.perf_counter()
        # Preemption poll — last statement of the body, so a SIGTERM that
        # arrived any time this iteration is acted on at the freshest
        # fenced boundary (and never mid-window: pending must be empty).
        # coordinate() makes the poll CROSS-HOST on a jax.distributed
        # rendezvous: any rank's guard flag is published on the
        # coordination service, every rank sees it at its next boundary,
        # and the agreed stop step (max of the ack boundaries) keeps the
        # emergency checkpoint one coherent collective save — today a
        # non-zero rank's SIGTERM no longer loses the run. Single-process
        # runs reduce to the plain flag check. The FINAL iteration still
        # COORDINATES (a host that skipped its last ack would leave a
        # late-SIGTERM'd peer blocking out its whole ack timeout inside
        # the grace window) but never STOPS: every step has executed by
        # then, so aborting would trade a complete measurement for a
        # resume that deterministically refuses — the post-loop branch
        # publishes instead.
        if not pending:
            # Cross-host hang coherence (faults/watchdog.py): a peer whose
            # watchdog fired published a hang flag; this rank is healthy
            # (it reached a boundary) but the RUN is hung — join the
            # coherent EXIT_HUNG abort instead of finishing a half-world
            # measurement. Non-blocking ~1ms KV poll, armed runs only.
            peer_hang = watchdog.peer_hang()
            if peer_hang is not None:
                watchdog.disarm()
                abort_on_peer_hang(recorder, step, peer_hang)
            preempt_target = preempt.coordinate(step)
            if (
                preempt_target is not None
                and step >= preempt_target
                and step < steps - 1
            ):
                _emergency_stop(step)

    sync_window(t_window)
    # Refresh the deadline at loop exit: the watchdog stays armed through
    # the final checkpoint save and the cross-host barrier below — the
    # barrier is exactly where a one-stalled-rank hang wedges every
    # HEALTHY rank (a rank that raced ahead blocks there forever), and
    # the watchdog firing inside it is what turns that into a coherent
    # all-host exit 76 instead of a coordination-service crash code.
    watchdog.beat(steps - 1)
    if numerics is not None and numerics.trip is not None:
        # A guard tripped at the very last boundary: there are no steps
        # left to replay the poison out of, so publishing would put the
        # corrupted tail into the row. Fail loudly instead.
        _trip = numerics.trip
        raise SentinelTripped(
            _trip["kind"], _trip["step"],
            f"{_trip['detail']}; tripped at the final boundary — nothing "
            "left to replay, not publishing a poisoned row",
        )
    if preempt.requested and is_main:
        # SIGTERM during the final window: every step already executed
        # and synced, so aborting would promise a resume that has NOTHING
        # left to run (the retry would refuse deterministically). The
        # honest reaction is to PUBLISH: the remaining finalize tail is
        # seconds against a grace window sized in minutes, and a kill
        # landing mid-finalize still leaves the normal crash trail plus
        # the final checkpoint committed below.
        print("NOTE: preemption requested during the final window; all "
              "steps completed — publishing the result before exiting")
    if ckpt is not None:
        recorder.begin_phase("checkpoint")
        # Final save only if this run actually executed steps — and only
        # when the final step is not ALREADY committed (a checkpoint
        # cadence dividing steps-1 lands the periodic save there first;
        # orbax refuses same-step overwrites even with force=True).
        if start_step < steps and ckpt.latest_step() != steps - 1:
            if numerics is not None:
                # Final-state checksum: the last committed checkpoint is
                # what every future --resume restores, so a poisoned
                # final state must fail the run loudly, not be enshrined.
                _observe_checksum(steps - 1)
                if numerics.trip is not None:
                    _trip = numerics.trip
                    raise SentinelTripped(
                        _trip["kind"], _trip["step"],
                        f"{_trip['detail']}; final-state checksum failed — "
                        "not committing a poisoned final checkpoint",
                    )
            try:
                chaos.maybe_fail_save()
                ckpt.save(steps - 1, params, opt_state, force=True,
                          meta={"last_loss": last_loss_box[0]},
                          stream_state=_stream_state_for(steps - 1))
            except OSError as e:
                recorder.note("checkpoint_failed", step=steps - 1,
                              error=str(e))
                if is_main:
                    print(f"WARNING: final checkpoint save failed ({e})")
        ckpt.close()
        # The final save/close is legitimate watchdog-covered time, but it
        # is IO, not cadence: refresh the deadline so the barrier below
        # gets the full timeout budget (operators must still size
        # --hang-timeout-sec above their slowest checkpoint write —
        # docs/FAULT_TOLERANCE.md).
        watchdog.beat(steps - 1)
    if trace_started:
        # stop_trace serializes the Chrome trace to disk — seconds for a
        # large run; bracket it so that cost attributes to 'trace', not to
        # whatever phase the loop left open.
        recorder.begin_phase("trace")
        jax.profiler.stop_trace()
    # Everything after the loop — barrier, memory accounting, diagnostics,
    # result computation/emission — is 'finalize': without a phase of its
    # own it would silently pad whatever phase happened to be open, and
    # the phase sum would drift from the measured wall time.
    recorder.begin_phase("finalize")

    dist.barrier()
    # Past the barrier every rank is provably alive and synced: nothing
    # beats the watchdog again, and the remaining finalize work (AOT
    # memory accounting, diagnostics, result emission) is single-host and
    # unbounded — that stretch belongs to the external liveness probe
    # (scripts/liveness_probe.sh).
    watchdog.disarm()

    if prefetch is not None:
        # Every step consumed its batch; release the producer thread and
        # the shard file handles before the finalize tail.
        prefetch.stop()
        stream.close()

    # Fetch the step executable for XLA's compile-time accounting — one
    # fetch serves all three consumers below: measure_peak_hbm rung 2
    # (when the allocator can't report a peak), the step-anatomy
    # roofline, and the memory-anatomy reconciliation (which ALWAYS
    # wants the compile-time half). Cache hit after the run — the AOT
    # path shares the jit executable cache, <1ms — so a failure here is a
    # fault in the step the run just measured, and it raises.
    # Streaming runs compile against an abstract batch aval (their
    # step takes a per-step batch, not the table); shapes/shardings
    # match the prefetcher's device puts, so it is the same cache-hit.
    aot_batch = table
    if use_stream:
        aot_batch = jax.ShapeDtypeStruct(
            (grad_accum, global_micro, seq_len), jnp.int32,
            sharding=batch_sharding,
        )
    compiled_step = state.aot_compile(params, opt_state, aot_batch, 0)

    # Step-anatomy attribution (analysis/step_anatomy.py, docs/
    # OBSERVABILITY.md): when this run captured a profiler trace, decompose
    # the traced device steps into compute / exposed-vs-overlapped
    # collective / idle time, position the arm on the roofline (the jitted
    # step's cost_analysis() FLOPs+bytes — available even on the CPU
    # dryrun — against utils/platform.py peaks), and publish the fractions
    # as additive result fields. The cost JSON lands beside the trace so
    # the offline CLI reproduces the same table later. A trace the engine
    # finds nothing usable in (its ValueError) degrades with a warning and
    # None fields; any other failure raises.
    step_anatomy_fields = None
    if trace_started and is_main and profile_dir:
        try:
            from ..analysis import step_anatomy as anatomy_mod

            cost = anatomy_mod.cost_from_compiled(
                compiled_step, device_kind=devices[0].device_kind,
                world_size=world_size,
            )
            if cost is not None:
                anatomy_mod.write_cost_json(profile_dir, cost)
            report = anatomy_mod.analyze_profile_dir(
                profile_dir, telemetry_path=recorder.path, cost=cost,
                pipeline_schedule=(pipeline_schedule if pp > 1 else None),
            )
            step_anatomy_fields = anatomy_mod.result_fields(report)
            # The per-class exposed split rides the telemetry event only
            # (compute_result pins the scalar result schema): the flight
            # recorder names WHICH collective class owns the exposed
            # time, most exposed first.
            recorder.note(
                "step_anatomy", **step_anatomy_fields,
                comms_exposed_by_class=(
                    anatomy_mod.exposed_by_class_fracs(report)
                ),
            )
            print(anatomy_mod.format_report(report))
        except ValueError as e:
            print(f"WARNING: step-anatomy attribution skipped: {e}")

    # Memory-anatomy reconciliation (analysis/memory_anatomy.py, docs/
    # OBSERVABILITY.md): fold the three memory sources this run already
    # produced — the pre-flight analytic estimate, XLA's compile-time
    # buffer accounting of the step executable (what ``aot_compile`` above
    # put into the process's record: ``scopes.step_memory()``, the one the
    # benchmark's reader prints), and the allocator's measured peak
    # (explicitly null-with-reason on backends without memory_stats) — into
    # the per-class attribution + the hbm_model_drift_frac secondary metric.
    from ..analysis import memory_anatomy as memano
    from ..utils import scopes

    measured_b, measured_reason = memano.measured_peak_bytes(prior_peak_bytes)
    mem_report = memano.reconcile(
        est,
        compile_mem=scopes.step_memory()["compiled"],
        measured_bytes=measured_b,
        measured_reason=measured_reason,
    )
    memory_anatomy_fields = memano.result_fields(
        mem_report, est_breakdown=est.breakdown()
    )
    recorder.note("memory_anatomy", **memory_anatomy_fields)
    if is_main:
        print(memano.format_report(mem_report))

    # MoE runs: measure the expert-capacity overflow (dropped-assignment
    # fraction) on the trained params with one diagnostic forward — the
    # published row's routing-health column (models.tinygpt
    # .moe_overflow_fraction).
    expert_overflow_pct = None
    # The interleaved schedule physically PERMUTES the stacked layer axis
    # (parallel/interleaved.py layer_permutation), so a plain apply_blocks
    # forward over those params would run layers out of order and publish a
    # silently wrong number — skip rather than mislead.
    interleaved_params = pp > 1 and pipeline_schedule == "interleaved"
    if n_experts > 0 and use_stream:
        # The diagnostic's probe batch comes from the synthetic table;
        # a streaming MoE arm skips it honestly rather than re-reading
        # records outside the accounted cursor.
        if is_main:
            print("NOTE: MoE overflow diagnostic skipped on the "
                  "streaming data path")
    elif n_experts > 0 and not interleaved_params:
        import functools

        from ..models import tinygpt as _tg
        from ..parallel import strategies as strat_mod

        ov_batch = jax.device_put(
            ds.batch_for_step(0, global_micro),
            NamedSharding(mesh, strat_mod.batch_partition_spec(mesh)),
        )
        with jax.set_mesh(mesh):
            # One-off post-run diagnostic forward: params are read-only
            # here and the scalar output needs no layout pin.
            frac = jax.jit(  # graftcheck: disable=GC101
                functools.partial(_tg.moe_overflow_fraction, state.model_config)
            )(params, ov_batch)
        expert_overflow_pct = round(float(jax.device_get(frac)) * 100.0, 4)

    # Extract the timed distributions from their step-keyed form (the
    # sentinel's rollback truncation is why they carry step ids at all);
    # replayed steps are absent from timed_times by construction.
    step_times = [dt for _s, dt, _f in timed_times]
    losses = [lf for _s, lf in timed_losses]
    if is_main and step_times:
        print(
            "Window clock, median s/step: "
            f"{np.median(step_times):.6f} stopped at block_until_ready, "
            f"{np.median([f for _s, _dt, f in timed_times]):.6f} stopped "
            "after the loss fetch"
        )
    # Streaming-data accounting for the published row: data_stall_frac is
    # the fraction of TIMED step wall spent starved for input (the waits
    # happen inside the windows whose times the row publishes, so the
    # fraction is structurally in [0, 1]); cursor start/end make the
    # resume continuity closed-form for validate_results.
    data_stall_frac = None
    data_stall_sec = 0.0
    records_consumed = 0
    records_skipped_total = 0
    stream_cursor_end = -1
    if use_stream:
        timed_total = sum(step_times)
        data_stall_sec = data_wait_timed[0]
        data_stall_frac = (
            max(0.0, min(data_stall_sec / timed_total, 1.0))
            if timed_total > 0 else 0.0
        )
        # MEASURED end position — the last consumed batch's cursor
        # snapshot, not the closed form: publishing the arithmetic would
        # make the validator's replayed-or-skipped check tautological
        # (both sides derived from the same multiplication). A healthy
        # run lands exactly on (steps - start_step) * records_per_step;
        # a drifted stream (double-advance, substitution over-consume)
        # now fails validation instead of hiding.
        stream_cursor_end = (data_meta_box[0] or {}).get(
            "cursor", cursor_start
        )
        records_consumed = stream_cursor_end - cursor_start
        records_skipped_total = stream.records_skipped
    result = metrics_mod.compute_result(
        strategy=strategy.name,
        world_size=world_size,
        rank=rank,
        seq_len=seq_len,
        tier=tier,
        steps=steps,
        per_device_batch=per_device_batch,
        grad_accum=grad_accum,
        step_times=step_times,
        losses=losses,
        n_rollbacks=numerics.n_rollbacks if numerics is not None else 0,
        rollback_steps_replayed=(
            numerics.rollback_steps_replayed if numerics is not None else 0
        ),
        device_kind=devices[0].device_kind,
        backend=jax.default_backend(),
        platform=devices[0].platform,
        device_count=jax.device_count(),
        n_params=state.n_params,
        attention_impl=attention_impl,
        dropout=model_config.dropout,
        flops_per_token=flops_mod.train_flops_per_token(model_config),
        est_hbm_gb=round(est.total / 1e9, 3),  # decimal GB, same unit as peak_hbm_gb
        compiled_step=compiled_step,
        sync_every=sync_every,
        tensor_parallel=tp,
        sequence_parallel=sp,
        pipeline_parallel=pp,
        pipeline_schedule=pipeline_schedule,
        virtual_stages=(
            virtual_stages if pp > 1 and pipeline_schedule == "interleaved"
            else 1
        ),
        expert_parallel=ep,
        n_experts=n_experts,
        remat_policy=state.model_config.remat,
        param_dtype=strategy.param_dtype,
        causal=model_config.causal,
        ring_zigzag=(
            "auto" if model_config.ring_zigzag is None
            else "on" if model_config.ring_zigzag else "off"
        ),
        tp_collective_matmul=model_config.tp_collective_matmul,
        expert_overflow_pct=expert_overflow_pct,
        model_family=model_family,
        resumed=resume_step >= 0,
        n_restarts=n_restarts,
        resume_step=resume_step,
        resume_baseline_loss=resume_baseline_loss,
        resume_geometry_changed=resume_geometry_changed,
        prior_peak_bytes=prior_peak_bytes,
        wall_time_total_sec=recorder.wall_time_total(),
        phase_times=recorder.phase_times(),
        n_anomalies=recorder.n_anomalies,
        step_anatomy=step_anatomy_fields,
        memory_anatomy=memory_anatomy_fields,
        data_mode="stream" if use_stream else "synthetic",
        data_stall_frac=(
            round(data_stall_frac, 6) if data_stall_frac is not None else None
        ),
        data_stall_sec=round(data_stall_sec, 4),
        records_consumed=records_consumed,
        records_skipped=records_skipped_total,
        stream_cursor_start=cursor_start if use_stream else -1,
        stream_cursor_end=stream_cursor_end,
    )
    if results_dir is not None:
        metrics_mod.emit_result(result, results_dir, is_main=is_main)
    recorder.close("ok")
    return result
