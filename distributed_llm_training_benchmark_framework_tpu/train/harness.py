"""CLI harness — flag-compatible with the reference, TPU semantics underneath.

Reference CLI: ``benchmarking/train_harness.py:465-504``. Unlike the
reference, every accepted flag is *live* (SURVEY §2.1 C9 lists ``--synthetic``
and ``--fsdp-config`` as accepted-but-inert there, and ``--grad-accum`` as
silently ignored for DDP/FSDP). Two reference flags that could only be inert
here are not accepted: ``--synthetic`` (the data path is chosen by
``--data-path`` alone) and ``--local-rank`` (device selection is mesh-driven).

Semantics mapping:
- ``--world-size`` counts chips (== the reference's GPU count). On a single
  host it selects the first N local devices; multi-host runs additionally set
  ``--num-processes``/``--process-id`` (or the env contract in
  ``runtime.distributed``).
- ``--rank``/``--master-addr``/``--master-port`` map onto the
  jax.distributed coordinator contract.
- ``--deepspeed-config``/``--fsdp-config`` are accepted aliases for
  ``--strategy-config`` pointing at ``configs/strategies/*.json`` (our live
  format). A DeepSpeed-format JSON is detected and *translated*: its
  optimizer/scheduler/clipping/precision values are mapped into the
  StrategyConfig (``parallel.strategies.from_deepspeed_config``), matching the
  reference's behavior of reading and mutating the file at runtime.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

from ..parallel import get_strategy, load_strategy_config, STRATEGIES
from ..parallel.strategies import from_deepspeed_config, is_deepspeed_config
from ..runtime import distributed as dist


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(description="TPU Distributed Training Benchmark")
    # Strategy (reference parity + our extended arms)
    p.add_argument("--strategy", type=str, required=True,
                   choices=sorted(STRATEGIES),
                   help="Distributed strategy arm")
    # Distributed
    p.add_argument("--world-size", type=int, required=True,
                   help="Total number of chips (== reference GPU count)")
    p.add_argument("--rank", type=int, default=0, help="Global process rank")
    p.add_argument("--master-addr", type=str, default="localhost",
                   help="Coordinator address (multi-host only)")
    p.add_argument("--master-port", type=int, default=29500)
    p.add_argument("--num-processes", type=int, default=None,
                   help="Number of host processes (default: env NUM_PROCESSES or 1)")
    p.add_argument("--tensor-parallel", type=int, default=1,
                   help="Tensor-parallel ('model' mesh axis) width")
    p.add_argument("--sequence-parallel", type=int, default=1,
                   help="Sequence-parallel ('seq' mesh axis) width; needs "
                        "--attention ring")
    p.add_argument("--pipeline-parallel", type=int, default=1,
                   help="Pipeline-parallel ('pipe' mesh axis) width; layer "
                        "count must divide evenly; grad-accum microbatches "
                        "feed the pipeline schedule")
    p.add_argument("--skip-memory-check", action="store_true",
                   help="Attempt the run even when the pre-flight HBM "
                        "estimate says it will not fit on this device")
    p.add_argument("--pipeline-schedule",
                   choices=["gpipe", "1f1b", "interleaved"],
                   default="gpipe",
                   help="Pipeline schedule: 'gpipe' (autodiff fill-drain, "
                        "O(M) activation liveness), '1f1b' (hand-scheduled "
                        "backward, O(P) liveness for long accumulation "
                        "chains), or 'interleaved' (Megatron virtual stages "
                        "— shrinks the fill/drain bubble by ~the "
                        "--virtual-stages factor)")
    p.add_argument("--virtual-stages", type=int, default=2,
                   help="Layer chunks per pipeline stage for "
                        "--pipeline-schedule interleaved (pipe * virtual "
                        "must divide n_layer)")
    p.add_argument("--expert-parallel", type=int, default=1,
                   help="Expert-parallel ('expert' mesh axis) width; needs "
                        "--num-experts divisible by it")
    p.add_argument("--num-experts", type=int, default=0,
                   help="Mixture-of-Experts MLP with this many experts "
                        "(0 = dense TinyGPT)")
    # Model & data
    p.add_argument("--tier", type=str, required=True, choices=["A", "B", "S"],
                   help="Model tier (S = tiny CPU/smoke tier, ours)")
    p.add_argument("--model-family", choices=["tinygpt", "llama"],
                   default="tinygpt",
                   help="Model architecture family: 'tinygpt' (reference "
                        "parity: LayerNorm/learned-pos/GELU, maskless by "
                        "default) or 'llama' (RMSNorm/RoPE/SwiGLU/GQA, "
                        "causal, head_dim-128 tiers — models.llama)")
    p.add_argument("--seq-len", type=int, required=True)
    p.add_argument("--data-path", type=str, default=None,
                   help="Directory of tokenized record shards "
                        "(scripts/make_tokenized_shards.py format): the "
                        "fault-tolerant streaming input path — checksummed "
                        "records, skip-and-quarantine healing, bounded "
                        "read retries, exact-resume cursor sidecars, and "
                        "a published data_stall_frac. Default: the "
                        "synthetic table (zero input IO)")
    p.add_argument("--data-stall-timeout-sec", type=float, default=60.0,
                   help="With --data-path: abort as reason=data_stall "
                        "(exit 78, retryable with --resume) when the "
                        "timed loop starves for input this long — "
                        "distinct from the watchdog's hang. Size it "
                        "BELOW --hang-timeout-sec so an input outage "
                        "classifies as data, not device")
    p.add_argument("--dataset-size", type=int, default=1000)
    p.add_argument("--attention", type=str, default="reference",
                   choices=["reference", "flash", "ring", "ulysses"],
                   help="Attention kernel implementation")
    p.add_argument("--dropout", type=float, default=None,
                   help="Override model dropout rate (default: tier's 0.1, "
                        "parity with the reference model)")
    p.add_argument("--ring-zigzag", choices=["auto", "on", "off"],
                   default="auto",
                   help="Zigzag causal load balancing on ring attention: "
                        "auto (on for causal rings when the geometry "
                        "allows), on (force; errors if it can't), off "
                        "(contiguous layout — the scaling-day A/B arm)")
    p.add_argument("--causal", action="store_true",
                   help="Causal (autoregressive) attention masking. Default "
                        "off for reference parity (train_harness.py:127 "
                        "applies no mask); on causal rings this auto-enables "
                        "the zigzag load-balanced layout")
    p.add_argument("--prng-impl", choices=["rbg", "threefry"], default="rbg",
                   help="Dropout-key PRNG: rbg (fast, default) or threefry "
                        "(bit-reproducible across backends)")
    p.add_argument("--tp-collective-matmul", action="store_true",
                   help="Overlap round 3 (ops/collective_matmul.py): run "
                        "the tensor-parallel projections as shard_map "
                        "collective matmuls — the activation all-gather/"
                        "reduce-scatter decomposed into ppermute ring hops "
                        "that hide inside the dots, with the residual "
                        "stream sequence-sharded over 'model'. Inert "
                        "without a >1 tensor-parallel axis; refuses "
                        "pipeline/sequence-parallel/MoE compositions. "
                        "Joins the result row and the regress lineage key "
                        "so cmm and plain runs never cross-gate")
    p.add_argument("--layer-loop", choices=["scan", "unrolled"], default="scan",
                   help="Transformer layer iteration: lax.scan over stacked "
                        "weights (fast compile) or an unrolled loop (~15%% "
                        "faster single-chip step; slower compile)")
    # Training
    p.add_argument("--steps", type=int, required=True)
    p.add_argument("--warmup-steps", type=int, default=5)
    p.add_argument("--per-device-batch", type=int, required=True)
    p.add_argument("--grad-accum", type=int, required=True)
    p.add_argument("--seed", type=int, default=42)
    p.add_argument("--sync-every", type=int, default=1,
                   help="Hard-sync (block on loss) every N steps; 1 = the "
                        "reference's per-step discipline, N>1 keeps host RPC "
                        "latency out of the timed loop on slow host links")
    # Configs
    p.add_argument("--param-dtype", choices=["f32", "bf16"], default=None,
                   help="Parameter/Adam-state storage dtype (default: the "
                        "arm's config, normally f32 master weights). bf16 "
                        "halves params+grads+moments — the knob that fits "
                        "tier B (1.68B, ~25 GiB fp32 state) on one 16 GiB "
                        "chip, at bf16-rounded-update precision")
    p.add_argument("--strategy-config", type=str, default=None,
                   help="Path to a configs/strategies/*.json file")
    p.add_argument("--deepspeed-config", type=str, default=None,
                   help="Alias for --strategy-config (reference CLI parity)")
    p.add_argument("--fsdp-config", type=str, default=None,
                   help="Alias for --strategy-config (reference CLI parity)")
    # Output
    p.add_argument("--results-dir", type=str, required=True)
    p.add_argument("--profile-dir", type=str, default=None,
                   help="If set, capture a jax.profiler trace after warmup")
    # Flight-recorder telemetry (docs/OBSERVABILITY.md): streaming JSONL
    # events + BENCHMARK_HEARTBEAT stdout markers so a hung/OOM'd/preempted
    # pod still leaves scrapeable progress in kubectl logs.
    p.add_argument("--telemetry", choices=["on", "off"], default="on",
                   help="Flight-recorder telemetry: JSONL event stream "
                        "(telemetry_<arm>.jsonl beside the result) plus "
                        "heartbeat stdout markers at sync boundaries")
    p.add_argument("--heartbeat-sec", type=float, default=30.0,
                   help="Minimum seconds between BENCHMARK_HEARTBEAT stdout "
                        "markers (rank 0, sync-window boundaries only; "
                        "0 = every window)")
    # Checkpoint / resume (orbax; absent entirely in the reference)
    p.add_argument("--checkpoint-dir", type=str, default=None)
    p.add_argument("--checkpoint-every", type=int, default=0,
                   help="Save every N steps (0 = only final)")
    p.add_argument("--checkpoint-async", action="store_true",
                   help="Dispatch periodic saves through orbax's async "
                        "writer and fence the commit at a later "
                        "sync-window boundary, so the timed path never "
                        "blocks on checkpoint IO; a preemption then only "
                        "FLUSHES the in-flight save (the steps since it "
                        "are bounded recompute on resume) — "
                        "docs/FAULT_TOLERANCE.md 'async delta'")
    p.add_argument("--resume", action="store_true",
                   help="Resume from the latest checkpoint in --checkpoint-dir "
                        "(elastic: a checkpoint saved under a different "
                        "mesh geometry is reshard-restored, publishing "
                        "resume_geometry_changed=true)")
    p.add_argument("--debug", action="store_true",
                   help="Fail-fast numerics: NaN checks, tracer-leak checks")
    # Chaos harness (faults/, docs/FAULT_TOLERANCE.md): deterministic
    # fault injection for recovery proofs; INJECT_FAULT env is the
    # flagless fallback.
    p.add_argument("--inject-fault", type=str, default=None,
                   help="Arm one deterministic chaos fault: sigkill@N, "
                        "sigterm@N, nan-loss@N, hang@N[:SECS], "
                        "stall-rank@N:R[:SECS], bitflip@N, "
                        "grad-explode@N, torn-checkpoint, enospc-on-save, "
                        "or (with --data-path) data-stall@N[:SECS], "
                        "data-corrupt-record@N, data-slow-reader@N:MS, "
                        "data-missing-shard@K — each fires at an exact "
                        "sync-window boundary (or record/shard index) so "
                        "chaos runs are reproducible "
                        "(scripts/chaos_suite.sh drives the matrix)")
    # Self-healing loop (faults/watchdog.py + faults/sentinel.py,
    # docs/FAULT_TOLERANCE.md): in-process hang detection with a
    # stack-dump abort, and numerics guards that roll back and replay
    # instead of dying.
    p.add_argument("--hang-timeout-sec", type=float, default=0.0,
                   help="Arm the hang watchdog: when no sync-window "
                        "boundary arrives for this many seconds, dump "
                        "all-thread stacks into a hang_dump telemetry "
                        "event, broadcast the hang to every rank, and "
                        "exit the distinct retryable code 76 (EXIT_HUNG). "
                        "0 = off. The k8s liveness probe's grace window "
                        "must EXCEED this timeout so the in-process dump "
                        "wins the race (scripts/liveness_probe.sh)")
    p.add_argument("--sentinel", choices=["on", "off"], default="off",
                   help="Numerics sentinel: screen each synced window's "
                        "loss and in-step global grad-norm; on a trip, "
                        "roll back in-process to the last validated "
                        "checkpoint, reseed the data stream and replay "
                        "(n_rollbacks accounting on the result row) "
                        "instead of dying. Adds one fused grad-norm "
                        "reduction to the step, so it is opt-in")
    p.add_argument("--sentinel-checksum-every", type=int, default=0,
                   help="With --sentinel on: every N steps, checksum the "
                        "parameter tree (global L2 norm) at a fenced "
                        "boundary to catch silent data corruption "
                        "(bitflips) that no loss/grad screen sees. "
                        "0 = checksum guard off")
    # Overlap round 2 (docs/PERFORMANCE.md): turn on XLA's latency-hiding
    # scheduler + async collective fusion (utils.platform
    # .LATENCY_HIDING_XLA_FLAGS) — the compiler half of the zero2
    # per-block reduce-scatter overlap. The flag set joins the result
    # row's env fingerprint (xla_scheduler_flags) and the regress
    # registry's config key, so flagged and unflagged runs never
    # cross-gate.
    p.add_argument("--xla-latency-hiding", action="store_true",
                   help="Append the latency-hiding-scheduler XLA flag set "
                        "to XLA_FLAGS before backend init (recorded in "
                        "the result row as xla_scheduler_flags)")
    return p


def resolve_strategy(args: argparse.Namespace):
    path = args.strategy_config or args.deepspeed_config or args.fsdp_config
    if path and not os.path.exists(path):
        raise FileNotFoundError(f"strategy config not found: {path}")
    if path:
        with open(path) as f:
            try:
                raw = json.load(f)
            except json.JSONDecodeError as e:
                raise ValueError(f"strategy config {path} is not valid JSON: {e}")
        if isinstance(raw, dict) and "strategy" in raw:
            sc = load_strategy_config(path)
            if sc.name != args.strategy:
                raise ValueError(
                    f"--strategy {args.strategy} but config file is for {sc.name}"
                )
            return sc
        if is_deepspeed_config(raw):
            # Honor the file's optimizer/scheduler/clipping values — the
            # reference reads and mutates its DeepSpeed JSON at runtime
            # (train_harness.py:246-262); "accepted alias" must not mean
            # "accepted and discarded".
            print(f"Note: translating DeepSpeed-format config {path} "
                  f"into the {args.strategy!r} arm")
            return from_deepspeed_config(raw, args.strategy)
        print(f"Note: {path} is not a recognized strategy config format; "
              f"using built-in {args.strategy!r} defaults")
    return get_strategy(args.strategy)


def main(argv=None) -> int:
    from ..utils.platform import (
        apply_latency_hiding_flags,
        enable_compile_cache,
        require_tpu,
    )

    args = build_parser().parse_args(argv)
    if args.xla_latency_hiding:
        # Must land in XLA_FLAGS before the first backend client exists —
        # setup_distributed below initializes it.
        apply_latency_hiding_flags()
    enable_compile_cache()
    # Reference parity: ZeRO arms demand a config path (train_harness.py:501-502).
    if args.strategy in ("zero2", "zero3") and not (
        args.strategy_config or args.deepspeed_config or args.fsdp_config
    ):
        default = os.path.join(
            os.path.dirname(__file__), "..", "..", "configs", "strategies",
            f"{args.strategy}.json",
        )
        if os.path.exists(default):
            args.strategy_config = default
        else:
            raise ValueError("ZeRO strategy requires --strategy-config")

    from ..runtime.debug import debug_requested, enable_debug

    if args.debug or debug_requested():
        enable_debug()

    strategy = resolve_strategy(args)
    if args.param_dtype is not None:
        import dataclasses as _dc

        strategy = _dc.replace(strategy, param_dtype=args.param_dtype)
    dist.setup_distributed(
        master_addr=args.master_addr,
        master_port=args.master_port,
        num_processes=args.num_processes,
        process_id=args.rank if args.num_processes else None,
    )
    from ..data import EXIT_DATA_STALL, DataStalled
    from ..faults import (
        EXIT_HUNG,
        EXIT_NOTHING_TO_RESUME,
        EXIT_PREEMPTED,
        Hung,
        NothingToResume,
        Preempted,
    )

    try:
        # After the rendezvous (jax.distributed must initialize before the
        # first backend touch): no chip found is an error, not a CPU run.
        require_tpu()
        from .loop import run_benchmark

        run_benchmark(
            strategy=strategy,
            tier=args.tier,
            model_family=args.model_family,
            seq_len=args.seq_len,
            steps=args.steps,
            warmup_steps=args.warmup_steps,
            per_device_batch=args.per_device_batch,
            grad_accum=args.grad_accum,
            world_size=args.world_size,
            rank=args.rank,
            tensor_parallel=args.tensor_parallel,
            sequence_parallel=args.sequence_parallel,
            pipeline_parallel=args.pipeline_parallel,
            pipeline_schedule=args.pipeline_schedule,
            virtual_stages=args.virtual_stages,
            skip_memory_check=args.skip_memory_check,
            expert_parallel=args.expert_parallel,
            n_experts=args.num_experts,
            results_dir=args.results_dir,
            seed=args.seed,
            attention_impl=args.attention,
            dropout=args.dropout,
            causal=args.causal,
            ring_zigzag={"auto": None, "on": True, "off": False}[args.ring_zigzag],
            layer_loop=args.layer_loop,
            tp_collective_matmul=args.tp_collective_matmul,
            prng_impl=args.prng_impl,
            dataset_size=args.dataset_size,
            sync_every=args.sync_every,
            profile_dir=args.profile_dir,
            checkpoint_dir=args.checkpoint_dir,
            checkpoint_every=args.checkpoint_every,
            checkpoint_async=args.checkpoint_async,
            resume=args.resume,
            telemetry=args.telemetry == "on",
            heartbeat_sec=args.heartbeat_sec,
            inject_fault=args.inject_fault,
            hang_timeout_sec=args.hang_timeout_sec,
            sentinel=args.sentinel == "on",
            sentinel_checksum_every=args.sentinel_checksum_every,
            data_path=args.data_path,
            data_stall_timeout_sec=args.data_stall_timeout_sec,
        )
    except Preempted as e:
        # Distinct exit code: the retrying orchestration (with_retries.sh,
        # docker/entrypoint.sh) keys resume-instead-of-cold-restart on it.
        print(f"PREEMPTED: {e} — exiting {EXIT_PREEMPTED} "
              "(resume with --resume)", flush=True)
        return EXIT_PREEMPTED
    except NothingToResume as e:
        # Deterministic refusal — its own code so retry wrappers stop
        # instead of burning their backoff budget on identical attempts.
        print(f"NOTHING TO RESUME: {e} — exiting {EXIT_NOTHING_TO_RESUME}",
              flush=True)
        return EXIT_NOTHING_TO_RESUME
    except DataStalled as e:
        # The input path starved the timed loop: its own retryable code —
        # the device was healthy, so retry wrappers resume exactly like a
        # preemption (the stream sidecar carries the cursor), while the
        # classification separates an input outage from a device hang.
        print(f"DATA STALL: {e} — exiting {EXIT_DATA_STALL} "
              "(resume with --resume)", flush=True)
        return EXIT_DATA_STALL
    except Hung as e:
        # A PEER rank's watchdog reported a hang (this rank is healthy —
        # the stuck one already dumped its stacks and exited 76 from its
        # own watchdog thread). Unanimous EXIT_HUNG: the retry wrappers
        # treat it as retryable-with-resume on every rank.
        print(f"HUNG: {e} — exiting {EXIT_HUNG} (retryable with --resume)",
              flush=True)
        return EXIT_HUNG
    finally:
        dist.cleanup_distributed()
    return 0


if __name__ == "__main__":
    sys.exit(main())
