#!/usr/bin/env python
"""Headline benchmark: parity tokens/sec/chip PLUS the flagship llama arm.

Prints exactly ONE JSON line on stdout:
    {"metric": ..., "value": N, "unit": ..., "vs_baseline": N,
     ..., "flagship": {...}}

Baseline: the reference's best published per-GPU throughput — DeepSpeed
ZeRO-2 on 4x A10 at 18,147 tokens/sec total = 4,536.75 tokens/sec/GPU
(reference README.md:221, BASELINE.md), at the same parity config:
tier A (~236M params), seq_len 2048, per-device batch 1, grad-accum 4,
100 steps with 5 warmup steps excluded.

The top-level contract keys (metric/value/unit/vs_baseline) deliberately
keep the reference's model shape + dropout so vs_baseline stays
apples-to-apples. The framework's FASTEST measured arm is the Llama
family (58.2k tok/s at 45.2% MFU on the same chip — README "Measured
results", docs/PERFORMANCE.md §16), and the default invocation now also
RUNS it: the additive ``"flagship"`` sub-object carries the llama arm's
tokens/sec/chip, MFU and peak-HBM (with provenance) from a real measured
run at the family's swept geometry (per-device batch 2 x grad-accum 2,
unrolled layer loop — §16's published row). ``--model-family llama``
instead makes the llama arm the top-level metric; ``--flagship off``
skips the extra run.
"""

import argparse
import contextlib
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

REFERENCE_BEST_TOKENS_PER_SEC_PER_GPU = 18147.0 / 4  # ZeRO-2, 4x A10

# The flagship arm's swept batch geometry (docs/PERFORMANCE.md §16: b2 fills
# the MXU's M dimension without b4's activation pressure; unrolled beats the
# scan by ~22% at the family's wider MLP).
FLAGSHIP_FAMILY = "llama"
FLAGSHIP_PER_DEVICE_BATCH = 2
FLAGSHIP_GRAD_ACCUM = 2
FLAGSHIP_LAYER_LOOP = "unrolled"

# The remat/HBM frontier (--remat-sweep): every policy the model accepts
# (models/tinygpt.normalize_remat) plus 'auto' (the loop's AOT-probe
# resolver). Ordered from zero recompute to full recompute.
REMAT_SWEEP_POLICIES = ("none", "dots", "full", "auto")


def _measure_row(args, world, *, model_family, per_device_batch, grad_accum,
                 layer_loop, attention_impl=None, dropout="inherit",
                 use_checkpoint=True, profile_dir=None, remat="inherit"):
    """Run one benchmark arm and return its contract-shaped row dict.

    Shared by the parity row and the flagship sub-object so the contract
    keys (metric/value/unit/vs_baseline) and the additive visibility keys
    are built in exactly one place. ``attention_impl``/``dropout`` default
    to the CLI flags; the flagship caller pins them so its row always
    means the published configuration.
    """
    from distributed_llm_training_benchmark_framework_tpu.parallel import get_strategy
    from distributed_llm_training_benchmark_framework_tpu.train.loop import run_benchmark

    strategy = get_strategy(args.strategy)
    if remat != "inherit":
        # Remat/HBM frontier sweep (--remat-sweep): the same arm at an
        # overridden remat policy. Strategy-level because that is where
        # the policy lives for every arm (train/step.py folds it into the
        # model config; 'auto' resolves via the loop's AOT probe).
        import dataclasses

        strategy = dataclasses.replace(strategy, remat=remat)

    # Keep stdout clean for the single JSON line; progress goes to stderr.
    # Checkpointing (off by default — a headline measurement doesn't
    # checkpoint): --checkpoint-dir/-every/-async thread through so the
    # async-delta cadence is measurable from the headline driver too
    # (time_in_checkpoint_sec rides the contract row's phase fields).
    with contextlib.redirect_stdout(sys.stderr):
        result = run_benchmark(
            strategy=strategy,
            tier=args.tier,
            seq_len=args.seq_len,
            model_family=model_family,
            steps=args.steps,
            warmup_steps=args.warmup_steps,
            per_device_batch=per_device_batch,
            grad_accum=grad_accum,
            world_size=world,
            results_dir=None,
            attention_impl=(
                args.attention if attention_impl is None else attention_impl
            ),
            dropout=args.dropout if dropout == "inherit" else dropout,
            sync_every=args.sync_every,
            layer_loop=layer_loop,
            tp_collective_matmul=args.tp_collective_matmul,
            checkpoint_dir=args.checkpoint_dir if use_checkpoint else None,
            checkpoint_every=args.checkpoint_every if use_checkpoint else 0,
            checkpoint_async=args.checkpoint_async and use_checkpoint,
            profile_dir=profile_dir,
            # Streaming data path (off by default — the headline stays the
            # zero-IO synthetic table, contract row byte-identical).
            data_path=args.data_path,
            data_stall_timeout_sec=args.data_stall_timeout_sec,
        )
    per_chip = result.tokens_per_sec / world
    row_extra = {}
    if result.xla_scheduler_flags:
        # Scheduler-flag provenance (additive, only when flags are live):
        # store.config_key reads it off the row, so a --xla-latency-hiding
        # run forms its own regress lineage instead of cross-gating
        # against unflagged history. Default runs keep the contract row
        # byte-identical (empty fingerprint -> key omitted -> "" lineage).
        row_extra["xla_scheduler_flags"] = result.xla_scheduler_flags
    if result.tp_collective_matmul:
        # Collective-matmul provenance (additive, only when the fusion is
        # live): store.config_key reads it off the row, so a
        # --tp-collective-matmul run forms its own regress lineage instead
        # of cross-gating against the plain-tp history. Default runs keep
        # the contract row byte-identical (key omitted -> plain lineage).
        row_extra["tp_collective_matmul"] = True
    if result.comms_exposed_frac is not None:
        # Step-anatomy secondaries (additive, only when the arm profiled):
        # these ride into the registry record's result row, where the gate
        # verdicts comms_exposed_frac beside MFU/peak-HBM
        # (stats.SECONDARY_METRICS). update(), not assignment — a profiled
        # run under --xla-latency-hiding must keep its scheduler-flag
        # lineage key too.
        row_extra.update({
            k: getattr(result, k) for k in (
                "anatomy_compute_frac", "comms_exposed_frac",
                "comms_overlap_frac", "anatomy_idle_frac", "bubble_frac",
                "roofline_flops_pct_of_peak", "roofline_hbm_pct_of_peak",
            ) if getattr(result, k) is not None
        })
    if result.data_mode == "stream":
        # Streaming-data columns (additive, stream arms only): the
        # data_stall_frac rides into the registry result row, where the
        # gate verdicts it beside the other SECONDARY_METRICS — and the
        # data_mode key splits stream arms into their own lineage so a
        # streamed run never cross-gates against the synthetic headline.
        row_extra.update({
            "data_mode": result.data_mode,
            "data_stall_frac": result.data_stall_frac,
            "records_skipped": result.records_skipped,
        })
    if result.hbm_attribution is not None:
        # Memory-anatomy columns (analysis/memory_anatomy.py): the
        # measured+attributed HBM of this arm, riding into the registry
        # result row so hbm_model_drift_frac gates as a secondary metric
        # and make_report's frontier/memory tables read the attribution.
        row_extra.update({
            "hbm_estimate_gib": (result.hbm_estimate or {}).get("total_gib"),
            "hbm_measured": result.hbm_measured,
            "hbm_measured_reason": result.hbm_measured_reason,
            "hbm_attribution": result.hbm_attribution,
            "hbm_attribution_source": result.hbm_attribution_source,
            "hbm_reference_gib": result.hbm_reference_gib,
            "hbm_model_drift_frac": result.hbm_model_drift_frac,
        })
    if remat != "inherit":
        # Frontier-sweep provenance: the REQUESTED policy keys the regress
        # lineage (store.config_key) — 'auto' stays one lineage even
        # though the probe may resolve it differently across hardware —
        # and the resolved policy + HBM headroom (capacity minus measured
        # peak; None off-TPU) make the frontier table self-contained.
        from distributed_llm_training_benchmark_framework_tpu.utils import (
            memory as memory_mod,
        )

        cap = memory_mod.device_hbm_bytes(result.device_kind)
        row_extra.update({
            "remat_policy": remat,
            "remat_policy_resolved": result.remat_policy,
            "hbm_headroom_gb": (
                round(cap / 2**30 - result.peak_hbm_gb, 2)
                if cap else None
            ),
        })
    return {
        "metric": (
            f"{model_family}_tier{args.tier}_seq{args.seq_len}"
            "_tokens_per_sec_per_chip"
        ),
        "value": round(per_chip, 2),
        "unit": "tokens/sec/chip",
        "vs_baseline": round(per_chip / REFERENCE_BEST_TOKENS_PER_SEC_PER_GPU, 3),
        # Where the number was taken, as jax reports it: a value is a
        # device measurement only when platform says "tpu".
        "platform": result.platform,
        "device_kind": result.device_kind,
        "device_count": result.device_count,
        # Visibility extras (additive; the contract keys above are unchanged):
        # exactly which semantics produced the number, and how far from peak.
        "attention_impl": result.attention_impl,
        "dropout": result.dropout,
        "model_tflops_per_sec_per_chip": round(
            result.model_tflops_per_sec_per_chip, 2
        ),
        "mfu_pct": round(result.mfu_pct, 2),
        # Measured peak device memory (allocator or XLA buffer-assignment;
        # see utils/metrics.measure_peak_hbm) with its provenance.
        "peak_hbm_gb": round(result.peak_hbm_gb, 2),
        "peak_hbm_method": result.peak_hbm_method,
        "tokens_per_dollar": (
            round(result.tokens_per_dollar) if result.tokens_per_dollar else None
        ),
        # Flight-recorder phase attribution (telemetry.TelemetryRecorder):
        # where this arm's wall time went — compile vs timed is the number
        # that explains a slow bench.py invocation at a glance.
        "wall_time_total_sec": round(result.wall_time_total_sec, 2),
        "time_in_compile_sec": round(result.time_in_compile_sec, 2),
        "time_in_timed_sec": round(result.time_in_timed_sec, 2),
        "n_anomalies": result.n_anomalies,
        **row_extra,
    }


def build_parser():
    p = argparse.ArgumentParser()
    p.add_argument("--strategy", default="zero2")
    p.add_argument("--tier", default="A")
    p.add_argument("--seq-len", type=int, default=2048)
    p.add_argument("--steps", type=int, default=100)
    p.add_argument("--warmup-steps", type=int, default=5)
    p.add_argument("--per-device-batch", type=int, default=1)
    p.add_argument("--grad-accum", type=int, default=4)
    p.add_argument("--world-size", type=int, default=None,
                   help="default: all visible devices")
    # The top-level metric's model family. 'tinygpt' (default) keeps the
    # reference-parity architecture for vs_baseline; 'llama' makes the
    # wide-head family (models/llama.py) the headline row itself.
    p.add_argument("--model-family", default="tinygpt",
                   choices=["tinygpt", "llama"])
    # The flagship sub-object: 'auto' runs the llama arm at its swept
    # geometry whenever the top-level family is tinygpt (one default
    # invocation reports both parity AND the framework's honest best);
    # 'on' forces it even for --model-family llama; 'off' skips the run.
    p.add_argument("--flagship", default="auto", choices=["auto", "on", "off"])
    # flash is the headline config: same model/loss/optimizer/data as the
    # parity setup, including in-kernel attention-probability dropout (the
    # probabilities still never materialize in HBM). Pass
    # --attention reference for the materialized-softmax run.
    p.add_argument("--attention", default="flash",
                   choices=["reference", "flash", "ring", "ulysses"])
    p.add_argument("--dropout", type=float, default=None)
    # Hard-sync every N steps instead of every step: totals are identical
    # (steps are device-sequential), but per-step host sync latency stays
    # out of the hot loop — see the timing-discipline note in train/loop.py.
    p.add_argument("--sync-every", type=int, default=10)
    # Unrolled layer loop measures ~15% faster than lax.scan on one chip
    # (no dynamic-update-slice activation stacking); scan remains the
    # harness default for compile time and pipeline runs.
    p.add_argument("--layer-loop", default="unrolled", choices=["scan", "unrolled"])
    # Checkpoint cadence (off by default): measure the checkpoint tax —
    # with --checkpoint-async the periodic saves leave the timed path and
    # time_in_checkpoint_sec shows the saving directly.
    # Profiler capture for the top-level arm (the flagship sub-run gets a
    # `<dir>_flagship` sibling): wraps the timed window in jax.profiler,
    # runs the step-anatomy attribution (analysis/step_anatomy.py) and
    # rides the compute/exposed-comms/idle + roofline fields into the row
    # — and so into the registry, where they gate as secondary metrics.
    p.add_argument("--profile-dir", default=None)
    p.add_argument("--data-path", default=None,
                   help="tokenized record shards for the streaming input "
                        "path (data/stream.py); default: synthetic table")
    p.add_argument("--data-stall-timeout-sec", type=float, default=60.0,
                   help="with --data-path: abort as reason=data_stall "
                        "past this input starvation (exit 78)")
    p.add_argument("--checkpoint-dir", default=None)
    p.add_argument("--checkpoint-every", type=int, default=0)
    p.add_argument("--checkpoint-async", action="store_true",
                   help="async periodic saves (orbax async writer, commit "
                        "fenced at sync boundaries) — the emergency path "
                        "then only flushes the in-flight delta")
    # Run-registry integration (regress/, docs/REGRESSION.md): 'auto'
    # ingests this invocation's rows and prints a one-line verdict vs the
    # last known good WHEN a registry already exists (seeded at
    # results/registry, or pointed at by $REGRESS_REGISTRY); 'on' creates
    # the registry if needed; 'off' skips. Verdict goes to stderr — the
    # stdout single-JSON-line contract is untouched.
    p.add_argument("--regress", default="auto", choices=["auto", "on", "off"])
    p.add_argument("--registry", default=None,
                   help="registry root (default: $REGRESS_REGISTRY or "
                        "results/registry)")
    # Overlap round 2 (docs/PERFORMANCE.md): the latency-hiding-scheduler
    # XLA flag set (utils.platform.LATENCY_HIDING_XLA_FLAGS), applied
    # before backend init. Recorded as xla_scheduler_flags in every row,
    # which keys a SEPARATE regress lineage — flagged and unflagged runs
    # never cross-gate.
    p.add_argument("--xla-latency-hiding", action="store_true",
                   help="turn on XLA's latency-hiding scheduler + async "
                        "collective fusion for this invocation")
    # Overlap round 3 (docs/PERFORMANCE.md §20): run the tp projections as
    # ppermute-ring collective matmuls (ops/collective_matmul.py). Inert
    # without tensor parallelism; recorded on the row and in the regress
    # lineage key so cmm and plain runs never cross-gate.
    p.add_argument("--tp-collective-matmul", action="store_true",
                   help="decompose the tensor-parallel projection comms "
                        "into ppermute rings that overlap the matmuls "
                        "(collective matmul; needs a >1 'model' mesh axis "
                        "to have any effect)")
    # Remat/HBM frontier sweep: re-run the flagship arm once per remat
    # policy and report tokens/sec vs peak-HBM per policy (additive
    # "remat_sweep" sub-object; one registry record per policy, the
    # policy inside the config key so lineages stay separate).
    p.add_argument("--remat-sweep", action="store_true",
                   help="sweep the flagship arm across remat policies "
                        f"{REMAT_SWEEP_POLICIES} (the HBM-vs-recompute "
                        "frontier; make_report renders the table)")
    return p


def main():
    args = build_parser().parse_args()

    from distributed_llm_training_benchmark_framework_tpu.utils.platform import (
        apply_latency_hiding_flags,
        enable_compile_cache,
        require_tpu,
    )

    if args.xla_latency_hiding:
        # Must precede the first jax backend touch below.
        apply_latency_hiding_flags()
    enable_compile_cache()
    # One process per chip: this process measures, and starts no child.
    require_tpu()

    import jax

    world = args.world_size or jax.device_count()

    payload = _measure_row(
        args, world,
        model_family=args.model_family,
        per_device_batch=args.per_device_batch,
        grad_accum=args.grad_accum,
        layer_loop=args.layer_loop,
        profile_dir=args.profile_dir,
    )

    run_flagship = args.flagship == "on" or (
        args.flagship == "auto" and args.model_family != FLAGSHIP_FAMILY
    )
    if run_flagship:
        # The flagship arm: same tier/seq/steps/strategy as the top-level
        # row, llama family at its swept batch geometry, with the published
        # row's flash + dropout-free semantics PINNED — a parity-arm
        # --dropout/--attention override must not silently change what the
        # "flagship" key measures. Run in the same process, reported
        # additively.
        payload["flagship"] = {
            **_measure_row(
                args, world,
                model_family=FLAGSHIP_FAMILY,
                per_device_batch=FLAGSHIP_PER_DEVICE_BATCH,
                grad_accum=FLAGSHIP_GRAD_ACCUM,
                layer_loop=FLAGSHIP_LAYER_LOOP,
                attention_impl="flash",
                dropout=None,  # the family's native 0.0
                # A shared --checkpoint-dir must not mix two arms' states
                # in one directory; checkpointing belongs to the top row.
                use_checkpoint=False,
                # Separate profile dir: two arms' traces in one directory
                # would make the anatomy/summary run selection ambiguous.
                profile_dir=(f"{args.profile_dir}_flagship"
                             if args.profile_dir else None),
            ),
            # Run-identity provenance: exactly which configuration produced
            # the flagship number (the §16 swept geometry).
            "model_family": FLAGSHIP_FAMILY,
            "strategy": args.strategy,
            "tier": args.tier,
            "seq_len": args.seq_len,
            "per_device_batch": FLAGSHIP_PER_DEVICE_BATCH,
            "grad_accum": FLAGSHIP_GRAD_ACCUM,
            "layer_loop": FLAGSHIP_LAYER_LOOP,
        }

    if args.remat_sweep:
        # The HBM-vs-recompute frontier: the flagship configuration once
        # per policy (additive "remat_sweep" sub-object keyed by the
        # REQUESTED policy — rows carry the resolved policy and the
        # per-chip HBM headroom; make_report renders the frontier table
        # from the registry records these become).
        payload["remat_sweep"] = {
            pol: _measure_row(
                args, world,
                model_family=FLAGSHIP_FAMILY,
                per_device_batch=FLAGSHIP_PER_DEVICE_BATCH,
                grad_accum=FLAGSHIP_GRAD_ACCUM,
                layer_loop=FLAGSHIP_LAYER_LOOP,
                attention_impl="flash",
                dropout=None,
                use_checkpoint=False,
                remat=pol,
            )
            for pol in REMAT_SWEEP_POLICIES
        }

    print(json.dumps(payload))
    record_in_registry(args, payload)


def registry_rows(args, payload):
    """(source, contract_row, run_params) per registry record to ingest.

    Run parameters ride into each record: the registry's config_key
    includes them, so a --steps 12 smoke invocation forms its own
    lineage instead of polluting the default 100-step headline's noise
    floor — and a DEFAULT invocation's key matches the committed legacy
    seed's (store.ingest_legacy backfills the same flagless defaults;
    pinned by tests/test_regress.py).
    """
    run_params = {
        "strategy": args.strategy, "tier": args.tier,
        "seq_len": args.seq_len, "steps": args.steps,
        "warmup_steps": args.warmup_steps,
        "sync_every": args.sync_every,
    }
    rows = [("bench.py", {k: v for k, v in payload.items()
                          if k not in ("flagship", "remat_sweep")},
             dict(run_params, model_family=args.model_family,
                  per_device_batch=args.per_device_batch,
                  grad_accum=args.grad_accum,
                  layer_loop=args.layer_loop))]
    if "flagship" in payload:
        # The flagship sub-object already carries its swept geometry
        # provenance keys; only the shared run length is added.
        rows.append(("bench.py:flagship", payload["flagship"], run_params))
    for pol, row in sorted(payload.get("remat_sweep", {}).items()):
        # One record per policy. The row already carries remat_policy
        # (the config-key axis that keeps each policy its own lineage);
        # the flagship geometry is backfilled the same way the flagship
        # sub-object records its own.
        rows.append((
            f"bench.py:remat-sweep:{pol}", row,
            dict(run_params, model_family=FLAGSHIP_FAMILY,
                 per_device_batch=FLAGSHIP_PER_DEVICE_BATCH,
                 grad_accum=FLAGSHIP_GRAD_ACCUM,
                 layer_loop=FLAGSHIP_LAYER_LOOP),
        ))
    return rows


def record_in_registry(args, payload) -> None:
    """Ingest this invocation's rows and report a verdict vs last-good.

    Runs after the result line is printed, so the measurement is already
    out; a registry that cannot be read or written then fails the process
    (non-zero exit) instead of passing as a warning. Everything prints to
    stderr.
    """
    if args.regress == "off":
        return
    from distributed_llm_training_benchmark_framework_tpu.regress import (
        compare as regress_compare,
        store as regress_store,
    )

    reg = regress_store.Registry(args.registry)
    if args.regress == "auto" and not reg.exists():
        print(
            f"regress: no registry at {reg.root} — skipping ingest "
            "(seed one with `regress ingest --legacy`, or pass "
            "--regress on)", file=sys.stderr,
        )
        return
    for source, row, extra in registry_rows(args, payload):
        rec = regress_store.record_from_bench_row(
            row, source=source, extra_result=extra,
        )
        rec, created = reg.ingest(rec)
        tag = "" if created else " (already ingested)"
        print(f"regress: recorded {rec['arm']} {rec['record_id']}"
              f"{tag} -> {reg.root}", file=sys.stderr)
        print(regress_compare.verdict_line_for_bench(reg, rec),
              file=sys.stderr)


if __name__ == "__main__":
    main()
