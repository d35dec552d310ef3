#!/usr/bin/env bash
# Container entrypoint: env-var contract -> harness CLI.
#
# Contract parity with the reference entrypoint (docker/entrypoint.sh there:
# env defaults, RANK from JOB_COMPLETION_INDEX, MASTER_ADDR resolution, device
# probe, exec python -u). TPU differences:
#   - all workers are symmetric (no master/worker split): the process id comes
#     from TPU_WORKER_ID (pod slices) or JOB_COMPLETION_INDEX (Indexed Jobs);
#   - NUM_PROCESSES counts hosts; WORLD_SIZE counts chips;
#   - the device probe is a JAX device listing instead of nvidia-smi.
set -euo pipefail

echo "=== TPU Distributed Training Entrypoint ==="
date

export STRATEGY="${STRATEGY:-ddp}"            # ddp | fsdp | zero2 | zero3
export WORLD_SIZE="${WORLD_SIZE:-1}"          # total chips
export NUM_PROCESSES="${NUM_PROCESSES:-1}"    # host processes

# Process id: TPU pod-slice env wins, then K8s Indexed Job completion index.
if [ -n "${TPU_WORKER_ID:-}" ]; then
  export RANK="$TPU_WORKER_ID"
elif [ -n "${JOB_COMPLETION_INDEX:-}" ]; then
  export RANK="$JOB_COMPLETION_INDEX"
else
  export RANK="${RANK:-0}"
fi

# Coordinator: rank 0 announces its own POD_IP; everyone else uses the
# headless-service DNS name (same hostNetwork/DNS pattern the reference
# documents for its NCCL rendezvous).
if [ "$RANK" = "0" ] && [ -n "${POD_IP:-}" ]; then
  export MASTER_ADDR="$POD_IP"
else
  export MASTER_ADDR="${MASTER_ADDR:-127.0.0.1}"
fi
export MASTER_PORT="${MASTER_PORT:-29500}"

export SEQ_LEN="${SEQ_LEN:-2048}"
export TIER="${TIER:-A}"                      # A | B | S
export STEPS="${STEPS:-50}"
export WARMUP_STEPS="${WARMUP_STEPS:-5}"
export PER_DEVICE_BATCH="${PER_DEVICE_BATCH:-1}"
export GRAD_ACCUM="${GRAD_ACCUM:-1}"
export ATTENTION="${ATTENTION:-reference}"
export LAYER_LOOP="${LAYER_LOOP:-scan}"
export RESULTS_DIR="${RESULTS_DIR:-/results}"
# Extended axes (defaults = off); set via pod env overlays for composition
# runs — every accepted knob is live (no inert flags).
export TENSOR_PARALLEL="${TENSOR_PARALLEL:-1}"
export SEQUENCE_PARALLEL="${SEQUENCE_PARALLEL:-1}"
export PIPELINE_PARALLEL="${PIPELINE_PARALLEL:-1}"
export PIPELINE_SCHEDULE="${PIPELINE_SCHEDULE:-gpipe}"
export VIRTUAL_STAGES="${VIRTUAL_STAGES:-2}"
export EXPERT_PARALLEL="${EXPERT_PARALLEL:-1}"
export NUM_EXPERTS="${NUM_EXPERTS:-0}"
export PARAM_DTYPE="${PARAM_DTYPE:-}"
export CAUSAL="${CAUSAL:-0}"
export MODEL_FAMILY="${MODEL_FAMILY:-tinygpt}"
export RING_ZIGZAG="${RING_ZIGZAG:-auto}"
# Full flag-surface coverage (empty = harness default; graftcheck rule
# GC201 — analysis/static/lint.py, pinned by tests/test_distributed_runtime
# and run in every preflight — checks that every harness flag is reachable
# from the container env, so new flags cannot silently miss the k8s path).
export SEED="${SEED:-}"
export SYNC_EVERY="${SYNC_EVERY:-}"
export DATASET_SIZE="${DATASET_SIZE:-}"
# Streaming data path (data/stream.py, docs/FAULT_TOLERANCE.md): a
# directory of tokenized record shards mounted into the pod; empty keeps
# the zero-IO synthetic table. The stall timeout classifies an input
# outage as reason=data_stall (exit 78) — size it below HANG_TIMEOUT_SEC.
export DATA_PATH="${DATA_PATH:-}"
export DATA_STALL_TIMEOUT_SEC="${DATA_STALL_TIMEOUT_SEC:-}"
export DROPOUT="${DROPOUT:-}"
export PRNG_IMPL="${PRNG_IMPL:-}"
export SKIP_MEMORY_CHECK="${SKIP_MEMORY_CHECK:-0}"
export PROFILE_DIR="${PROFILE_DIR:-}"
export CHECKPOINT_DIR="${CHECKPOINT_DIR:-}"
export CHECKPOINT_EVERY="${CHECKPOINT_EVERY:-}"
export RESUME="${RESUME:-0}"
export DEBUG="${DEBUG:-0}"
# Chaos harness (faults/, docs/FAULT_TOLERANCE.md): arm one deterministic
# fault (sigkill@N / sigterm@N / nan-loss@N / hang@N / stall-rank@N:R /
# bitflip@N / grad-explode@N / torn-checkpoint / enospc-on-save) — chaos
# pods prove the recovery path on real slices.
export INJECT_FAULT="${INJECT_FAULT:-}"
# Self-healing loop (faults/watchdog.py + faults/sentinel.py): in-process
# hang watchdog (seconds; 0 = off — MUST stay below the liveness probe's
# LIVENESS_GRACE_SEC so the stack-dump abort wins the race, see
# scripts/liveness_probe.sh) and the numerics sentinel's
# rollback-and-replay guards.
export HANG_TIMEOUT_SEC="${HANG_TIMEOUT_SEC:-}"
# SENTINEL accepts the harness's on|off AND this file's 0/1 boolean
# convention (CHECKPOINT_ASYNC=1 et al.) — an operator mirroring the
# sibling toggles must not crash argparse.
export SENTINEL="${SENTINEL:-}"
case "$SENTINEL" in 1) SENTINEL=on ;; 0) SENTINEL="" ;; esac
export SENTINEL_CHECKSUM_EVERY="${SENTINEL_CHECKSUM_EVERY:-}"
# In-pod recovery supervision: 0/0 (default) keeps the exec'd
# single-attempt path (python as PID 1 — the preStop/terminationGrace
# SIGTERM contract). SUPERVISOR=1 or MAX_ARM_RETRIES > 0 execs
# scripts/with_retries.sh as PID 1 instead, which is now a thin shim
# into the elastic fleet supervisor (runtime/supervisor.py, docs/
# FAULT_TOLERANCE.md) — the ONE retry implementation for the whole
# repo: it supervises the harness as a child with a trap-and-forward
# TERM handler (kubelet's grace signal still reaches the preemption
# handler), classifies every exit against the EXIT_* registry, retries
# under the recovery policy with backoff, resumes from CHECKPOINT_DIR
# when one is configured (shrinking the geometry against the checkpoint
# sidecar when device capacity dropped), never re-fires an injected
# chaos fault on a recovery attempt, and writes the per-attempt
# supervision.json ledger into RESULTS_DIR.
#   SUPERVISOR=1        run under the supervisor even with
#                       MAX_ARM_RETRIES=0 (policy decides the budgets)
#   RECOVERY_POLICY     recovery-policy JSON path (empty = the legacy
#                       MAX_ARM_RETRIES/RETRY_BACKOFF_SEC env mapping)
export SUPERVISOR="${SUPERVISOR:-0}"
export RECOVERY_POLICY="${RECOVERY_POLICY:-}"
export MAX_ARM_RETRIES="${MAX_ARM_RETRIES:-0}"
export RETRY_BACKOFF_SEC="${RETRY_BACKOFF_SEC:-5}"
# Async delta checkpointing (docs/FAULT_TOLERANCE.md): periodic saves off
# the timed path; the emergency path only flushes the in-flight delta.
export CHECKPOINT_ASYNC="${CHECKPOINT_ASYNC:-0}"
# Flight-recorder telemetry (docs/OBSERVABILITY.md): on by default — the
# heartbeat markers are what scripts/collect_results.sh scrapes into a
# partial_<arm>.json when a pod dies before the final result marker.
export TELEMETRY="${TELEMETRY:-}"
export HEARTBEAT_SEC="${HEARTBEAT_SEC:-}"
# Overlap round 2 (docs/PERFORMANCE.md): 1 = turn on XLA's latency-hiding
# scheduler + async collective fusion before backend init. The flag set is
# recorded in the result row (xla_scheduler_flags) and keys a separate
# regress lineage, so flagged pods never cross-gate against unflagged
# history.
export XLA_LATENCY_HIDING="${XLA_LATENCY_HIDING:-0}"
# Overlap round 3 (docs/PERFORMANCE.md §20): 1 = run the tensor-parallel
# projections as collective matmuls (ppermute-ring decomposed comms,
# ops/collective_matmul.py). Joins the result row + regress lineage key,
# so cmm pods never cross-gate against plain-tp history.
export TP_COLLECTIVE_MATMUL="${TP_COLLECTIVE_MATMUL:-0}"

echo "Config:"
for v in STRATEGY WORLD_SIZE NUM_PROCESSES RANK MASTER_ADDR MASTER_PORT \
         SEQ_LEN TIER STEPS WARMUP_STEPS PER_DEVICE_BATCH GRAD_ACCUM \
         ATTENTION LAYER_LOOP; do
  echo "  $v=${!v}"
done
echo ""

echo "TPU Status:"
python - <<'EOF' || echo "WARNING: device probe failed"
import jax
print(f"  backend={jax.default_backend()} devices={jax.devices()}")
EOF
echo ""

ARGS="--strategy ${STRATEGY} --world-size ${WORLD_SIZE} --rank ${RANK}"
ARGS="${ARGS} --num-processes ${NUM_PROCESSES}"
ARGS="${ARGS} --master-addr ${MASTER_ADDR} --master-port ${MASTER_PORT}"
ARGS="${ARGS} --seq-len ${SEQ_LEN} --tier ${TIER} --steps ${STEPS}"
ARGS="${ARGS} --warmup-steps ${WARMUP_STEPS}"
ARGS="${ARGS} --per-device-batch ${PER_DEVICE_BATCH} --grad-accum ${GRAD_ACCUM}"
ARGS="${ARGS} --attention ${ATTENTION} --layer-loop ${LAYER_LOOP}"
ARGS="${ARGS} --results-dir ${RESULTS_DIR}"
if [ "${TENSOR_PARALLEL}" != "1" ]; then
  ARGS="${ARGS} --tensor-parallel ${TENSOR_PARALLEL}"; fi
if [ "${SEQUENCE_PARALLEL}" != "1" ]; then
  ARGS="${ARGS} --sequence-parallel ${SEQUENCE_PARALLEL}"; fi
if [ "${PIPELINE_PARALLEL}" != "1" ]; then
  ARGS="${ARGS} --pipeline-parallel ${PIPELINE_PARALLEL}"
  ARGS="${ARGS} --pipeline-schedule ${PIPELINE_SCHEDULE}"
  if [ "${PIPELINE_SCHEDULE}" = "interleaved" ]; then
    ARGS="${ARGS} --virtual-stages ${VIRTUAL_STAGES}"; fi
fi
if [ "${EXPERT_PARALLEL}" != "1" ]; then
  ARGS="${ARGS} --expert-parallel ${EXPERT_PARALLEL}"; fi
if [ "${NUM_EXPERTS}" != "0" ]; then
  ARGS="${ARGS} --num-experts ${NUM_EXPERTS}"; fi
if [ -n "${PARAM_DTYPE}" ]; then
  ARGS="${ARGS} --param-dtype ${PARAM_DTYPE}"; fi
if [ "${MODEL_FAMILY}" != "tinygpt" ]; then
  ARGS="${ARGS} --model-family ${MODEL_FAMILY}"; fi
if [ "${CAUSAL}" = "1" ]; then
  ARGS="${ARGS} --causal"; fi
if [ "${RING_ZIGZAG}" != "auto" ]; then
  ARGS="${ARGS} --ring-zigzag ${RING_ZIGZAG}"; fi
# Valued knobs: empty means "use the harness default".
if [ -n "${SEED}" ]; then ARGS="${ARGS} --seed ${SEED}"; fi
if [ -n "${SYNC_EVERY}" ]; then ARGS="${ARGS} --sync-every ${SYNC_EVERY}"; fi
if [ -n "${DATASET_SIZE}" ]; then
  ARGS="${ARGS} --dataset-size ${DATASET_SIZE}"; fi
if [ -n "${DATA_PATH}" ]; then
  ARGS="${ARGS} --data-path ${DATA_PATH}"; fi
if [ -n "${DATA_STALL_TIMEOUT_SEC}" ]; then
  ARGS="${ARGS} --data-stall-timeout-sec ${DATA_STALL_TIMEOUT_SEC}"; fi
if [ -n "${DROPOUT}" ]; then ARGS="${ARGS} --dropout ${DROPOUT}"; fi
if [ -n "${PRNG_IMPL}" ]; then ARGS="${ARGS} --prng-impl ${PRNG_IMPL}"; fi
if [ -n "${PROFILE_DIR}" ]; then
  ARGS="${ARGS} --profile-dir ${PROFILE_DIR}"; fi
if [ -n "${CHECKPOINT_DIR}" ]; then
  ARGS="${ARGS} --checkpoint-dir ${CHECKPOINT_DIR}"; fi
if [ -n "${CHECKPOINT_EVERY}" ]; then
  ARGS="${ARGS} --checkpoint-every ${CHECKPOINT_EVERY}"; fi
if [ -n "${TELEMETRY}" ]; then
  ARGS="${ARGS} --telemetry ${TELEMETRY}"; fi
if [ -n "${HEARTBEAT_SEC}" ]; then
  ARGS="${ARGS} --heartbeat-sec ${HEARTBEAT_SEC}"; fi
# Boolean knobs: 1 = pass the flag.
if [ "${SKIP_MEMORY_CHECK}" = "1" ]; then
  ARGS="${ARGS} --skip-memory-check"; fi
if [ "${RESUME}" = "1" ]; then ARGS="${ARGS} --resume"; fi
if [ "${XLA_LATENCY_HIDING}" = "1" ]; then
  ARGS="${ARGS} --xla-latency-hiding"; fi
if [ "${TP_COLLECTIVE_MATMUL}" = "1" ]; then
  ARGS="${ARGS} --tp-collective-matmul"; fi
if [ "${DEBUG}" = "1" ]; then ARGS="${ARGS} --debug"; fi
if [ "${CHECKPOINT_ASYNC}" = "1" ]; then ARGS="${ARGS} --checkpoint-async"; fi
if [ -n "${INJECT_FAULT}" ]; then
  ARGS="${ARGS} --inject-fault ${INJECT_FAULT}"; fi
if [ -n "${HANG_TIMEOUT_SEC}" ]; then
  ARGS="${ARGS} --hang-timeout-sec ${HANG_TIMEOUT_SEC}"; fi
if [ -n "${SENTINEL}" ]; then
  ARGS="${ARGS} --sentinel ${SENTINEL}"; fi
if [ -n "${SENTINEL_CHECKSUM_EVERY}" ]; then
  ARGS="${ARGS} --sentinel-checksum-every ${SENTINEL_CHECKSUM_EVERY}"; fi

# GRAFTCHECK=1: run the static preflight (collective-budget audit + lint,
# scripts/graftcheck.sh) before launching. Runs on the container's host CPU
# (the tool pins its own CPU backend), so a sharding regression in the image
# fails the pod in seconds instead of burning slice time. Off by default:
# multi-host launches would redundantly audit once per worker.
export GRAFTCHECK="${GRAFTCHECK:-0}"
if [ "${GRAFTCHECK}" = "1" ]; then
  echo "=== Preflight: graftcheck static analysis ==="
  /app/scripts/graftcheck.sh || exit 1
  echo ""
fi
if [[ "${STRATEGY}" == "zero2" || "${STRATEGY}" == "zero3" ]]; then
  ARGS="${ARGS} --strategy-config /app/configs/strategies/${STRATEGY}.json"
fi

echo "=== Launching Training ==="
echo "Command: python -u /app/benchmarking/train_harness.py ${ARGS}"
echo ""
# The k8s livenessProbe (scripts/liveness_probe.sh) reads run progress
# from the flight recorder's telemetry JSONL under $RESULTS_DIR — the
# stdout stream stays untouched (interposing a tee on PID 1's stdout
# risks losing the final result markers in the teardown race), and exec
# keeps python as PID 1.
if [ "${SUPERVISOR}" = "0" ] && [ "${MAX_ARM_RETRIES}" = "0" ]; then
  exec python -u /app/benchmarking/train_harness.py ${ARGS}
fi

# Supervised mode: exec scripts/with_retries.sh as PID 1 — the thin shim
# into the elastic fleet supervisor (the ONE retry implementation:
# exit classification, policy-driven bounded attempts with backoff,
# resume-not-cold-restart, geometry shrink/regrow against the checkpoint
# sidecar, injected-fault stripping, and the trap-and-forward TERM
# handler that keeps kubelet's grace signal reaching the harness child
# even though the supervisor, not the harness, is PID 1). Resume only
# makes sense with a checkpoint dir behind it — --resume without one is
# a silent no-op in the harness, but passing the flag conditionally
# keeps retry argvs byte-honest about what they can actually do. The
# supervisor reads RECOVERY_POLICY (or the MAX_ARM_RETRIES/
# RETRY_BACKOFF_SEC legacy mapping) from the environment and drops its
# supervision.json ledger beside the results.
WRAPPER_FLAGS=(--drop-on-retry --inject-fault --results-dir "${RESULTS_DIR}")
if [ -n "${CHECKPOINT_DIR}" ]; then
  WRAPPER_FLAGS+=(--resume-flag --resume)
fi
exec bash /app/scripts/with_retries.sh "${WRAPPER_FLAGS[@]}" -- \
  python -u /app/benchmarking/train_harness.py ${ARGS}
