#!/usr/bin/env bash
# Launch a multi-chip TPU benchmark job on Kubernetes.
#
# Parity with reference scripts/launch_multi.sh (arg parse, sed-substitute
# {{VARS}} into the job template, kubectl apply), with the master/worker
# template pair collapsed into one symmetric Indexed Job.
set -euo pipefail
cd "$(dirname "$0")/.."

STRATEGY="ddp"
WORLD_SIZE=8
NUM_HOSTS=1
SEQ_LEN=2048
TIER="A"
STEPS=100
PER_DEVICE_BATCH=1
GRAD_ACCUM=4
ATTENTION="reference"
LAYER_LOOP="scan"
# Extended composition axes (docker/entrypoint.sh consumes these as env
# vars and turns non-default values into harness flags).
TENSOR_PARALLEL=1
SEQUENCE_PARALLEL=1
PIPELINE_PARALLEL=1
PIPELINE_SCHEDULE="gpipe"
VIRTUAL_STAGES=2
EXPERT_PARALLEL=1
NUM_EXPERTS=0
PARAM_DTYPE=""
MODEL_FAMILY="tinygpt"
CAUSAL=0
RING_ZIGZAG="auto"
# Overlap round 3: 1 = collective-matmul tp fusion (ppermute-ring
# projection comms, ops/collective_matmul.py; needs TENSOR_PARALLEL > 1
# to have any effect).
TP_COLLECTIVE_MATMUL=0
# Flight-recorder heartbeat cadence (harness --heartbeat-sec); also drives
# the job's livenessProbe — the probe period tracks the cadence and its
# grace window is derived inside scripts/liveness_probe.sh (10x, floor
# 120s), so one knob moves scrape cadence and liveness together.
HEARTBEAT_SEC="${HEARTBEAT_SEC:-30}"
# Elastic-resilience checkpointing (docs/FAULT_TOLERANCE.md): empty/0 =
# off (the default — an emptyDir checkpoint dies with the pod anyway);
# point CHECKPOINT_DIR at a persistent-volume mount to make relaunches
# resume, and set CHECKPOINT_ASYNC=1 for the async-delta cadence.
CHECKPOINT_DIR="${CHECKPOINT_DIR:-}"
CHECKPOINT_EVERY="${CHECKPOINT_EVERY:-}"
CHECKPOINT_ASYNC="${CHECKPOINT_ASYNC:-0}"
# In-process hang watchdog (faults/watchdog.py): empty = off. When set,
# it must stay BELOW the liveness probe's grace window (10 x
# HEARTBEAT_SEC, floor 120s) — enforced below — so the stack-dump abort
# fires before kubelet's forensics-free kill.
HANG_TIMEOUT_SEC="${HANG_TIMEOUT_SEC:-}"
# Elastic fleet supervisor (runtime/supervisor.py, docs/
# FAULT_TOLERANCE.md): SUPERVISOR=1 makes the entrypoint exec
# scripts/with_retries.sh (the supervisor shim) as PID 1 — in-pod
# classify->decide->recover with the per-attempt supervision.json
# ledger, including geometry shrink-resume when capacity dropped.
# RECOVERY_POLICY names a policy JSON inside the image (e.g.
# /app/configs/recovery_policy.json); empty maps the legacy
# MAX_ARM_RETRIES/RETRY_BACKOFF_SEC env knobs onto an equivalent policy.
SUPERVISOR="${SUPERVISOR:-0}"
RECOVERY_POLICY="${RECOVERY_POLICY:-}"
# SIGTERM grace (docs/FAULT_TOLERANCE.md): kubelet preemption sends
# SIGTERM and waits terminationGracePeriodSeconds before SIGKILL. The
# preemption handler (train/loop.py) acts at the NEXT sync-window
# boundary and then writes an emergency checkpoint, so the grace must
# cover one full sync window plus the save — 4x the heartbeat cadence
# with a 120s floor tracks that (windows outpace heartbeats by design).
TERMINATION_GRACE_SEC="${TERMINATION_GRACE_SEC:-}"
IMAGE="tpu-llm-bench:latest"
TPU_ACCELERATOR="${TPU_ACCELERATOR:-tpu-v5-lite-podslice}"
TPU_TOPOLOGY="${TPU_TOPOLOGY:-2x4}"
NAMESPACE="bench"
JOB_NAME="tpu-bench"

while [ $# -gt 0 ]; do
  case "$1" in
    --strategy) STRATEGY="$2"; shift 2 ;;
    --world-size) WORLD_SIZE="$2"; shift 2 ;;
    --num-hosts) NUM_HOSTS="$2"; shift 2 ;;
    --seq-len) SEQ_LEN="$2"; shift 2 ;;
    --tier) TIER="$2"; shift 2 ;;
    --steps) STEPS="$2"; shift 2 ;;
    --per-device-batch) PER_DEVICE_BATCH="$2"; shift 2 ;;
    --grad-accum) GRAD_ACCUM="$2"; shift 2 ;;
    --attention) ATTENTION="$2"; shift 2 ;;
    --layer-loop) LAYER_LOOP="$2"; shift 2 ;;
    --tensor-parallel) TENSOR_PARALLEL="$2"; shift 2 ;;
    --sequence-parallel) SEQUENCE_PARALLEL="$2"; shift 2 ;;
    --pipeline-parallel) PIPELINE_PARALLEL="$2"; shift 2 ;;
    --pipeline-schedule) PIPELINE_SCHEDULE="$2"; shift 2 ;;
    --virtual-stages) VIRTUAL_STAGES="$2"; shift 2 ;;
    --expert-parallel) EXPERT_PARALLEL="$2"; shift 2 ;;
    --num-experts) NUM_EXPERTS="$2"; shift 2 ;;
    --param-dtype) PARAM_DTYPE="$2"; shift 2 ;;
    --model-family) MODEL_FAMILY="$2"; shift 2 ;;
    --causal) CAUSAL=1; shift 1 ;;
    --tp-collective-matmul) TP_COLLECTIVE_MATMUL=1; shift 1 ;;
    --ring-zigzag) RING_ZIGZAG="$2"; shift 2 ;;
    --heartbeat-sec) HEARTBEAT_SEC="$2"; shift 2 ;;
    --checkpoint-dir) CHECKPOINT_DIR="$2"; shift 2 ;;
    --checkpoint-every) CHECKPOINT_EVERY="$2"; shift 2 ;;
    --checkpoint-async) CHECKPOINT_ASYNC=1; shift 1 ;;
    --hang-timeout-sec) HANG_TIMEOUT_SEC="$2"; shift 2 ;;
    --supervisor) SUPERVISOR=1; shift 1 ;;
    --recovery-policy) RECOVERY_POLICY="$2"; shift 2 ;;
    --termination-grace-sec) TERMINATION_GRACE_SEC="$2"; shift 2 ;;
    --image) IMAGE="$2"; shift 2 ;;
    --topology) TPU_TOPOLOGY="$2"; shift 2 ;;
    --job-name) JOB_NAME="$2"; shift 2 ;;
    *) echo "unknown flag $1"; exit 1 ;;
  esac
done

if [ "$WORLD_SIZE" -lt 1 ]; then
  echo "ERROR: --world-size must be >= 1"; exit 1
fi
TPU_PER_HOST=$(( WORLD_SIZE / NUM_HOSTS ))
if [ $(( TPU_PER_HOST * NUM_HOSTS )) -ne "$WORLD_SIZE" ]; then
  echo "ERROR: world-size $WORLD_SIZE not divisible by num-hosts $NUM_HOSTS"; exit 1
fi

# Liveness probe period tracks the heartbeat cadence, with a floor so a
# tight test cadence doesn't hammer kubelet exec.
LIVENESS_PERIOD="$HEARTBEAT_SEC"
if [ "$LIVENESS_PERIOD" -lt 10 ] 2>/dev/null; then LIVENESS_PERIOD=10; fi
# Default SIGTERM grace derived from the heartbeat cadence (see the knob
# comment above): 4x cadence, floor 120s.
if [ -z "$TERMINATION_GRACE_SEC" ]; then
  TERMINATION_GRACE_SEC=$(( HEARTBEAT_SEC * 4 ))
  if [ "$TERMINATION_GRACE_SEC" -lt 120 ] 2>/dev/null; then
    TERMINATION_GRACE_SEC=120
  fi
fi
# Watchdog-vs-probe ordering (scripts/liveness_probe.sh): a HANG_TIMEOUT
# at or above the probe's grace window would let kubelet's forensics-free
# kill win the race against the in-process stack-dump abort. Refuse the
# misconfiguration rather than launch it. The effective grace is an
# explicit LIVENESS_GRACE_SEC when the operator set one (plumbed into the
# pod below so the probe actually honors it), else the probe's own
# derived default (10 x HEARTBEAT_SEC, floor 120).
LIVENESS_GRACE_SEC="${LIVENESS_GRACE_SEC:-}"
if [ -n "$HANG_TIMEOUT_SEC" ]; then
  if [ -n "$LIVENESS_GRACE_SEC" ]; then
    PROBE_GRACE="$LIVENESS_GRACE_SEC"
  else
    PROBE_GRACE=$(( HEARTBEAT_SEC * 10 ))
    if [ "$PROBE_GRACE" -lt 120 ] 2>/dev/null; then PROBE_GRACE=120; fi
  fi
  if [ "${HANG_TIMEOUT_SEC%.*}" -ge "${PROBE_GRACE%.*}" ] 2>/dev/null; then
    echo "ERROR: --hang-timeout-sec $HANG_TIMEOUT_SEC >= the liveness" \
         "probe grace (${PROBE_GRACE}s) — the watchdog must fire FIRST;" \
         "lower the timeout or raise HEARTBEAT_SEC/LIVENESS_GRACE_SEC"
    exit 1
  fi
fi
echo "Launching: job=$JOB_NAME strategy=$STRATEGY world_size=$WORLD_SIZE hosts=$NUM_HOSTS"
kubectl apply -f k8s/namespace.yaml
kubectl apply -f k8s/serviceaccount.yaml
kubectl apply -f k8s/service-coordinator.yaml

sed -e "s|{{JOB_NAME}}|$JOB_NAME|g" \
    -e "s|{{STRATEGY}}|$STRATEGY|g" \
    -e "s|{{WORLD_SIZE}}|$WORLD_SIZE|g" \
    -e "s|{{NUM_HOSTS}}|$NUM_HOSTS|g" \
    -e "s|{{TPU_PER_HOST}}|$TPU_PER_HOST|g" \
    -e "s|{{SEQ_LEN}}|$SEQ_LEN|g" \
    -e "s|{{TIER}}|$TIER|g" \
    -e "s|{{STEPS}}|$STEPS|g" \
    -e "s|{{PER_DEVICE_BATCH}}|$PER_DEVICE_BATCH|g" \
    -e "s|{{GRAD_ACCUM}}|$GRAD_ACCUM|g" \
    -e "s|{{ATTENTION}}|$ATTENTION|g" \
    -e "s|{{LAYER_LOOP}}|$LAYER_LOOP|g" \
    -e "s|{{TENSOR_PARALLEL}}|$TENSOR_PARALLEL|g" \
    -e "s|{{SEQUENCE_PARALLEL}}|$SEQUENCE_PARALLEL|g" \
    -e "s|{{PIPELINE_PARALLEL}}|$PIPELINE_PARALLEL|g" \
    -e "s|{{PIPELINE_SCHEDULE}}|$PIPELINE_SCHEDULE|g" \
    -e "s|{{VIRTUAL_STAGES}}|$VIRTUAL_STAGES|g" \
    -e "s|{{EXPERT_PARALLEL}}|$EXPERT_PARALLEL|g" \
    -e "s|{{NUM_EXPERTS}}|$NUM_EXPERTS|g" \
    -e "s|{{PARAM_DTYPE}}|$PARAM_DTYPE|g" \
    -e "s|{{MODEL_FAMILY}}|$MODEL_FAMILY|g" \
    -e "s|{{CAUSAL}}|$CAUSAL|g" \
    -e "s|{{RING_ZIGZAG}}|$RING_ZIGZAG|g" \
    -e "s|{{TP_COLLECTIVE_MATMUL}}|$TP_COLLECTIVE_MATMUL|g" \
    -e "s|{{HEARTBEAT_SEC}}|$HEARTBEAT_SEC|g" \
    -e "s|{{CHECKPOINT_DIR}}|$CHECKPOINT_DIR|g" \
    -e "s|{{CHECKPOINT_EVERY}}|$CHECKPOINT_EVERY|g" \
    -e "s|{{CHECKPOINT_ASYNC}}|$CHECKPOINT_ASYNC|g" \
    -e "s|{{HANG_TIMEOUT_SEC}}|$HANG_TIMEOUT_SEC|g" \
    -e "s|{{SUPERVISOR}}|$SUPERVISOR|g" \
    -e "s|{{RECOVERY_POLICY}}|$RECOVERY_POLICY|g" \
    -e "s|{{LIVENESS_GRACE_SEC}}|$LIVENESS_GRACE_SEC|g" \
    -e "s|{{LIVENESS_PERIOD}}|$LIVENESS_PERIOD|g" \
    -e "s|{{TERMINATION_GRACE_SEC}}|$TERMINATION_GRACE_SEC|g" \
    -e "s|{{IMAGE}}|$IMAGE|g" \
    -e "s|{{TPU_ACCELERATOR}}|$TPU_ACCELERATOR|g" \
    -e "s|{{TPU_TOPOLOGY}}|$TPU_TOPOLOGY|g" \
    k8s/job-benchmark.template.yaml | kubectl apply -f -

echo "Job applied. Watch: kubectl -n $NAMESPACE get pods -w"
