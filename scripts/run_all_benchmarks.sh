#!/usr/bin/env bash
# Full benchmark suite: strategy x chip-count matrix -> results -> analysis.
#
# Suite-orchestrator parity with the reference (scripts/run_all_benchmarks.sh
# there: fixed matrix, per-run launch/wait/collect/cleanup, then
# parse -> plot -> report), redesigned for TPU:
#   - local mode (default): one host with N chips; each arm runs as a local
#     process over a world_size-chip mesh. Includes world_size=1 so scaling
#     efficiency is measured against a true single-chip baseline (the
#     reference's minimum was 2, pinning those rows at 50%).
#   - --k8s mode: kubectl-driven TPU pod-slice jobs via launch_multi.sh.
set -euo pipefail

cd "$(dirname "$0")/.."
REPO_ROOT="$(pwd)"

MODE="local"
RESULTS_DIR="${RESULTS_DIR:-$REPO_ROOT/results}"
TIER="${TIER:-A}"
SEQ_LEN="${SEQ_LEN:-2048}"
STEPS="${STEPS:-100}"
WARMUP_STEPS="${WARMUP_STEPS:-5}"
PER_DEVICE_BATCH="${PER_DEVICE_BATCH:-1}"
GRAD_ACCUM="${GRAD_ACCUM:-4}"
# Hard-sync (block on the loss) every N steps. Totals are identical — steps
# are device-sequential — but syncing each step puts the host's dispatch and
# fetch latency inside every timed step. 10 matches bench.py's timing
# discipline.
SYNC_EVERY="${SYNC_EVERY:-10}"
# Layer iteration: 'unrolled' measures ~15% faster per step single-chip (no
# dynamic-update-slice activation stacking); 'scan' compiles ~16x faster.
LAYER_LOOP="${LAYER_LOOP:-unrolled}"
STRATEGIES="${STRATEGIES:-ddp fsdp zero2 zero3}"
# Attention implementation per run: 'reference' (exact reference semantics)
# or 'flash' (Pallas TPU kernel). Suites for both impls can share one
# RESULTS_DIR — run names (and so result dirs) carry a -flash suffix, and the
# final analysis pass aggregates whatever has accumulated.
ATTENTION="${ATTENTION:-reference}"
WORLD_SIZES="${WORLD_SIZES:-}"
NAMESPACE="${NAMESPACE:-bench}"
IMAGE="${IMAGE:-}"
TIMEOUT_PER_RUN="${TIMEOUT_PER_RUN:-1800}"
# Extra harness flags appended to every local run — the hook for composition
# arms the fixed matrix doesn't enumerate, e.g.
#   EXTRA_ARGS="--pipeline-parallel 2 --pipeline-schedule interleaved"
#   EXTRA_ARGS="--param-dtype bf16"   (with TIER=B)
# Space-separated (values must not themselves contain spaces or glob chars —
# it is an env string, not an array). Run names get a slug of these flags
# (override with RUN_SUFFIX) so composition arms never overwrite the
# baseline arms' results in a shared RESULTS_DIR — the same collision the
# -flash suffix prevents for ATTENTION.
EXTRA_ARGS="${EXTRA_ARGS:-}"
RUN_SUFFIX="${RUN_SUFFIX:-}"
if [ -n "$EXTRA_ARGS" ] && [ -z "$RUN_SUFFIX" ]; then
  RUN_SUFFIX=$(echo "$EXTRA_ARGS" | tr -cs 'a-zA-Z0-9' '-' | sed 's/^-*//; s/-*$//')
fi
# Composition roster: when the widest world size can hold a second axis
# (>= 4 chips: 2-way composition axis x >= 2-way data), the suite
# auto-appends one run per extended-axis arm at that world size — tensor,
# pipeline (all three schedules), sequence (ring + Ulysses) and expert
# parallelism, plus the llama-flagship arm (the family at its swept
# b2 x accum2 unrolled flash geometry — the bench.py flagship sub-object's
# configuration, reproducible from the suite orchestrator) — so ONE
# invocation on a pod slice produces the complete scaling story, the way
# the reference hard-codes its full matrix
# (reference scripts/run_all_benchmarks.sh fixed strategy x gpu grid).
# COMPOSITIONS=off disables; =only skips the pure-strategy matrix.
COMPOSITIONS="${COMPOSITIONS:-auto}"
# SUITE_DRY_RUN=1: print the planned run list (one "PLAN <mode> <name>
# strategy=<s> ws=<n> flags=<...>" line per run) without executing anything
# — the hermetic contract for the multi-chip day-one suite shape
# (tests/test_suite_plan.py asserts the {strategies} x {1,2,4,..,N} matrix
# + composition roster against a faked device count). Analysis/validation
# are skipped too (there is nothing to analyze).
SUITE_DRY_RUN="${SUITE_DRY_RUN:-0}"
# Run-registry + regression gate (regress/, docs/REGRESSION.md): the finish
# path ingests every arm's result row + telemetry windows into the
# persistent registry and gates each arm's fresh run against its last known
# good — a statistically significant throughput regression fails the suite
# the same way a validation violation does. SKIP_REGRESS=1 bypasses; dry
# runs never reach it. The default registry root rides under RESULTS_DIR
# (the default RESULTS_DIR is the repo's persistent results/, so history
# accumulates across suite invocations there; hermetic runs that point
# RESULTS_DIR elsewhere stay self-contained) — pin REGISTRY_DIR to share
# one registry across differently-rooted suites. The default is resolved
# AFTER the flag loop below: --results-dir must redirect the registry
# too, or a flag-redirected CI run would dirty the repo's committed
# seed and gate against unrelated history.
SKIP_REGRESS="${SKIP_REGRESS:-0}"
# Chaos smoke (scripts/chaos_suite.sh --smoke, docs/FAULT_TOLERANCE.md):
# before burning slice time on the matrix, prove in ~a minute on the host
# CPU that the recovery machinery works — a SIGKILL'd arm resumes from
# its checkpoint, a torn checkpoint quarantines + falls back, a
# bitflip-poisoned arm is healed in-process by the numerics sentinel
# (rollback + replay, n_rollbacks=1, validated), and a corrupt record on
# the streaming data path quarantines + substitutes with an honest
# records_skipped ledger. Runs in a throwaway
# tmpdir so its artifacts never pollute RESULTS_DIR, the registry, or
# the report. SKIP_CHAOS=1 bypasses (same escape hatch as
# SKIP_REGRESS); dry runs plan only and skip it too.
SKIP_CHAOS="${SKIP_CHAOS:-0}"
# Retrying orchestration (scripts/with_retries.sh): each local arm gets
# MAX_ARM_RETRIES bounded retries with exponential backoff
# (RETRY_BACKOFF_SEC), and retries RESUME from the arm's checkpoint dir
# instead of cold-restarting — preemption (exit 75), OOM-kills and
# timeouts all salvage their completed steps. ARM_CHECKPOINT_EVERY sets
# the checkpoint cadence backing that resume: 'auto' = STEPS/4 (the
# save sits at a sync boundary outside the timed windows, so headline
# metrics are unaffected); 0 disables checkpointing and makes retries
# cold. Resumed rows publish resumed=true/n_restarts and are never
# regression baselines.
MAX_ARM_RETRIES="${MAX_ARM_RETRIES:-1}"
RETRY_BACKOFF_SEC="${RETRY_BACKOFF_SEC:-5}"
ARM_CHECKPOINT_EVERY="${ARM_CHECKPOINT_EVERY:-auto}"
# Step anatomy (analysis/step_anatomy.py, docs/OBSERVABILITY.md): PROFILE=1
# gives every local arm a --profile-dir ($RESULTS_DIR/<name>_profile), so
# each run's result row carries the trace-derived compute/exposed-comms/
# idle + roofline attribution. After the matrix, the analysis pass renders
# the per-arm anatomy table for ANY arm that produced a profile dir —
# including dirs from earlier or manual runs — into
# $SUMMARY/step_anatomy.txt and ships it into BENCHMARK_REPORT.md.
PROFILE="${PROFILE:-0}"
# Remat/HBM frontier (bench.py --remat-sweep, docs/PERFORMANCE.md):
# REMAT_SWEEP=1 re-runs the flagship configuration once per remat policy
# after the matrix, ingests one registry record per policy (the policy is
# part of the config key, so each is its own lineage) and refreshes the
# report so the frontier table lands in BENCHMARK_REPORT.md. Local mode
# only — the sweep is a bench.py in-process run, not a pod matrix.
REMAT_SWEEP="${REMAT_SWEEP:-0}"
# Scaling observatory (scripts/scaling_suite.sh, docs/SCALING.md):
# SCALING_SUITE=1 appends the scaling sweep's CPU dryrun smoke after the
# matrix — 2 forced-host-device geometries end-to-end through
# stamp -> registry -> curves -> gate -> report, proving the observatory
# pipeline works before a pod-scale sweep is paid for. The smoke runs in
# a throwaway tmpdir with its own registry (its tiny CPU points must
# never pollute the suite registry's lineages). SKIP_SCALING=1 bypasses
# even when SCALING_SUITE=1 (same escape-hatch shape as SKIP_CHAOS).
# For a REAL scaling sweep on hardware, run scripts/scaling_suite.sh
# directly (no --dryrun) with RESULTS_DIR/REGISTRY_DIR pointed at the
# persistent tree.
SCALING_SUITE="${SCALING_SUITE:-0}"
SKIP_SCALING="${SKIP_SCALING:-0}"

while [ $# -gt 0 ]; do
  case "$1" in
    --k8s) MODE="k8s"; shift ;;
    --attention) ATTENTION="$2"; shift 2 ;;
    --tier) TIER="$2"; shift 2 ;;
    --seq-len) SEQ_LEN="$2"; shift 2 ;;
    --steps) STEPS="$2"; shift 2 ;;
    --results-dir) RESULTS_DIR="$2"; shift 2 ;;
    --image) IMAGE="$2"; shift 2 ;;
    *) echo "unknown flag $1"; exit 1 ;;
  esac
done

REGISTRY_DIR="${REGISTRY_DIR:-$RESULTS_DIR/registry}"

if [ "$MODE" = "k8s" ] && [ -n "$EXTRA_ARGS" ]; then
  # launch_multi.sh/the job template don't carry arbitrary flags; silently
  # running f32 baselines when the operator asked for a composition arm
  # would mislabel every scraped result.
  echo "ERROR: EXTRA_ARGS is local-mode only (set the pod env knobs in" \
       "docker/entrypoint.sh for k8s composition runs)"; exit 1
fi

mkdir -p "$RESULTS_DIR"

if [ -z "$WORLD_SIZES" ]; then
  if [ "$MODE" = "local" ]; then
    # A throwaway child that exits before any arm starts (a chip belongs
    # to one process at a time).
    NCHIPS=$(python -c "import jax; print(jax.device_count())" 2>/dev/null || echo 1)
    WORLD_SIZES="1"
    for ws in 2 4 8; do [ "$ws" -le "$NCHIPS" ] && WORLD_SIZES="$WORLD_SIZES $ws"; done
  else
    WORLD_SIZES="1 2 4 8"
  fi
fi

echo "=== TPU Benchmark Suite ==="
echo "mode=$MODE strategies=[$STRATEGIES] world_sizes=[$WORLD_SIZES] attention=$ATTENTION"
echo "tier=$TIER seq=$SEQ_LEN steps=$STEPS batch=$PER_DEVICE_BATCH accum=$GRAD_ACCUM"
echo ""

if [ "$SUITE_DRY_RUN" != "1" ] && [ "$SKIP_CHAOS" != "1" ]; then
  echo "=== Chaos smoke: recovery proof (sigkill + torn-checkpoint + bitflip-heal + corrupt-record stream heal + elastic + supervisor) ==="
  CHAOS_DIR=$(mktemp -d /tmp/chaos_smoke.XXXXXX)
  # --elastic: the geometry-change resume proof (save@dp4 -> resume@dp2 ->
  # validate_results passes with resume_geometry_changed=true) rides the
  # same SKIP_CHAOS=1 hatch as the rest of the smoke.
  # --supervisor: the elastic fleet supervisor's proofs ride here too —
  # lose-host shrink-resume (preempt -> probe sees 2 chips -> dp4
  # checkpoint resumes at dp2 with a ledgered 4->2 leg), the
  # preempt-storm budget drain, and the sentinel x stream bitflip heal
  # with an exactly-rewound cursor (runtime/supervisor.py,
  # docs/FAULT_TOLERANCE.md).
  if scripts/chaos_suite.sh --smoke --elastic --supervisor \
       --results-dir "$CHAOS_DIR"; then
    rm -rf "$CHAOS_DIR"
  else
    echo "CHAOS SMOKE FAILED — the recovery machinery is broken, so a" \
         "preempted arm would be a total loss; not launching" \
         "(SKIP_CHAOS=1 to override). Artifacts: $CHAOS_DIR"
    exit 1
  fi
  echo ""
fi

PASS=0; FAIL=0
SUITE_START=$(date +%s)

# Resolve the auto checkpoint cadence now that STEPS is final.
if [ "$ARM_CHECKPOINT_EVERY" = "auto" ]; then
  ARM_CHECKPOINT_EVERY=$((STEPS / 4))
  [ "$ARM_CHECKPOINT_EVERY" -lt 1 ] && ARM_CHECKPOINT_EVERY=1
fi

run_local() {
  local strategy="$1" ws="$2" extra="${3-$EXTRA_ARGS}" suffix="${4-$RUN_SUFFIX}"
  local name="bench-${strategy}-ws${ws}-seq${SEQ_LEN}"
  [ "$ATTENTION" != "reference" ] && name="${name}-${ATTENTION}"
  [ -n "$suffix" ] && name="${name}-${suffix}"
  local log="$RESULTS_DIR/${name}.log"
  if [ "$SUITE_DRY_RUN" = "1" ]; then
    echo "PLAN local $name strategy=$strategy ws=$ws flags=$extra"
    PASS=$((PASS+1)); return
  fi
  echo "--- $name ---"
  local t0=$(date +%s)
  # Bounded retry with resume (with_retries.sh): the checkpoint cadence
  # backs the resume; retries drop any injected chaos fault so a
  # deterministic fault cannot re-fire on its own recovery attempt.
  local prof_flags=""
  if [ "$PROFILE" = "1" ]; then
    # Fresh dir per invocation, like the checkpoint dir below: a stale
    # trace from last week must not be attributed as this run's anatomy.
    rm -rf "$RESULTS_DIR/${name}_profile"
    prof_flags="--profile-dir $RESULTS_DIR/${name}_profile"
  fi
  local ckpt_flags=""
  if [ "$ARM_CHECKPOINT_EVERY" != "0" ]; then
    # Fresh dir per invocation: the checkpoints only exist to back THIS
    # suite run's retry-resume. A previous invocation's committed steps
    # (RESULTS_DIR defaults to the persistent results/) would collide
    # with this run's saves — and resuming last week's final state into
    # a fresh measurement would be dishonest anyway.
    rm -rf "$RESULTS_DIR/${name}_ckpt"
    ckpt_flags="--checkpoint-dir $RESULTS_DIR/${name}_ckpt"
    ckpt_flags="$ckpt_flags --checkpoint-every $ARM_CHECKPOINT_EVERY"
  fi
  if scripts/with_retries.sh \
      ${ckpt_flags:+--resume-flag --resume} --drop-on-retry --inject-fault -- \
      timeout "$TIMEOUT_PER_RUN" python -u benchmarking/train_harness.py \
      --strategy "$strategy" --world-size "$ws" --rank 0 \
      --tier "$TIER" --seq-len "$SEQ_LEN" --attention "$ATTENTION" \
      --steps "$STEPS" --warmup-steps "$WARMUP_STEPS" \
      --per-device-batch "$PER_DEVICE_BATCH" --grad-accum "$GRAD_ACCUM" \
      --sync-every "$SYNC_EVERY" --layer-loop "$LAYER_LOOP" \
      --results-dir "$RESULTS_DIR/${name}_results" \
      $extra $ckpt_flags $prof_flags \
      > "$log" 2>&1; then
    scripts/collect_results.sh --log "$log" "$RESULTS_DIR/${name}_results" \
      || true
    echo "OK ($(( $(date +%s) - t0 ))s)"
    PASS=$((PASS+1))
  else
    echo "FAILED — last 20 log lines:"
    tail -20 "$log" || true
    # Salvage partial progress from the flight-recorder heartbeats so the
    # failed arm appears in the report as a partial row instead of
    # vanishing (collect_results.sh falls back to partial_<arm>.json).
    scripts/collect_results.sh --log "$log" "$RESULTS_DIR/${name}_results" \
      || true
    FAIL=$((FAIL+1))
  fi
}

run_k8s() {
  local strategy="$1" ws="$2" comp="${3-}" suffix="${4-}"
  # Unique job name per run: the collector scrapes into
  # $RESULTS_DIR/<job>_results, so a shared name would make each of the
  # matrix runs overwrite the previous one's result.json (pod filesystems
  # are ephemeral — the scrape is the only copy).
  local job="tpu-bench-${strategy}-ws${ws}"
  [ -n "$suffix" ] && job="${job}-${suffix}"
  if [ "$SUITE_DRY_RUN" = "1" ]; then
    echo "PLAN k8s $job strategy=$strategy ws=$ws flags=$comp"
    PASS=$((PASS+1)); return
  fi
  echo "--- $job (k8s) ---"
  # Bounded retry, mirroring run_local's. k8s retries are COLD relaunches
  # (the pod's emptyDir checkpoints die with it — resume across pods
  # needs a persistent CHECKPOINT_DIR volume, which the operator wires
  # via pod env overlays); what the loop buys is survival of preemption
  # and transient scheduling failures without losing the whole matrix.
  local attempt=0 done_ok=0
  while :; do
    attempt=$((attempt+1))
    scripts/launch_multi.sh --strategy "$strategy" --world-size "$ws" \
      --seq-len "$SEQ_LEN" --tier "$TIER" --steps "$STEPS" \
      --per-device-batch "$PER_DEVICE_BATCH" --grad-accum "$GRAD_ACCUM" \
      --attention "$ATTENTION" --layer-loop "$LAYER_LOOP" --job-name "$job" \
      $comp \
      ${IMAGE:+--image "$IMAGE"}
    if kubectl -n "$NAMESPACE" wait --for=condition=complete \
         "job/$job" --timeout=900s; then
      done_ok=1
      break
    fi
    echo "FAILED (attempt $attempt) — last 100 log lines:"
    kubectl -n "$NAMESPACE" logs -l "job-name=$job" --tail=100 || true
    # Still collect: saves every pod's log for diagnosis and salvages a
    # partial_<arm>.json from the heartbeat markers when any pod got far
    # enough to print one (the pod filesystem dies with the pod — the
    # scrape is the only copy).
    scripts/collect_results.sh --k8s "$NAMESPACE" "$job" "$RESULTS_DIR" || true
    kubectl -n "$NAMESPACE" delete job "$job" --ignore-not-found
    if [ "$attempt" -gt "$MAX_ARM_RETRIES" ]; then
      break
    fi
    backoff=$((RETRY_BACKOFF_SEC * (1 << (attempt - 1))))
    echo "retrying $job in ${backoff}s..."
    sleep "$backoff"
  done
  if [ "$done_ok" -eq 1 ]; then
    scripts/collect_results.sh --k8s "$NAMESPACE" "$job" "$RESULTS_DIR"
    PASS=$((PASS+1))
  else
    FAIL=$((FAIL+1))
  fi
  kubectl -n "$NAMESPACE" delete job "$job" --ignore-not-found
}

if [ "$COMPOSITIONS" != "only" ]; then
  for strategy in $STRATEGIES; do
    for ws in $WORLD_SIZES; do
      if [ "$MODE" = "local" ]; then run_local "$strategy" "$ws"; else run_k8s "$strategy" "$ws"; fi
    done
  done
fi

# --- composition roster (see COMPOSITIONS above) ---
WS_MAX=0
for ws in $WORLD_SIZES; do [ "$ws" -gt "$WS_MAX" ] && WS_MAX=$ws; done
if [ "$COMPOSITIONS" != "off" ] && [ "$WS_MAX" -ge 4 ]; then
  # Interleaved needs n_layer % (pp * V) == 0: tier S has 2 layers -> V=1.
  VIRT=2; [ "$TIER" = "S" ] && VIRT=1
  # name|strategy|local harness flags|k8s launcher flags
  ROSTER="
tp2|ddp|--tensor-parallel 2|--tensor-parallel 2
pp2-gpipe|ddp|--pipeline-parallel 2 --pipeline-schedule gpipe|--pipeline-parallel 2 --pipeline-schedule gpipe
pp2-1f1b|ddp|--pipeline-parallel 2 --pipeline-schedule 1f1b|--pipeline-parallel 2 --pipeline-schedule 1f1b
pp2-interleaved|ddp|--pipeline-parallel 2 --pipeline-schedule interleaved --virtual-stages $VIRT|--pipeline-parallel 2 --pipeline-schedule interleaved --virtual-stages $VIRT
sp2-ring|zero2|--sequence-parallel 2 --attention ring|--sequence-parallel 2 --attention ring
sp2-ring-causal|zero2|--sequence-parallel 2 --attention ring --causal|--sequence-parallel 2 --attention ring --causal
sp2-ring-causal-nozz|zero2|--sequence-parallel 2 --attention ring --causal --ring-zigzag off|--sequence-parallel 2 --attention ring --causal --ring-zigzag off
sp2-ulysses|zero2|--sequence-parallel 2 --attention ulysses|--sequence-parallel 2 --attention ulysses
moe-ep2|zero2|--num-experts 4 --expert-parallel 2|--num-experts 4 --expert-parallel 2
moe8-ep2|zero2|--num-experts 8 --expert-parallel 2|--num-experts 8 --expert-parallel 2
llama-tp2|fsdp|--model-family llama --tensor-parallel 2|--model-family llama --tensor-parallel 2
llama-tp2-ddp|ddp|--model-family llama --tensor-parallel 2|--model-family llama --tensor-parallel 2
llama-tp2-cmm|ddp|--model-family llama --tensor-parallel 2 --tp-collective-matmul|--model-family llama --tensor-parallel 2 --tp-collective-matmul
llama-flagship|zero2|--model-family llama --per-device-batch 2 --grad-accum 2 --layer-loop unrolled --attention flash|--model-family llama --per-device-batch 2 --grad-accum 2 --layer-loop unrolled --attention flash
"
  echo ""
  echo "=== Composition arms (ws=$WS_MAX) ==="
  while IFS='|' read -r cname cstrat cflags kflags; do
    [ -z "$cname" ] && continue
    if [ "$MODE" = "local" ]; then
      # Keep the operator's EXTRA_ARGS (e.g. --param-dtype bf16) on the
      # composition arms too — dropping them would silently measure the
      # roster under a different config than the pure matrix; the suffix
      # carries both slugs so run names stay collision-free.
      run_local "$cstrat" "$WS_MAX" "$cflags $EXTRA_ARGS" \
        "$cname${RUN_SUFFIX:+-$RUN_SUFFIX}"
    else
      run_k8s "$cstrat" "$WS_MAX" "$kflags" "$cname"
    fi
  done <<EOF
$ROSTER
EOF
fi

if [ "$SUITE_DRY_RUN" = "1" ]; then
  echo ""
  echo "=== Dry run: $PASS runs planned, nothing executed ==="
  exit 0
fi

echo ""
echo "=== Analysis ==="
SUMMARY="$RESULTS_DIR/summary"
python -m distributed_llm_training_benchmark_framework_tpu.analysis.parse_metrics \
  --results-dir "$RESULTS_DIR" --out "$SUMMARY"
python -m distributed_llm_training_benchmark_framework_tpu.analysis.plot \
  --results "$SUMMARY/metrics.csv" --out "$RESULTS_DIR/plots"

# Step anatomy on every arm that produced a profile dir (see PROFILE
# above): the attribution tables land in $SUMMARY/step_anatomy.txt and
# ride into the report. Best-effort per dir — an unreadable trace warns
# on stderr without failing the suite.
ANATOMY_TXT="$SUMMARY/step_anatomy.txt"
mkdir -p "$SUMMARY"
rm -f "$ANATOMY_TXT"
for prof in "$RESULTS_DIR"/*_profile; do
  [ -d "$prof" ] || continue
  base="${prof%_profile}"
  tfile=$(ls "${base}_results"/telemetry_*.jsonl 2>/dev/null | head -1 || true)
  python -m distributed_llm_training_benchmark_framework_tpu.analysis.step_anatomy \
    --profile-dir "$prof" ${tfile:+--telemetry "$tfile"} \
    >> "$ANATOMY_TXT" 2>/dev/null \
    && { echo "" >> "$ANATOMY_TXT"; } \
    || echo "WARNING: step-anatomy failed for $prof" >&2
done
if [ -s "$ANATOMY_TXT" ]; then
  echo "--- step anatomy ($(grep -c '^== Step anatomy' "$ANATOMY_TXT")" \
       "profiled arm(s)) -> $ANATOMY_TXT ---"
  STEP_ANATOMY_FLAG="--step-anatomy $ANATOMY_TXT"
else
  rm -f "$ANATOMY_TXT"
  STEP_ANATOMY_FLAG=""
fi

python -m distributed_llm_training_benchmark_framework_tpu.analysis.make_report \
  --csv "$SUMMARY/metrics.csv" --out "$SUMMARY" --plots-dir ../plots \
  $STEP_ANATOMY_FLAG

echo ""
echo "=== Validation (sanity envelopes, results/example_output/README.md) ==="
python -m distributed_llm_training_benchmark_framework_tpu.analysis.validate_results \
  --results-dir "$RESULTS_DIR" --logs-dir "$RESULTS_DIR" \
  || { echo "VALIDATION FAILED"; FAIL=$((FAIL+1)); }

if [ "$SKIP_REGRESS" != "1" ]; then
  echo ""
  echo "=== Regression gate (registry: $REGISTRY_DIR) ==="
  # Ingest first (full rows as ok, heartbeat partials as partial), then
  # gate every arm's latest vs its last known good. A first-ever run on a
  # fresh registry gates clean (insufficient-data is not a failure).
  python -m distributed_llm_training_benchmark_framework_tpu.regress \
    --registry "$REGISTRY_DIR" ingest --results-dir "$RESULTS_DIR" \
    || { echo "REGISTRY INGEST FAILED"; FAIL=$((FAIL+1)); }
  python -m distributed_llm_training_benchmark_framework_tpu.regress \
    --registry "$REGISTRY_DIR" gate --all \
    || { echo "REGRESSION GATE FAILED (SKIP_REGRESS=1 to override)"; \
         FAIL=$((FAIL+1)); }
  # Refresh the report with the per-arm trend section now that the
  # registry carries this suite's records.
  python -m distributed_llm_training_benchmark_framework_tpu.analysis.make_report \
    --csv "$SUMMARY/metrics.csv" --out "$SUMMARY" --plots-dir ../plots \
    --registry "$REGISTRY_DIR" $STEP_ANATOMY_FLAG || true
fi

if [ "$REMAT_SWEEP" = "1" ] && [ "$MODE" != "local" ]; then
  echo "NOTE: REMAT_SWEEP=1 only runs in local mode (the sweep is an" \
       "in-process bench.py run, not a pod matrix) — skipping it in" \
       "mode '$MODE'"
fi
if [ "$REMAT_SWEEP" = "1" ] && [ "$MODE" = "local" ]; then
  echo ""
  echo "=== Remat/HBM frontier sweep (registry: $REGISTRY_DIR) ==="
  # The sweep arms ride the suite's run length; --flagship off because
  # the sweep's 'none' point IS the flagship configuration. The records
  # land in the registry (--regress on creates it if needed) and the
  # report refresh below renders the frontier table from them.
  if python bench.py --remat-sweep --flagship off \
       --steps "$STEPS" --warmup-steps "$WARMUP_STEPS" \
       --sync-every "$SYNC_EVERY" \
       --regress on --registry "$REGISTRY_DIR" \
       > "$RESULTS_DIR/remat_sweep.json" 2> "$RESULTS_DIR/remat_sweep.log"
  then
    python -m distributed_llm_training_benchmark_framework_tpu.analysis.make_report \
      --csv "$SUMMARY/metrics.csv" --out "$SUMMARY" --plots-dir ../plots \
      --registry "$REGISTRY_DIR" $STEP_ANATOMY_FLAG || true
    echo "frontier records + report refreshed ($RESULTS_DIR/remat_sweep.json)"
  else
    echo "REMAT SWEEP FAILED — last 20 log lines:"
    tail -20 "$RESULTS_DIR/remat_sweep.log" || true
    FAIL=$((FAIL+1))
  fi
fi

if [ "$SCALING_SUITE" = "1" ] && [ "$SKIP_SCALING" != "1" ]; then
  echo ""
  echo "=== Scaling observatory smoke (scripts/scaling_suite.sh --dryrun) ==="
  SCALING_DIR=$(mktemp -d /tmp/scaling_smoke.XXXXXX)
  # --registry pinned INSIDE the tmpdir: an operator-exported
  # REGISTRY_DIR (the documented share-one-registry knob above) must not
  # leak into the smoke, or its tiny CPU points ingest permanently.
  if scripts/scaling_suite.sh --dryrun --results-dir "$SCALING_DIR" \
       --registry "$SCALING_DIR/registry"; then
    rm -rf "$SCALING_DIR"
  else
    echo "SCALING SMOKE FAILED (SKIP_SCALING=1 to override)." \
         "Artifacts: $SCALING_DIR"
    FAIL=$((FAIL+1))
  fi
fi

echo ""
echo "=== Suite complete: $PASS passed, $FAIL failed, $(( $(date +%s) - SUITE_START ))s total ==="
[ "$FAIL" -eq 0 ]
