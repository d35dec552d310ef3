#!/usr/bin/env bash
# Scaling observatory suite: weak/strong scaling sweep -> registry ->
# curves -> gate -> report (docs/SCALING.md).
#
# For each strategy the suite measures one FRESH run per mesh geometry
# (the clean curve points), and between geometries rides the PR 6
# reshard-on-restore path: a short continuation run at the NEXT geometry
# resumes the previous geometry's checkpoint (grow leg), and the last
# geometry's checkpoint resumes at the first (shrink leg). The stitch
# runs publish resumed=true / resume_geometry_changed=true and flow into
# the registry as honest-but-flagged points — the curves show them
# STITCHED, the gate skips them, and parse_metrics never lets them
# anchor scaling efficiency (the `_eligible` posture, end to end).
#
# After the sweep: analysis.scaling --stamp-results-dir writes each clean
# row's scaling_efficiency (fraction of ideal per-chip throughput vs the
# suite's smallest geometry) into its result JSON, ingest records it, and
# `regress gate --all` then verdicts an efficiency regression AT ANY
# GEOMETRY by name (stats.SECONDARY_METRICS 'scaling_efficiency').
#
#   scripts/scaling_suite.sh [--dryrun] [--results-dir DIR] [--registry DIR]
#
# --dryrun: the CPU smoke — 2 forced-host-device geometries (ws 1 -> 2)
# end-to-end through registry -> curves -> report in ~2 minutes; wired
# into run_all_benchmarks.sh behind SCALING_SUITE=1 (SKIP_SCALING=1
# bypasses). Knobs (env): SCALING_STRATEGIES, SCALING_GEOMETRIES,
# SCALING_MODE=weak|strong, SKIP_STITCH=1, SKIP_GATE=1, plus the usual
# TIER/SEQ_LEN/STEPS/WARMUP_STEPS/PER_DEVICE_BATCH/GRAD_ACCUM/SYNC_EVERY/
# LAYER_LOOP/ATTENTION/TIMEOUT_PER_RUN.
set -euo pipefail
cd "$(dirname "$0")/.."
REPO_ROOT="$(pwd)"
PKG=distributed_llm_training_benchmark_framework_tpu

DRYRUN=0
RESULTS_DIR="${RESULTS_DIR:-}"
REGISTRY_DIR="${REGISTRY_DIR:-}"
while [ $# -gt 0 ]; do
  case "$1" in
    --dryrun) DRYRUN=1; shift ;;
    --results-dir) RESULTS_DIR="$2"; shift 2 ;;
    --registry) REGISTRY_DIR="$2"; shift 2 ;;
    *) echo "unknown flag $1"; exit 1 ;;
  esac
done

if [ "$DRYRUN" = "1" ]; then
  # Hermetic CPU smoke: tiny model, 2 virtual host devices, fsdp (the
  # dp1 -> dp2 resume is a REAL reshard, not a replicated no-op).
  export JAX_PLATFORMS=cpu
  case "${XLA_FLAGS:-}" in
    *xla_force_host_platform_device_count*) : ;;
    *) export XLA_FLAGS="${XLA_FLAGS:-} --xla_force_host_platform_device_count=2" ;;
  esac
  TIER="${TIER:-S}"; SEQ_LEN="${SEQ_LEN:-64}"; STEPS="${STEPS:-12}"
  WARMUP_STEPS="${WARMUP_STEPS:-2}"; SYNC_EVERY="${SYNC_EVERY:-2}"
  PER_DEVICE_BATCH="${PER_DEVICE_BATCH:-2}"; GRAD_ACCUM="${GRAD_ACCUM:-1}"
  SCALING_STRATEGIES="${SCALING_STRATEGIES:-fsdp}"
  SCALING_GEOMETRIES="${SCALING_GEOMETRIES:-1 2}"
  RESULTS_DIR="${RESULTS_DIR:-$(mktemp -d /tmp/scaling_dryrun.XXXXXX)}"
else
  TIER="${TIER:-A}"; SEQ_LEN="${SEQ_LEN:-2048}"; STEPS="${STEPS:-100}"
  WARMUP_STEPS="${WARMUP_STEPS:-5}"; SYNC_EVERY="${SYNC_EVERY:-10}"
  PER_DEVICE_BATCH="${PER_DEVICE_BATCH:-1}"; GRAD_ACCUM="${GRAD_ACCUM:-4}"
  SCALING_STRATEGIES="${SCALING_STRATEGIES:-ddp fsdp zero2}"
  RESULTS_DIR="${RESULTS_DIR:-$REPO_ROOT/results/scaling}"
fi
LAYER_LOOP="${LAYER_LOOP:-unrolled}"
ATTENTION="${ATTENTION:-reference}"
# PROFILE=1 gives every point (fresh AND stitch legs) a --profile-dir so
# the rows carry step anatomy and the efficiency-loss waterfall actually
# attributes (unprofiled sweeps render '[unattributed: no anatomy]').
# Profiled-ness is part of the curve lineage, so profile either the
# whole sweep or none of it — a mixed sweep splits into two curves.
PROFILE="${PROFILE:-0}"
SCALING_MODE="${SCALING_MODE:-weak}"
SKIP_STITCH="${SKIP_STITCH:-0}"
SKIP_GATE="${SKIP_GATE:-0}"
TIMEOUT_PER_RUN="${TIMEOUT_PER_RUN:-1800}"
REGISTRY_DIR="${REGISTRY_DIR:-$RESULTS_DIR/registry}"

if [ -z "${SCALING_GEOMETRIES:-}" ]; then
  # A throwaway child that exits before any arm starts (a chip belongs to
  # one process at a time).
  NCHIPS=$(python -c "import jax; print(jax.device_count())" 2>/dev/null || echo 1)
  SCALING_GEOMETRIES="1"
  for ws in 2 4 8 16; do
    [ "$ws" -le "$NCHIPS" ] && SCALING_GEOMETRIES="$SCALING_GEOMETRIES $ws"
  done
fi
WS_MIN=""; WS_MAX=0
for ws in $SCALING_GEOMETRIES; do
  [ -z "$WS_MIN" ] && WS_MIN=$ws
  [ "$ws" -gt "$WS_MAX" ] && WS_MAX=$ws
done
CKPT_EVERY=$((STEPS / 4)); [ "$CKPT_EVERY" -lt 1 ] && CKPT_EVERY=1

echo "=== Scaling suite ==="
echo "strategies=[$SCALING_STRATEGIES] geometries=[$SCALING_GEOMETRIES]" \
     "mode=$SCALING_MODE tier=$TIER seq=$SEQ_LEN steps=$STEPS"
echo "results=$RESULTS_DIR registry=$REGISTRY_DIR"
mkdir -p "$RESULTS_DIR"

FAIL=0

# point_batch <ws>: the per-device batch for one geometry. Weak scaling
# keeps it constant (global batch grows with the mesh); strong scaling
# pins the GLOBAL batch at the widest geometry's and shrinks per-device
# work as the mesh grows (skipping non-divisible points loudly).
point_batch() {
  local ws="$1"
  if [ "$SCALING_MODE" = "strong" ]; then
    local total=$((PER_DEVICE_BATCH * WS_MAX))
    if [ $((total % ws)) -ne 0 ]; then
      echo ""
    else
      echo $((total / ws))
    fi
  else
    echo "$PER_DEVICE_BATCH"
  fi
}

# run_point <strategy> <ws> <suffix> <ckpt_dir> <extra flags...>
run_point() {
  local strategy="$1" ws="$2" suffix="$3" ckpt_dir="$4"; shift 4
  local pdb; pdb=$(point_batch "$ws")
  if [ -z "$pdb" ]; then
    echo "--- scaling-$strategy-ws$ws$suffix SKIPPED (strong-mode global" \
         "batch $((PER_DEVICE_BATCH * WS_MAX)) not divisible by ws=$ws) ---"
    return 0
  fi
  local name="scaling-${strategy}-ws${ws}${suffix}"
  local log="$RESULTS_DIR/${name}.log"
  echo "--- $name ---"
  local t0=$(date +%s)
  local prof_flags=""
  if [ "$PROFILE" = "1" ]; then
    rm -rf "$RESULTS_DIR/${name}_profile"
    prof_flags="--profile-dir $RESULTS_DIR/${name}_profile"
  fi
  if timeout "$TIMEOUT_PER_RUN" python -u benchmarking/train_harness.py \
      --strategy "$strategy" --world-size "$ws" --rank 0 \
      --tier "$TIER" --seq-len "$SEQ_LEN" --attention "$ATTENTION" \
      --steps "$STEPS" --warmup-steps "$WARMUP_STEPS" \
      --per-device-batch "$pdb" --grad-accum "$GRAD_ACCUM" \
      --sync-every "$SYNC_EVERY" --layer-loop "$LAYER_LOOP" \
      --results-dir "$RESULTS_DIR/${name}_results" \
      --checkpoint-dir "$ckpt_dir" --checkpoint-every "$CKPT_EVERY" \
      $prof_flags "$@" > "$log" 2>&1; then
    echo "OK ($(( $(date +%s) - t0 ))s)"
  else
    echo "FAILED — last 20 log lines:"
    tail -20 "$log" || true
    scripts/collect_results.sh --log "$log" \
      "$RESULTS_DIR/${name}_results" || true
    FAIL=$((FAIL+1))
  fi
}

for strategy in $SCALING_STRATEGIES; do
  prev_ckpt=""
  for ws in $SCALING_GEOMETRIES; do
    ckpt="$RESULTS_DIR/scaling-${strategy}-ws${ws}_ckpt"
    rm -rf "$ckpt"
    run_point "$strategy" "$ws" "" "$ckpt"
    if [ -n "$prev_ckpt" ] && [ "$SKIP_STITCH" != "1" ]; then
      # Grow leg: continue the PREVIOUS geometry's training state on
      # THIS mesh (reshard-on-restore). The source run's final save sits
      # at its last step, so the continuation gets CKPT_EVERY extra
      # steps to actually run — the scaling engine matches the stitched
      # point back to the clean curve modulo run length, flagged.
      run_point "$strategy" "$ws" "-stitch" "$prev_ckpt" --resume \
        --steps $((STEPS + CKPT_EVERY))
    fi
    prev_ckpt="$ckpt"
  done
  if [ "$SKIP_STITCH" != "1" ] && [ "$WS_MIN" != "$WS_MAX" ]; then
    # Shrink leg: the widest geometry's state back onto the smallest
    # mesh — the preemption-recovery direction (PR 6's dp4 -> dp2).
    run_point "$strategy" "$WS_MIN" "-shrink" "$prev_ckpt" --resume \
      --steps $((STEPS + CKPT_EVERY))
  fi
done

echo ""
echo "=== Efficiency stamp (clean rows only) ==="
python -m "$PKG.analysis.scaling" --stamp-results-dir "$RESULTS_DIR" \
  || FAIL=$((FAIL+1))

echo ""
echo "=== Validation ==="
python -m "$PKG.analysis.validate_results" \
  --results-dir "$RESULTS_DIR" --logs-dir "$RESULTS_DIR" \
  || { echo "VALIDATION FAILED"; FAIL=$((FAIL+1)); }

echo ""
echo "=== Registry ingest + scaling curves (registry: $REGISTRY_DIR) ==="
python -m "$PKG.regress" --registry "$REGISTRY_DIR" ingest \
  --results-dir "$RESULTS_DIR" \
  || { echo "REGISTRY INGEST FAILED"; FAIL=$((FAIL+1)); }
SUMMARY="$RESULTS_DIR/summary"
mkdir -p "$SUMMARY"
python -m "$PKG.analysis.scaling" --registry "$REGISTRY_DIR" \
  --out "$SUMMARY" --png --json | tee "$SUMMARY/scaling_curves.txt" \
  || { echo "SCALING CURVES FAILED"; FAIL=$((FAIL+1)); }

if [ "$SKIP_GATE" != "1" ]; then
  echo ""
  echo "=== Regression gate ==="
  python -m "$PKG.regress" --registry "$REGISTRY_DIR" gate --all \
    || { echo "REGRESSION GATE FAILED (SKIP_GATE=1 to override)"; \
         FAIL=$((FAIL+1)); }
fi

echo ""
echo "=== Report ==="
python -m "$PKG.analysis.parse_metrics" \
  --results-dir "$RESULTS_DIR" --out "$SUMMARY" || FAIL=$((FAIL+1))
python -m "$PKG.analysis.make_report" \
  --csv "$SUMMARY/metrics.csv" --out "$SUMMARY" --plots-dir ../plots \
  --registry "$REGISTRY_DIR" || FAIL=$((FAIL+1))

echo ""
echo "=== Scaling suite complete: $FAIL failure(s) ==="
echo "curves: $SUMMARY/scaling_curves.txt (+ .png/.json), report:" \
     "$SUMMARY/BENCHMARK_REPORT.md"
[ "$FAIL" -eq 0 ]
