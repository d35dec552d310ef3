#!/usr/bin/env bash
# Chaos suite: drive the fault-injection matrix end to end and assert that
# EVERY fault class lands in one of the two honest outcomes
# (docs/FAULT_TOLERANCE.md):
#
#   - a completed, validated result (after resume where the class allows
#     recovery): sigkill, sigterm, torn-checkpoint, enospc-on-save;
#   - a completed, validated result WITHOUT any restart (self-healing
#     round): bitflip, grad-explode and opt-moments trip the numerics
#     sentinel (checksum, loss-envelope and grad-norm guards
#     respectively — opt-moments corrupts the Adam moment buffers so the
#     NEXT step's grad-norm explodes while its loss stays finite, the
#     one class the grad-norm guard catches FIRST), which rolls back
#     in-process to the last validated checkpoint and replays — the row
#     publishes n_rollbacks=1 and its registry record is never a gate
#     baseline;
#   - a correctly classified failure: nan-loss completes but
#     validate_results REJECTS the row (unresolved anomaly); hang is
#     caught by the IN-PROCESS watchdog (--hang-timeout-sec), which dumps
#     all-thread stacks into a hang_dump telemetry event and exits the
#     distinct retryable code 76 — no external timeout or liveness probe
#     involved — and the arm then RESUMES to a validated result;
#     stall-rank proves the hang abort is COHERENT across ranks (the
#     stuck rank's watchdog broadcasts over the coordination-service KV
#     store; every rank exits 76).
#
# Faults fire at exact sync-window boundaries (faults/injection.py), so
# the whole suite is reproducible: same spec, same abort step, every run.
#
#   - the streaming-data matrix (data/stream.py, --data-path arms):
#     data-corrupt-record heals by quarantine+substitution with an honest
#     records_skipped ledger; data-slow-reader degrades with a measured
#     data_stall_frac; data-stall classifies reason=data_stall (exit 78,
#     distinct from hang) and RESUMES at the exact stream cursor;
#     data-missing-shard refuses loudly naming the shard.
#
#   chaos_suite.sh                 # full matrix on the tinygpt smoke config
#   chaos_suite.sh --smoke         # 4-fault smoke (sigkill + torn-checkpoint
#                                  #   + bitflip sentinel-rollback +
#                                  #   data-corrupt-record stream heal)
#   chaos_suite.sh --faults "sigterm hang" --results-dir /tmp/chaos
#   chaos_suite.sh --elastic       # + geometry-change resume proofs
#                                  #   (save@dp4 -> resume@dp2, and
#                                  #    save@tp2 -> resume@tp1 — validated)
#   chaos_suite.sh --k8s-chaos     # + coordinator-pod-death recovery proof
#                                  #   (fake kubectl, Indexed Job relaunch)
#
# Elastic-resilience arms (docs/FAULT_TOLERANCE.md):
#   sigterm-rank  (in the full matrix) — the multihost dryrun: two ranks
#       share a real jax.distributed rendezvous on localhost, each driving
#       its own local mesh; SIGTERM lands on rank 1 ONLY, and the
#       cross-host preempt-soon broadcast must stop BOTH ranks coherently
#       (unanimous exit 75, emergency checkpoints on both, rank 1 visible
#       in its own telemetry rank file).
#   elastic       (--elastic, opt-in for --smoke) — a checkpoint saved
#       under dp4 resumes and trains onward under dp2, publishing
#       resume_geometry_changed=true and passing validate_results.
#   k8s-coordinator (--k8s-chaos, opt-in) — the k8s path's own chaos arm:
#       the coordinator pod dies mid-rendezvous (fake kubectl fails the
#       first `kubectl wait`), and the suite's Indexed-Job retry loop must
#       relaunch and recover the arm.
#
# Runs on the host CPU by default (the recovery logic is host-level; no
# slice time is worth burning on it) — set CHAOS_ON_DEVICE=1 to inherit
# the caller's JAX platform instead.
set -uo pipefail

cd "$(dirname "$0")/.."
REPO_ROOT="$(pwd)"

FAULTS="sigkill sigterm sigterm-rank nan-loss hang stall-rank bitflip grad-explode opt-moments torn-checkpoint enospc-on-save data-corrupt-record data-stall data-slow-reader data-missing-shard"
ROOT=""
KEEP=0
ELASTIC=0
K8S_CHAOS=0
SUPERVISOR=0
while [ $# -gt 0 ]; do
  case "$1" in
    --smoke) FAULTS="sigkill torn-checkpoint bitflip data-corrupt-record"; shift ;;
    --faults) FAULTS="$2"; shift 2 ;;
    --elastic) ELASTIC=1; shift ;;
    --k8s-chaos) K8S_CHAOS=1; shift ;;
    --supervisor) SUPERVISOR=1; shift ;;
    --results-dir) ROOT="$2"; shift 2 ;;
    --keep) KEEP=1; shift ;;
    *) echo "chaos_suite: unknown flag $1" >&2; exit 2 ;;
  esac
done
[ "$ELASTIC" = "1" ] && FAULTS="$FAULTS elastic elastic-tp"
[ "$K8S_CHAOS" = "1" ] && FAULTS="$FAULTS k8s-coordinator"
# --supervisor (elastic-fleet-supervisor round, runtime/supervisor.py):
#   supervisor-shrink — a dp4 arm is preempted; the supervisor's device
#       probe (capped by the lose-host@2 chaos spec) sees only 2 chips,
#       so it resumes the checkpoint on the largest divisor-legal
#       geometry (dp2) through the elastic path; supervision.json must
#       record the 4->2 shrink leg and validate_results must PASS the
#       recovered row.
#   supervisor-storm — repeated preemption: the injected fault stays
#       armed through attempt 2 (preempt-storm@2), so the supervisor
#       must spend its per-class budget attempt by attempt and still
#       land a validated result on the third, clean, attempt.
#   supervisor-stream-bitflip — the sentinel x stream composition: a
#       sentinel-armed STREAMING run heals a bitflip in-process by
#       rolling back and REWINDING the stream cursor to the validated
#       checkpoint's sidecar, replaying the same records with no loss
#       or duplication (records_consumed == steps, validator-checked).
[ "$SUPERVISOR" = "1" ] && \
  FAULTS="$FAULTS supervisor-shrink supervisor-storm supervisor-stream-bitflip"
if [ -z "$ROOT" ]; then
  ROOT="$(mktemp -d /tmp/chaos_suite.XXXXXX)"
else
  mkdir -p "$ROOT"
fi

if [ "${CHAOS_ON_DEVICE:-0}" != "1" ]; then
  export JAX_PLATFORMS=cpu
  case "${XLA_FLAGS:-}" in
    *xla_force_host_platform_device_count*) : ;;
    *) export XLA_FLAGS="${XLA_FLAGS:-} --xla_force_host_platform_device_count=8" ;;
  esac
fi

# The tinygpt smoke config: small enough that the whole matrix is minutes
# on a laptop CPU, checkpoint cadence dense enough that every recovery
# fault has a committed step behind it. Faults are pinned mid-timed-loop
# (warmup 2, inject at 8/9) so the recovery proof covers the measured
# region, not just warmup.
STEPS=14; WARMUP=2; CKPT_EVERY=4
# sync-every 2: windowed timing, same discipline as the real suite — a
# tiny CPU smoke's per-step jitter would otherwise trip the validator's
# CV envelope and masquerade as a chaos failure.
HARNESS=(python -u benchmarking/train_harness.py
         --strategy ddp --world-size 1 --rank 0 --tier S --seq-len 32
         --steps "$STEPS" --warmup-steps "$WARMUP" --per-device-batch 1
         --grad-accum 1 --dataset-size 64 --heartbeat-sec 0 --sync-every 2)

# Streaming-data fixtures (data/stream.py): the data-fault arms read
# tokenized shards, generated fresh per run (a few KB, <1 s; the
# byte-frozen copies the unit tests pin live in tests/fixtures/shards/).
SHARDS="$ROOT/shards"
python scripts/make_tokenized_shards.py --out "$SHARDS" \
  --num-shards 4 --records-per-shard 64 --seq-len 32 --vocab-size 512 \
  > /dev/null

PASS=0; FAIL=0
declare -a SUMMARY

fail() { echo "CHAOS FAIL $1: $2" >&2; FAIL=$((FAIL+1)); SUMMARY+=("FAIL $1: $2"); }
ok()   { echo "CHAOS OK   $1: $2"; PASS=$((PASS+1)); SUMMARY+=("ok   $1: $2"); }

run_arm() {  # run_arm <dir> <log> [extra flags...]
  local dir="$1" log="$2"; shift 2
  "${HARNESS[@]}" --results-dir "$dir/results" \
    --checkpoint-dir "$dir/ckpt" --checkpoint-every "$CKPT_EVERY" \
    "$@" > "$log" 2>&1
}

validate() {  # validate <dir> -> validator exit code
  python -m distributed_llm_training_benchmark_framework_tpu.analysis.validate_results \
    --results-dir "$1/results" > "$1/validate.log" 2>&1
}

check_recovered() {  # check_recovered <fault> <dir> [extra harness flags...]
  local fault="$1" dir="$2"; shift 2
  if ! run_arm "$dir" "$dir/resume.log" --resume "$@"; then
    fail "$fault" "resume attempt did not complete (see $dir/resume.log)"
    return
  fi
  local row="$dir/results/result_ddp_ws1_seq32_tierS.json"
  if [ ! -f "$row" ]; then fail "$fault" "no result row after resume"; return; fi
  if ! python - "$row" <<'EOF'
import json, sys
r = json.load(open(sys.argv[1]))
assert r["resumed"] is True, f"resumed={r['resumed']}"
assert r["n_restarts"] >= 1, f"n_restarts={r['n_restarts']}"
assert r["resume_step"] >= 0, f"resume_step={r['resume_step']}"
EOF
  then fail "$fault" "resumed row missing honest accounting"; return; fi
  if ! validate "$dir"; then
    fail "$fault" "validate_results rejected the resumed row (see $dir/validate.log)"
    return
  fi
  ok "$fault" "resumed from checkpoint; result validated with resumed=true"
}

for fault in $FAULTS; do
  dir="$ROOT/$fault"
  mkdir -p "$dir"
  echo "=== chaos: $fault ==="
  case "$fault" in
    sigkill)
      run_arm "$dir" "$dir/phase1.log" --inject-fault "sigkill@9"
      rc=$?
      if [ "$rc" -eq 0 ]; then fail "$fault" "run survived its own SIGKILL (rc=0)"; continue; fi
      if ! ls "$dir/ckpt" 2>/dev/null | grep -q '^[0-9]*$'; then
        fail "$fault" "no checkpoint committed before the kill"; continue
      fi
      check_recovered "$fault" "$dir"
      ;;
    sigterm)
      run_arm "$dir" "$dir/phase1.log" --inject-fault "sigterm@9"
      rc=$?
      if [ "$rc" -ne 75 ]; then
        fail "$fault" "expected EXIT_PREEMPTED (75), got rc=$rc"; continue
      fi
      if ! grep -aq '"event": "run_aborted".*"reason": "preempted"' \
           "$dir/results"/telemetry_*.jsonl; then
        fail "$fault" "no run_aborted reason=preempted telemetry event"; continue
      fi
      if ! grep -aq '"reason": "preempted"' <(grep -a '^BENCHMARK_HEARTBEAT ' "$dir/phase1.log" | tail -1); then
        fail "$fault" "final heartbeat does not carry reason=preempted"; continue
      fi
      check_recovered "$fault" "$dir"
      ;;
    torn-checkpoint)
      run_arm "$dir" "$dir/phase1.log" --inject-fault "torn-checkpoint"
      rc=$?
      if [ "$rc" -eq 0 ]; then fail "$fault" "run survived its own SIGKILL (rc=0)"; continue; fi
      check_recovered "$fault" "$dir"
      if [ ! -d "$dir/ckpt/quarantine" ]; then
        fail "$fault" "torn step was not quarantined"
      elif ! grep -q "Resumed from checkpoint" "$dir/resume.log"; then
        fail "$fault" "resume log does not show the fallback restore"
      fi
      ;;
    nan-loss)
      run_arm "$dir" "$dir/phase1.log" --inject-fault "nan-loss@8"
      rc=$?
      if [ "$rc" -ne 0 ]; then
        fail "$fault" "run should complete (anomaly-screened), got rc=$rc"; continue
      fi
      if validate "$dir"; then
        fail "$fault" "validate_results ACCEPTED a NaN-loss run"; continue
      fi
      if ! grep -q "unresolved anomaly" "$dir/validate.log"; then
        fail "$fault" "rejection does not name the unresolved anomaly"; continue
      fi
      ok "$fault" "run completed; validator correctly rejected the row"
      ;;
    hang)
      # Self-healing round: the IN-PROCESS watchdog catches the stall —
      # the external `timeout` below is only a backstop that must never
      # fire (a 124/137 here means the watchdog is broken).
      timeout -k 5 "${CHAOS_HANG_TIMEOUT:-60}" \
        "${HARNESS[@]}" --results-dir "$dir/results" \
        --checkpoint-dir "$dir/ckpt" --checkpoint-every "$CKPT_EVERY" \
        --hang-timeout-sec 5 \
        --inject-fault "hang@6:600" > "$dir/phase1.log" 2>&1
      rc=$?
      if [ "$rc" -ne 76 ]; then
        fail "$fault" "expected the watchdog's EXIT_HUNG (76), got rc=$rc"; continue
      fi
      if ! grep -aq '"event": "hang_dump"' "$dir/results"/telemetry_*.jsonl; then
        fail "$fault" "no hang_dump stack-dump telemetry event"; continue
      fi
      if ! grep -aq '"event": "run_aborted".*"reason": "hang"' \
           "$dir/results"/telemetry_*.jsonl; then
        fail "$fault" "no run_aborted reason=hang telemetry event"; continue
      fi
      if ! scripts/collect_results.sh --log "$dir/phase1.log" \
           "$dir/salvage" > "$dir/collect.log" 2>&1; then
        fail "$fault" "heartbeat salvage failed (see $dir/collect.log)"; continue
      fi
      if ! grep -q '"reason": "hang"' "$dir/salvage"/partial_*.json; then
        fail "$fault" "salvaged partial row not classified reason=hang"; continue
      fi
      check_recovered "$fault" "$dir"
      ;;
    bitflip|grad-explode|opt-moments)
      # Numerics-sentinel heal: the fault poisons the params mid-run, a
      # guard trips, the loop rolls back to the last VALIDATED checkpoint
      # and replays — the run completes IN PROCESS (rc 0, no restart),
      # publishes n_rollbacks=1, passes validate_results, and its
      # registry record is never a gate baseline.
      run_arm "$dir" "$dir/phase1.log" \
        --sentinel on --sentinel-checksum-every "$CKPT_EVERY" \
        --inject-fault "$fault@9"
      rc=$?
      if [ "$rc" -ne 0 ]; then
        fail "$fault" "sentinel should heal in-process (rc=0), got rc=$rc"; continue
      fi
      row="$dir/results/result_ddp_ws1_seq32_tierS.json"
      if [ ! -f "$row" ]; then fail "$fault" "no result row"; continue; fi
      if ! python - "$row" <<'EOF'
import json, sys
r = json.load(open(sys.argv[1]))
assert r["n_rollbacks"] == 1, f"n_rollbacks={r['n_rollbacks']}"
assert r["rollback_steps_replayed"] >= 1, \
    f"rollback_steps_replayed={r['rollback_steps_replayed']}"
assert r["resumed"] is False, "heal must not be a restart"
EOF
      then fail "$fault" "healed row missing honest rollback accounting"; continue; fi
      if ! grep -aq '"event": "sentinel_trip"' "$dir/results"/telemetry_*.jsonl \
         || ! grep -aq '"event": "rollback"' "$dir/results"/telemetry_*.jsonl; then
        fail "$fault" "telemetry missing sentinel_trip/rollback events"; continue
      fi
      if [ "$fault" = "opt-moments" ] && ! grep -aq \
           '"event": "sentinel_trip", .*"kind": "grad_explode"' \
           "$dir/results"/telemetry_*.jsonl; then
        fail "$fault" "opt-moments must trip the GRAD-NORM guard first"; continue
      fi
      if ! validate "$dir"; then
        fail "$fault" "validate_results rejected the healed row (see $dir/validate.log)"
        continue
      fi
      # Never-baseline proof: ingest into a throwaway registry; the gate
      # must SKIP the rolled-back candidate, not verdict from it.
      if ! python -m distributed_llm_training_benchmark_framework_tpu.regress \
           --registry "$dir/registry" ingest --results-dir "$dir/results" \
           > "$dir/regress.log" 2>&1; then
        fail "$fault" "registry ingest of the healed row failed"; continue
      fi
      if ! python -m distributed_llm_training_benchmark_framework_tpu.regress \
           --registry "$dir/registry" gate --all >> "$dir/regress.log" 2>&1 \
         || ! grep -q "rolled-back (sentinel-healed)" "$dir/regress.log"; then
        fail "$fault" "gate did not SKIP the rolled-back record as never-baseline"
        continue
      fi
      ok "$fault" "sentinel tripped, rolled back + replayed in-process; row validated, never a baseline"
      ;;
    stall-rank)
      # Coherent all-host hang abort (self-healing round): rank 1 stalls;
      # its watchdog dumps + broadcasts over the coordination-service KV
      # store, and BOTH ranks must exit the same EXIT_HUNG (76) — no
      # external timeout, no liveness probe, no coordination-service
      # crash code.
      port=$((29820 + RANDOM % 200))
      timeout -k 5 "${CHAOS_MH_TIMEOUT:-180}" \
        "${HARNESS[@]}" --rank 0 --num-processes 2 \
        --master-addr 127.0.0.1 --master-port "$port" \
        --hang-timeout-sec 5 \
        --results-dir "$dir/results" \
        --checkpoint-dir "$dir/ckpt" --checkpoint-every "$CKPT_EVERY" \
        --inject-fault "stall-rank@6:1:600" > "$dir/rank0.log" 2>&1 &
      pid0=$!
      timeout -k 5 "${CHAOS_MH_TIMEOUT:-180}" \
        "${HARNESS[@]}" --rank 1 --num-processes 2 \
        --master-addr 127.0.0.1 --master-port "$port" \
        --hang-timeout-sec 5 \
        --results-dir "$dir/results1" \
        --checkpoint-dir "$dir/ckpt1" --checkpoint-every "$CKPT_EVERY" \
        --inject-fault "stall-rank@6:1:600" > "$dir/rank1.log" 2>&1 &
      pid1=$!
      wait "$pid0"; rc0=$?
      wait "$pid1"; rc1=$?
      if [ "$rc0" -ne 76 ] || [ "$rc1" -ne 76 ]; then
        fail "$fault" "expected unanimous EXIT_HUNG (76/76), got rc0=$rc0 rc1=$rc1"
        continue
      fi
      if ! grep -aq '"event": "hang_dump"' "$dir/results"/telemetry_*.jsonl; then
        fail "$fault" "rank 0 has no hang_dump stack-dump event"; continue
      fi
      ok "$fault" "rank-1 stall aborted BOTH ranks coherently at 76 with stack dumps"
      ;;
    sigterm-rank)
      # Multihost dryrun (elastic-resilience round): two harness
      # processes rendezvous over jax.distributed on localhost; each
      # drives its own local 1-chip mesh (world_size fits the host, so
      # the loop selects local devices). The injected SIGTERM hits rank
      # 1 ONLY; rank 0 must learn of it from the coordination-service
      # broadcast and still write a coherent emergency checkpoint.
      port=$((29610 + RANDOM % 200))
      timeout -k 5 "${CHAOS_MH_TIMEOUT:-180}" \
        "${HARNESS[@]}" --rank 0 --num-processes 2 \
        --master-addr 127.0.0.1 --master-port "$port" \
        --results-dir "$dir/results" \
        --checkpoint-dir "$dir/ckpt" --checkpoint-every "$CKPT_EVERY" \
        --inject-fault "sigterm-rank@9:1" > "$dir/rank0.log" 2>&1 &
      pid0=$!
      timeout -k 5 "${CHAOS_MH_TIMEOUT:-180}" \
        "${HARNESS[@]}" --rank 1 --num-processes 2 \
        --master-addr 127.0.0.1 --master-port "$port" \
        --results-dir "$dir/results1" \
        --checkpoint-dir "$dir/ckpt1" --checkpoint-every "$CKPT_EVERY" \
        --inject-fault "sigterm-rank@9:1" > "$dir/rank1.log" 2>&1 &
      pid1=$!
      wait "$pid0"; rc0=$?
      wait "$pid1"; rc1=$?
      if [ "$rc0" -ne 75 ] || [ "$rc1" -ne 75 ]; then
        fail "$fault" "expected unanimous EXIT_PREEMPTED (75/75), got rc0=$rc0 rc1=$rc1"
        continue
      fi
      if ! grep -aq '"event": "run_aborted".*"reason": "preempted"' \
           "$dir/results"/telemetry_*.jsonl; then
        fail "$fault" "rank 0 has no run_aborted reason=preempted trail"; continue
      fi
      if ! ls "$dir/ckpt" 2>/dev/null | grep -q '^[0-9]*$'; then
        fail "$fault" "rank 0 committed no emergency checkpoint"; continue
      fi
      if ! grep -aq '"fault": "sigterm-rank@9:1"' \
           "$dir/results1"/telemetry_*.rank1.jsonl; then
        fail "$fault" "rank 1's telemetry rank file missing the fault trail"
        continue
      fi
      ok "$fault" "rank-1 SIGTERM stopped BOTH ranks at 75 with checkpoints"
      ;;
    elastic)
      # Geometry-change resume: die under dp4, resume under dp2 — the
      # resharded row must publish resume_geometry_changed=true and pass
      # validate_results (fsdp so the params are genuinely resharded,
      # not just replicated).
      EHARNESS=(python -u benchmarking/train_harness.py
                --strategy fsdp --rank 0 --tier S --seq-len 32
                --steps "$STEPS" --warmup-steps "$WARMUP"
                --per-device-batch 1 --grad-accum 1 --dataset-size 64
                --heartbeat-sec 0 --sync-every 2)
      "${EHARNESS[@]}" --world-size 4 --results-dir "$dir/results" \
        --checkpoint-dir "$dir/ckpt" --checkpoint-every "$CKPT_EVERY" \
        --inject-fault "sigkill@9" > "$dir/phase1.log" 2>&1
      rc=$?
      if [ "$rc" -eq 0 ]; then fail "$fault" "run survived its own SIGKILL (rc=0)"; continue; fi
      if ! ls "$dir/ckpt" 2>/dev/null | grep -q '^[0-9]*$'; then
        fail "$fault" "no dp4 checkpoint committed before the kill"; continue
      fi
      if ! "${EHARNESS[@]}" --world-size 2 --results-dir "$dir/results" \
           --checkpoint-dir "$dir/ckpt" --checkpoint-every "$CKPT_EVERY" \
           --resume > "$dir/resume.log" 2>&1; then
        fail "$fault" "dp2 resume did not complete (see $dir/resume.log)"; continue
      fi
      if ! grep -q "Elastic resume" "$dir/resume.log"; then
        fail "$fault" "resume log does not show the reshard restore"; continue
      fi
      row="$dir/results/result_fsdp_ws2_seq32_tierS.json"
      if [ ! -f "$row" ]; then fail "$fault" "no dp2 result row after resume"; continue; fi
      if ! python - "$row" <<'EOF'
import json, sys
r = json.load(open(sys.argv[1]))
assert r["resumed"] is True, f"resumed={r['resumed']}"
assert r["resume_geometry_changed"] is True, "stitch not recorded"
assert r["n_restarts"] >= 1, f"n_restarts={r['n_restarts']}"
assert r["world_size"] == 2, f"world_size={r['world_size']}"
EOF
      then fail "$fault" "resharded row missing honest accounting"; continue; fi
      if ! validate "$dir"; then
        fail "$fault" "validate_results rejected the resharded resume (see $dir/validate.log)"
        continue
      fi
      ok "$fault" "dp4 checkpoint resumed under dp2; resume_geometry_changed=true validated"
      ;;
    elastic-tp)
      # Chaos follow-up (e) from the ROADMAP: the tp-CHANGE arm — a
      # checkpoint saved under a tensor-parallel mesh (dp2 x tp2) resumes
      # under tp1 (dp2) through the reshard-on-restore path. Previously
      # unit-tested only; this is the subprocess proof.
      EHARNESS=(python -u benchmarking/train_harness.py
                --strategy fsdp --rank 0 --tier S --seq-len 32
                --steps "$STEPS" --warmup-steps "$WARMUP"
                --per-device-batch 1 --grad-accum 1 --dataset-size 64
                --heartbeat-sec 0 --sync-every 2)
      "${EHARNESS[@]}" --world-size 4 --tensor-parallel 2 \
        --results-dir "$dir/results" \
        --checkpoint-dir "$dir/ckpt" --checkpoint-every "$CKPT_EVERY" \
        --inject-fault "sigkill@9" > "$dir/phase1.log" 2>&1
      rc=$?
      if [ "$rc" -eq 0 ]; then fail "$fault" "run survived its own SIGKILL (rc=0)"; continue; fi
      if ! ls "$dir/ckpt" 2>/dev/null | grep -q '^[0-9]*$'; then
        fail "$fault" "no tp2 checkpoint committed before the kill"; continue
      fi
      if ! "${EHARNESS[@]}" --world-size 2 --results-dir "$dir/results" \
           --checkpoint-dir "$dir/ckpt" --checkpoint-every "$CKPT_EVERY" \
           --resume > "$dir/resume.log" 2>&1; then
        fail "$fault" "tp1 resume did not complete (see $dir/resume.log)"; continue
      fi
      if ! grep -q "Elastic resume" "$dir/resume.log"; then
        fail "$fault" "resume log does not show the reshard restore"; continue
      fi
      row="$dir/results/result_fsdp_ws2_seq32_tierS.json"
      if [ ! -f "$row" ]; then fail "$fault" "no tp1 result row after resume"; continue; fi
      if ! python - "$row" <<'EOF'
import json, sys
r = json.load(open(sys.argv[1]))
assert r["resumed"] is True, f"resumed={r['resumed']}"
assert r["resume_geometry_changed"] is True, "tp-change stitch not recorded"
assert r["tensor_parallel"] == 1, f"tensor_parallel={r['tensor_parallel']}"
EOF
      then fail "$fault" "tp-resharded row missing honest accounting"; continue; fi
      if ! validate "$dir"; then
        fail "$fault" "validate_results rejected the tp-change resume (see $dir/validate.log)"
        continue
      fi
      ok "$fault" "tp2 checkpoint resumed under tp1; resume_geometry_changed=true validated"
      ;;
    k8s-coordinator)
      # The k8s path's own chaos arm: the coordinator pod (completion
      # index 0) dies mid-rendezvous, failing the first `kubectl wait`;
      # run_all_benchmarks.sh's bounded Indexed-Job retry loop must
      # relaunch and the second attempt recovers a scrapeable result.
      # Entirely fake kubectl — dryrun-able anywhere, no cluster.
      bindir="$dir/bin"; mkdir -p "$bindir"
      cat > "$bindir/kubectl" <<'PYEOF'
#!/usr/bin/env python3
"""Stateful fake kubectl: first `wait` fails (coordinator pod died
mid-rendezvous), later waits succeed; pod logs carry the result markers
only after a successful wait."""
import json, os, sys
argv = sys.argv[1:]
d = os.environ["FAKE_KUBECTL_DIR"]
with open(os.path.join(d, "calls.log"), "a") as f:
    f.write(json.dumps(argv) + "\n")
def count(name):
    p = os.path.join(d, name)
    n = int(open(p).read()) if os.path.exists(p) else 0
    return n
def bump(name):
    n = count(name) + 1
    with open(os.path.join(d, name), "w") as f:
        f.write(str(n))
    return n
if "apply" in argv:
    if "-" in argv:
        sys.stdin.read()
    print("applied"); sys.exit(0)
if "wait" in argv:
    n = bump("wait_count")
    if n == 1:
        print("error: job failed: coordinator pod deleted mid-rendezvous",
              file=sys.stderr)
        sys.exit(1)
    sys.exit(0)
if "get" in argv and "pods" in argv:
    print("tpu-bench-ddp-ws8-0"); sys.exit(0)
if "get" in argv and "pod" in argv:
    print("Succeeded", end=""); sys.exit(0)
if "logs" in argv:
    if count("wait_count") < 2:
        print("jax.distributed rendezvous failed: coordinator unreachable")
        sys.exit(0)
    print("boot log line rank=0")
    result = {
        "strategy": "ddp", "world_size": 8, "rank": 0, "seq_len": 128,
        "tier": "S", "steps": 6, "per_device_batch": 1, "grad_accum": 1,
        "tokens_per_sec": 8000.0, "mean_step_time_sec": 0.128,
        "mean_loss": 6.0, "peak_vram_gb": 1.0, "h2d_gbps_per_gpu": 1e-5,
    }
    print("BENCHMARK_RESULT_JSON_START")
    print(json.dumps(result, indent=2))
    print("BENCHMARK_RESULT_JSON_END")
    sys.exit(0)
if "delete" in argv:
    print("deleted"); sys.exit(0)
sys.exit(0)
PYEOF
      chmod +x "$bindir/kubectl"
      if ! env FAKE_KUBECTL_DIR="$dir" PATH="$bindir:$PATH" \
           RESULTS_DIR="$dir/results" STRATEGIES="ddp" WORLD_SIZES="8" \
           COMPOSITIONS=off SKIP_CHAOS=1 SKIP_REGRESS=1 \
           MAX_ARM_RETRIES=1 RETRY_BACKOFF_SEC=0 \
           bash scripts/run_all_benchmarks.sh --k8s > "$dir/phase1.log" 2>&1
      then
        fail "$fault" "suite did not recover from the coordinator death (see $dir/phase1.log)"
        continue
      fi
      if [ "$(cat "$dir/wait_count" 2>/dev/null)" != "2" ]; then
        fail "$fault" "expected exactly one relaunch (2 waits), got $(cat "$dir/wait_count" 2>/dev/null)"
        continue
      fi
      if [ ! -f "$dir/results/tpu-bench-ddp-ws8_results/result.json" ]; then
        fail "$fault" "no result scraped after the recovery relaunch"; continue
      fi
      ok "$fault" "coordinator death -> Indexed Job relaunched -> result recovered"
      ;;
    data-corrupt-record)
      # Streaming-data heal arm (docs/FAULT_TOLERANCE.md): one record's
      # payload bit-rots in flight; the CRC check quarantines it, the
      # slot heals by substitution, and the run COMPLETES with an honest
      # records_skipped=1 ledger that validate_results cross-checks
      # against the data_corrupt_record telemetry event.
      run_arm "$dir" "$dir/phase1.log" --data-path "$SHARDS" \
        --inject-fault "data-corrupt-record@9"
      rc=$?
      if [ "$rc" -ne 0 ]; then
        fail "$fault" "corrupt record must heal in-stream (rc=0), got rc=$rc"; continue
      fi
      row="$dir/results/result_ddp_ws1_seq32_tierS.json"
      if [ ! -f "$row" ]; then fail "$fault" "no result row"; continue; fi
      if ! python - "$row" <<'EOF'
import json, sys
r = json.load(open(sys.argv[1]))
assert r["data_mode"] == "stream", r["data_mode"]
assert r["records_skipped"] == 1, f"records_skipped={r['records_skipped']}"
assert r["records_consumed"] == r["steps"], "cursor arithmetic broke"
EOF
      then fail "$fault" "healed row missing honest skip ledger"; continue; fi
      if ! grep -aq '"event": "data_corrupt_record"' \
           "$dir/results"/telemetry_*.jsonl; then
        fail "$fault" "telemetry missing the data_corrupt_record event"; continue
      fi
      if ! validate "$dir"; then
        fail "$fault" "validate_results rejected the healed row (see $dir/validate.log)"
        continue
      fi
      ok "$fault" "corrupt record quarantined + substituted; ledger validated"
      ;;
    data-stall)
      # Input-source outage: the producer goes silent before step 9's
      # batch; the loop must classify reason=data_stall (exit 78 — NOT
      # the watchdog's hang), leave an emergency checkpoint + stream
      # sidecar, salvage a reason=data_stall partial, and the resume must
      # consume exactly the un-consumed records (validated cursor).
      timeout -k 5 "${CHAOS_HANG_TIMEOUT:-60}" \
        "${HARNESS[@]}" --results-dir "$dir/results" \
        --checkpoint-dir "$dir/ckpt" --checkpoint-every "$CKPT_EVERY" \
        --data-path "$SHARDS" --data-stall-timeout-sec 5 \
        --inject-fault "data-stall@9:600" > "$dir/phase1.log" 2>&1
      rc=$?
      if [ "$rc" -ne 78 ]; then
        fail "$fault" "expected EXIT_DATA_STALL (78), got rc=$rc"; continue
      fi
      if ! grep -aq '"event": "run_aborted".*"reason": "data_stall"' \
           "$dir/results"/telemetry_*.jsonl; then
        fail "$fault" "no run_aborted reason=data_stall telemetry event"; continue
      fi
      if ! scripts/collect_results.sh --log "$dir/phase1.log" \
           "$dir/salvage" > "$dir/collect.log" 2>&1; then
        fail "$fault" "heartbeat salvage failed (see $dir/collect.log)"; continue
      fi
      if ! grep -q '"reason": "data_stall"' "$dir/salvage"/partial_*.json; then
        fail "$fault" "salvaged partial row not classified reason=data_stall"; continue
      fi
      check_recovered "$fault" "$dir" --data-path "$SHARDS"
      if ! python - "$dir/results/result_ddp_ws1_seq32_tierS.json" <<'EOF'
import json, sys
r = json.load(open(sys.argv[1]))
assert r["data_mode"] == "stream", r["data_mode"]
expected = (r["resume_step"] + 1)  # 1 record/step at this geometry
assert r["stream_cursor_start"] == expected, \
    f"cursor_start={r['stream_cursor_start']} != {expected}"
EOF
      then fail "$fault" "resumed stream did not continue at the exact cursor"; fi
      ;;
    data-slow-reader)
      # Degraded-mount arm: every record read from record 4 on takes
      # +40 ms. The run must COMPLETE (degrade, never die) with an
      # honest, visibly elevated data_stall_frac — the metric the gate
      # polices as a secondary (regress.stats.SECONDARY_METRICS).
      run_arm "$dir" "$dir/phase1.log" --data-path "$SHARDS" \
        --inject-fault "data-slow-reader@4:40"
      rc=$?
      if [ "$rc" -ne 0 ]; then
        fail "$fault" "slow reader must degrade, not kill (rc=$rc)"; continue
      fi
      row="$dir/results/result_ddp_ws1_seq32_tierS.json"
      if [ ! -f "$row" ]; then fail "$fault" "no result row"; continue; fi
      if ! python - "$row" <<'EOF'
import json, sys
r = json.load(open(sys.argv[1]))
assert r["data_mode"] == "stream", r["data_mode"]
assert r["data_stall_frac"] is not None and r["data_stall_frac"] > 0.02, \
    f"data_stall_frac={r['data_stall_frac']} — the degradation is invisible"
EOF
      then fail "$fault" "row does not carry the measured input-boundedness"; continue; fi
      if ! validate "$dir"; then
        fail "$fault" "validate_results rejected the degraded row"; continue
      fi
      ok "$fault" "reader degraded; run completed with measured data_stall_frac"
      ;;
    data-missing-shard)
      # A hole in the corpus: the stream must refuse loudly, naming the
      # shard, BEFORE any device work — never train on a silently
      # truncated dataset.
      run_arm "$dir" "$dir/phase1.log" --data-path "$SHARDS" \
        --inject-fault "data-missing-shard@2"
      rc=$?
      if [ "$rc" -eq 0 ]; then
        fail "$fault" "run trained on a truncated corpus (rc=0)"; continue
      fi
      if ! grep -q "missing shard 2" "$dir/phase1.log"; then
        fail "$fault" "refusal does not name the missing shard"; continue
      fi
      if ls "$dir/results"/result_*.json >/dev/null 2>&1; then
        fail "$fault" "a result row was published despite the refusal"; continue
      fi
      ok "$fault" "incomplete shard set refused loudly, naming shard 2"
      ;;
    enospc-on-save)
      run_arm "$dir" "$dir/phase1.log" --inject-fault "enospc-on-save"
      rc=$?
      if [ "$rc" -ne 0 ]; then
        fail "$fault" "save failures must degrade, not kill (rc=$rc)"; continue
      fi
      if ! grep -q "checkpoint save at step .* failed" "$dir/phase1.log"; then
        fail "$fault" "no save-degraded warning in the log"; continue
      fi
      if ! validate "$dir"; then
        fail "$fault" "validate_results rejected the degraded-save run"; continue
      fi
      ok "$fault" "saves degraded with warnings; run completed and validated"
      ;;
    supervisor-shrink)
      # The supervisor's headline proof: preempt a dp4 arm, cap the
      # device probe at 2 chips from attempt 2 (lose-host@2), and the
      # supervisor must resume the checkpoint on the largest
      # divisor-legal geometry (dp2) through the elastic path — ledger
      # records the 4->2 shrink leg, the recovered row carries the
      # supervision stamp AND the elastic-resume accounting, and
      # validate_results passes it.
      cat > "$dir/policy.json" <<'EOF'
{"schema_version": 1, "backoff_base_sec": 0, "backoff_max_sec": 0,
 "jitter_frac": 0,
 "classes": {"preempted": {"action": "resume-shrunk", "max_attempts": 3},
             "hung": {"action": "resume", "max_attempts": 2},
             "data_stall": {"action": "resume", "max_attempts": 2},
             "crash": {"action": "cold-retry", "max_attempts": 1},
             "nothing-to-resume": {"action": "give-up", "max_attempts": 0}}}
EOF
      env RECOVERY_POLICY="$dir/policy.json" \
        bash scripts/with_retries.sh --resume-flag --resume \
        --drop-on-retry --inject-fault --chaos "lose-host@2" -- \
        python -u benchmarking/train_harness.py \
        --strategy fsdp --world-size 4 --rank 0 --tier S --seq-len 32 \
        --steps "$STEPS" --warmup-steps "$WARMUP" --per-device-batch 1 \
        --grad-accum 1 --dataset-size 64 --heartbeat-sec 0 --sync-every 2 \
        --results-dir "$dir/results" \
        --checkpoint-dir "$dir/ckpt" --checkpoint-every "$CKPT_EVERY" \
        --inject-fault "sigterm@9" > "$dir/phase1.log" 2>&1
      rc=$?
      if [ "$rc" -ne 0 ]; then
        fail "$fault" "supervised arm did not recover (rc=$rc, see $dir/phase1.log)"
        continue
      fi
      if [ ! -f "$dir/results/supervision.json" ]; then
        fail "$fault" "supervisor left no supervision.json ledger"; continue
      fi
      row="$dir/results/result_fsdp_ws2_seq32_tierS.json"
      if [ ! -f "$row" ]; then
        fail "$fault" "no dp2 result row after the shrink-resume"; continue
      fi
      if ! python - "$dir/results/supervision.json" "$row" <<'EOF'
import json, sys
led = json.load(open(sys.argv[1]))
r = json.load(open(sys.argv[2]))
assert led["shrink_legs"] == ["4->2"], f"shrink_legs={led['shrink_legs']}"
assert led["n_attempts"] == 2, f"n_attempts={led['n_attempts']}"
assert led["attempts"][0]["class"] == "preempted", led["attempts"][0]
assert led["attempts"][0]["action"] == "resume-shrunk", led["attempts"][0]
assert led["final_class"] == "ok" and not led["gave_up"], led
assert r["world_size"] == 2, f"world_size={r['world_size']}"
assert r["resumed"] is True and r["resume_geometry_changed"] is True, r
assert r["supervision"]["n_attempts"] == 2, r.get("supervision")
assert r["supervision"]["shrink_legs"] == ["4->2"], r.get("supervision")
EOF
      then fail "$fault" "ledger/row recovery accounting incoherent"; continue; fi
      if ! validate "$dir"; then
        fail "$fault" "validate_results rejected the shrink-resumed row (see $dir/validate.log)"
        continue
      fi
      ok "$fault" "preempt -> probe saw 2 chips -> dp4 checkpoint resumed at dp2; ledger + row validated"
      ;;
    supervisor-storm)
      # Repeated preemption: preempt-storm@2 keeps the injected SIGTERM
      # armed through attempt 2, so the supervisor spends its preempted
      # budget attempt by attempt (75 -> resume -> 75 -> resume) and
      # lands a validated result on the third, clean, attempt.
      cat > "$dir/policy.json" <<'EOF'
{"schema_version": 1, "backoff_base_sec": 0, "backoff_max_sec": 0,
 "jitter_frac": 0,
 "classes": {"preempted": {"action": "resume", "max_attempts": 3},
             "nothing-to-resume": {"action": "give-up", "max_attempts": 0}}}
EOF
      env RECOVERY_POLICY="$dir/policy.json" \
        bash scripts/with_retries.sh --resume-flag --resume \
        --drop-on-retry --inject-fault --chaos "preempt-storm@2" -- \
        "${HARNESS[@]}" --results-dir "$dir/results" \
        --checkpoint-dir "$dir/ckpt" --checkpoint-every "$CKPT_EVERY" \
        --inject-fault "sigterm@9" > "$dir/phase1.log" 2>&1
      rc=$?
      if [ "$rc" -ne 0 ]; then
        fail "$fault" "storm did not drain to a clean attempt (rc=$rc)"; continue
      fi
      if ! python - "$dir/results/supervision.json" <<'EOF'
import json, sys
led = json.load(open(sys.argv[1]))
classes = [a["class"] for a in led["attempts"]]
assert classes == ["preempted", "preempted", "ok"], classes
# fault_kept is planning metadata: it rides the entry of the attempt
# whose FAILURE planned the next (still-faulted) cmd — attempt 1 plans
# the storm's attempt 2; attempt 2 plans the clean attempt 3.
assert led["attempts"][0].get("fault_kept") is True, led["attempts"][0]
assert led["attempts"][1].get("fault_kept") is None, led["attempts"][1]
assert led["n_attempts"] == 3 and not led["gave_up"], led
assert led["shrink_legs"] == [], led["shrink_legs"]
EOF
      then fail "$fault" "storm ledger does not show 75 -> 75 -> ok"; continue; fi
      if ! validate "$dir"; then
        fail "$fault" "validate_results rejected the storm-recovered row"; continue
      fi
      ok "$fault" "fault stayed armed 2 attempts; budgeted resumes drained the storm to a validated row"
      ;;
    supervisor-stream-bitflip)
      # Sentinel x stream composition: a sentinel-armed STREAMING run
      # takes a bitflip, rolls back in-process to the last validated
      # checkpoint AND rewinds the stream cursor to that checkpoint's
      # sidecar — replaying the same records, so the final ledger shows
      # no record loss or duplication (records_consumed == steps at this
      # 1-record/step geometry, cursor arithmetic validator-checked).
      run_arm "$dir" "$dir/phase1.log" --data-path "$SHARDS" \
        --sentinel on --sentinel-checksum-every "$CKPT_EVERY" \
        --inject-fault "bitflip@9"
      rc=$?
      if [ "$rc" -ne 0 ]; then
        fail "$fault" "sentinel should heal the streaming run in-process (rc=$rc)"
        continue
      fi
      if ! grep -q "stream rewound to cursor" "$dir/phase1.log"; then
        fail "$fault" "rollback did not rewind the stream cursor"; continue
      fi
      row="$dir/results/result_ddp_ws1_seq32_tierS.json"
      if [ ! -f "$row" ]; then fail "$fault" "no result row"; continue; fi
      if ! python - "$row" <<'EOF'
import json, sys
r = json.load(open(sys.argv[1]))
assert r["data_mode"] == "stream", r["data_mode"]
assert r["n_rollbacks"] == 1, f"n_rollbacks={r['n_rollbacks']}"
assert r["rollback_steps_replayed"] >= 1, r["rollback_steps_replayed"]
assert r["resumed"] is False, "heal must not be a restart"
assert r["records_consumed"] == r["steps"], (
    f"records_consumed={r['records_consumed']} != steps={r['steps']} "
    "— the rewind lost or duplicated records")
assert r["records_skipped"] == 0, f"records_skipped={r['records_skipped']}"
EOF
      then fail "$fault" "healed streaming row's cursor ledger broke"; continue; fi
      if ! grep -aq '"event": "sentinel_trip"' "$dir/results"/telemetry_*.jsonl \
         || ! grep -aq '"event": "rollback"' "$dir/results"/telemetry_*.jsonl; then
        fail "$fault" "telemetry missing sentinel_trip/rollback events"; continue
      fi
      if ! validate "$dir"; then
        fail "$fault" "validate_results rejected the healed streaming row (see $dir/validate.log)"
        continue
      fi
      ok "$fault" "bitflip on stream healed in-process; cursor rewound exactly, no loss/duplication"
      ;;
    *)
      fail "$fault" "unknown fault class"; continue
      ;;
  esac
done

echo ""
echo "=== chaos suite: $PASS ok, $FAIL failed ==="
for line in "${SUMMARY[@]}"; do echo "  $line"; done
if [ "$KEEP" = "0" ] && [ "$FAIL" -eq 0 ] && [[ "$ROOT" == /tmp/chaos_suite.* ]]; then
  rm -rf "$ROOT"
else
  echo "artifacts: $ROOT"
fi
[ "$FAIL" -eq 0 ]
