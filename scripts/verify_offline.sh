#!/usr/bin/env bash
# Offline verification: prove the stack imports, builds models and constructs
# data with zero network access.
#
# Parity with reference scripts/verify_offline.sh (its four --network none
# docker tests: imports, tier instantiation + param counts, dataset build,
# bundled-config presence). Runs either against a built image
# (`verify_offline.sh --image <tag>`) or the local checkout (default), since
# the TPU framework is testable without containers.
set -euo pipefail
cd "$(dirname "$0")/.."

MODE="local"
IMAGE=""
if [ "${1:-}" = "--image" ]; then MODE="docker"; IMAGE="$2"; fi

PY_TESTS=$(cat <<'EOF'
import os
os.environ.setdefault("JAX_PLATFORMS", "cpu")

print("--- [1/5] imports ---")
import jax, optax, numpy, pandas, matplotlib
import distributed_llm_training_benchmark_framework_tpu as fw
print(f"OK: jax {jax.__version__}, optax {optax.__version__}, framework {fw.__version__}")

print("--- [2/5] model tiers instantiate on CPU ---")
from distributed_llm_training_benchmark_framework_tpu.models import (
    get_model_config, init_params, count_params)
for tier in ("S", "A"):
    cfg = get_model_config(tier, 256)
    params = init_params(cfg, jax.random.key(0))
    print(f"OK: tier {tier}: {count_params(params)/1e6:.2f}M params")
shapes = jax.eval_shape(
    lambda k: init_params(get_model_config("B", 256), k), jax.random.key(0))
n = sum(int(numpy.prod(l.shape)) for l in jax.tree_util.tree_leaves(shapes))
print(f"OK: tier B (eval_shape only): {n/1e6:.2f}M params")

print("--- [3/5] synthetic dataset ---")
from distributed_llm_training_benchmark_framework_tpu.data import SyntheticDataset
ds = SyntheticDataset(vocab_size=32000, seq_len=128, size=16)
assert ds.batch_for_step(0, 4).shape == (4, 128)
print("OK: dataset constructs and batches")

print("--- [4/5] bundled configs ---")
import glob, json
files = sorted(glob.glob("configs/strategies/*.json"))
assert len(files) >= 4, files
for f in files:
    json.load(open(f))
print(f"OK: {len(files)} strategy configs parse")
print("PY CHECKS PASSED")
EOF
)

GRAFTCHECK_MEMORY="distributed_llm_training_benchmark_framework_tpu.analysis.static"

if [ "$MODE" = "docker" ]; then
  echo "=== Offline verification (docker --network none, image $IMAGE) ==="
  docker run --rm --network none --entrypoint python "$IMAGE" -c "$PY_TESTS"
  echo "--- [5/5] graftcheck --memory (GC110 compile-time memory budgets) ---"
  docker run --rm --network none --entrypoint python "$IMAGE" -m "$GRAFTCHECK_MEMORY" --memory
else
  echo "=== Offline verification (local checkout) ==="
  python -c "$PY_TESTS"
  echo "--- [5/5] graftcheck --memory (GC110 compile-time memory budgets) ---"
  # The memory-budget audit is itself a zero-network, CPU-host check:
  # every roster arm's compile-time memory accounting against the frozen
  # memory_budgets section + the cross-tier growth laws (no hardware).
  python -m "$GRAFTCHECK_MEMORY" --memory
fi
echo "ALL OFFLINE CHECKS (incl. GC110 memory audit) PASSED"
