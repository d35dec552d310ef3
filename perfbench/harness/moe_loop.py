"""The driver of an OLMoE-class cell: ``step_loop.run``'s protocol (same
clocks, same result keys, same ``facts`` keys, so every reader that has no
``workloads`` filter runs here too) with what a routed model changes: the
initial check is against ``reference_moe`` and counts dropped assignments,
the FLOPs are the active parameters' (``flops_moe``), and the routing counter
goes into ``facts``. A workload file names it under ``driver``.

It repeats ``step_loop.run``'s body because that function reaches its
reference and its FLOP count by import, not by name; everything that is a
function there (``steps``, ``CompileCounter``, ``memory_peaks``, the window
check, the builders) is used from there.
"""

import glob
import math
import os
import statistics
import time

import jax
import jax.numpy as jnp
import numpy as np

from . import build, build_moe, correct, flops_moe, manifest, peaks, reference_moe, trace_reduce
from .step_loop import TRACE_DIR, TRACED_STEPS, CompileCounter, memory_peaks, steps

# Calibrated on the v5e at the published widths (tools/calibrate_correct_moe.py
# and every run's "initial check" line; PERF.md section 6, PR 26), in the units
# of ``correct.py``: root-mean-square difference of the per-position losses
# over their spread in the reference. The program (bfloat16 matmuls) reads
# 0.047-0.049 over its seeds: 354-375 of 8192 positions choose another set of 8
# experts than the float32 reference, because the router's input is rounded to
# bfloat16 and the 8th and 9th probabilities are often a hair apart; the two
# experts traded carry nearly the same small gate, so a flip moves a position
# about as much as bfloat16 rounding does (the reference itself in bfloat16
# passes reads 0.042-0.045). What the limit has to refuse: one expert fewer a
# token 0.155-0.157; the reference with every weight rounded to float8_e4m3fn,
# the nearest precision below the cell's, 0.195-0.198; QK-norm left out
# 0.347-0.352; gates renormalised 0.390-0.394; the causal mask dropped 0.94.
# mean_loss: the program 8e-6 to 6e-5; it tells none of the wrong models
# apart (3e-6 to 3e-4) and stays as the guard it is in ``correct.py``.
TOLERANCE = {"per_position": 0.1, "mean_loss": 2e-4}


def token_losses(model_config, shape):
    """(params, batch) -> the program's and the reference's (B, S) per-position
    losses and the program's (layers, experts) assignment counts. The
    parameters are an argument (closed over they become constants)."""
    from distributed_llm_training_benchmark_framework_tpu.models import tinygpt

    def both(params, batch):
        logits, _ = tinygpt.forward(model_config, params, batch)
        logp = jax.nn.log_softmax(logits, -1)
        got = -jnp.take_along_axis(logp, batch[..., None], -1)[..., 0]
        with jax.default_matmul_precision("highest"):
            want = jax.vmap(lambda t: reference_moe.token_losses(shape, params, t))(batch)
        return got, want, tinygpt.moe_expert_counts(model_config, params, batch)

    return both


def check_initial(state, shape, batch):
    """-> (ok, numbers): the per-position comparison of ``correct.py`` against
    the routed reference, and no assignment dropped."""
    with jax.set_mesh(state.mesh):
        got, want, counts = jax.jit(token_losses(state.model_config, shape))(state.params, batch)
    want, got = np.asarray(want, np.float64), np.asarray(got, np.float64)
    counts = np.asarray(counts)
    assignments = batch.size * shape["experts_per_token"]
    numbers = {
        "loss_program": got.mean(),
        "loss_reference": want.mean(),
        "mean_loss_rel_err": abs(got.mean() - want.mean()) / abs(want.mean()),
        "per_position_err": math.sqrt(np.mean((got - want) ** 2)) / want.std(),
        "dropped_assignments": int(assignments * counts.shape[0] - counts.sum()),
        "expert_load_max_over_mean": (counts.max(-1) / (assignments / shape["experts"])).max(),
    }
    ok = (numbers["per_position_err"] <= TOLERANCE["per_position"]
          and numbers["mean_loss_rel_err"] <= TOLERANCE["mean_loss"]
          and numbers["dropped_assignments"] == 0)
    return bool(ok), {k: float(v) for k, v in numbers.items()}


def run(entry, workload, config, args, devices, process_start):
    manifest_ = manifest.load_manifest()
    on_chip = devices[0].platform == "tpu"
    if not on_chip:  # the dry run: eight experts, two a token, as the CPU tests have
        config = {**config, "num_experts": 8, "num_experts_per_tok": 2}
    shape = build_moe.moe_shape(workload, config)
    sync_every = workload["sync_every"]
    chips = workload["chips"]
    tokens_per_step = (workload["grad_accum"] * workload["micro_batch_per_chip"]
                       * workload["mesh"]["data"] * workload["seq_len"])
    counter = CompileCounter()

    t = time.perf_counter()
    state, table, tokens = build.build_state(workload, config, devices, args.seed)
    init_s = time.perf_counter() - t
    print(f"perfbench: mesh {dict(state.mesh.shape)}, strategy {state.strategy.describe()}, "
          f"remat {state.model_config.remat}, {state.n_params / 1e6:.1f}M parameters, "
          f"{tokens_per_step} tokens a step", flush=True)

    t = time.perf_counter()
    batch = correct.first_micro_batch(state, tokens, workload)
    initial_ok, initial = check_initial(state, shape, batch)
    check_s = time.perf_counter() - t
    print(f"perfbench: initial check ok={initial_ok} {initial} ({check_s:.1f} s)", flush=True)

    t = time.perf_counter()
    compiled = state.aot_compile(state.params, state.opt_state, table, 0)
    compile_s = time.perf_counter() - t
    warm = max(1, -(-workload["warmup_steps"] // sync_every))
    _, _, step = steps(state, table, 0, sync_every, windows=warm)
    setup_s = time.perf_counter() - process_start

    counter.on = True
    times, losses, step = steps(state, table, step, sync_every, seconds=args.seconds)
    counter.on = False
    n_steps = len(times) * sync_every
    tokens_per_s_per_chip = tokens_per_step * n_steps / sum(times) / chips
    window_ok, failed = correct.check_window(losses, sync_every, counter.count)
    allocator, assigned = memory_peaks(devices, compiled)
    print(f"perfbench: {n_steps} steps in {sum(times):.3f} s; ms a step by window: "
          f"{[round(1e3 * w / sync_every, 3) for w in times]}; loss {losses[0]:.4f} -> "
          f"{losses[-1]:.4f}; compilations in the window: {counter.count}", flush=True)
    print(f"perfbench: memory peak, bytes: allocator {allocator}, buffer assignment "
          f"{assigned}; set-up: init {init_s:.2f} s, check {check_s:.2f} s, compile "
          f"{compile_s:.2f} s, total {setup_s:.2f} s", flush=True)

    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": max(allocator or 0, assigned)}
    result = {"correct": bool(initial_ok and window_ok), "attempted": n_steps,
              "failed": failed, "metrics": {}, "device": device}
    measured = {
        "tokens_per_s_per_chip": tokens_per_s_per_chip,
        "step_time_p50_ms": 1e3 * statistics.median(w / sync_every for w in times),
        "setup_s": setup_s,
    }
    if not args.trace:
        if on_chip:
            units = {m["name"]: m["unit"] for m in manifest.cell_metrics(
                manifest_, entry["name"], "end_to_end")}
            result["metrics"] = {
                name: {"value": measured[name], "unit": unit} for name, unit in units.items()
            }
        return result

    trace_dir = os.path.join(TRACE_DIR, entry["name"])
    for old in glob.glob(os.path.join(trace_dir, "plugins/profile/*/*")):
        os.remove(old)
    counter.on = True
    jax.profiler.start_trace(trace_dir)
    try:
        steps(state, table, step, TRACED_STEPS, windows=1)
    finally:
        jax.profiler.stop_trace()
    counter.on = False
    trace = trace_reduce.load(
        max(glob.glob(os.path.join(trace_dir, "plugins/profile/*/*.xplane.pb")))
    )
    hlo_text = compiled.as_text()
    with open(os.path.join(trace_dir, "step_hlo.txt"), "w") as f:
        f.write(hlo_text)  # beside the trace, for whoever reads it by hand
    facts = {
        "workload": workload, "config": config, "shape": shape, "chips": chips,
        "init_s": init_s, "compile_s": compile_s, "hlo_text": hlo_text,
        "tokens_per_s_per_chip": tokens_per_s_per_chip,
        "flops_per_token": flops_moe.train_flops_per_token(shape),
        "memory_allocator_bytes": allocator, "memory_assigned_bytes": assigned,
        "compiles_in_window": counter.count, "traced_steps": TRACED_STEPS,
        "peaks": peaks.peaks(devices[0].device_kind) if on_chip else None,
        "expert_load_max_over_mean": initial["expert_load_max_over_mean"],
    }
    for metric in manifest.cell_metrics(manifest_, entry["name"], "per_layer"):
        value = manifest.metric_reader(metric["name"])(trace, facts)
        if value is None:
            continue
        if on_chip:
            result["metrics"][metric["name"]] = {"value": value, "unit": metric["unit"]}
        else:
            print(f"perfbench: dry run, not reported: {metric['name']} = {value}", flush=True)
    if on_chip:
        busy, window = trace_reduce.busy_and_window(trace)
        device.update(busy_s=busy, window_s=window)
        result["breakdown"] = trace_reduce.breakdown(
            trace, trace_reduce.matmul_computations(hlo_text))
    return result
