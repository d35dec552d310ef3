"""The driver of a cell whose model is named by its workload file:
``step_loop.run``'s protocol (same clocks, same result keys, same ``facts``
keys, so every reader that has no ``workloads`` filter runs here too), with
the model's parts taken **by dotted name from the workload file's ``parts``**
and not by import:

* ``shape``: (workload, config) -> the dict the reference, the FLOP count and
  the readers read (``build_laguna:laguna_shape``);
* ``tiny``: (workload, config) -> the two cut to what ``--allow-cpu`` runs;
* ``check``: (state, shape, batch) -> (ok, numbers), the initial comparison
  with the plain reference (``check_laguna:check_initial``);
* ``flops``: shape -> the chip's operations a token, forward + backward;
* ``counters`` (optional): (model_config, workload) -> {fact: value}, the
  program's trace-time counters the cell's readers take, printed and handed on;
* ``state`` (optional): ``build.build_state``'s signature, where a cell tunes
  the seeded state (the Mellum and Kimi cells' stand-in scales).

A cell of a routed model that holds a part of its experts: the train step's
fourth output (the held experts' rows, the held assignments that did not fit
their buffer) is read after every sync window, and any assignment that did not
fit fails the run.

This is the frame ``step_loop.run``, ``moe_loop.run``, ``mla_loop.run``,
``bd_loop.run``, ``mellum_loop.run`` and ``kda_loop.run`` each repeat with
their model imported (debt D10): written so that a ``benchmark`` PR can fold
those six into it by giving their workload files ``parts``. Everything that is
a function there is used from there (``steps``, ``CompileCounter``,
``memory_peaks``, ``HeldCounter``, ``fresh_moments``, ``fall_and_spread``).
"""

import glob
import os
import statistics
import time

import jax
import numpy as np

from . import build, correct, manifest, peaks, trace_reduce
from .bd_loop import fall_and_spread, fresh_moments
from .mla_loop import HeldCounter
from .step_loop import TRACE_DIR, TRACED_STEPS, CompileCounter, memory_peaks, steps


def run(entry, workload, config, args, devices, process_start):
    from distributed_llm_training_benchmark_framework_tpu.models import tinygpt

    manifest_ = manifest.load_manifest()
    parts = {name: manifest.resolve(dotted) for name, dotted in workload["parts"].items()}
    on_chip = devices[0].platform == "tpu"
    if not on_chip:  # the dry run: tiny widths
        workload, config = parts["tiny"](workload, config)
    shape = parts["shape"](workload, config)
    sync_every = workload["sync_every"]
    chips = workload["chips"]
    tokens_per_step = (workload["grad_accum"] * workload["micro_batch_per_chip"]
                       * workload["mesh"]["data"] * workload["seq_len"])
    routed_layers = shape.get("moe_layers", shape["layers"])
    expected_rows = (tokens_per_step * shape["experts_per_token"] * shape["held"][1]
                     / shape["experts"] * routed_layers)  # a step, all routed layers
    counter = CompileCounter()

    t = time.perf_counter()
    state, table, tokens = parts.get("state", build.build_state)(
        workload, config, devices, args.seed)
    init_s = time.perf_counter() - t
    model_config = state.model_config
    print(f"perfbench: mesh {dict(state.mesh.shape)}, strategy {state.strategy.describe()}, "
          f"remat {model_config.remat}, {state.n_params / 1e6:.1f}M parameters, "
          f"{tokens_per_step} tokens a step, layers {model_config.layer_types}, stacks "
          f"{[(name, len(layers)) for name, layers in model_config.layer_groups]}", flush=True)

    t = time.perf_counter()
    batch = correct.first_micro_batch(state, tokens, workload)
    state.opt_state = None  # the moments' bytes the check's programs need
    initial_ok, initial = parts["check"](state, shape, batch)
    state.opt_state = fresh_moments(state)
    check_s = time.perf_counter() - t
    print(f"perfbench: initial check ok={initial_ok} {initial} ({check_s:.1f} s)", flush=True)

    t = time.perf_counter()
    compiled = state.aot_compile(state.params, state.opt_state, table, 0)
    compile_s = time.perf_counter() - t
    report = HeldCounter(state)
    warm = max(1, -(-workload["warmup_steps"] // sync_every))
    _, _, step = steps(state, table, 0, sync_every, windows=warm)
    overflow = report.drain()[:, 1].sum()
    setup_s = time.perf_counter() - process_start

    counter.on = True
    times, losses, step = steps(state, table, step, sync_every, seconds=args.seconds)
    counter.on = False
    window = report.drain()
    overflow += window[:, 1].sum()
    n_steps = len(times) * sync_every
    tokens_per_s_per_chip = tokens_per_step * n_steps / sum(times) / chips
    window_ok, failed = correct.check_window(losses, sync_every, counter.count)
    fall, spread = fall_and_spread(losses, sync_every)
    allocator, assigned = memory_peaks(devices, compiled)
    with jax.set_mesh(state.mesh):  # the first sequence again, at the weights the window left
        _, held_now = jax.jit(
            lambda params, batch: tinygpt.moe_routing_rows(model_config, params, batch)
        )(state.params, batch)
    by_layer = np.asarray(held_now)[:, 0] * routed_layers / expected_rows
    counters = parts["counters"](model_config, workload) if "counters" in parts else {}
    print(f"perfbench: {n_steps} steps in {sum(times):.3f} s; ms a step by window: "
          f"{[round(1e3 * w / sync_every, 3) for w in times]}; loss {losses[0]:.4f} -> "
          f"{losses[-1]:.4f}; window means fall by {fall:.4f}, their spread {spread:.4f}; "
          f"compilations in the window: {counter.count}; held rows over expected, least and "
          f"most of a step: {window[:, 0].min() / expected_rows:.3f}, "
          f"{window[:, 0].max() / expected_rows:.3f}; held assignments that did not fit: "
          f"{overflow:.0f} (the first sequence's held rows by routed layer after the window: "
          f"{[round(float(x), 3) for x in by_layer]}, "
          f"{initial['held_rows_over_expected_max']:.3f} the worst before it)", flush=True)
    for name, value in counters.items():
        print(f"perfbench: {name}: {value}", flush=True)
    print(f"perfbench: memory peak, bytes: allocator {allocator}, buffer assignment "
          f"{assigned}; set-up: init {init_s:.2f} s, check {check_s:.2f} s, compile "
          f"{compile_s:.2f} s, total {setup_s:.2f} s", flush=True)

    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": max(allocator or 0, assigned)}
    result = {"correct": bool(initial_ok and window_ok and overflow == 0),
              "attempted": n_steps, "failed": failed, "metrics": {}, "device": device}
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
    traced = report.drain()
    result["correct"] = bool(result["correct"] and traced[:, 1].sum() == 0)
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
        "flops_per_token": parts["flops"](shape),
        "memory_allocator_bytes": allocator, "memory_assigned_bytes": assigned,
        "compiles_in_window": counter.count, "traced_steps": TRACED_STEPS,
        "peaks": peaks.peaks(devices[0].device_kind) if on_chip else None,
        "held_rows_traced": traced[:, 0].sum(),
        "held_rows_over_expected": traced[:, 0].mean() / expected_rows,
        "expert_load_max_over_mean": initial["expert_load_max_over_mean"],
        **counters,
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
        busy, window_s = trace_reduce.busy_and_window(trace)
        device.update(busy_s=busy, window_s=window_s)
        result["breakdown"] = trace_reduce.breakdown(
            trace, trace_reduce.matmul_computations(hlo_text))
    return result
