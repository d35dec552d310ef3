"""The default driver: one cell as a plain loop over the program's jitted step.

It stands where ``train/loop.py`` stands for the program's users, and drives
the same step (``train/step.py``) through the program's public builders.
``run`` returns what ``run.py`` prints; a workload file may name another
``driver`` with the same signature for a job that is not a plain step loop.
"""

import glob
import os
import statistics
import time

from . import build, correct, flops, manifest, peaks, trace_reduce

TRACED_STEPS = 5
TRACE_DIR = os.path.join(manifest.BENCH_DIR, ".trace")


class CompileCounter:
    """Counts backend compilations (cache reads included) while ``on``."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax

        self.on, self.count = False, 0
        jax.monitoring.register_event_duration_secs_listener(self._listen)

    def _listen(self, event, duration, **_):
        if self.on and event == self.EVENT:
            self.count += 1


def steps(state, table, first_step, sync_every, seconds=None, windows=None):
    """Run sync windows of ``sync_every`` steps until ``seconds`` have passed
    (or for ``windows`` windows). The clock stops after the losses are on the
    host. -> (window seconds, losses, next step)"""
    import jax

    params, opt_state = state.params, state.opt_state
    times, losses, step = [], [], first_step
    start = time.perf_counter()
    while True:
        w0 = time.perf_counter()
        pending = []
        for _ in range(sync_every):
            with jax.profiler.TraceAnnotation("dispatch"):
                params, opt_state, loss = state.step_fn(params, opt_state, table, step)
            pending.append(loss)
            step += 1
        with jax.profiler.TraceAnnotation("loss_fetch"):
            losses += [float(x) for x in jax.device_get(pending)]
        w1 = time.perf_counter()
        times.append(w1 - w0)
        if (windows is not None and len(times) >= windows) or (
            seconds is not None and w1 - start >= seconds
        ):
            break
    state.params, state.opt_state = params, opt_state
    return times, losses, step


def memory_peaks(devices, compiled):
    """Both rungs, in bytes: the allocator's high-water mark on the fullest
    device (None where the backend keeps none) and the compiled step's
    buffer-assignment peak (arguments + outputs + temporaries - aliases)."""
    marks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devices]
    allocator = max((m for m in marks if m), default=None)
    return allocator, int(compiled.memory_analysis().peak_memory_in_bytes)


def run(entry, workload, config, args, devices, process_start):
    import jax

    manifest_ = manifest.load_manifest()
    on_chip = devices[0].platform == "tpu"
    shape = build.model_shape(workload, config)
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
    initial_ok, initial = correct.check_initial(
        state, shape, batch, workload.get("check_grads", False), args.seed
    )
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
    cache = jax.config.jax_compilation_cache_dir
    cache_files = [e for e in os.scandir(cache) if e.is_file()] if cache and os.path.isdir(cache) else []
    print(f"perfbench: memory peak, bytes: allocator {allocator}, buffer assignment "
          f"{assigned}; set-up: init {init_s:.2f} s, check {check_s:.2f} s, compile "
          f"{compile_s:.2f} s, total {setup_s:.2f} s; compile cache: {len(cache_files)} files, "
          f"{sum(e.stat().st_size for e in cache_files) / 2**20:.1f} MiB", flush=True)

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
        "flops_per_token": flops.train_flops_per_token(shape),
        "memory_allocator_bytes": allocator, "memory_assigned_bytes": assigned,
        "compiles_in_window": counter.count, "traced_steps": TRACED_STEPS,
        "peaks": peaks.peaks(devices[0].device_kind) if on_chip else None,
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
