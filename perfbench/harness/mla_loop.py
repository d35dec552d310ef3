"""The driver of a DeepSeek-V2-class cell: ``step_loop.run``'s protocol (same
clocks, same result keys, same ``facts`` keys, so every reader that has no
``workloads`` filter runs here too) with what this model changes: the initial
check is against ``reference_mla``, holds the held experts' gradients and the
buffer's rows to the reference's too and requires every held assignment
computed, the FLOPs are this chip's active count (``flops_mla``), and the
train step's fourth output (the held experts' rows and the held assignments
that did not fit their buffer) is read after every sync window: any that did
not fit fails the run. A workload file names it under ``driver``.

It repeats ``step_loop.run``'s body, as ``moe_loop.run`` does and for the same
reason (that function reaches its reference and its FLOP count by import);
everything that is a function there is used from there.
"""

import glob
import math
import os
import statistics
import time

import jax
import jax.numpy as jnp
import numpy as np

from . import build, build_mla, correct, flops_mla, manifest, peaks, reference_mla, trace_reduce
from .step_loop import TRACE_DIR, TRACED_STEPS, CompileCounter, memory_peaks, steps

# Calibrated on the v5e at the published widths (tools/calibrate_correct_mla.py,
# which runs every wrong model below through ``check_initial``, and every run's
# "initial check" line; PERF.md section 6, PR 30). Each limit lies between two
# readings: the program over its seeds, and the nearest wrong model.
#
# per_position, in the units of ``correct.py``: root-mean-square difference of
# the per-position losses over their spread in the reference. The program
# (bfloat16 matmuls) reads 0.0147-0.0152; 114-185 of a layer's 98,304
# assignments choose another expert than the float32 reference does (near-ties
# of the 6th and 7th probability), each moving one small gate's term in or out
# of the held sum, about what bfloat16 rounding moves a position by (the
# reference itself in bfloat16 passes reads 0.011). Refused by it: the
# reference with every weight rounded to float8_e4m3fn, the nearest precision
# below the cell's, 0.166-0.169; the latent's norm left out 0.173-0.176; the
# scale without YaRN's m^2 0.45; YaRN off 0.64; the shared experts left out
# 0.83; rotary over the whole head 1.03. The limit is the geometric middle of
# 0.0152 and 0.166. One held expert fewer reads 0.013, under the program's own
# distance (a held expert's term is a gate of about 1/64 on 0.75 rows a token):
# this limit cannot see the routed share, and the next two do.
#
# expert_grad: the worst, over the routed layers, the held experts and their two
# matrices, of |program - reference| / max(|program|, |reference|) of the
# gradient of the mean per-position loss: through the dispatch, the grouped
# matmuls and the combine with their hand-written transposes, at the timed
# sizes and remat policy. The program reads 0.099-0.110 over its seeds (the
# worst of 80 slices; a flipped choice upstream moves a token's rows in every
# layer behind it); float8 weights 0.397-0.406, the latent's norm left out
# 0.414-0.417, every other wrong model 0.81-1.34; one held expert fewer 1.0,
# exactly: the reference has no gradient for the expert it leaves out. The
# limit is the geometric middle of 0.103 and 0.397.
#
# held_rows: the rows the program's dispatch put into the held experts' buffer
# against the float32 reference's own count of assignments on those experts,
# the worst layer, over the expected rows. The program reads 0.0010-0.0017
# (the flips that cross the held experts' edge); one held expert fewer
# 0.1755-0.1763 (that expert's assignments); float8 weights 0.007-0.011, under
# the limit: it is refused by the two above. The limit is the geometric middle
# of 0.0017 and 0.1755.
#
# mean_loss: the program 8e-8 to 9e-6; the guard it is in ``correct.py``.
TOLERANCE = {"per_position": 0.05, "expert_grad": 0.2, "held_rows": 0.02, "mean_loss": 2e-4}
EXPERT_LEAVES = ("moe_wgu", "moe_wd")


def program_side(model_config):
    """(params, batch) -> the program's (B, S) per-position losses, the gradient
    of their mean by the held experts' two leaves, its (routed layers, experts)
    assignment counts and its (routed layers, 2) held rows and overflow. The
    parameters are an argument (closed over they become constants)."""
    from distributed_llm_training_benchmark_framework_tpu.models import tinygpt

    def program(params, batch):
        def mean_loss(experts):
            p = {**params, "blocks": {**params["blocks"], **experts}}
            logits, _ = tinygpt.forward(model_config, p, batch)
            logp = jax.nn.log_softmax(logits, -1)
            losses = -jnp.take_along_axis(logp, batch[..., None], -1)[..., 0]
            return jnp.mean(losses), losses

        experts = {k: params["blocks"][k] for k in EXPERT_LEAVES}
        grads, losses = jax.grad(mean_loss, has_aux=True)(experts)
        return (losses, grads, *tinygpt.moe_routing_rows(model_config, params, batch))

    return program


def reference_side(shape):
    """(params, batch, the program's gradients) -> the reference's (B, S)
    per-position losses, its (routed layers, experts) assignment counts, and the
    (leaves, routed layers, held experts) distance of the program's gradients
    from its own ``jax.grad``, expert by expert."""
    def reference(params, batch, got):
        def mean_loss(experts):
            p = {**params, "blocks": {**params["blocks"], **experts}}
            # a sequence at a time, its forward run again in the backward pass:
            # beside the training state there is room for one, not for both
            losses, counts = jax.lax.map(jax.checkpoint(
                lambda t: reference_mla.token_losses_and_counts(shape, p, t)), batch)
            return jnp.mean(losses), (losses, counts.sum(0))

        experts = {k: params["blocks"][k] for k in EXPERT_LEAVES}
        with jax.default_matmul_precision("highest"):
            want, (losses, counts) = jax.grad(mean_loss, has_aux=True)(experts)
        norm = lambda x: jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)), (2, 3)))
        distance = jnp.stack([
            norm(got[k] - want[k]) / jnp.maximum(jnp.maximum(norm(got[k]), norm(want[k])), 1e-30)
            for k in EXPERT_LEAVES])
        return losses, counts, distance

    return reference


def check_initial(state, shape, batch, program=None, reference_params=None):
    """-> (ok, numbers): the per-position comparison of ``correct.py`` against
    the reference; the held experts' gradients against the reference's, expert
    by expert; the buffer's rows against the reference's count of assignments
    on the held experts; and no held assignment over the buffer. ``program``
    (the program's side, computed before) and ``reference_params`` (other
    weights for the reference alone) are the calibration's."""
    with jax.set_mesh(state.mesh):
        got, grads, counts, held = program or jax.jit(program_side(state.model_config))(
            state.params, batch)
        want, want_counts, distance = jax.jit(reference_side(shape))(
            state.params if reference_params is None else reference_params, batch, grads)
    want, got = np.asarray(want, np.float64), np.asarray(got, np.float64)
    counts, want_counts, held = np.asarray(counts), np.asarray(want_counts), np.asarray(held)
    first, count = shape["held"]
    assignments = batch.size * shape["experts_per_token"]
    expected = assignments * count / shape["experts"]
    routed_here = counts[:, first:first + count].sum(-1)  # (routed layers,)
    numbers = {
        "loss_program": got.mean(),
        "loss_reference": want.mean(),
        "mean_loss_rel_err": abs(got.mean() - want.mean()) / abs(want.mean()),
        "per_position_err": math.sqrt(np.mean((got - want) ** 2)) / want.std(),
        "expert_grad_err": np.asarray(distance, np.float64).max(),
        "held_rows_err": (np.abs(
            held[:, 0] - want_counts[:, first:first + count].sum(-1)) / expected).max(),
        "held_overflow": int(held[:, 1].sum()),
        "held_rows_over_expected_max": (routed_here / expected).max(),
        "held_rows_over_expected_mean": (routed_here / expected).mean(),
        "expert_load_max_over_mean": (counts.max(-1) / (assignments / shape["experts"])).max(),
    }
    ok = (numbers["per_position_err"] <= TOLERANCE["per_position"]
          and numbers["mean_loss_rel_err"] <= TOLERANCE["mean_loss"]
          and numbers["expert_grad_err"] <= TOLERANCE["expert_grad"]
          and numbers["held_rows_err"] <= TOLERANCE["held_rows"]
          and numbers["held_overflow"] == 0)
    return bool(ok), {k: float(v) for k, v in numbers.items()}


class HeldCounter:
    """Wraps the step so that ``step_loop.steps`` sees the three outputs it
    knows, and keeps the fourth: (rows, overflow) of every step, on the device
    until ``drain`` (after a window's losses are on the host anyway)."""

    def __init__(self, state):
        self.pending, inner = [], state.step_fn

        def step_fn(params, opt_state, table, step):
            params, opt_state, loss, held = inner(params, opt_state, table, step)
            self.pending.append(held)
            return params, opt_state, loss

        state.step_fn = step_fn

    def drain(self):
        """-> (steps, 2) float64 since the last drain."""
        out = np.asarray(jax.device_get(self.pending), np.float64).reshape(-1, 2)
        self.pending.clear()
        return out


def run(entry, workload, config, args, devices, process_start):
    manifest_ = manifest.load_manifest()
    on_chip = devices[0].platform == "tpu"
    if not on_chip:  # the dry run: tiny latent widths, 4 of 8 experts held, 3 a token
        config = build_mla.tiny_mla(config)
    shape = build_mla.mla_shape(workload, config)
    sync_every = workload["sync_every"]
    chips = workload["chips"]
    tokens_per_step = (workload["grad_accum"] * workload["micro_batch_per_chip"]
                       * workload["mesh"]["data"] * workload["seq_len"])
    expected_rows = (tokens_per_step * shape["experts_per_token"] * shape["held"][1]
                     / shape["experts"] * shape["moe_layers"])  # a step, all routed layers
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
    held = HeldCounter(state)
    warm = max(1, -(-workload["warmup_steps"] // sync_every))
    _, _, step = steps(state, table, 0, sync_every, windows=warm)
    overflow = held.drain()[:, 1].sum()
    setup_s = time.perf_counter() - process_start

    counter.on = True
    times, losses, step = steps(state, table, step, sync_every, seconds=args.seconds)
    counter.on = False
    window_held = held.drain()
    overflow += window_held[:, 1].sum()
    n_steps = len(times) * sync_every
    tokens_per_s_per_chip = tokens_per_step * n_steps / sum(times) / chips
    window_ok, failed = correct.check_window(losses, sync_every, counter.count)
    allocator, assigned = memory_peaks(devices, compiled)
    print(f"perfbench: {n_steps} steps in {sum(times):.3f} s; ms a step by window: "
          f"{[round(1e3 * w / sync_every, 3) for w in times]}; loss {losses[0]:.4f} -> "
          f"{losses[-1]:.4f}; compilations in the window: {counter.count}; held rows over "
          f"expected, least and most of a step: "
          f"{window_held[:, 0].min() / expected_rows:.3f}, "
          f"{window_held[:, 0].max() / expected_rows:.3f}; held assignments that did not "
          f"fit: {overflow:.0f}", flush=True)
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
    traced_held = held.drain()
    result["correct"] = bool(result["correct"] and traced_held[:, 1].sum() == 0)
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
        "flops_per_token": flops_mla.train_flops_per_token(shape),
        "memory_allocator_bytes": allocator, "memory_assigned_bytes": assigned,
        "compiles_in_window": counter.count, "traced_steps": TRACED_STEPS,
        "peaks": peaks.peaks(devices[0].device_kind) if on_chip else None,
        "held_rows_traced": traced_held[:, 0].sum(),
        "held_rows_over_expected": traced_held[:, 0].mean() / expected_rows,
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
