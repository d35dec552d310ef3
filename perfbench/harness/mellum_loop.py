"""The driver of a Mellum-2-class cell: ``step_loop.run``'s protocol (same
clocks, same result keys, same ``facts`` keys, so every reader that has no
``workloads`` filter runs here too) with what this model changes: the initial
check is against ``reference_mellum``, at the cell's own weights and the timed
``model_config``, one layer at a time, a layer of each kind apart (the note
above ``TOLERANCE``); the FLOPs are this chip's count (``flops_mellum``); the
train step's fourth output (the held experts' rows, the held assignments that
did not fit their buffer) is read after every sync window: any assignment that
did not fit fails the run; and the program's counter of what each kind of
layer's kernels visit (``tinygpt.attn_mask_stats``) goes to the readers. A
workload file names it under ``driver``.

It repeats ``step_loop.run``'s body, as ``moe_loop.run``, ``mla_loop.run`` and
``bd_loop.run`` do and for the same reason (that function reaches its reference
and its FLOP count by import): the fifth copy, debt D10. Everything that is a
function there, in ``mla_loop`` or in ``bd_loop`` is used from there
(``steps``, ``CompileCounter``, ``memory_peaks``, ``HeldCounter``, ``Worst``,
``_distance``, ``_layer``, ``fresh_moments``, ``fall_and_spread``); what is
copied is ``run``'s frame (about 110 lines, the check, the window's line and
the facts aside) and the shape of ``check_initial``'s walk over the layers.
"""

import dataclasses
import functools
import glob
import os
import statistics
import time

import jax
import jax.numpy as jnp
import numpy as np

from . import build, build_mellum, correct, flops_mellum, manifest, peaks, reference_mellum, trace_reduce
from .bd_loop import MARGIN, Worst, _distance, _layer, fall_and_spread, fresh_moments
from .mla_loop import HeldCounter
from .step_loop import TRACE_DIR, TRACED_STEPS, CompileCounter, memory_peaks, steps

# How the comparison is made, and why a layer at a time: several routed layers
# in a row are chaotic under top-k (a token whose 8th and 9th probabilities lie
# close takes another expert in bfloat16, and every later layer sees it), so no
# limit on the whole network's logits could tell float8 from bfloat16 (PERF.md
# section 6, PR 36). One layer is not. So the program is fed the reference's
# own hidden states, a sublayer at a time (teacher forcing), at the cell's
# weights, shapes, kernels, remat policy and bounded buffer, through the
# program's own loop over layers (``tinygpt.apply_blocks`` with the timed
# ``model_config`` cut to the one layer, which keeps its kind), forward and
# backward:
#
# * attention, a layer of each kind: the layer with its ``moe_wd`` zeroed is
#   x + attention(x). It is given the reference's input of that layer and, as
#   the output's cotangent, the reference's own gradient of the training loss
#   there. Held to the reference, the window layers' worst and the global
#   layer's apart: what the sublayer adds, over the sequence and over its
#   first FIRST_POSITIONS positions (where a window of 1024 first bites: a
#   window one key long or short, or none, shows among few keys), and the
#   gradient by its six leaves under the whole cotangent and under the
#   cotangent of those first positions alone.
# * the routed share: the layer with its ``wo`` zeroed is h + experts(h). It is
#   given the reference's attention output h. Held to the reference: what the
#   held experts add, the gradient by their two leaves and by the norm's
#   scale; the rows the bounded buffer held against the reference's own
#   count of assignments on the held experts; no assignment over the buffer.
#   The reference chooses its own experts; tokens whose last chosen and first
#   unchosen probabilities lie within MARGIN are left out of the output's
#   comparison and carry no cotangent (``bd_loop``'s rule).
# * the head: ``tinygpt.head`` on the reference's last hidden state,
#   per-position losses against the reference's in the units of ``correct.py``.
# * the loss: the program's whole ``forward`` (every layer live, the timed
#   config) against the reference's training loss assembled from its own
#   layer-wise pass (mean cross-entropy + the router term): a mean over 16,384
#   positions, which near-ties do not move.
FIRST_POSITIONS = 1100
ATTENTION_LEAVES = ("wq", "wkv", "q_norm", "k_norm", "wo", "ln1_scale")
ROUTED_LEAVES = ("moe_wgu", "moe_wd", "ln2_scale")

# Calibrated on the v5e at the published widths (tools/calibrate_correct_mellum.py,
# which runs every wrong model below through the same comparison; PERF.md
# section 6, PR 38: seeds 3800000201-2 of everything and every run's "initial
# check" line). Each limit is the geometric middle of two readings: the
# program's largest, and the nearest of the float8 reference (every weight
# rounded to float8_e4m3fn, the nearest precision below the cell's) and the
# wrong models that this limit has to refuse. Every reading is the worst of its
# layers (and of its leaves).
#
# window_out, |program - reference| / |reference| of what a sliding layer's
# attention adds to its input: the program 0.01197-0.01198; a window of 1025
# 0.0348-0.0350, of 1023 0.0352-0.0360, **by this limit alone**; float8 0.114,
# no window 1.25.
# global_out, the same of the global layer: the program 0.0160; float8 0.142,
# YaRN without its attention_factor 0.69, the window on it 1.10, the sliding
# layers' table 1.60.
# window_first / global_first, the same over the first 1100 positions: the
# program 0.0112-0.0113 / 0.0128-0.0130; no window on a sliding layer 0.048,
# the window on the global layer 0.046-0.052 (76 of 1100 positions differ);
# float8 0.108 / 0.120. A window one key long or short reads 0.013-0.019 here,
# under the limit: few positions differ; window_out refuses it.
# window_grad / global_grad, of the gradient by wq, wkv, q_norm, k_norm, wo,
# ln1_scale through the fused backward on the band / under causal, each
# kind's rotary table, the norms: the program 0.0203-0.0209 / 0.0292-0.0306
# (the float32 reference with bfloat16 weights is 0.019-0.033 from itself);
# float8 0.161-0.184 / 0.198-0.207; a window of 1023 / 1025 0.040-0.044, under
# window_grad's limit (window_out's to refuse).
# first_grad, the same leaves under the cotangent of the first 1100 positions
# alone, both kinds: the program 0.0293-0.0340; float8 0.192-0.226.
# moe_out, of what the held experts add, over the clear tokens (75.5-75.8 % of
# a layer's): the program 0.00644-0.00645; float8 0.122-0.124, one held expert
# fewer 0.267-0.314, a buffer of 0.8 of the expected rows (24,255-24,975
# assignments dropped) 0.45-0.47, gates not renormalised 1.37.
# expert_grad, of the gradient by moe_wgu, moe_wd, ln2_scale: the program
# 0.0066-0.0069; float8 0.124-0.125.
# held_rows: the rows the bounded buffer held against the reference's own
# count, over the expected rows: the program 0.0004-0.0008; one held expert
# fewer 0.074-0.088, the short buffer 0.204-0.209; float8 0.0009-0.0012 is
# under it and refused by eleven others.
# per_position, in the units of ``correct.py``: the program 0.00235-0.00236;
# float8 0.0328-0.0330.
# loss, |program - reference| / reference of the training loss through the
# whole forward, every layer live: the program 2e-5 to 2.7e-4 over ten seeds
# (near-ties that flip an expert in bfloat16 move it: the float32 reference
# with bfloat16 weights is 9e-5 to 1.6e-4 from itself); float8 1.5e-3 to
# 1.6e-3. The accepted cells' 2e-4 is inside the program's own readings: this
# is the middle of the two readings.
TOLERANCE = {
    "window_out": 0.02, "global_out": 0.048, "window_first": 0.023, "global_first": 0.025,
    "window_grad": 0.058, "global_grad": 0.078, "first_grad": 0.08,
    "moe_out": 0.028, "expert_grad": 0.029, "held_rows": 0.008, "per_position": 0.009, "loss": 6.5e-4,
}


def one_layer_config(model_config, kind):
    """The timed config cut to one layer of ``kind``: what ``apply_blocks``
    runs a one-layer stack under, with that kind's rule and table."""
    window = kind == "window"
    rotary = tuple(pair for pair in (model_config.layer_rotary or ()) if pair[0] == kind) or None
    return dataclasses.replace(
        model_config, n_layer=1, layer_types=(kind,),
        sliding_window=model_config.sliding_window if window else None, layer_rotary=rotary)


def program_layer(model_config, kind):
    """(one layer's weights, x (B, S, D), the output's cotangent) -> what the
    timed config's layer of ``kind`` adds to x (its output less the x it was
    given, in the compute dtype), its report (the held experts' rows, the
    assignments over the buffer) and the gradient by the layer's leaves: the
    program's own loop over layers, remat policy, kernels and buffer, on a
    stack of one layer."""
    from distributed_llm_training_benchmark_framework_tpu.models import tinygpt

    config = one_layer_config(model_config, kind)

    def layer(weights, x, cotangent):
        stack = {k: v[None] for k, v in weights.items()}
        x, cotangent = (a.astype(config.compute_dtype) for a in (x, cotangent))
        y, pull_back, aux = jax.vjp(
            lambda stack, x: tinygpt.apply_blocks(config, stack, x), stack, x, has_aux=True)
        d_stack, _ = pull_back(cotangent)
        return (y.astype(jnp.float32) - x.astype(jnp.float32), aux[1:],
                {k: v[0] for k, v in d_stack.items()})

    return layer


@functools.lru_cache(maxsize=4)  # the calibration checks one program many times
def _programs(model_config):
    from distributed_llm_training_benchmark_framework_tpu.models import tinygpt

    def head_losses(params, x, batch):
        logits = tinygpt.head(model_config, params, x.astype(model_config.compute_dtype))
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), -1)
        return -jnp.take_along_axis(logp, batch[..., None], -1)[..., 0]

    return {
        **{kind: jax.jit(program_layer(model_config, kind)) for kind in set(model_config.layer_types)},
        "head": jax.jit(head_losses),
        "loss": jax.jit(lambda params, batch: tinygpt.loss_fn(model_config, params, batch, batch)),
        "routing": jax.jit(lambda params, batch: tinygpt.moe_routing_rows(model_config, params, batch)),
    }


class Reference:
    """The reference's sides of the comparison over the batch's sequences,
    under ``jax.default_matmul_precision("highest")``, each compiled once a
    (kind of table, kind of mask)."""

    def __init__(self, m):
        self.m = m

        def highest(f):
            def call(*a):
                with jax.default_matmul_precision("highest"):
                    return f(*a)
            return jax.jit(call)

        f32 = lambda w: jax.tree.map(lambda t: t.astype(jnp.float32), w)
        self._highest, self._f32 = highest, f32
        routed = lambda w, x: jax.vmap(lambda x: reference_mellum.routed_sublayer(m, x, w))(x)

        def routed_back(w, x, cotangent):
            _, pull_back, _ = jax.vjp(routed, f32(w), x, has_aux=True)
            return pull_back(cotangent)

        def head(params, x, batch):
            def mean_loss(x):
                losses = jax.lax.map(
                    lambda one: reference_mellum.head_losses(m, params, *one), (x, batch))
                return jnp.mean(losses), losses
            return jax.value_and_grad(mean_loss, has_aux=True)(x)

        self.embed = highest(lambda params, batch: jax.lax.map(
            lambda tokens: reference_mellum.embed(m, params, tokens), batch))
        self.routed = highest(lambda w, x: routed(f32(w), x))
        self.routed_back = highest(routed_back)
        # -> ((mean loss, losses), the mean loss's gradient by the last hidden state)
        self.head = highest(lambda params, x, batch: head(f32(params), x, batch))

    def attention(self, layer):
        """(forward, backward) of layer ``layer``'s attention sublayer; layers
        of one kind (table and mask) share their programs."""
        return self._attention_of(
            (self.m["kinds"][layer], self.m.get("mask_kinds", self.m["kinds"])[layer]))

    @functools.lru_cache(maxsize=8)
    def _attention_of(self, kinds):
        one = {**self.m, "kinds": kinds[:1], "mask_kinds": kinds[1:]}
        forward = lambda w, x: jax.vmap(
            lambda x: reference_mellum.attention_sublayer(one, x, w, 0))(x)

        def backward(w, x, cotangent):
            _, pull_back = jax.vjp(forward, self._f32(w), x)
            return pull_back(cotangent)

        return (self._highest(lambda w, x: forward(self._f32(w), x)), self._highest(backward))


def reference_for(shape):
    return _reference(tuple(sorted(shape.items())))


@functools.lru_cache(maxsize=2)
def _reference(items):
    return Reference(dict(items))


def check_initial_numbers(state, shape, batch, model_config=None, reference_params=None):
    """-> numbers: the comparison the note above ``TOLERANCE`` describes."""
    config = model_config or state.model_config
    params = state.params
    weights = params if reference_params is None else reference_params
    reference, programs = reference_for(shape), _programs(config)
    first, count = shape["held"]
    numbers, last = Worst(), shape["layers"] - 1
    early = jnp.arange(batch.shape[1]) < FIRST_POSITIONS
    with jax.set_mesh(state.mesh):
        # the reference's forward pass, every sublayer's input kept
        x = reference.embed(weights, batch)
        inputs, counts, probability = [], [], []
        for i in range(shape["layers"]):
            h = reference.attention(i)[0](_layer(weights, i), x)
            y, router = reference.routed(_layer(weights, i), h)
            inputs.append((x, h, router["margin"] >= MARGIN))
            counts.append(jnp.sum(router["assignments"], 0))
            probability.append(jnp.sum(router["probability"], 0))
            x = y
        ((want_mean, want_losses), cotangent) = reference.head(weights, x, batch)
        got_losses = programs["head"](params, x, batch)
        numbers["per_position_err"] = float(
            jnp.sqrt(jnp.mean(jnp.square(got_losses - want_losses))) / jnp.std(want_losses))
        # backward, the last layer first: each sublayer of the program beside the reference's
        rows = []
        for i in reversed(range(shape["layers"])):
            (x, h, clear), y = inputs[i], x if i == last else inputs[i + 1][0]
            w, own, kind = _layer(weights, i), _layer(params, i), config.layer_types[i]
            layer = programs[kind]
            # the routed share: h -> h + the held experts' part, under the clear tokens' cotangent
            of_clear = cotangent * clear[..., None]
            got_add, report, got_dw = layer({**own, "wo": jnp.zeros_like(own["wo"])}, h, of_clear)
            want_dw, _ = reference.routed_back(w, h, of_clear)
            numbers.see("moe_out_err", _distance(got_add, y - h, clear))
            for k in ROUTED_LEAVES:
                numbers.see("expert_grad_err", _distance(got_dw[k], want_dw[k]), k)
            rows.append(report)
            cotangent = reference.routed_back(w, h, cotangent)[1]
            # attention: x -> x + attention(x), under the whole cotangent and the first positions'
            attention_back = reference.attention(i)[1]
            without_experts = {**own, "moe_wd": jnp.zeros_like(own["moe_wd"])}
            got_add, _, got_dw = layer(without_experts, x, cotangent)
            want_dw, cotangent_in = attention_back(w, x, cotangent)
            numbers.see(f"{kind}_out_err", _distance(got_add, h - x))
            numbers.see(f"{kind}_first_err", _distance(got_add, h - x, early[None, :]))
            for k in ATTENTION_LEAVES:
                numbers.see(f"{kind}_grad_err", _distance(got_dw[k], want_dw[k]), f"{kind}.{k}")
            of_early = cotangent * early[None, :, None]
            _, _, got_dw = layer(without_experts, x, of_early)
            want_dw, _ = attention_back(w, x, of_early)
            for k in ATTENTION_LEAVES:
                numbers.see("first_grad_err", _distance(got_dw[k], want_dw[k]), f"first.{kind}.{k}")
            cotangent = cotangent_in
            numbers["clear_tokens_share_min"] = min(
                numbers.get("clear_tokens_share_min", 1.0), float(jnp.mean(clear)))
        del inputs
        got_loss = float(programs["loss"](params, batch))  # the whole forward, every layer live
        program_counts, _ = programs["routing"](params, batch)
    rows = np.asarray(rows[::-1], np.float64)  # (layers, 2): rows held, assignments over the buffer
    counts, program_counts = np.asarray(counts, np.float64), np.asarray(program_counts, np.float64)
    assignments = batch.size * shape["experts_per_token"]
    expected = assignments * count / shape["experts"]
    balance = shape["experts"] * np.sum(
        counts / assignments * np.asarray(probability, np.float64) / batch.size, -1)
    want_loss = float(want_mean) + shape["aux_coef"] * float(balance.mean())
    by_layer = rows[:, 0] / expected
    numbers.update({
        "loss_program": got_loss, "loss_reference": want_loss,
        "loss_err": abs(got_loss - want_loss) / abs(want_loss),
        "held_rows_err": (np.abs(rows[:, 0] - counts[:, first:first + count].sum(-1)) / expected).max(),
        "held_overflow": int(rows[:, 1].sum()),
        "held_rows_over_expected_max": by_layer.max(),
        "held_rows_over_expected_mean": by_layer.mean(),
        "expert_load_max_over_mean": (program_counts.max(-1) / (assignments / shape["experts"])).max(),
    })
    numbers.update({f"held_rows_over_expected.layer{i}": r for i, r in enumerate(by_layer)})
    return numbers


def refused_by(numbers):
    """The limits of TOLERANCE that these readings are over."""
    return [k for k, limit in TOLERANCE.items()
            if f"{k}_err" in numbers and numbers[f"{k}_err"] > limit]


def check_initial(state, shape, batch):
    """-> (ok, numbers)."""
    numbers = check_initial_numbers(state, shape, batch)
    ok = not refused_by(numbers) and numbers["held_overflow"] == 0
    return bool(ok), {k: float(v) for k, v in numbers.items()}


def build_state(workload, config, devices, seed):
    """``build.build_state`` (uniform ids over the slice from the seed, as the
    DeepSeek cell) and, where the config file has ``qk_norm_scale_init``, the
    QK-norm scales there (the stand-in PR 36 found for a routed stack whose
    seeded weights collapse; the program starts them from 1.0)."""
    state, table, tokens = build.build_state(workload, config, devices, seed)
    if config.get("qk_norm_scale_init") is not None:
        blocks = dict(state.params["blocks"])
        for k in ("q_norm", "k_norm"):
            blocks[k] = jax.device_put(
                jnp.full_like(blocks[k], config["qk_norm_scale_init"]), blocks[k].sharding)
        state.params = {**state.params, "blocks": blocks}
    return state, table, tokens


def run(entry, workload, config, args, devices, process_start):
    from distributed_llm_training_benchmark_framework_tpu.models import tinygpt

    manifest_ = manifest.load_manifest()
    on_chip = devices[0].platform == "tpu"
    if not on_chip:  # the dry run: tiny widths, one period, 4 of 8 experts held, 3 a token
        workload, config = build_mellum.tiny_mellum(workload, config)
    shape = build_mellum.mellum_shape(workload, config)
    sync_every = workload["sync_every"]
    chips = workload["chips"]
    tokens_per_step = (workload["grad_accum"] * workload["micro_batch_per_chip"]
                       * workload["mesh"]["data"] * workload["seq_len"])
    expected_rows = (tokens_per_step * shape["experts_per_token"] * shape["held"][1]
                     / shape["experts"] * shape["layers"])  # a step, all layers
    counter = CompileCounter()

    t = time.perf_counter()
    state, table, tokens = build_state(workload, config, devices, args.seed)
    init_s = time.perf_counter() - t
    print(f"perfbench: mesh {dict(state.mesh.shape)}, strategy {state.strategy.describe()}, "
          f"remat {state.model_config.remat}, {state.n_params / 1e6:.1f}M parameters, "
          f"{tokens_per_step} tokens a step, layers {state.model_config.layer_types}", flush=True)

    t = time.perf_counter()
    batch = correct.first_micro_batch(state, tokens, workload)
    state.opt_state = None  # the moments' bytes the check's programs need
    initial_ok, initial = check_initial(state, shape, batch)
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
        _, held_now = _programs(state.model_config)["routing"](state.params, batch)
    by_layer = np.asarray(held_now)[:, 0] * shape["layers"] / expected_rows
    print(f"perfbench: {n_steps} steps in {sum(times):.3f} s; ms a step by window: "
          f"{[round(1e3 * w / sync_every, 3) for w in times]}; loss {losses[0]:.4f} -> "
          f"{losses[-1]:.4f}; window means fall by {fall:.4f}, their spread {spread:.4f}; "
          f"compilations in the window: {counter.count}; held rows over expected, least and "
          f"most of a step: {window[:, 0].min() / expected_rows:.3f}, "
          f"{window[:, 0].max() / expected_rows:.3f}; held assignments that did not fit: "
          f"{overflow:.0f} (the first sequence's held rows by layer after the window: "
          f"{[round(float(x), 3) for x in by_layer]}, "
          f"{initial['held_rows_over_expected_max']:.3f} the worst before it)", flush=True)
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
        "flops_per_token": flops_mellum.train_flops_per_token(shape),
        "memory_allocator_bytes": allocator, "memory_assigned_bytes": assigned,
        "compiles_in_window": counter.count, "traced_steps": TRACED_STEPS,
        "peaks": peaks.peaks(devices[0].device_kind) if on_chip else None,
        "held_rows_traced": traced[:, 0].sum(),
        "held_rows_over_expected": traced[:, 0].mean() / expected_rows,
        "expert_load_max_over_mean": initial["expert_load_max_over_mean"],
        "attn_mask_stats": tinygpt.attn_mask_stats(state.model_config, workload["seq_len"]),
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
