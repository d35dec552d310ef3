"""The driver of a Kimi-Linear-class cell: ``step_loop.run``'s protocol (same
clocks, same result keys, same ``facts`` keys, so every reader that has no
``workloads`` filter runs here too) with what this model changes: the initial
check is against ``reference_kda``, at the cell's own weights and the timed
``model_config``, one layer at a time, a KDA layer and the latent-attention
layer apart (the note above ``TOLERANCE``); the FLOPs are this chip's count
(``flops_kda``); the train step's fourth output (the held experts' rows, the
held assignments that did not fit their buffer) is read after every sync
window: any assignment that did not fit fails the run; and the program's
counter of the recurrence (``tinygpt.kda_stats``) goes to the readers. A
workload file names it under ``driver``.

It repeats ``step_loop.run``'s body, as ``moe_loop.run``, ``mla_loop.run``,
``bd_loop.run`` and ``mellum_loop.run`` do and for the same reason (that
function reaches its reference and its FLOP count by import): the sixth copy,
debt D10. Everything that is a function there, in ``mla_loop`` or in
``bd_loop`` is used from there (``steps``, ``CompileCounter``,
``memory_peaks``, ``HeldCounter``, ``Worst``, ``_distance``, ``fresh_moments``,
``fall_and_spread``); what is copied is ``run``'s frame and the shape of
``check_initial``'s walk over the layers.
"""

import functools
import glob
import os
import statistics
import time

import jax
import jax.numpy as jnp
import numpy as np

from . import build, build_kda, correct, flops_kda, manifest, peaks, reference_kda, trace_reduce
from .bd_loop import MARGIN, Worst, _distance, fall_and_spread, fresh_moments
from .mla_loop import HeldCounter
from .step_loop import TRACE_DIR, TRACED_STEPS, CompileCounter, memory_peaks, steps

# How the comparison is made, and why a layer at a time: several routed layers
# in a row are chaotic under top-k (a token whose 8th and 9th scores lie close
# takes another expert in bfloat16, and every later layer sees it), so no limit
# on the whole network's logits could tell float8 from bfloat16 (PERF.md
# section 6, PR 36). One layer is not. So the program is fed the reference's
# own hidden states, a sublayer at a time (teacher forcing), at the cell's
# weights, shapes, kernels, remat policy and bounded buffer, through the
# program's own layer (``tinygpt.apply_layer``: what its loop over unequal
# stacks runs, on the layer's own slice), forward and backward:
#
# * the mixer, a KDA layer and the latent-attention layer apart: the layer
#   with its MLP's last projections zeroed is x + mixer(x). It is given the
#   reference's input of that layer and, as the output's cotangent, the
#   reference's own gradient of the training loss there. Held to the reference
#   (whose recurrence is a scan over the 16,384 positions): what the sublayer
#   adds, and the gradient by every leaf of the mixer. **Both sides are given
#   that input scaled down to the norm of what the mixer adds to it** (where
#   that is less): a mixer reads RMSNorm(x), which a positive scale leaves as it
#   is but for the norm's eps, and the layer returns x + add in bfloat16: where
#   the stream holds forty times what a sublayer adds (behind the 9216-wide MLP
#   a KDA layer whose head-norm scales start at 0.03 adds 1/40 of its input)
#   the sum's rounding is a tenth of the add, and the reading would be that
#   rounding and not the sublayer (first seen on the chip: kda_out 0.126,
#   global_out 0.029). **So the mixers are compared at another operating point
#   than the timed step's**: the scale read 0.0133 at the least
#   (``mixer_input_scale_min``), which brings the input's rms down to the add's,
#   about 0.02, where eps 1e-5 is some 3 % of the mean square and the normed
#   input a per cent or two smaller than on the timed path (arithmetic from the
#   seeded scales, not a reading). Both sides see it alike; the whole forward
#   under ``loss`` is the timed operating point.
# * the MLP: the layer with its ``wo`` zeroed is h + MLP(h). It is given the
#   reference's mixer output h. The leading dense layer: what it adds and the
#   gradient by its three leaves. A routed layer: what the held experts and the
#   shared expert add, the gradient by their four leaves and by the norm's
#   scale; the rows the bounded buffer held against the reference's own count
#   of assignments on the held experts; no assignment over the buffer. The
#   reference chooses its own experts; tokens whose last chosen and first
#   unchosen scores lie within MARGIN are left out of the output's comparison
#   and carry no cotangent (``bd_loop``'s rule).
# * the head: ``tinygpt.head`` on the reference's last hidden state,
#   per-position losses against the reference's in the units of ``correct.py``.
# * the loss: the program's whole ``forward`` (every layer live, the timed
#   config) against the reference's training loss from its own layer-wise
#   pass: a mean over 16,384 positions, which near-ties do not move.
KDA_LEAVES = ("kda_wqkv", "kda_conv", "kda_wfa", "kda_wfb", "kda_a_log", "kda_dt_bias", "kda_wb",
              "kda_wga", "kda_wgb", "kda_norm", "wo", "ln1_scale")
GLOBAL_LEAVES = ("wq", "wkv_a", "kv_norm", "wkv_b", "wo", "ln1_scale")
DENSE_LEAVES = ("wgu", "wproj", "ln2_scale")
ROUTED_LEAVES = ("moe_wgu", "moe_wd", "shared_wgu", "shared_wd", "ln2_scale")
LAST_PROJECTIONS = ("wproj", "moe_wd", "shared_wd")  # zeroed, a layer's MLP adds nothing

# Calibrated on the v5e at the published widths (tools/calibrate_correct_kda.py,
# which runs every wrong model below through the same comparison; PERF.md
# section 6, PR 44: seed 4400000401 of the wrong models, the bfloat16 state on
# 402 and 801-803, and every run's "initial check" line for the program). Each
# limit lies between two readings: the program's largest over its seeds, and
# the nearest of the float8 reference (every weight rounded to float8_e4m3fn,
# the nearest precision below the cell's) and the wrong models that this limit
# has to refuse. Every reading is the worst of its layers (and of its leaves);
# ``kda_out_err.layer<i>`` gives the layers apart.
#
# kda_out, |program - reference| / |reference| of what a KDA layer's mixer adds
# to its (scaled) input: the program 0.00853-0.00867 over twenty-one seeds
# (steady to about a per cent: a mean over 16,384 x 2304 numbers; its four
# layers read within 2 % of each other); **the reference with its state
# rounded to bfloat16 after every position 0.01175-0.01234 over four seeds
# (0.0119, 0.01189, 0.01175, 0.01234; a layer alone 0.0111 at the least), by
# this limit alone on every one**: the float32 state is held by one limit
# with a sixth of room either way, which is ten times the program's
# scatter by seed and three times the control's, and not the three times of
# room the other limits have, because bfloat16 products (the cell's
# precision) already put the program 0.0086 from the reference and the rounded
# state adds 0.008 to that in quadrature. An l2norm eps of 1 0.038, float8
# 0.108, filters of three taps 0.81.
# kda_grad, of the gradient by the mixer's twelve leaves through kda_bwd, the
# convolutions' kernels and the norms: the program 0.0106-0.0159 over twenty-one
# seeds (the worst leaf is A_log's 32 numbers a layer, which is what scatters);
# an l2norm eps of 1 0.068, float8 0.172. The bfloat16 state reads 0.0188-0.0205
# where the program read 0.0106-0.0127 on the same seeds: inside the program's
# own scatter by seed, so not this limit's to refuse (kda_out's: it was first
# set at 0.0164, which one seed of seven came within 3 % of).
# global_out / global_grad, the same of the NoPE latent-attention layer (six
# leaves): the program 0.0043-0.0044 / 0.0059; float8 0.059 / 0.067.
# dense_out / dense_grad, of the leading dense layer's 9216-wide SwiGLU: the
# program 0.0054 / 0.0050; float8 0.058 / 0.060.
# moe_out, of what the held experts and the shared expert add, over the clear
# tokens (6.4-6.7 % of a layer's at sigmoid scores of 0.5 to two digits: a
# near-tie is the rule at the seeded start): the program 0.0055; float8 0.058,
# a buffer of 0.8 of the expected rows 0.060, one held expert fewer 0.061, no
# scaling factor 0.092, gates not renormalised 0.64, no shared expert 7.0.
# expert_grad, of the gradient by moe_wgu, moe_wd, shared_wgu, shared_wd,
# ln2_scale: the program 0.0060; float8 0.061, the short buffer 0.38.
# held_rows: the rows the bounded buffer held against the reference's own
# count, over the expected rows: the program 0.0007-0.0034 over twenty-one seeds
# (near-ties that fall the other way in bfloat16: a handful of 4,096 rows); one
# held expert fewer 0.183, the short buffer 0.192; float8 0.012 is under it and
# refused by ten others.
# per_position, in the units of ``correct.py``: the program 0.00233-0.00239;
# float8 0.033.
# loss, |program - reference| / reference of the training loss through the
# whole forward, every layer live: the program 1e-6 to 4e-5 over twenty-one seeds;
# the harness's accepted 2e-4 leaves it five times of room and refuses filters
# of three taps (2.3e-4), no shared expert (5.2e-4) and gates not renormalised
# (8.6e-4); float8 (1.1e-5) does not move a loss that starts at ln 20480, and
# ten other limits refuse it.
TOLERANCE = {
    "kda_out": 0.0101, "kda_grad": 0.033, "global_out": 0.016, "global_grad": 0.02,
    "dense_out": 0.018, "dense_grad": 0.017, "moe_out": 0.018, "expert_grad": 0.019,
    "held_rows": 0.02, "per_position": 0.009, "loss": 2e-4,
}


def program_layer(model_config, kind):
    """(one layer's weights, x (B, S, D), the output's cotangent) -> what the
    timed config's layer of ``kind`` adds to x (its output less the x it was
    given, in the compute dtype), its report (the held experts' rows, the
    assignments over the buffer) and the gradient by the layer's leaves: the
    program's own layer, remat policy, kernels and buffer."""
    from distributed_llm_training_benchmark_framework_tpu.models import tinygpt

    def layer(weights, x, cotangent):
        x, cotangent = (a.astype(model_config.compute_dtype) for a in (x, cotangent))
        y, pull_back, aux = jax.vjp(
            lambda weights, x: tinygpt.apply_layer(model_config, weights, x, kind),
            weights, x, has_aux=True)
        d_weights, _ = pull_back(cotangent)
        return y.astype(jnp.float32) - x.astype(jnp.float32), aux[1:], d_weights

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
    under ``jax.default_matmul_precision("highest")``, each compiled once: a
    mixer a kind, an MLP a sort (leading dense, routed), the head."""

    def __init__(self, m):
        self.m = m

        def highest(f):
            def call(*a):
                with jax.default_matmul_precision("highest"):
                    return f(*a)
            return jax.jit(call)

        f32 = lambda w: jax.tree.map(lambda t: t.astype(jnp.float32), w)

        def both_ways(sublayer, has_aux=False):
            """(forward, backward) of ``sublayer(m, x, w)`` over the batch."""
            forward = lambda w, x: jax.vmap(lambda x: sublayer(m, x, w))(x)

            def backward(w, x, cotangent):
                _, pull_back, *_ = jax.vjp(forward, f32(w), x, has_aux=has_aux)
                return pull_back(cotangent)

            return highest(lambda w, x: forward(f32(w), x)), highest(backward)

        def head(params, x, batch):
            def mean_loss(x):
                losses = jax.lax.map(
                    lambda one: reference_kda.head_losses(m, params, *one), (x, batch))
                return jnp.mean(losses), losses
            return jax.value_and_grad(mean_loss, has_aux=True)(x)

        self.embed = highest(lambda params, batch: jax.lax.map(
            lambda tokens: reference_kda.embed(m, params, tokens), batch))
        self.mixer = {"kda": both_ways(reference_kda.kda_sublayer),
                      "global": both_ways(reference_kda.latent_sublayer)}
        self.dense = both_ways(reference_kda.dense_sublayer)
        self.routed = both_ways(reference_kda.routed_sublayer, has_aux=True)
        # -> ((mean loss, losses), the mean loss's gradient by the last hidden state)
        self.head = highest(lambda params, x, batch: head(f32(params), x, batch))


def reference_for(shape):
    return _reference(tuple(sorted(shape.items())))


@functools.lru_cache(maxsize=2)
def _reference(items):
    return Reference(dict(items))


def _zeroed(weights, *leaves):
    return {k: jnp.zeros_like(v) if k in leaves else v for k, v in weights.items()}


def check_initial_numbers(state, shape, batch, model_config=None, reference_params=None):
    """-> numbers: the comparison the note above ``TOLERANCE`` describes."""
    from distributed_llm_training_benchmark_framework_tpu.models import tinygpt

    config = model_config or state.model_config
    params = state.params
    weights = params if reference_params is None else reference_params
    reference, programs = reference_for(shape), _programs(config)
    first, count = shape["held"]
    numbers, last = Worst(), shape["layers"] - 1
    routed_at = [i for i in range(shape["layers"]) if i >= shape["dense_layers"]]
    theirs = lambda i: reference_kda.layer_weights(shape, weights, i)
    with jax.set_mesh(state.mesh):
        # the reference's forward pass, every sublayer's input kept
        x = reference.embed(weights, batch)
        inputs, counts = [], []
        for i, kind in enumerate(shape["kinds"]):
            h = reference.mixer[kind][0](theirs(i), x)
            if i in routed_at:
                y, router = reference.routed[0](theirs(i), h)
                clear = router["margin"] >= MARGIN
                counts.append(jnp.sum(router["assignments"], 0))
            else:
                y, clear = reference.dense[0](theirs(i), h), None
            inputs.append((x, h, clear))
            x = y
        ((want_mean, want_losses), cotangent) = reference.head(weights, x, batch)
        got_losses = programs["head"](params, x, batch)
        numbers["per_position_err"] = float(
            jnp.sqrt(jnp.mean(jnp.square(got_losses - want_losses))) / jnp.std(want_losses))
        # backward, the last layer first: each sublayer of the program beside the reference's
        rows = []
        for i in reversed(range(shape["layers"])):
            (x, h, clear), y = inputs[i], x if i == last else inputs[i + 1][0]
            w, own, kind = theirs(i), tinygpt.layer_weights(config, params, i), shape["kinds"][i]
            layer = programs[kind]
            without_mixer = _zeroed(own, "wo")
            if i in routed_at:  # h -> h + the held experts' and the shared expert's part
                of_clear = cotangent * clear[..., None]
                got_add, report, got_dw = layer(without_mixer, h, of_clear)
                want_dw, _ = reference.routed[1](w, h, of_clear)
                numbers.see("moe_out_err", _distance(got_add, y - h, clear))
                for k in ROUTED_LEAVES:
                    numbers.see("expert_grad_err", _distance(got_dw[k], want_dw[k]), k)
                rows.append(report)
                cotangent = reference.routed[1](w, h, cotangent)[1]
                numbers["clear_tokens_share_min"] = min(
                    numbers.get("clear_tokens_share_min", 1.0), float(jnp.mean(clear)))
            else:  # the leading dense layer
                got_add, _, got_dw = layer(without_mixer, h, cotangent)
                want_dw, cotangent_in = reference.dense[1](w, h, cotangent)
                numbers.see("dense_out_err", _distance(got_add, y - h))
                for k in DENSE_LEAVES:
                    numbers.see("dense_grad_err", _distance(got_dw[k], want_dw[k]), f"dense.{k}")
                cotangent = cotangent_in
            # the mixer: x -> x + mixer(x), at the input scaled down to what the mixer adds
            small = min(1.0, float(jnp.linalg.norm(h - x) / jnp.linalg.norm(x)))
            xs = x * small
            want_add = reference.mixer[kind][0](w, xs) - xs
            got_add, _, got_dw = layer(_zeroed(own, *LAST_PROJECTIONS), xs, cotangent)
            want_dw, through = reference.mixer[kind][1](w, xs, cotangent)
            numbers[f"{kind}_out_err.layer{i}"] = float(_distance(got_add, want_add))
            numbers.see(f"{kind}_out_err", numbers[f"{kind}_out_err.layer{i}"])
            for k in KDA_LEAVES if kind == "kda" else GLOBAL_LEAVES:
                numbers.see(f"{kind}_grad_err", _distance(got_dw[k], want_dw[k]), f"{kind}.{k}")
            # on to the layer below: the mixer's Jacobian at x is ``small`` times its own at xs
            cotangent = cotangent + small * (through - cotangent)
            numbers["mixer_input_scale_min"] = min(numbers.get("mixer_input_scale_min", 1.0), small)
        del inputs
        got_loss = float(programs["loss"](params, batch))  # the whole forward, every layer live
        program_counts, _ = programs["routing"](params, batch)
    rows = np.asarray(rows[::-1], np.float64)  # (routed layers, 2): rows held, over the buffer
    counts, program_counts = np.asarray(counts, np.float64), np.asarray(program_counts, np.float64)
    assignments = batch.size * shape["experts_per_token"]
    expected = assignments * count / shape["experts"]
    want_loss = float(want_mean)  # no auxiliary term: the sigmoid router's balancer is its bias
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
    numbers.update({f"held_rows_over_expected.layer{i}": r for i, r in zip(routed_at, by_layer)})
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
    DeepSeek cell) and, where the config file has ``kda_norm_scale_init``, the
    KDA layers' head-norm scales there (a stand-in for a checkpoint, as PR 36's
    and PR 38's QK-norm scales were: the config file's ``assumed`` says why;
    the program starts them from 1.0)."""
    state, table, tokens = build.build_state(workload, config, devices, seed)
    if config.get("kda_norm_scale_init") is not None:
        params = dict(state.params)
        for name in [n for n in params if n.startswith("kda_") and n.endswith("blocks")]:
            scale = params[name]["kda_norm"]
            params[name] = {**params[name], "kda_norm": jax.device_put(
                jnp.full_like(scale, config["kda_norm_scale_init"]), scale.sharding)}
        state.params = params
    return state, table, tokens


def run(entry, workload, config, args, devices, process_start):
    from distributed_llm_training_benchmark_framework_tpu.models import tinygpt

    manifest_ = manifest.load_manifest()
    on_chip = devices[0].platform == "tpu"
    if not on_chip:  # the dry run: tiny widths, five layers, 4 of 8 experts held, 3 a token
        workload, config = build_kda.tiny_kda(workload, config)
    shape = build_kda.kda_shape(workload, config)
    sync_every = workload["sync_every"]
    chips = workload["chips"]
    tokens_per_step = (workload["grad_accum"] * workload["micro_batch_per_chip"]
                       * workload["mesh"]["data"] * workload["seq_len"])
    expected_rows = (tokens_per_step * shape["experts_per_token"] * shape["held"][1]
                     / shape["experts"] * shape["moe_layers"])  # a step, all routed layers
    counter = CompileCounter()

    t = time.perf_counter()
    state, table, tokens = build_state(workload, config, devices, args.seed)
    init_s = time.perf_counter() - t
    print(f"perfbench: mesh {dict(state.mesh.shape)}, strategy {state.strategy.describe()}, "
          f"remat {state.model_config.remat}, {state.n_params / 1e6:.1f}M parameters, "
          f"{tokens_per_step} tokens a step, layers {state.model_config.layer_types}, stacks "
          f"{[(name, len(layers)) for name, layers in state.model_config.layer_groups]}", flush=True)

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
    by_layer = np.asarray(held_now)[:, 0] * shape["moe_layers"] / expected_rows
    kda_stats = tinygpt.kda_stats(state.model_config, workload["seq_len"])
    print(f"perfbench: {n_steps} steps in {sum(times):.3f} s; ms a step by window: "
          f"{[round(1e3 * w / sync_every, 3) for w in times]}; loss {losses[0]:.4f} -> "
          f"{losses[-1]:.4f}; window means fall by {fall:.4f}, their spread {spread:.4f}; "
          f"compilations in the window: {counter.count}; held rows over expected, least and "
          f"most of a step: {window[:, 0].min() / expected_rows:.3f}, "
          f"{window[:, 0].max() / expected_rows:.3f}; held assignments that did not fit: "
          f"{overflow:.0f} (the first sequence's held rows by routed layer after the window: "
          f"{[round(float(x), 3) for x in by_layer]}, "
          f"{initial['held_rows_over_expected_max']:.3f} the worst before it)", flush=True)
    print(f"perfbench: kda: {kda_stats}", flush=True)
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
        "flops_per_token": flops_kda.train_flops_per_token(shape),
        "memory_allocator_bytes": allocator, "memory_assigned_bytes": assigned,
        "compiles_in_window": counter.count, "traced_steps": TRACED_STEPS,
        "peaks": peaks.peaks(devices[0].device_kind) if on_chip else None,
        "held_rows_traced": traced[:, 0].sum(),
        "held_rows_over_expected": traced[:, 0].mean() / expected_rows,
        "expert_load_max_over_mean": initial["expert_load_max_over_mean"],
        "kda_stats": kda_stats,
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
