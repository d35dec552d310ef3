"""The driver of an SDAR-class cell trained by block diffusion: ``step_loop.run``'s
protocol (same clocks, same result keys, same ``facts`` keys, so every reader
that has no ``workloads`` filter runs here too) with what this model and this
objective change: the documents come from the cell's own Zipf generator
(``build_bd.token_table``) and the twelve QK-norm scale leaves start from the
config file's ``qk_norm_scale_init`` (``build_state``); ``tokens_per_s_per_chip``
counts DATA tokens (a document of L tokens is L tokens, though the step runs a
stream of 2 L: the second copy is the method's cost, not throughput); the
initial check is against ``reference_bd`` under the program's own noise, at the
cell's own weights and the timed ``model_config``, one layer at a time (the
note above ``TOLERANCE``); the FLOPs are this chip's count by the data token
(``flops_bd``); and the train step's fourth output (the held experts' rows, the
held assignments that did not fit their buffer, the masked tokens) is read
after every sync window: any assignment that did not fit fails the run. A
workload file names it under ``driver``.

It repeats ``step_loop.run``'s body, as ``moe_loop.run`` and ``mla_loop.run`` do
and for the same reason (that function reaches its reference and its FLOP
count by import); everything that is a function there is used from there.
"""

import functools
import glob
import math
import os
import statistics
import time

import jax
import jax.numpy as jnp
import numpy as np

from . import build, build_bd, correct, flops_bd, manifest, peaks, reference_bd, trace_reduce
from .step_loop import TRACE_DIR, TRACED_STEPS, CompileCounter, memory_peaks, steps

# How the comparison is made. The cell's weights start from QK-norm scales of
# 4.0 (the config file's ``qk_norm_scale_init``): scores with a standard
# deviation of 16, attention on a query's top few keys. Six such layers in a
# row are chaotic in the plain sense: the float32 reference run in bfloat16
# passes is 0.87 spreads from itself at the logits (my chip run, PR 36), so
# no limit on the whole network's output could tell float8 from bfloat16. One
# layer is not. So the program is fed the reference's own hidden states, a
# sublayer at a time (teacher forcing), at the cell's weights, shapes, kernels,
# remat policy and bounded buffer, through the program's own loop over layers
# (``tinygpt.apply_blocks`` with the timed ``model_config`` on one layer's slice
# of the weights), forward and backward:
#
# * attention: the layer with its ``moe_wd`` zeroed is x + attention(x). It is
#   given the reference's input of that layer and, as the output's cotangent,
#   the reference's own gradient of the training loss there. Held to the
#   reference: what the sublayer adds (over the stream, and over the noisy
#   copy's first FIRST_BLOCKS positions, where a block's own four keys are a
#   large part of what a query sees) and the gradient by its six leaves (dq, dk
#   and dv through the projections; the pull-back to the input through the
#   norm's scale, whose gradient is a sum of it over the tokens).
# * the routed share: the layer with its ``wo`` zeroed is h + experts(h). It is
#   given the reference's attention output h. Held to the reference: what the
#   held experts add, the gradient by their two leaves and by the norm's
#   scale; the rows the bounded buffer held against the reference's own
#   count of assignments on the held experts; no assignment over the buffer.
#   The reference chooses its own experts. A token whose last chosen and first
#   unchosen probabilities lie within MARGIN of each other (as a share of the
#   former) may take another expert in bfloat16: such tokens are left out of
#   the output's comparison and carry no cotangent into the gradients'
#   (program and reference alike); the row count is over every token.
# * the head: ``tinygpt.head`` on the reference's last hidden state, per-position
#   losses against the reference's in the units of ``correct.py``.
# * the objective: the program's whole ``forward`` (its noise, stream, embedding,
#   layers, head and weighted loss) with every layer's ``wo`` and ``moe_wd``
#   zeroed, so that the layers add nothing and nothing is chaotic: its training
#   loss and the gradient by the head, the final norm and the token table
#   against the reference's under the same weights. This is where 1 / t, the
#   1 / L and the masked positions are held.
FIRST_BLOCKS = 64  # positions of the noisy copy
MARGIN = 0.02  # ten times what bfloat16's rounding of the router's input moves a logit by (estimated)
ATTENTION_LEAVES = ("wq", "wkv", "q_norm", "k_norm", "wo", "ln1_scale")
ROUTED_LEAVES = ("moe_wgu", "moe_wd", "ln2_scale")
OBJECTIVE_LEAVES = ("lm_head", "lnf_scale", "wte")
STEP_SEED = 0  # ``build.build_state``'s dropout_seed: the step's noise is folded from it

# Calibrated on the v5e at the published widths (tools/calibrate_correct_bd.py,
# which runs every wrong model below through the same comparison; PERF.md
# section 6, PR 36: two seeds of everything, and every run's "initial check"
# line). Each limit is the geometric middle of two readings: the program's
# largest, and the nearest wrong model that this limit has to refuse. Every
# reading is the worst of its six layers (and of its leaves).
#
# attn_out, |program - reference| / |reference| of what attention adds to its
# input: the program 0.0223 (both seeds: bfloat16 scores of a standard
# deviation of 16); float8 weights (the nearest precision below the cell's)
# 0.204, QK-norm over the whole vector 0.62, positions along the stream 0.98,
# a causal mask 1.25. The noisy copy seeing its own clean block reads 0.050
# over the stream (four keys more among thousands), under this limit:
# first_blocks, the same over the noisy copy's first 64 positions: the program
# 0.0163-0.0166; float8 0.158; the own clean block seen 0.386, by this limit
# alone.
# attn_grad, the same distance of the gradient by wq, wkv, q_norm, k_norm, wo,
# ln1_scale (through the fused backward under the rule, rotary, the norms):
# the program 0.0863-0.0868 (q_norm the worst, wo 0.021); float8 0.49-0.50.
# moe_out, of what the held experts add, over the clear tokens (70 % of a
# layer's): the program 0.0064; float8 0.144, one held expert fewer 0.31, a
# buffer of 0.8 of the expected rows (18,413-21,308 assignments dropped) 0.52,
# gates not renormalised 2.8.
# expert_grad, of the gradient by moe_wgu, moe_wd, ln2_scale (dispatch, grouped
# matmuls and combine with their hand-written transposes, at the timed buffer
# and remat policy): the program 0.0066-0.0068; float8 0.151-0.163, one held
# expert fewer 0.30-0.40.
# held_rows: the rows the bounded buffer held against the reference's own count
# of assignments on the held experts, over the expected rows: the program
# 0.0011-0.0013 (near-ties that cross the held experts' edge); one held expert
# fewer 0.088-0.103; float8 0.0034-0.0048 is under it and refused by six others.
# per_position, in the units of ``correct.py``: the program 0.0023-0.0024;
# float8 0.033.
# loss, |program - reference| / reference of the training loss through the whole
# forward: the program 1.4e-6 to 3.7e-5; float8 2.8e-4 to 7.3e-4; without 1 / t
# 1.05, divided by the masked count 0.49. The limit is the accepted cells'
# (``correct.py``, ``mla_loop.py``), which leaves the program five times of room.
# objective_grad, of that loss's gradient by lm_head, lnf_scale, wte: the
# program 0.0025-0.0026; float8 0.048; the two wrong objectives 0.49-1.2.
TOLERANCE = {"attn_out": 0.07, "first_blocks": 0.05, "attn_grad": 0.2, "moe_out": 0.03,
             "expert_grad": 0.03, "held_rows": 0.01, "per_position": 0.009, "loss": 2e-4,
             "objective_grad": 0.011}


def first_step_key():
    """The key the train step gives its first micro-batch at step 0
    (``train/step.py``: fold_in(fold_in(key(seed), step), micro-batch 0) under
    grad_accum 1): the initial check runs under the noise the first timed
    program step draws."""
    return jax.random.fold_in(jax.random.fold_in(jax.random.key(STEP_SEED), 0), 0)


def _layer(params, i):
    return {k: v[i] for k, v in params["blocks"].items()}


def _without(params, *leaves):
    """``params`` with those leaves of every layer zeroed: what they project adds nothing."""
    blocks = {k: jnp.zeros_like(v) if k in leaves else v for k, v in params["blocks"].items()}
    return {**params, "blocks": blocks}


def _distance(got, want, rows=None):
    """|got - want| / |want|, over the rows where ``rows`` (bool) is true."""
    got, want = got.astype(jnp.float32), want.astype(jnp.float32)
    if rows is not None:
        got, want = got * rows[..., None], want * rows[..., None]
    return jnp.sqrt(jnp.sum(jnp.square(got - want)) / jnp.maximum(jnp.sum(jnp.square(want)), 1e-60))


def program_layer(model_config):
    """(one layer's weights, x (B, 2L, D), the output's cotangent) -> what the
    timed config's layer adds to x (its output less the x it was given, in the
    compute dtype), its report (the held experts' rows, the assignments over
    the buffer) and the gradient by the layer's leaves: the program's own loop
    over layers, remat policy, kernels and buffer, on a stack of one layer."""
    from distributed_llm_training_benchmark_framework_tpu.models import tinygpt

    def layer(weights, x, cotangent):
        stack = {k: v[None] for k, v in weights.items()}
        x, cotangent = (a.astype(model_config.compute_dtype) for a in (x, cotangent))
        y, pull_back, aux = jax.vjp(
            lambda stack, x: tinygpt.apply_blocks(model_config, stack, x), stack, x, has_aux=True)
        d_stack, _ = pull_back(cotangent)
        return (y.astype(jnp.float32) - x.astype(jnp.float32), aux[1:],
                {k: v[0] for k, v in d_stack.items()})

    return layer


def program_objective(model_config):
    """(params, batch, key) -> the program's training loss and its gradient by
    OBJECTIVE_LEAVES, through its whole ``forward``."""
    from distributed_llm_training_benchmark_framework_tpu.models import tinygpt

    def objective(params, batch, key):
        def training_loss(leaves):
            return tinygpt.forward(model_config, {**params, **leaves}, batch, batch,
                                   dropout_key=key)[1]

        return jax.value_and_grad(training_loss)({k: params[k] for k in OBJECTIVE_LEAVES})

    return objective


def program_head_losses(model_config):
    """(params, x (B, 2L, D), batch) -> (B, L) cross-entropy of each noisy
    position against its own token, from the program's final norm and head."""
    from distributed_llm_training_benchmark_framework_tpu.models import tinygpt

    def losses(params, x, batch):
        L = batch.shape[1]
        logits = tinygpt.head(model_config, params, x[:, :L].astype(model_config.compute_dtype))
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), -1)
        return -jnp.take_along_axis(logp, batch[..., None], -1)[..., 0]

    return losses


def routing_side(model_config):
    """(params, batch, key) -> the program's (layers, experts) assignment counts
    and its (layers, 2) held rows and overflow over the batch's streams, on its
    own forward pass."""
    from distributed_llm_training_benchmark_framework_tpu.models import tinygpt

    def routing(params, batch, key):
        stream = tinygpt.bd_stream(model_config, batch, key)[0]
        return tinygpt.moe_routing_rows(model_config, params, stream)

    return routing


@functools.lru_cache(maxsize=4)  # the calibration checks one program many times
def _programs(model_config):
    return (jax.jit(program_layer(model_config)), jax.jit(program_head_losses(model_config)),
            jax.jit(program_objective(model_config)), jax.jit(routing_side(model_config)))


def fresh_moments(state):
    """AdamW's state as ``create_train_state`` makes it (zeros, in its layout)."""
    from distributed_llm_training_benchmark_framework_tpu.parallel import strategies

    with state.mesh:
        return jax.jit(
            strategies.make_optimizer(state.strategy).init,
            out_shardings=strategies.opt_state_shardings(
                state.mesh, state.opt_specs, state.strategy))(state.params)


class Reference:
    """The reference's sides of the comparison over the batch's documents, under
    ``jax.default_matmul_precision("highest")``, each compiled once."""

    def __init__(self, m):
        over_documents = lambda f: lambda *a: jax.lax.map(lambda one: f(*one), a)

        def highest(f):
            def call(*a):
                with jax.default_matmul_precision("highest"):
                    return f(*a)
            return jax.jit(call)

        f32 = lambda w: jax.tree.map(lambda t: t.astype(jnp.float32), w)
        attention = lambda w, x: jax.vmap(lambda x: reference_bd.attention_sublayer(m, x, w))(x)
        routed = lambda w, x: jax.vmap(lambda x: reference_bd.routed_sublayer(m, x, w))(x)

        def weighted(params, x, batch, t, masked):
            losses = over_documents(lambda x, tokens: reference_bd.head_losses(m, params, x, tokens))(
                x, batch)
            loss = reference_bd.weighted_loss(
                losses, t, masked, m["block"], m["loss_weight"], m["loss_over"])
            return loss, losses

        def attention_back(w, x, cotangent):
            _, pull_back = jax.vjp(attention, f32(w), x)
            return pull_back(cotangent)

        def routed_back(w, x, cotangent):
            _, pull_back, _ = jax.vjp(routed, f32(w), x, has_aux=True)
            return pull_back(cotangent)

        self.embed = highest(lambda params, batch, masked: over_documents(
            lambda tokens, masked: reference_bd.embed(m, params, tokens, masked))(batch, masked))
        self.attention = highest(lambda w, x: attention(f32(w), x))
        self.routed = highest(lambda w, x: routed(f32(w), x))
        self.attention_back = highest(attention_back)
        self.routed_back = highest(routed_back)
        # -> ((loss, losses), the loss's gradient by the last hidden state)
        self.head = highest(lambda params, x, batch, t, masked: jax.value_and_grad(
            weighted, argnums=1, has_aux=True)(f32(params), x, batch, t, masked))
        self.objective = highest(lambda params, batch, t, masked: jax.value_and_grad(
            lambda leaves: reference_bd.loss(m, {**params, **leaves}, batch, t, masked))(
                {k: params[k] for k in OBJECTIVE_LEAVES}))


class Worst(dict):
    """Readings, each the worst of its layers and leaves."""

    def see(self, name, value, leaf=None):
        for key in (name,) if leaf is None else (name, f"grad_err.{leaf}"):
            self[key] = max(self.get(key, 0.0), float(value))


def reference_for(shape):
    return _reference(tuple(sorted(shape.items())))


@functools.lru_cache(maxsize=2)
def _reference(items):
    return Reference(dict(items))


def check_layers(state, shape, batch, model_config=None, reference_params=None):
    """-> numbers: the program's layers, a sublayer at a time, and its head
    beside the reference's, at the cell's own weights (the first three parts
    of the note above ``TOLERANCE``)."""
    from distributed_llm_training_benchmark_framework_tpu.models import tinygpt

    config = model_config or state.model_config
    params = state.params
    weights = params if reference_params is None else reference_params
    reference = reference_for(shape)
    layer, head_losses, _, routing = _programs(config)
    first, count = shape["held"]
    numbers, last = Worst(), shape["layers"] - 1
    with jax.set_mesh(state.mesh):
        key = first_step_key()
        t, masked = jax.jit(lambda key: tinygpt.bd_noise(config, key, batch.shape))(key)
        # the reference's forward pass, every sublayer's input kept
        x = reference.embed(weights, batch, masked)
        inputs, counts = [], []
        for i in range(shape["layers"]):
            h = reference.attention(_layer(weights, i), x)
            y, router = reference.routed(_layer(weights, i), h)
            inputs.append((x, h, router["margin"] >= MARGIN))
            counts.append(jnp.sum(router["assignments"], 0))
            x = y
        ((want_loss, want_losses), cotangent) = reference.head(weights, x, batch, t, masked)
        got_losses = head_losses(params, x, batch)
        numbers["per_position_err"] = float(
            jnp.sqrt(jnp.mean(jnp.square(got_losses - want_losses))) / jnp.std(want_losses))
        # backward, the last layer first: each sublayer of the program beside the reference's
        rows = []
        for i in reversed(range(shape["layers"])):
            (x, h, clear), y = inputs[i], x if i == last else inputs[i + 1][0]
            w, own = _layer(weights, i), _layer(params, i)
            # the routed share: h -> h + the held experts' part, under the clear tokens' cotangent
            of_clear = cotangent * clear[..., None]
            got_add, report, got_dw = layer({**own, "wo": jnp.zeros_like(own["wo"])}, h, of_clear)
            want_dw, _ = reference.routed_back(w, h, of_clear)
            numbers.see("moe_out_err", _distance(got_add, y - h, clear))
            for k in ROUTED_LEAVES:
                numbers.see("expert_grad_err", _distance(got_dw[k], want_dw[k]), k)
            rows.append(report)
            cotangent = reference.routed_back(w, h, cotangent)[1]
            # attention: x -> x + attention(x)
            got_add, _, got_dw = layer(
                {**own, "moe_wd": jnp.zeros_like(own["moe_wd"])}, x, cotangent)
            want_dw, cotangent_in = reference.attention_back(w, x, cotangent)
            numbers.see("attn_out_err", _distance(got_add, h - x))
            numbers.see("first_blocks_err",
                        _distance(got_add[:, :FIRST_BLOCKS], (h - x)[:, :FIRST_BLOCKS]))
            for k in ATTENTION_LEAVES:
                numbers.see("attn_grad_err", _distance(got_dw[k], want_dw[k]), k)
            cotangent = cotangent_in
            numbers["clear_tokens_share_min"] = min(
                numbers.get("clear_tokens_share_min", 1.0), float(jnp.mean(clear)))
        del inputs
        program_counts, _ = routing(params, batch, key)  # on the program's own forward pass
    rows = np.asarray(rows[::-1], np.float64)  # (layers, 2): rows held, assignments over the buffer
    counts, program_counts = np.asarray(counts, np.float64), np.asarray(program_counts, np.float64)
    assignments = 2 * batch.size * shape["experts_per_token"]  # the stream's
    expected = assignments * count / shape["experts"]
    numbers.update({
        "weighted_loss_at_the_cells_weights": float(want_loss),
        "held_rows_err": (np.abs(rows[:, 0] - counts[:, first:first + count].sum(-1)) / expected).max(),
        "held_overflow": int(rows[:, 1].sum()),
        "held_rows_over_expected_max": (rows[:, 0] / expected).max(),
        "held_rows_over_expected_mean": (rows[:, 0] / expected).mean(),
        "expert_load_max_over_mean": (program_counts.max(-1) / (assignments / shape["experts"])).max(),
        "masked_share": float(np.asarray(masked).mean()),
    })
    return numbers


def check_objective(state, shape, batch, model_config=None, reference_params=None):
    """-> numbers: the program's whole ``forward`` with layers that add nothing
    beside the reference's under the same weights: the training loss and its
    gradient by OBJECTIVE_LEAVES (the last part of the note above ``TOLERANCE``)."""
    from distributed_llm_training_benchmark_framework_tpu.models import tinygpt

    config = model_config or state.model_config
    weights = state.params if reference_params is None else reference_params
    numbers = Worst()
    with jax.set_mesh(state.mesh):
        key = first_step_key()
        t, masked = jax.jit(lambda key: tinygpt.bd_noise(config, key, batch.shape))(key)
        got_loss, got_d = _programs(config)[2](_without(state.params, "wo", "moe_wd"), batch, key)
        want_loss, want_d = reference_for(shape).objective(
            _without(weights, "wo", "moe_wd"), batch, t, masked)
        for k in OBJECTIVE_LEAVES:
            numbers.see("objective_grad_err", _distance(got_d[k], want_d[k]), k)
    numbers.update({
        "loss_program": float(got_loss), "loss_reference": float(want_loss),
        "loss_err": abs(float(got_loss) - float(want_loss)) / abs(float(want_loss)),
    })
    return numbers


def refused_by(numbers):
    """The limits of TOLERANCE that these readings are over."""
    return [k for k, limit in TOLERANCE.items()
            if f"{k}_err" in numbers and numbers[f"{k}_err"] > limit]


def check_initial(state, shape, batch):
    """-> (ok, numbers): the comparison the note above ``TOLERANCE`` describes."""
    numbers = {**check_layers(state, shape, batch), **check_objective(state, shape, batch)}
    ok = not refused_by(numbers) and numbers["held_overflow"] == 0
    return bool(ok), {k: float(v) for k, v in numbers.items()}


def build_state(workload, config, shape, devices, seed):
    """``build.build_state`` with the cell's own documents in the table and the
    QK-norm scales at the config file's ``qk_norm_scale_init`` (the program
    starts them from 1.0, a model trained from scratch; the file says what the
    cell's start stands for)."""
    state, table, _ = build.build_state(workload, config, devices, seed)
    blocks = dict(state.params["blocks"])
    for k in ("q_norm", "k_norm"):
        blocks[k] = jax.device_put(
            jnp.full_like(blocks[k], config["qk_norm_scale_init"]), blocks[k].sharding)
    state.params = {**state.params, "blocks": blocks}
    tokens = build_bd.token_table(shape, workload, seed)
    table = jax.device_put(tokens, table.sharding)
    jax.block_until_ready(table)
    return state, table, tokens


def fall_and_spread(losses, sync_every):
    """(first window's mean - last window's, the spread of the window means:
    what is left of them once their trend is taken out, as the standard
    deviation of their second differences over sqrt(6), which a straight or
    gently bending fall adds nothing to)."""
    means = np.asarray(losses, np.float64).reshape(-1, sync_every).mean(-1)
    if len(means) < 4:
        return float(means[0] - means[-1]), float("nan")
    return float(means[0] - means[-1]), float(np.diff(means, 2).std() / math.sqrt(6))


class ReportCounter:
    """Wraps the step so that ``step_loop.steps`` sees the three outputs it
    knows, and keeps the fourth: (rows, overflow, masked tokens) of every step,
    on the device until ``drain`` (after a window's losses are on the host
    anyway)."""

    def __init__(self, state):
        self.pending, inner = [], state.step_fn

        def step_fn(params, opt_state, table, step):
            params, opt_state, loss, report = inner(params, opt_state, table, step)
            self.pending.append(report)
            return params, opt_state, loss

        state.step_fn = step_fn

    def drain(self):
        """-> (steps, 3) float64 since the last drain."""
        out = np.asarray(jax.device_get(self.pending), np.float64).reshape(-1, 3)
        self.pending.clear()
        return out


def run(entry, workload, config, args, devices, process_start):
    from distributed_llm_training_benchmark_framework_tpu.models import tinygpt

    manifest_ = manifest.load_manifest()
    on_chip = devices[0].platform == "tpu"
    if not on_chip:  # the dry run: tiny widths, 4 of 8 experts held, 3 a token
        config = build_bd.tiny_bd(config)
    shape = build_bd.bd_shape(workload, config)
    sync_every = workload["sync_every"]
    chips = workload["chips"]
    # data tokens: the stream the step runs is twice as long
    tokens_per_step = (workload["grad_accum"] * workload["micro_batch_per_chip"]
                       * workload["mesh"]["data"] * workload["seq_len"])
    expected_rows = (2 * tokens_per_step * shape["experts_per_token"] * shape["held"][1]
                     / shape["experts"] * shape["layers"])  # a step, all layers
    counter = CompileCounter()

    t = time.perf_counter()
    state, table, tokens = build_state(workload, config, shape, devices, args.seed)
    init_s = time.perf_counter() - t
    print(f"perfbench: mesh {dict(state.mesh.shape)}, strategy {state.strategy.describe()}, "
          f"remat {state.model_config.remat}, {state.n_params / 1e6:.1f}M parameters, "
          f"{tokens_per_step} data tokens a step (a stream of {2 * tokens_per_step})", flush=True)

    t = time.perf_counter()
    batch = correct.first_micro_batch(state, tokens, workload)
    state.opt_state = None  # 5.2 GB the check's programs need (the note above TOLERANCE)
    initial_ok, initial = check_initial(state, shape, batch)
    state.opt_state = fresh_moments(state)
    check_s = time.perf_counter() - t
    print(f"perfbench: initial check ok={initial_ok} {initial} ({check_s:.1f} s)", flush=True)

    t = time.perf_counter()
    compiled = state.aot_compile(state.params, state.opt_state, table, 0)
    compile_s = time.perf_counter() - t
    report = ReportCounter(state)
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
    with jax.set_mesh(state.mesh):  # the first document again, at the weights the window left
        _, held_now = _programs(state.model_config)[3](state.params, batch, first_step_key())
    by_layer = np.asarray(held_now)[:, 0] * shape["layers"] / expected_rows
    print(f"perfbench: {n_steps} steps in {sum(times):.3f} s; ms a step by window: "
          f"{[round(1e3 * w / sync_every, 3) for w in times]}; loss {losses[0]:.4f} -> "
          f"{losses[-1]:.4f}; window means fall by {fall:.4f}, their spread {spread:.4f} "
          f"({fall / spread:.1f} spreads); compilations in the window: {counter.count}; held "
          f"rows over expected, least and most of a step: "
          f"{window[:, 0].min() / expected_rows:.3f}, {window[:, 0].max() / expected_rows:.3f}; "
          f"held assignments that did not fit: {overflow:.0f} (the first document's held rows by "
          f"layer after the window: {[round(float(x), 3) for x in by_layer]}, "
          f"{initial['held_rows_over_expected_max']:.3f} the worst before it); masked share of the data "
          f"tokens, least and most: {window[:, 2].min() / tokens_per_step:.3f}, "
          f"{window[:, 2].max() / tokens_per_step:.3f}", flush=True)
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
        "flops_per_token": flops_bd.train_flops_per_token(shape),
        "memory_allocator_bytes": allocator, "memory_assigned_bytes": assigned,
        "compiles_in_window": counter.count, "traced_steps": TRACED_STEPS,
        "peaks": peaks.peaks(devices[0].device_kind) if on_chip else None,
        "held_rows_traced": traced[:, 0].sum(),
        "held_rows_over_expected": traced[:, 0].mean() / expected_rows,
        "expert_load_max_over_mean": initial["expert_load_max_over_mean"],
        "bd_masked_share_pct": 100.0 * traced[:, 2].mean() / tokens_per_step,
        "bd_mask_stats": tinygpt.bd_mask_stats(state.model_config, workload["seq_len"]),
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
