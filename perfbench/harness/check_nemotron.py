"""The comparison that decides ``correct`` in a Nemotron-H-class cell: the
program against ``reference_nemotron``, at the cell's own weights and the timed
``model_config``, one block at a time, a Mamba-2 block, the attention block and
a routed block apart (the note above ``TOLERANCE``). The workload file names
``check_initial``, ``program_counters`` and ``build_state`` under ``parts``;
``laguna_loop.run`` calls them. ``tools/calibrate_correct_nemotron.py`` runs the
wrong models through ``check_initial_numbers``.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import build, reference_nemotron
from .bd_loop import MARGIN, Worst, _distance
from .kda_loop import _programs, _zeroed  # the program's own layer, head, loss and routing, jitted

# How the comparison is made, and why a block at a time: several routed blocks
# in a row are chaotic under top-k (a token whose 6th and 7th scores lie close
# takes another expert in bfloat16, and every later block sees it), so no limit
# on the whole network's logits could tell float8 from bfloat16 (PERF.md
# section 6, PR 36). One block is not. So the program is fed the reference's
# own hidden states, a block at a time (teacher forcing), at the cell's
# weights, shapes, kernels, remat policy and bounded buffer, through the
# program's own layer (``tinygpt.apply_layer``: what its loop over stacks of
# unequal leaves runs, on the block's own slice), forward and backward. Every
# block here is one sublayer alone, so nothing is zeroed to take a half away:
#
# * a mixer block, ``ssd`` (in_proj, the convolution's kernels with bias and
#   SiLU, the scan's two kernels at chunk 128 over 16,384 positions against a
#   ``lax.scan`` over the positions, the gated grouped norm, out_proj) or
#   ``global`` (32 query heads over 2 KV heads through the flash kernels, no
#   positions): it is given the reference's input of that block and, as the
#   output's cotangent, the reference's own gradient of the training loss there.
#   Held to the reference: what the block adds, and the gradient by its leaves
#   (the nine of a Mamba-2 block, ``SSD_LEAVES``; ``wq``, ``wkv``, ``wo``,
#   ``ln1_scale`` of the attention block). **Both sides are given that input
#   scaled down to the norm of what the block adds to it** (where that is less;
#   ``kda_loop``'s rule and its reason: the layer returns x + add in bfloat16,
#   and where the stream holds many times what a block adds the reading would
#   be the sum's rounding and not the block). RMSNorm(x) is x's direction but
#   for eps, so both sides see the same normed input; the whole forward under
#   ``loss`` is the timed operating point.
# * a routed block: what the held experts and the shared expert add, the
#   gradient by their four leaves and by the norm's scale; the shared expert
#   alone (``moe_wd`` zeroed) against the reference with no expert held; the
#   rows the bounded buffer held against the reference's own count of
#   assignments on the held experts; no assignment over the buffer. The
#   reference chooses its own experts; tokens whose last chosen and first
#   unchosen scores lie within MARGIN are left out of the output's comparison
#   and carry no cotangent (``bd_loop``'s rule).
# * the head: ``tinygpt.head`` on the reference's last hidden state,
#   per-position losses against the reference's in the units of ``correct.py``.
# * the loss: the program's whole ``forward`` (every block live, the timed
#   config) against the reference's training loss from its own block-wise
#   pass: a mean over 16,384 positions, which near-ties do not move.
SSD_LEAVES = ("ssd_win", "ssd_conv", "ssd_conv_bias", "ssd_dt_bias", "ssd_a_log", "ssd_d",
              "ssd_norm", "wo", "ln1_scale")
ATTENTION_LEAVES = ("wq", "wkv", "wo", "ln1_scale")
ROUTED_LEAVES = ("moe_wu", "moe_wd", "shared_wu", "shared_wd", "ln2_scale")
MIXER_LEAVES = {"ssd": SSD_LEAVES, "global": ATTENTION_LEAVES}

# Calibrated on the v5e at the published widths (tools/calibrate_correct_nemotron.py,
# which runs every wrong model below through the same comparison; PERF.md
# section 6, PR 51: every wrong model on seed 5100000201, the program's side
# also from every run's "initial check, reading / limit" line, four seeds).
# Each limit is the geometric middle of two readings: the program's largest
# over its seeds, and the nearest of the float8 reference (every weight rounded
# to float8_e4m3fn, the nearest precision below the cell's) and the wrong
# models that this limit has to refuse. Every reading is the worst of its
# blocks (and of its leaves); ``<kind>_out_err.layer<i>`` gives the blocks apart.
#
# ssd_out, |program - reference| / |reference| of what a Mamba-2 block adds to
# its (scaled) input: the program 0.00518-0.00521; float8 0.0635, the heads
# reading the next group's B and C 0.207, no bias on the convolution 0.55, the
# norm before the gate 0.64, no skip 0.99. A bfloat16 state in the reference's
# scan reads 0.0073, under this limit: ssd_grad refuses it.
# ssd_grad, of the gradient by the mixer's nine leaves through ``ssd_bwd``, the
# convolution's backward and the grouped norm (the worst leaf is dt_bias or
# A_log, 64 numbers each, and moves by seed): the program 0.0087-0.0144 (the
# reference with bfloat16 weights 0.0112); **a bfloat16 state in the reference
# 0.0794, by this limit alone**; float8 0.174. The limit leaves the program's
# largest reading 2.5 times of room, since fresh seeds read higher.
# global_out / global_grad, of the attention block (32 query heads over 2 KV
# heads, no positions) and of the gradient by wq, wkv, wo, ln1_scale: the
# program 0.00318-0.00335 / 0.00677-0.00683; float8 0.0498 / 0.0699, rotary on
# the block 0.28 / 1.49.
# moe_out, of what the held experts and the shared expert add, over the clear
# tokens (18.8 % of a block's at the least): the program 0.00528-0.00529;
# float8 0.0517, one held expert fewer 0.0742, a buffer of 0.8 of the expected
# rows (4,225 assignments dropped) 0.095, gates not times 2.5 0.114, gates not
# renormalised 0.58, relu not squared 0.96, no shared expert 5.8.
# shared_out, of the shared expert alone: the program 0.00526-0.00527; float8
# 0.0510, relu not squared 0.96.
# expert_grad, of the gradient by moe_wu, moe_wd, shared_wu, shared_wd,
# ln2_scale: the program 0.00547-0.00551; float8 0.0674, one held expert fewer
# 0.45.
# held_rows: the rows the bounded buffer held against the reference's own
# count, over the expected rows: the program 0.00065-0.00179 (near-ties that
# fall the other way in bfloat16); one held expert fewer 0.192, the short
# buffer 0.275; float8 0.0055 is under it and refused by eight others.
# per_position, in the units of ``correct.py``: the program 0.00230-0.00236;
# float8 0.0334.
# loss, |program - reference| / reference of the training loss through the
# whole forward, every block live: the program under 1e-5; the harness's
# accepted 2e-4 leaves the first reading twenty times of room and refuses no
# skip (5.3e-4), no bias on the convolution (4.7e-4), no shared expert
# (4.6e-4), gates not renormalised (3.5e-4) and relu not squared (2.3e-4);
# float8 (6e-5) does not move a loss that starts at ln 16384, and eight other
# limits refuse it.
TOLERANCE = {
    "ssd_out": 0.0182, "ssd_grad": 0.036, "global_out": 0.0129, "global_grad": 0.0218,
    "moe_out": 0.0165, "shared_out": 0.0164, "expert_grad": 0.0193, "held_rows": 0.0185,
    "per_position": 0.0089, "loss": 2e-4,
}


class Reference:
    """The reference's sides of the comparison over the batch's sequences,
    under ``jax.default_matmul_precision("highest")``, each compiled once: a
    mixer a kind, the routed block, the shared expert alone, the head."""

    def __init__(self, m):
        self.m = m

        def highest(f):
            def call(*a):
                with jax.default_matmul_precision("highest"):
                    return f(*a)
            return jax.jit(call)

        f32 = lambda w: jax.tree.map(lambda t: t.astype(jnp.float32), w)

        def both_ways(sublayer, has_aux=False):
            """(forward, backward) of ``sublayer(x, w)`` over the batch."""
            forward = lambda w, x: jax.vmap(lambda x: sublayer(x, w))(x)

            def backward(w, x, cotangent):
                _, pull_back, *_ = jax.vjp(forward, f32(w), x, has_aux=has_aux)
                return pull_back(cotangent)

            return highest(lambda w, x: forward(f32(w), x)), highest(backward)

        def head(params, x, batch):
            def mean_loss(x):
                losses = jax.lax.map(
                    lambda one: reference_nemotron.head_losses(m, params, *one), (x, batch))
                return jnp.mean(losses), losses
            return jax.value_and_grad(mean_loss, has_aux=True)(x)

        none_held = {**m, "held": (m["held"][0], 0)}
        self.embed = highest(lambda params, batch: jax.lax.map(
            lambda tokens: reference_nemotron.embed(m, params, tokens), batch))
        self.mixer = {
            "ssd": both_ways(lambda x, w: reference_nemotron.ssd_sublayer(m, x, w)),
            "global": both_ways(lambda x, w: reference_nemotron.attention_sublayer(m, x, w))}
        self.routed = both_ways(
            lambda x, w: reference_nemotron.routed_sublayer(m, x, w), has_aux=True)
        self.shared = both_ways(
            lambda x, w: reference_nemotron.routed_sublayer(none_held, x, w)[0])
        # -> ((mean loss, losses), the mean loss's gradient by the last hidden state)
        self.head = highest(lambda params, x, batch: head(f32(params), x, batch))


def reference_for(shape):
    return _reference(tuple(sorted(shape.items())))


@functools.lru_cache(maxsize=2)
def _reference(items):
    return Reference(dict(items))


def check_initial_numbers(state, shape, batch, model_config=None, reference_params=None):
    """-> numbers: the comparison the note above ``TOLERANCE`` describes."""
    from distributed_llm_training_benchmark_framework_tpu.models import tinygpt

    config = model_config or state.model_config
    params = state.params
    weights = params if reference_params is None else reference_params
    reference, programs = reference_for(shape), _programs(config)
    first, count = shape["held"]
    numbers, last = Worst(), shape["layers"] - 1
    routed_at = [i for i, kind in enumerate(shape["kinds"]) if kind == "mlp"]
    theirs = lambda i: reference_nemotron.layer_weights(shape, weights, i)
    with jax.set_mesh(state.mesh):
        # the reference's forward pass, every block's input kept
        x = reference.embed(weights, batch)
        inputs, counts = [], []
        for i, kind in enumerate(shape["kinds"]):
            if kind == "mlp":
                y, router = reference.routed[0](theirs(i), x)
                clear = router["margin"] >= MARGIN
                counts.append(jnp.sum(router["assignments"], 0))
            else:
                y, clear = reference.mixer[kind][0](theirs(i), x), None
            inputs.append((x, clear))
            x = y
        ((want_mean, want_losses), cotangent) = reference.head(weights, x, batch)
        got_losses = programs["head"](params, x, batch)
        numbers["per_position_err"] = float(
            jnp.sqrt(jnp.mean(jnp.square(got_losses - want_losses))) / jnp.std(want_losses))
        # backward, the last block first: each block of the program beside the reference's
        rows = []
        for i in reversed(range(shape["layers"])):
            (x, clear), y = inputs[i], x if i == last else inputs[i + 1][0]
            w, own = theirs(i), tinygpt.layer_weights(config, params, i)
            kind = shape["kinds"][i]
            layer = programs[kind]
            if kind == "mlp":  # x -> x + the held experts' and the shared expert's part
                of_clear = cotangent * clear[..., None]
                got_add, report, got_dw = layer(own, x, of_clear)
                want_dw, _ = reference.routed[1](w, x, of_clear)
                numbers.see("moe_out_err", _distance(got_add, y - x, clear))
                for k in ROUTED_LEAVES:
                    numbers.see("expert_grad_err", _distance(got_dw[k], want_dw[k]), k)
                rows.append(report)
                got_add, _, _ = layer(_zeroed(own, "moe_wd"), x, cotangent)
                numbers.see("shared_out_err", _distance(got_add, reference.shared[0](w, x) - x))
                cotangent = reference.routed[1](w, x, cotangent)[1]
                numbers["clear_tokens_share_min"] = min(
                    numbers.get("clear_tokens_share_min", 1.0), float(jnp.mean(clear)))
                continue
            # a mixer: x -> x + mixer(x), at the input scaled down to what it adds
            forward, backward = reference.mixer[kind]
            small = min(1.0, float(jnp.linalg.norm(y - x) / jnp.linalg.norm(x)))
            xs = x * small
            want_add = forward(w, xs) - xs
            got_add, _, got_dw = layer(own, xs, cotangent)
            want_dw, through = backward(w, xs, cotangent)
            numbers[f"{kind}_out_err.layer{i}"] = float(_distance(got_add, want_add))
            numbers.see(f"{kind}_out_err", numbers[f"{kind}_out_err.layer{i}"])
            for k in MIXER_LEAVES[kind]:
                numbers.see(f"{kind}_grad_err", _distance(got_dw[k], want_dw[k]), f"{kind}.{k}")
            # on to the block below: the block's Jacobian at x is ``small`` times its own at xs
            cotangent = cotangent + small * (through - cotangent)
            numbers["mixer_input_scale_min"] = min(numbers.get("mixer_input_scale_min", 1.0), small)
        del inputs
        got_loss = float(programs["loss"](params, batch))  # the whole forward, every block live
        program_counts, _ = programs["routing"](params, batch)
    rows = np.asarray(rows[::-1], np.float64)  # (routed blocks, 2): rows held, over the buffer
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
    """-> (ok, numbers); every number of the comparison is printed beside its limit."""
    numbers = check_initial_numbers(state, shape, batch)
    beside = ", ".join(f"{k} {numbers[f'{k}_err']:.5f} / {limit}" for k, limit in TOLERANCE.items()
                       if f"{k}_err" in numbers)
    print(f"perfbench: initial check, reading / limit: {beside}", flush=True)
    ok = not refused_by(numbers) and numbers["held_overflow"] == 0
    return bool(ok), {k: float(v) for k, v in numbers.items()}


def program_counters(model_config, workload):
    """The program's trace-time counters this cell's readers take: the scan's
    (``tinygpt.ssd_stats``: the chunk steps, the kernels' calls, the states
    kept a block) and what the attention block's kernels visit at the tiles
    taken (``tinygpt.attn_mask_stats``)."""
    from distributed_llm_training_benchmark_framework_tpu.models import tinygpt

    return {"ssd_stats": tinygpt.ssd_stats(model_config, workload["seq_len"]),
            "attn_mask_stats": tinygpt.attn_mask_stats(model_config, workload["seq_len"])}


def build_state(workload, config, devices, seed):
    """``build.build_state`` (uniform ids over the slice from the seed, as the
    DeepSeek cell) and, where the config file has ``embedding_scale_init``, the
    embedding's rows times that (a stand-in for a checkpoint, as the Kimi
    cell's ``kda_norm_scale_init``: the config file's ``assumed`` says why; the
    program starts them from normal(0, 0.02))."""
    state, table, tokens = build.build_state(workload, config, devices, seed)
    scale = config.get("embedding_scale_init")
    if scale is not None:
        wte = state.params["wte"]
        state.params = {**state.params, "wte": jax.device_put(wte * scale, wte.sharding)}
    return state, table, tokens
