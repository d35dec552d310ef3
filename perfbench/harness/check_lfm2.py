"""The comparison that decides ``correct`` in an LFM2-MoE-class cell: the program
against ``reference_lfm2``, at the cell's own weights and the timed
``model_config``, one layer at a time, a sublayer at a time: a gated-convolution
mixer, the attention mixer, the dense MLP and the held experts apart (the note
above ``TOLERANCE``). The workload file names ``check_initial`` and
``program_counters`` under ``parts``; ``laguna_loop.run`` calls them.
``tools/calibrate_correct_lfm2.py`` runs the wrong models through
``check_initial_numbers``.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import reference_lfm2
from .bd_loop import MARGIN, Worst, _distance
from .kda_loop import _programs, _zeroed  # the program's own layer, head, loss and routing, jitted

# How the comparison is made, and why a layer at a time: several routed layers
# in a row are chaotic under top-k (a token whose 4th and 5th scores lie close
# takes another expert in bfloat16, and every later layer sees it), so no limit
# on the whole network's logits could tell float8 from bfloat16 (PERF.md
# section 6, PR 36). One layer is not. So the program is fed the reference's
# own hidden states, a sublayer at a time (teacher forcing), at the cell's
# weights, shapes, kernels, remat policy and bounded buffer, through the
# program's own layer (``tinygpt.apply_layer``: what its loop over stacks of
# unequal leaves runs, on the layer's own slice), forward and backward:
#
# * a mixer, by the layer's kind: ``conv`` (W_in, the gated convolution's two
#   kernels over 16,384 positions, W_out) or ``global`` (32 query heads over 8
#   KV heads of 64 lanes, the per-head QK-norm and rotary on the ``jnp`` chain,
#   the flash kernels with k and v at their own head count). The layer with its
#   MLP's last projections zeroed is x + mixer(x). It is given the reference's
#   input of that layer and, as the output's cotangent, the reference's own
#   gradient of the training loss there. Held to the reference: what the
#   sublayer adds, over the sequence and, for a convolution, over its first
#   FIRST_POSITIONS positions (where the zeros before the sequence bite: taps
#   one position off, or a tap more or fewer, show among three positions), and
#   the gradient by its leaves (``sconv_win``, ``sconv_taps``, ``wo``,
#   ``ln1_scale``; ``wq``, ``wkv``, ``q_norm``, ``k_norm``, ``wo``,
#   ``ln1_scale``) under the whole cotangent and, for a convolution, under the
#   cotangent of those first positions alone. **Both sides are given that input
#   scaled down to the norm of what the sublayer adds to it** (where that is
#   less; ``kda_loop``'s rule and its reason: the layer returns x + add in
#   bfloat16, and where the stream holds many times what a sublayer adds the
#   reading would be the sum's rounding and not the sublayer). RMSNorm(x) is
#   x's direction but for eps, so both sides see the same normed input (at the
#   published widths what a mixer adds has an RMS of tenths; the dry run's tiny
#   widths take a smaller eps: ``build_lfm2.tiny_lfm2``). The whole forward
#   under ``loss`` is the timed operating point.
# * the MLP: the layer with its ``wo`` zeroed is h + MLP(h). It is given the
#   reference's mixer output h. The leading dense layer: what it adds and the
#   gradient by its three leaves. A routed layer: what the held experts add,
#   the gradient by their two leaves and by the norm's scale; the rows the
#   bounded buffer held against the reference's own count of assignments on the
#   held experts; no assignment over the buffer. The reference chooses its own
#   experts; tokens whose last chosen and first unchosen scores lie within
#   MARGIN are left out of the output's comparison and carry no cotangent
#   (``bd_loop``'s rule).
# * the head: ``tinygpt.head`` (tied to the embedding) on the reference's last
#   hidden state, per-position losses against the reference's in the units of
#   ``correct.py``.
# * the loss: the program's whole ``forward`` (every layer live, the timed
#   config) against the reference's training loss from its own layer-wise
#   pass: a mean over 32,768 positions, which near-ties do not move.
FIRST_POSITIONS = 3
MIXER_LEAVES = {"conv": ("sconv_win", "sconv_taps", "wo", "ln1_scale"),
                "global": ("wq", "wkv", "q_norm", "k_norm", "wo", "ln1_scale")}
DENSE_LEAVES = ("wgu", "wproj", "ln2_scale")
ROUTED_LEAVES = ("moe_wgu", "moe_wd", "ln2_scale")
LAST_PROJECTIONS = ("wproj", "moe_wd")  # zeroed, a layer's MLP adds nothing

# Calibrated on the v5e at the published widths (tools/calibrate_correct_lfm2.py,
# which runs every wrong model below through the same comparison; PERF.md
# section 6, PR 54: every wrong model on seed 5400000301, the program's side
# also on seeds 5400000302-303 and from every run's "initial check, reading /
# limit" line). Each limit is the geometric middle of two readings: the
# program's largest over its seeds (a variant that changes another part reads
# the program's own number here under another cotangent: those count), and the
# nearest of the float8 reference (every weight rounded to float8_e4m3fn, the
# nearest precision below the cell's) and the wrong models that this limit has
# to refuse. Every reading is the worst of its layers (and of its leaves);
# ``<kind>_out_err.layer<i>`` gives the layers apart.
#
# conv_out, |program - reference| / |reference| of what a conv mixer adds to
# its (scaled) input: the program 0.00664 on every seed; float8 0.0714-0.0718,
# four taps 0.506, two taps 0.723, the B gate left out 1.349, the C gate left
# out 1.350, taps one position later (a look-ahead) 1.424.
# conv_first, the same over the first 3 positions: the program 0.00685-0.00712;
# float8 0.0725-0.0755, two taps 0.469, a gate left out 1.36-1.39, taps one
# position later 1.363. A fourth tap reads 0.00697 here, under the limit: it
# meets only the zeros before the sequence; conv_out's and conv_grad's.
# conv_grad / conv_first_grad, of the gradient by sconv_win, sconv_taps, wo,
# ln1_scale through ``sconv_bwd``, under the whole cotangent and under that of
# the first 3 positions alone: the program 0.00625-0.00656 / 0.00653-0.00689;
# float8 0.0667-0.0682 / 0.0733-0.0746, four taps 0.502 (the first positions'
# 0.00662: as above), two taps 0.710 / 0.487, a gate left out 1.49 / 1.51-1.58.
# **A bfloat16 accumulation in the reference's convolution reads 0.00706 /
# 0.00723 / 0.00693 / 0.00728, within a tenth of the program's own**: three
# taps' rounding lies under the bfloat16 operands' own, so no limit refuses it
# and none is set to; that the program's kernels sum in float32 is tier 1's to
# hold (tests/test_lfm2.py: one rounding, at the end).
# global_out / global_grad, of the attention mixer (32 query heads over 8 KV
# heads of 64, QK-norm then rotary) and of the gradient by wq, wkv, q_norm,
# k_norm, wo, ln1_scale: the program 0.00562-0.00584 / 0.00777-0.00942 (the
# worst leaf moves with the cotangent); float8 0.0587-0.0601 / 0.0701-0.0861,
# no QK-norm 0.198 / no finite reading, no rotary 0.645 / 0.999. **The norm
# behind the rotation reads global_out 0.00573, the program's own: at scales of
# one the two orders agree forward; global_grad refuses it alone, 0.851** (the
# scales' gradients differ).
# dense_out / dense_grad, of the leading 7168-wide SwiGLU: the program 0.00542
# / 0.00526-0.00534; float8 0.0581 / 0.0492.
# moe_out, of what the held experts add, over the clear tokens (57 % of a
# layer's at the least): the program 0.00609-0.00611; **the gates taken from
# the biased score 0.0334** (the expert bias of both sides drawn within 0.05),
# float8 0.0797-0.0833, one held expert fewer 0.384, a buffer of 0.8 of the
# expected rows (24,114 assignments dropped) 0.437, the choice by the unbiased
# score 0.438, gates not renormalised 0.690.
# expert_grad, of the gradient by moe_wgu, moe_wd, ln2_scale: the program
# 0.00599-0.00648; the gates from the biased score 0.0334, float8 0.0765-0.0803,
# one held expert fewer 0.386.
# held_rows: the rows the bounded buffer held against the reference's own
# count, over the expected rows: the program 0.00021-0.00067 (near-ties that
# fall the other way in bfloat16: a score of rows of 32,768); the choice by the
# unbiased score 0.0558, one held expert fewer 0.149, the short buffer 0.198;
# float8 0.0016-0.0023 is under it and refused by eleven others. **bfloat16
# router logits in the reference read 0.00052 and pass every limit**: the
# program's router reads a bfloat16 input already, near-ties are left out of
# moe_out, and the few choices that flip are the program's own order of
# magnitude; that the program's logits are float32 at the highest precision is
# ``models/moe.py``'s own tests'.
# per_position, in the units of ``correct.py``: the program 0.00234-0.00236;
# float8 0.0331-0.0333, an untied head 1.63.
# loss, |program - reference| / reference of the training loss through the
# whole forward, every layer live: the program 1e-6 to 4e-5; the harness's
# accepted 2e-4 leaves the first reading five times of room and refuses a gate
# left out (4.3e-3, 5.3e-3), two and four taps (5.5e-3, 5.9e-3), taps one
# position later (1.7e-3), gates not renormalised (9.2e-3), the short buffer
# (3.4e-4) and an untied head (0.072); float8 (1.1e-4 to 2.5e-4) does not
# reliably move a loss that starts at ln 16384, and eleven other limits refuse
# it.
TOLERANCE = {
    "conv_out": 0.0218, "conv_first": 0.0227, "conv_grad": 0.0209, "conv_first_grad": 0.0225,
    "global_out": 0.0185, "global_grad": 0.0257, "dense_out": 0.0177, "dense_grad": 0.0162,
    "moe_out": 0.0143, "expert_grad": 0.0147, "held_rows": 0.0061, "per_position": 0.0088,
    "loss": 2e-4,
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
                    lambda one: reference_lfm2.head_losses(m, params, *one), (x, batch))
                return jnp.mean(losses), losses
            return jax.value_and_grad(mean_loss, has_aux=True)(x)

        self.embed = highest(lambda params, batch: jax.lax.map(
            lambda tokens: reference_lfm2.embed(m, params, tokens), batch))
        self.mixer = {"conv": both_ways(reference_lfm2.conv_sublayer),
                      "global": both_ways(reference_lfm2.attention_sublayer)}
        self.dense = both_ways(reference_lfm2.dense_sublayer)
        self.routed = both_ways(reference_lfm2.routed_sublayer, has_aux=True)
        # -> ((mean loss, losses), the mean loss's gradient by the last hidden state)
        self.head = highest(lambda params, x, batch: head(f32(params), x, batch))


def reference_for(shape):
    return _reference(tuple(sorted(shape.items())))


@functools.lru_cache(maxsize=2)
def _reference(items):
    return Reference(dict(items))


def check_initial_numbers(state, shape, batch, model_config=None, reference_params=None,
                          params=None):
    """-> numbers: the comparison the note above ``TOLERANCE`` describes.
    ``params``: the weights of both sides where they are not the state's (the
    calibration's expert bias away from zero)."""
    from distributed_llm_training_benchmark_framework_tpu.models import tinygpt

    config = model_config or state.model_config
    params = state.params if params is None else params
    weights = params if reference_params is None else reference_params
    reference, programs = reference_for(shape), _programs(config)
    first, count = shape["held"]
    numbers, last = Worst(), shape["layers"] - 1
    routed_at = [i for i in range(shape["layers"]) if i >= shape["dense_layers"]]
    theirs = lambda i: reference_lfm2.layer_weights(shape, weights, i)
    early = jnp.arange(batch.shape[1]) < FIRST_POSITIONS
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
            w, own = theirs(i), tinygpt.layer_weights(config, params, i)
            kind = shape["kinds"][i]
            layer = programs[kind]
            without_mixer = _zeroed(own, "wo")
            if i in routed_at:  # h -> h + the held experts' part
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
            # the mixer: x -> x + mixer(x), at the input scaled down to what it adds
            forward, backward = reference.mixer[kind]
            small = min(1.0, float(jnp.linalg.norm(h - x) / jnp.linalg.norm(x)))
            xs, without_mlp = x * small, _zeroed(own, *LAST_PROJECTIONS)
            want_add = forward(w, xs) - xs
            got_add, _, got_dw = layer(without_mlp, xs, cotangent)
            want_dw, through = backward(w, xs, cotangent)
            numbers[f"{kind}_out_err.layer{i}"] = float(_distance(got_add, want_add))
            numbers.see(f"{kind}_out_err", numbers[f"{kind}_out_err.layer{i}"])
            for k in MIXER_LEAVES[kind]:
                numbers.see(f"{kind}_grad_err", _distance(got_dw[k], want_dw[k]), f"{kind}.{k}")
            if kind == "conv":  # where the zeros before the sequence bite
                numbers.see("conv_first_err", _distance(got_add, want_add, early[None, :]))
                of_early = cotangent * early[None, :, None]
                _, _, got_dw = layer(without_mlp, xs, of_early)
                want_dw, _ = backward(w, xs, of_early)
                for k in MIXER_LEAVES[kind]:
                    numbers.see("conv_first_grad_err", _distance(got_dw[k], want_dw[k]),
                                f"first.{k}")
            # on to the layer below: the sublayer's Jacobian at x is ``small`` times its own at xs
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
    """-> (ok, numbers); every number of the comparison is printed beside its limit."""
    numbers = check_initial_numbers(state, shape, batch)
    beside = ", ".join(f"{k} {numbers[f'{k}_err']:.5f} / {limit}" for k, limit in TOLERANCE.items()
                       if f"{k}_err" in numbers)
    print(f"perfbench: initial check, reading / limit: {beside}", flush=True)
    ok = not refused_by(numbers) and numbers["held_overflow"] == 0
    return bool(ok), {k: float(v) for k, v in numbers.items()}


def program_counters(model_config, workload):
    """The program's trace-time counters this cell's readers take: the gated
    convolution's (``tinygpt.sconv_stats``: the layers of the kind, those whose
    convolution the Mosaic calls take, the bytes a call moves), what the
    attention layer's kernels visit at the tiles taken
    (``tinygpt.attn_mask_stats``) and whether its QK-norm and rotary took the
    one pass (``tinygpt.qk_prologue_stats``: not at heads of 64)."""
    from distributed_llm_training_benchmark_framework_tpu.models import tinygpt

    return {"sconv_stats": tinygpt.sconv_stats(model_config, workload["seq_len"]),
            "attn_mask_stats": tinygpt.attn_mask_stats(model_config, workload["seq_len"]),
            "qk_prologue_stats": tinygpt.qk_prologue_stats(model_config, workload["seq_len"])}
