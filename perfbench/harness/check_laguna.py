"""The comparison that decides ``correct`` in a Laguna-class cell: the program
against ``reference_laguna``, at the cell's own weights and the timed
``model_config``, one layer at a time, a sliding layer, the dense full layer
and the routed full layer apart (the note above ``TOLERANCE``). The workload
file names ``check_initial`` (and ``program_counters``) under ``parts``;
``laguna_loop.run`` calls them. ``tools/calibrate_correct_laguna.py`` runs the
wrong models through ``check_initial_numbers``.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import reference_laguna
from .bd_loop import MARGIN, Worst, _distance
from .kda_loop import _programs, _zeroed  # the program's own layer, head, loss and routing, jitted

# How the comparison is made, and why a layer at a time: several routed layers
# in a row are chaotic under top-k (a token whose 8th and 9th scores lie close
# takes another expert in bfloat16, and every later layer sees it), so no limit
# on the whole network's logits could tell float8 from bfloat16 (PERF.md
# section 6, PR 36). One layer is not. So the program is fed the reference's
# own hidden states, a sublayer at a time (teacher forcing), at the cell's
# weights, shapes, kernels, remat policy and bounded buffer, through the
# program's own layer (``tinygpt.apply_layer``: what its loop over stacks of
# unequal leaves runs, on the layer's own slice), forward and backward:
#
# * attention, by the layer's label: ``window`` (the sliding layers, 64
#   heads, whole-head rotary, the band), ``global_dense`` (layer 0: 48 heads,
#   half of each head rotated under YaRN, causal, before the dense MLP) and
#   ``global`` (the routed full layer). The layer with its MLP's last
#   projections zeroed is x + attention(x). It is given the reference's input
#   of that layer and, as the output's cotangent, the reference's own gradient
#   of the training loss there. Held to the reference: what the sublayer adds,
#   over the sequence and over its first FIRST_POSITIONS positions (where a
#   window of 512 first bites: a window one key long or short, or none, shows
#   among few keys), and the gradient by its five leaves (``wq``, ``wkv``,
#   ``wg``, ``wo``, ``ln1_scale``) under the whole cotangent and under the
#   cotangent of those first positions alone. **Both sides are given that
#   input scaled down to the norm of what the sublayer adds to it** (where
#   that is less; ``kda_loop``'s rule and its reason: behind the 8192-wide
#   dense MLP the stream holds many times what an attention sublayer adds, the
#   layer returns x + add in bfloat16, and the reading would be the sum's
#   rounding and not the sublayer). RMSNorm(x) is x's direction but for eps, so
#   both sides see the same normed input; the whole forward under ``loss`` is
#   the timed operating point.
# * the MLP: the layer with its ``wo`` zeroed is h + MLP(h). It is given the
#   reference's attention output h. The leading dense layer: what it adds and
#   the gradient by its three leaves. A routed layer: what the held experts
#   and the shared expert add, the gradient by their four leaves and by the
#   norm's scale; the shared expert alone (``moe_wd`` zeroed too) against the
#   reference with no expert held; the rows the bounded buffer held against
#   the reference's own count of assignments on the held experts; no
#   assignment over the buffer. The reference chooses its own experts; tokens
#   whose last chosen and first unchosen scores lie within MARGIN are left out
#   of the output's comparison and carry no cotangent (``bd_loop``'s rule).
# * the head: ``tinygpt.head`` on the reference's last hidden state,
#   per-position losses against the reference's in the units of ``correct.py``.
# * the loss: the program's whole ``forward`` (every layer live, the timed
#   config) against the reference's training loss from its own layer-wise
#   pass: a mean over 16,384 positions, which near-ties do not move.
FIRST_POSITIONS = 600
ATTENTION_LEAVES = ("wq", "wkv", "wg", "wo", "ln1_scale")
DENSE_LEAVES = ("wgu", "wproj", "ln2_scale")
ROUTED_LEAVES = ("moe_wgu", "moe_wd", "shared_wgu", "shared_wd", "ln2_scale")
LAST_PROJECTIONS = ("wproj", "moe_wd", "shared_wd")  # zeroed, a layer's MLP adds nothing

# Calibrated on the v5e at the published widths (tools/calibrate_correct_laguna.py,
# which runs every wrong model below through the same comparison; PERF.md
# section 6, PR 47: every wrong model on seed 4700000301, the program's side
# also from every run's "initial check, reading / limit" line). Each limit is
# the geometric middle of two readings: the program's largest over its seeds,
# and the nearest of the float8 reference (every weight rounded to
# float8_e4m3fn, the nearest precision below the cell's) and the wrong models
# that this limit has to refuse. Every reading is the worst of its layers (and
# of its leaves); ``<label>_out_err.layer<i>`` gives the layers apart.
#
# window_out, |program - reference| / |reference| of what a sliding layer's
# attention adds to its (scaled) input: the program 0.00480-0.00484; **a window
# of 513 keys 0.01815, of 511 0.01824, by this limit and window_grad's**;
# float8 0.0513, the full layers' theta on it 0.34, the gate from the un-normed
# input 0.32, the query heads grouped one off 0.51, the gate left out 0.54, the
# full layers' table 0.69, no window 0.70.
# global_out / global_dense_out, the same of the routed full layer and of the
# dense one (layer 0, whose input is the embedding alone: its own label because
# it reads higher): the program 0.00431-0.00438 / 0.00763-0.00766; float8 0.0508
# / 0.0722, YaRN without its attention_factor 0.094 / 0.73, lanes paired j with
# j + 64 inside the half 0.161 / 0.80, the sliding layers' theta 0.166 / 0.84,
# all 128 lanes rotated 0.32 / 0.93.
# window_first / global_first / global_dense_first, the same over the first 600
# positions: the program 0.00478-0.00479 / 0.00447-0.00452 / 0.00743-0.00745; no
# window on a sliding layer 0.0439, float8 0.0521 / 0.0517 / 0.0700. A window
# one key long or short reads 0.0065-0.0066 here, under the limit: few
# positions differ; window_out refuses it.
# window_grad / global_grad / global_dense_grad, of the gradient by wq, wkv, wg,
# wo, ln1_scale through the fused backward on the band at (512, 512) tiles /
# under causal, each kind's rotary pass and the gate: the program 0.00822-0.00824
# / 0.00849-0.00870 / 0.00849; a window of 511 / 513 0.0415, float8 0.0689 /
# 0.0712 / 0.0760.
# first_grad, the same leaves under the cotangent of the first 600 positions
# alone, all three labels: the program 0.01021-0.01025; float8 0.0807 (a window
# of 511 / 513 reads 0.013-0.014, under it: window_out's and window_grad's).
# dense_out / dense_grad, of the leading 8192-wide SwiGLU: the program 0.00543 /
# 0.00442-0.00449; float8 0.0581 / 0.0581.
# moe_out, of what the held experts and the shared expert add, over the clear
# tokens (6.8 % of a layer's at the least: sigmoid scores of 0.5 to two digits,
# a near-tie is the rule at the seeded start): the program 0.00548-0.00549;
# float8 0.0584, one held expert fewer 0.0712, a buffer of 0.8 of the expected
# rows (4,788 assignments dropped) 0.121, gates not times 2.5 0.141, gates not
# renormalised 0.74, no shared expert 5.3.
# shared_out, of the shared expert alone: the program 0.00546; float8 0.0582;
# no shared expert in the reference: no finite reading.
# expert_grad, of the gradient by moe_wgu, moe_wd, shared_wgu, shared_wd,
# ln2_scale: the program 0.00599; float8 0.0609, one held expert fewer 0.34.
# held_rows: the rows the bounded buffer held against the reference's own
# count, over the expected rows: the program 0.0015-0.0021 (near-ties that fall
# the other way in bfloat16: a dozen of 8,192 rows); one held expert fewer
# 0.1155, the short buffer 0.297; float8 0.0044 is under it and refused by
# thirteen others.
# per_position, in the units of ``correct.py``: the program 0.00235-0.00237;
# float8 0.0333.
# loss, |program - reference| / reference of the training loss through the
# whole forward, every layer live: the program 1e-5 to 2.6e-5; the harness's
# accepted 2e-4 leaves the first reading seven times of room and refuses lanes
# paired across the head (4.8e-4), the sliding theta on the full layers
# (6.3e-4), all 128 lanes rotated (3.3e-4) and no shared expert (5.6e-4); float8
# (5.6e-5) does not move a loss that starts at ln 12544, and thirteen other
# limits refuse it.
TOLERANCE = {
    "window_out": 0.0094, "global_out": 0.0149, "global_dense_out": 0.0235,
    "window_first": 0.0145, "global_first": 0.0153, "global_dense_first": 0.0228,
    "window_grad": 0.0185, "global_grad": 0.0249, "global_dense_grad": 0.0254, "first_grad": 0.0288,
    "dense_out": 0.0178, "dense_grad": 0.0161, "moe_out": 0.0179, "shared_out": 0.0178,
    "expert_grad": 0.0191, "held_rows": 0.0156, "per_position": 0.0089, "loss": 2e-4,
}


def label(shape, layer):
    """``window`` | ``global_dense`` | ``global``: which of the three the
    check keeps apart layer ``layer`` is."""
    kind = shape["kinds"][layer]
    return f"{kind}_dense" if layer < shape["dense_layers"] else kind


class Reference:
    """The reference's sides of the comparison over the batch's sequences,
    under ``jax.default_matmul_precision("highest")``, each compiled once:
    attention a (kind of table and heads, kind of mask), an MLP a sort
    (leading dense, routed, the shared expert alone), the head."""

    def __init__(self, m):
        self.m = m

        def highest(f):
            def call(*a):
                with jax.default_matmul_precision("highest"):
                    return f(*a)
            return jax.jit(call)

        f32 = lambda w: jax.tree.map(lambda t: t.astype(jnp.float32), w)
        self._highest, self._f32 = highest, f32

        def head(params, x, batch):
            def mean_loss(x):
                losses = jax.lax.map(
                    lambda one: reference_laguna.head_losses(m, params, *one), (x, batch))
                return jnp.mean(losses), losses
            return jax.value_and_grad(mean_loss, has_aux=True)(x)

        none_held = {**m, "held": (m["held"][0], 0)}
        self.embed = highest(lambda params, batch: jax.lax.map(
            lambda tokens: reference_laguna.embed(m, params, tokens), batch))
        self.dense = self.both_ways(lambda x, w: reference_laguna.dense_sublayer(m, x, w))
        self.routed = self.both_ways(
            lambda x, w: reference_laguna.routed_sublayer(m, x, w), has_aux=True)
        self.shared = self.both_ways(
            lambda x, w: reference_laguna.routed_sublayer(none_held, x, w)[0])
        # -> ((mean loss, losses), the mean loss's gradient by the last hidden state)
        self.head = highest(lambda params, x, batch: head(f32(params), x, batch))

    def both_ways(self, sublayer, has_aux=False):
        """(forward, backward) of ``sublayer(x, w)`` over the batch."""
        forward = lambda w, x: jax.vmap(lambda x: sublayer(x, w))(x)

        def backward(w, x, cotangent):
            _, pull_back, *_ = jax.vjp(forward, self._f32(w), x, has_aux=has_aux)
            return pull_back(cotangent)

        return self._highest(lambda w, x: forward(self._f32(w), x)), self._highest(backward)

    def attention(self, layer):
        """(forward, backward) of layer ``layer``'s attention sublayer; layers
        of one kind (table, heads and mask) share their programs."""
        return self._attention_of(
            (self.m["kinds"][layer], self.m.get("mask_kinds", self.m["kinds"])[layer]))

    @functools.lru_cache(maxsize=8)
    def _attention_of(self, kinds):
        one = {**self.m, "kinds": kinds[:1], "mask_kinds": kinds[1:]}
        return self.both_ways(lambda x, w: reference_laguna.attention_sublayer(one, x, w, 0))


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
    routed_at = [i for i in range(shape["layers"]) if i >= shape["dense_layers"]]
    theirs = lambda i: reference_laguna.layer_weights(shape, weights, i)
    early = jnp.arange(batch.shape[1]) < FIRST_POSITIONS
    with jax.set_mesh(state.mesh):
        # the reference's forward pass, every sublayer's input kept
        x = reference.embed(weights, batch)
        inputs, counts = [], []
        for i in range(shape["layers"]):
            h = reference.attention(i)[0](theirs(i), x)
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
            kind, name = shape["kinds"][i], label(shape, i)
            layer = programs[kind]
            without_attention = _zeroed(own, "wo")
            if i in routed_at:  # h -> h + the held experts' and the shared expert's part
                of_clear = cotangent * clear[..., None]
                got_add, report, got_dw = layer(without_attention, h, of_clear)
                want_dw, _ = reference.routed[1](w, h, of_clear)
                numbers.see("moe_out_err", _distance(got_add, y - h, clear))
                for k in ROUTED_LEAVES:
                    numbers.see("expert_grad_err", _distance(got_dw[k], want_dw[k]), k)
                rows.append(report)
                got_add, _, _ = layer(_zeroed(own, "wo", "moe_wd"), h, cotangent)
                numbers.see("shared_out_err", _distance(got_add, reference.shared[0](w, h) - h))
                cotangent = reference.routed[1](w, h, cotangent)[1]
                numbers["clear_tokens_share_min"] = min(
                    numbers.get("clear_tokens_share_min", 1.0), float(jnp.mean(clear)))
            else:  # the leading dense layer
                got_add, _, got_dw = layer(without_attention, h, cotangent)
                want_dw, cotangent_in = reference.dense[1](w, h, cotangent)
                numbers.see("dense_out_err", _distance(got_add, y - h))
                for k in DENSE_LEAVES:
                    numbers.see("dense_grad_err", _distance(got_dw[k], want_dw[k]), f"dense.{k}")
                cotangent = cotangent_in
            # attention: x -> x + attention(x), at the input scaled down to what it adds
            forward, backward = reference.attention(i)
            small = min(1.0, float(jnp.linalg.norm(h - x) / jnp.linalg.norm(x)))
            xs, without_mlp = x * small, _zeroed(own, *LAST_PROJECTIONS)
            want_add = forward(w, xs) - xs
            got_add, _, got_dw = layer(without_mlp, xs, cotangent)
            want_dw, through = backward(w, xs, cotangent)
            numbers[f"{name}_out_err.layer{i}"] = float(_distance(got_add, want_add))
            numbers.see(f"{name}_out_err", numbers[f"{name}_out_err.layer{i}"])
            numbers.see(f"{name}_first_err", _distance(got_add, want_add, early[None, :]))
            for k in ATTENTION_LEAVES:
                numbers.see(f"{name}_grad_err", _distance(got_dw[k], want_dw[k]), f"{name}.{k}")
            of_early = cotangent * early[None, :, None]
            _, _, got_dw = layer(without_mlp, xs, of_early)
            want_dw, _ = backward(w, xs, of_early)
            for k in ATTENTION_LEAVES:
                numbers.see("first_grad_err", _distance(got_dw[k], want_dw[k]), f"first.{name}.{k}")
            # on to the layer below: the sublayer's Jacobian at x is ``small`` times its own at xs
            cotangent = cotangent + small * (through - cotangent)
            numbers["attention_input_scale_min"] = min(
                numbers.get("attention_input_scale_min", 1.0), small)
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
    """The program's trace-time counters this cell's readers take: what each
    kind's kernels visit at the tiles taken (``tinygpt.attn_mask_stats``) and
    which layers took the rotary pass (``tinygpt.qk_prologue_stats``)."""
    from distributed_llm_training_benchmark_framework_tpu.models import tinygpt

    return {"attn_mask_stats": tinygpt.attn_mask_stats(model_config, workload["seq_len"]),
            "qk_prologue_stats": tinygpt.qk_prologue_stats(model_config, workload["seq_len"])}
