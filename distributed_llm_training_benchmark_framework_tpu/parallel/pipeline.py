"""Pipeline parallelism — GPipe microbatch schedule over a 'pipe' mesh axis.

Absent from the reference (SURVEY §2.3: PP is future-work prose in its README
only). TPU-native design: the stacked layer weights are sharded on their
leading 'layers' axis across the 'pipe' mesh axis (L/P contiguous layers per
stage), and activations flow stage-to-stage via ``ppermute`` on neighbor ICI
links. The schedule is the classic GPipe fill-drain: with M microbatches and
P stages, T = M + P - 1 ticks; at tick t stage s runs microbatch t - s.

Implementation notes:
- runs inside ``jax.shard_map`` manual ONLY over 'pipe' (``axis_names``):
  the 'data'/'model' axes stay auto, so data-parallel batch sharding and
  Megatron tensor parallelism compose with the pipeline for free;
- embeddings, final LN and the tied LM head are replicated across stages;
  every stage computes the (cheap) embed/head for schedule uniformity and a
  predicate selects the real producer — the fill/drain bubble, not this, is
  the dominant overhead;
- the whole schedule is differentiable (``ppermute`` transposes to the
  reverse permutation), so one ``jax.value_and_grad`` around the pipelined
  loss drives the backward schedule automatically;
- microbatches double as gradient accumulation: the step's (accum, batch,
  seq) input feeds the pipeline as its M microbatches.

Constraint: n_layer % pipe == 0. Sequence parallelism composes: with a >1
'seq' mesh axis the schedules go manual over ('pipe', 'seq') and attention
runs the sharded ring/Ulysses bodies inside each stage (see ``_seq_setup``).
MoE composes too — per-stage aux-loss accounting masks fill/drain ticks and
psums stage contributions.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from ..models import tinygpt
from ..utils.vma import pcast_missing

AXIS = "pipe"


def _key_data_or_none(base_key):
    """Raw uint32 key data for a typed PRNG key (None passes through).

    Typed key arrays must not cross the ``shard_map`` boundary here — the
    partial-auto lowering can build the boundary sharding
    from the rank-0 key aval but validate it against the rank-1 physical
    u32 key data, which XLA rejects ("Number of tile assignment dimensions
    ... is different than the input rank", the seed-old interleaved compile
    failure). Raw key data is an ordinary u32 array whose rank the boundary
    always handles; the body rebuilds the key with :func:`_rebuild_key`.

    Seen on jax 0.4.37. On jax 0.9.0 a typed key crosses and every pipeline
    arm compiles (PR 43's audit, which is why no injection reverts this any
    more); the crossing stays while ``pyproject.toml`` admits a jax (0.8)
    nobody has compiled the schedules on.
    """
    return None if base_key is None else jax.random.key_data(base_key)


def _rebuild_key(key_data):
    """The body-side half of the key boundary crossing (see above)."""
    if key_data is None:
        return None
    return jax.random.wrap_key_data(key_data)


def _stage_iota(n_stages: int) -> jax.Array:
    """Per-stage index fed through the shard_map as a P('pipe') operand.

    ``lax.axis_index`` inside a PARTIALLY-manual region lowers to a bare
    partition-id instruction that XLA's SPMD partitioner refuses whenever a
    real auto axis exists ("PartitionId instruction is not supported for
    SPMD partitioning"), which broke pipeline x dp>1 compositions. A
    sharded iota derives the same value from data: each stage's local shard
    of arange(P) is exactly its stage index.
    """
    return jnp.arange(n_stages, dtype=jnp.int32)


def _seq_setup(config: tinygpt.TinyGPTConfig, mesh: Mesh):
    """Manual-axes composition for a pipeline schedule's shard_map.

    Sequence parallel: a >1 'seq' mesh axis goes manual beside 'pipe' —
    activations hold local sequence chunks, attention runs the sharded
    ring/Ulysses bodies communicating over 'seq' (see
    tinygpt.TinyGPTConfig.seq_manual_axis), and losses/aux psum over 'seq'.
    'data' (and 'model'/'expert') stay auto: GSPMD owns their reductions.

    Returns (config, seq_axis_or_None, sp, manual_axes, batch_in_spec).
    """
    sp = mesh.shape.get("seq", 1)
    if sp <= 1:
        return config, None, sp, frozenset({AXIS}), P()
    config = dataclasses.replace(config, seq_manual_axis="seq")
    return config, "seq", sp, frozenset({AXIS, "seq"}), P(None, None, "seq")


def embed(config: tinygpt.TinyGPTConfig, mesh: Mesh, params, idx, dropout_key,
          deterministic: bool) -> jax.Array:
    """``tinygpt.embed`` of one microbatch inside a schedule's manual region,
    with the lookup manual over 'data' as well.

    The lookup's backward is a scatter-add of a data-sharded cotangent into a
    table every data replica holds. Left to the partitioner inside the partly
    manual region, the TPU compiler all-gathers the indices and the updates
    over 'data' (traffic and buffers that grow with the data degree: the
    topology audit's growth laws refuse it). Manual over 'data', each replica
    scatters its own rows and the transpose of the replicated table's
    broadcast is one psum over 'data': what data parallelism pays for any
    other gradient. Dropout stays outside, on the whole microbatch, so the
    mask is the one ``tinygpt.embed`` draws. A sequence-manual schedule keeps
    the plain lookup: ``tinygpt.embed`` asks ``lax.axis_index`` for its
    sequence shard, which does not lower inside a second manual region
    (jax 0.9.0: "axis 'seq' is already bound by a parent").
    """
    if mesh.shape.get("data", 1) == 1 or config.seq_manual_axis is not None:
        return tinygpt.embed(config, params, idx, dropout_key, deterministic)
    ep = {k: params[k] for k in tinygpt.embed_param_names(config)}
    x = jax.shard_map(
        lambda ep, idx: tinygpt.embed(config, ep, idx),
        in_specs=(P(), P("data")),
        out_specs=P("data"),
        axis_names={"data"},
    )(ep, idx)
    return tinygpt._dropout(x, config.dropout, dropout_key, deterministic)


def pipeline_param_specs(params, mesh: Mesh):
    """Manual-axis ('pipe'-only) specs: block stacks sharded on layers axis."""

    def spec(path, leaf):
        is_block = any(getattr(p, "key", None) == "blocks" for p in path)
        if is_block:
            return P(AXIS, *([None] * (leaf.ndim - 1)))
        return P(*([None] * leaf.ndim))

    return jax.tree_util.tree_map_with_path(spec, params)


def pipeline_loss_fn(
    config: tinygpt.TinyGPTConfig,
    mesh: Mesh,
    params,
    batch: jax.Array,  # (M, mb, S) microbatches; targets are the inputs
    base_key: Optional[jax.Array] = None,
    deterministic: bool = True,
) -> jax.Array:
    """Mean loss over M microbatches, computed on the GPipe schedule."""
    n_stages = mesh.shape[AXIS]
    if config.n_layer % n_stages != 0:
        raise ValueError(
            f"n_layer={config.n_layer} not divisible by pipe={n_stages}"
        )
    config, seq_ax, sp, manual_axes, batch_spec = _seq_setup(config, mesh)
    layers_per_stage = config.n_layer // n_stages
    n_micro = batch.shape[0]
    ticks = n_micro + n_stages - 1
    perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]
    key_data = _key_data_or_none(base_key)

    def staged(params, batch, stage_arr):
        stage = stage_arr[0]
        base_key = _rebuild_key(key_data)
        blocks = params["blocks"]  # local slice: (L/P, ...)
        mb, S = batch.shape[1], batch.shape[2]
        D = config.n_embd
        state = jnp.zeros((mb, S, D), config.compute_dtype)
        loss_sum = jnp.zeros((), jnp.float32)
        # MoE load-balance aux: each stage accumulates its own layers' aux for
        # the microbatches it actually processes (fill/drain ticks run on
        # dummy state for schedule uniformity — their aux is masked out).
        aux_sum = jnp.zeros((), jnp.float32)

        emb_key = (
            jax.random.fold_in(base_key, 1_000_003) if base_key is not None else None
        )
        offset = stage * layers_per_stage

        for t in range(ticks):
            # Stage 0 ingests a fresh microbatch while the schedule is filling;
            # downstream stages consume what the previous tick permuted in.
            if t < n_micro:
                ek = (
                    jax.random.fold_in(emb_key, t)
                    if emb_key is not None and not deterministic
                    else None
                )
                inject = embed(config, mesh, params, batch[t], ek, deterministic)
                state_in = jnp.where(stage == 0, inject, state)
            else:
                state_in = state
            bk = (
                jax.random.fold_in(base_key, t)
                if base_key is not None and not deterministic
                else None
            )
            state_out, aux_t = tinygpt.apply_blocks(
                config, blocks, state_in, bk, deterministic, layer_offset=offset
            )
            if config.n_experts > 0:
                if seq_ax is not None:
                    # Per-shard load-balance stats averaged across sequence
                    # shards (the standard local-aux formulation); also makes
                    # aux seq-invariant for the loss.
                    aux_t = lax.psum(aux_t, seq_ax) / sp
                fi = t - stage  # the microbatch this stage processed this tick
                aux_valid = (fi >= 0) & (fi < n_micro)
                aux_sum = aux_sum + jnp.where(aux_valid, aux_t, 0.0)

            # The last stage drains: at tick t it finishes microbatch
            # t - (P-1). The LM head is a (mb,S,D)x(V,D) einsum — layer-scale
            # compute — so on TPU a cond (legal per-device control flow inside
            # the manual region) skips it entirely on non-final stages. The
            # CPU backend compute-and-masks instead: XLA's CPU-only
            # AllReducePromotion pass aborts on the collectives the cond
            # lowering produces (same bug class as the pp x tp guard).
            li = t - (n_stages - 1)
            if 0 <= li < n_micro:
                if jax.default_backend() == "cpu":
                    logits = tinygpt.head(config, params, state_out)
                    l = tinygpt._cross_entropy(logits, batch[li], seq_axis=seq_ax)
                    loss_sum = loss_sum + jnp.where(stage == n_stages - 1, l, 0.0)
                else:
                    loss_sum = loss_sum + lax.cond(
                        stage == n_stages - 1,
                        lambda so=state_out, tgt=batch[li]: tinygpt._cross_entropy(
                            tinygpt.head(config, params, so), tgt, seq_axis=seq_ax
                        ),
                        # pcast marks the zero as device-varying over 'pipe'
                        # so both branches carry the same manual-axes type.
                        lambda: pcast_missing(
                            jnp.zeros((), jnp.float32), (AXIS,)
                        ),
                    )

            if t < ticks - 1:
                state = lax.ppermute(state_out, AXIS, perm)

        # Only the last stage accumulated loss; broadcast it to every stage.
        loss = lax.psum(loss_sum, AXIS) / n_micro
        if config.n_experts > 0:
            # Every (stage, microbatch) pair contributed its layers' aux once:
            # psum over stages = sum over all n_layer layers for all M
            # microbatches. Same normalization as tinygpt.forward
            # (coef * aux / n_layer), averaged over microbatches.
            loss = loss + config.router_aux_coef * lax.psum(
                aux_sum, AXIS
            ) / (config.n_layer * n_micro)
        return loss

    fn = jax.shard_map(
        staged,
        mesh=mesh,
        in_specs=(pipeline_param_specs(params, mesh), batch_spec, P(AXIS)),
        out_specs=P(),
        axis_names=manual_axes,
    )
    return fn(params, batch, _stage_iota(n_stages))


def pipeline_loss_and_grads_1f1b(
    config: tinygpt.TinyGPTConfig,
    mesh: Mesh,
    params,
    batch: jax.Array,  # (M, mb, S) microbatches; targets are the inputs
    base_key: Optional[jax.Array] = None,
    deterministic: bool = True,
):
    """1F1B-interleaved pipeline schedule with a hand-scheduled backward.

    Returns ``(loss, grads)`` directly — the backward is NOT generated by
    ``jax.grad`` over the forward schedule. That distinction is the point:
    autodiff of the GPipe loop above reverses the whole program, so every
    ppermute of the backward sits after every ppermute of the forward in
    program order and all M microbatches' residuals are live at the
    fwd/bwd boundary — O(M) activation memory per stage. Here each tick
    interleaves one forward with one backward (the Megatron-LM 1F1B idea,
    lockstep variant), so a microbatch's residual dies 2*(P-1-s) ticks after
    its forward: peak liveness is O(P) regardless of M, which is what lets
    long accumulation chains (M >> P) train without activation OOM.

    Schedule (P stages, M microbatches, T = M + 2(P-1) ticks): at tick t,
    stage s forwards microbatch ``t - s`` (exactly GPipe) and backwards
    microbatch ``t - 2(P-1) + s``. The last stage's backward of microbatch i
    starts the same tick its forward drains (its loss gradient is computed
    in place); gradients flow stage-to-stage over the reverse ppermute ring,
    one hop per tick, meeting each stage precisely 2(P-1-s) ticks after it
    forwarded that microbatch. Both the fill and drain bubbles are 2(P-1)
    ticks — the same fraction as GPipe; 1F1B's win is memory, not bubble
    (only *interleaved* virtual stages shrink the bubble).

    Residuals: instead of storing per-microbatch VJP closures (not SPMD-able —
    the tick a stage needs them at differs per stage), each stage keeps a
    rolling buffer of its last 2P-1 forward *inputs* and rematerializes the
    stage forward under ``jax.vjp`` at backward time (per-stage activation
    recompute, the standard Megatron configuration). Dropout keys are derived
    from the originating tick index, so the recompute replays the forward
    bit-for-bit.
    """
    n_stages = mesh.shape[AXIS]
    if config.n_layer % n_stages != 0:
        raise ValueError(
            f"n_layer={config.n_layer} not divisible by pipe={n_stages}"
        )
    config, seq_ax, sp, manual_axes, batch_spec = _seq_setup(config, mesh)
    layers_per_stage = config.n_layer // n_stages
    n_micro = batch.shape[0]
    ticks = n_micro + 2 * (n_stages - 1)
    depth = 2 * n_stages - 1  # rolling residual-buffer depth
    perm_fwd = [(i, (i + 1) % n_stages) for i in range(n_stages)]
    perm_bwd = [(i, (i - 1) % n_stages) for i in range(n_stages)]
    # The loss is the mean over microbatches; every hand-seeded cotangent
    # uses the same scale so the backward stays consistent with the
    # published loss.
    inv_m = 1.0 / n_micro
    key_data = _key_data_or_none(base_key)

    def staged(params, batch, stage_arr):
        stage = stage_arr[0]
        base_key = _rebuild_key(key_data)
        is_last = stage == n_stages - 1
        blocks = params["blocks"]  # local slice: (L/P, ...)
        mb, S = batch.shape[1], batch.shape[2]
        D = config.n_embd
        state = jnp.zeros((mb, S, D), config.compute_dtype)
        g_recv = jnp.zeros((mb, S, D), config.compute_dtype)
        buf = jnp.zeros((depth, mb, S, D), config.compute_dtype)
        loss_sum = jnp.zeros((), jnp.float32)

        d_blocks = jax.tree.map(jnp.zeros_like, blocks)
        hp = {k: params[k] for k in tinygpt.head_param_names(config)}
        ep = {k: params[k] for k in tinygpt.embed_param_names(config)}
        d_ep = jax.tree.map(jnp.zeros_like, ep)

        # Head strategy mirrors pipeline_loss_fn: on TPU a lax.cond skips the
        # layer-scale head fwd+vjp on non-final stages entirely; on CPU (where
        # XLA's AllReducePromotion pass aborts on cond-lowered collectives)
        # every stage computes it and dl=0 masks the cotangents. For the cond
        # path hp is pre-cast to 'varying' so the head vjp stays collective-
        # free inside the divergent branch (an invariant primal would make the
        # transpose insert a psum there — deadlock); the one psum that makes
        # d_hp invariant again runs after the tick loop.
        head_cond = jax.default_backend() != "cpu"
        if head_cond:
            hp_in = jax.tree.map(
                lambda x: pcast_missing(x, (AXIS,)), hp
            )
        else:
            hp_in = hp
        d_hp = jax.tree.map(jnp.zeros_like, hp_in)

        emb_key = (
            jax.random.fold_in(base_key, 1_000_003) if base_key is not None else None
        )
        offset = stage * layers_per_stage
        live_keys = base_key is not None and not deterministic

        # MoE: the load-balance aux is a second differentiable output of the
        # stage forward; its cotangent is the constant coef/(n_layer*n_micro)
        # (the aux term's weight in the final loss) whenever the backward
        # unit's microbatch is valid.
        moe = config.n_experts > 0
        aux_sum = jnp.zeros((), jnp.float32)
        aux_ct_const = (
            config.router_aux_coef / (config.n_layer * n_micro)
            if moe else 0.0
        )

        def stage_fwd(blk, x, key):
            y, aux = tinygpt.apply_blocks(
                config, blk, x, key, deterministic, layer_offset=offset
            )
            if moe and seq_ax is not None:
                # Shard-local aux averaged over sequence shards (seq-invariant
                # so the loss and its constant cotangent stay uniform).
                aux = lax.psum(aux, seq_ax) / sp
            return (y, aux) if moe else y

        for t in range(ticks):
            # ---- forward unit: stage s runs microbatch t - s (as GPipe) ----
            if t < n_micro:
                ek = jax.random.fold_in(emb_key, t) if live_keys else None
                inject = embed(config, mesh, params, batch[t], ek, deterministic)
                state_in = jnp.where(stage == 0, inject, state)
            else:
                state_in = state
            # Circular residual buffer: write slot t % depth (no O(depth)
            # shift-copy per tick).
            buf = lax.dynamic_update_index_in_dim(buf, state_in, t % depth, 0)
            if t < n_micro + n_stages - 1:  # fwd window; later ticks drain only
                bk = jax.random.fold_in(base_key, t) if live_keys else None
                out = stage_fwd(blocks, state_in, bk)
                if moe:
                    state_out, aux_t = out
                    fi = t - stage
                    aux_sum = aux_sum + jnp.where(
                        (fi >= 0) & (fi < n_micro), aux_t, 0.0
                    )
                else:
                    state_out = out
            else:
                state_out = state_in

            # ---- loss + its gradient, in place, on the last stage ----
            li = t - (n_stages - 1)
            d_x_head = jnp.zeros_like(state_out)
            if 0 <= li < n_micro:
                def head_loss(hp_arg, x):
                    return tinygpt._cross_entropy(
                        tinygpt.head(config, hp_arg, x), batch[li], seq_axis=seq_ax
                    )

                if head_cond:
                    def head_work(so=state_out, fn=head_loss):
                        l, vjp_head = jax.vjp(fn, hp_in, so)
                        dl = pcast_missing(
                            jnp.asarray(inv_m, jnp.float32), (AXIS,)
                        )
                        d_hp_t, d_xh = vjp_head(dl)
                        return l, d_hp_t, d_xh

                    def head_zero(so=state_out):
                        var = lambda z: pcast_missing(z, (AXIS,))
                        # The state cotangent is additionally seq-varying
                        # (it is a local sequence chunk's gradient).
                        var_x = lambda z: pcast_missing(
                            z, (AXIS,) + ((seq_ax,) if seq_ax else ())
                        )
                        return (
                            var(jnp.zeros((), jnp.float32)),
                            jax.tree.map(lambda x: var(jnp.zeros(x.shape, x.dtype)), hp),
                            var_x(jnp.zeros_like(so)),
                        )

                    l, d_hp_t, d_x_head = lax.cond(is_last, head_work, head_zero)
                    loss_sum = loss_sum + l
                else:
                    # compute-and-mask: dl = 0 on non-final stages zeroes both
                    # cotangents, so no cross-stage control flow is needed
                    l, vjp_head = jax.vjp(head_loss, hp_in, state_out)
                    loss_sum = loss_sum + jnp.where(is_last, l, 0.0)
                    dl = jnp.where(is_last, inv_m, 0.0)
                    d_hp_t, d_x_head = vjp_head(dl)
                d_hp = jax.tree.map(jnp.add, d_hp, d_hp_t)

            # ---- backward unit: stage s runs microbatch t - 2(P-1) + s ----
            if t >= n_stages - 1:  # before this no stage has backward work
                bi = t - 2 * (n_stages - 1) + stage
                vb = (bi >= 0) & (bi < n_micro)
                g_in = jnp.where(is_last, d_x_head.astype(g_recv.dtype), g_recv)
                g_in = jnp.where(vb, g_in, jnp.zeros((), g_in.dtype))
                # Residual: this stage forwarded microbatch bi at tick
                # t - 2(P-1) + 2s, i.e. 2(P-1-s) writes ago.
                k_back = jnp.clip(2 * (n_stages - 1) - 2 * stage, 0, depth - 1)
                x_saved = lax.dynamic_index_in_dim(
                    buf, jnp.mod(t - k_back, depth), 0, keepdims=False
                )
                bk_orig = (
                    jax.random.fold_in(base_key, t - 2 * (n_stages - 1) + 2 * stage)
                    if live_keys else None
                )
                _, vjp_blk = jax.vjp(
                    lambda blk, x: stage_fwd(blk, x, bk_orig), blocks, x_saved
                )
                if moe:
                    aux_ct = jnp.where(vb, aux_ct_const, 0.0).astype(jnp.float32)
                    d_blk_t, d_x = vjp_blk((g_in, aux_ct))
                else:
                    d_blk_t, d_x = vjp_blk(g_in)
                d_blocks = jax.tree.map(jnp.add, d_blocks, d_blk_t)

                # Stage 0's input cotangent belongs to the embedding. Its
                # backward microbatch index is static (bi at s=0), so the
                # embed recompute uses a static batch row.
                bi0 = t - 2 * (n_stages - 1)
                if 0 <= bi0 < n_micro:
                    ek0 = jax.random.fold_in(emb_key, bi0) if live_keys else None
                    # pcast marks the (stage-invariant) embed output as
                    # varying over 'pipe' so it accepts the varying cotangent;
                    # pcast's transpose is a psum, so d_ep_t comes back
                    # already reduced across stages (invariant) — the final
                    # grads need no further psum for wte/wpe. The pipe-psum
                    # transpose commutes with the wpe scatter (offsets are
                    # pipe-uniform) and 'seq' is handled implicitly.
                    _, vjp_emb = jax.vjp(
                        lambda ep: pcast_missing(
                            embed(config, mesh, ep, batch[bi0], ek0, deterministic),
                            (AXIS,),
                        ),
                        ep,
                    )
                    (d_ep_t,) = vjp_emb(
                        jnp.where(stage == 0, d_x, jnp.zeros((), d_x.dtype))
                    )
                    d_ep = jax.tree.map(jnp.add, d_ep, d_ep_t)

                if t < ticks - 1:
                    g_recv = lax.ppermute(d_x, AXIS, perm_bwd)

            if t < n_micro + n_stages - 2:
                state = lax.ppermute(state_out, AXIS, perm_fwd)

        loss = lax.psum(loss_sum, AXIS) * inv_m
        if moe:
            # Same accounting as the GPipe schedule: psum over stages covers
            # all n_layer layers once per microbatch.
            loss = loss + config.router_aux_coef * lax.psum(
                aux_sum, AXIS
            ) / (config.n_layer * n_micro)
        if head_cond:
            # cond path kept d_hp varying (nonzero on the last stage only);
            # one psum re-replicates it.
            d_hp = jax.tree.map(lambda x: lax.psum(x, AXIS), d_hp)
        # Otherwise d_hp is already pipe-invariant: the vjp of using an
        # invariant primal (hp) in a varying computation transposes the
        # implicit broadcast into a psum. d_ep likewise came back invariant
        # through the embed's explicit pcast — no further reduction, it
        # would double-count. Block grads are per-stage (out_spec
        # P('pipe', ...)); their 'seq' sum happens inside the vjp.
        grads = {"blocks": d_blocks}
        for _dtree in (d_hp, d_ep):  # wte appears in both when tied: sum
            for _k, _v in _dtree.items():
                grads[_k] = grads[_k] + _v if _k in grads else _v
        return loss, grads

    specs = pipeline_param_specs(params, mesh)
    fn = jax.shard_map(
        staged,
        mesh=mesh,
        in_specs=(specs, batch_spec, P(AXIS)),
        out_specs=(P(), specs),
        axis_names=manual_axes,
    )
    return fn(params, batch, _stage_iota(n_stages))
