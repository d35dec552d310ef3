"""The unified train step — one compiled function serves all strategy arms.

Where the reference maintains two divergent hot loops (a DeepSpeed engine path
and an AMP/GradScaler path, reference ``benchmarking/train_harness.py:364-382``),
here there is exactly one train step:

    value_and_grad(loss) -> [sharding constraint] -> optax update -> apply

jitted with per-strategy ``in_shardings``/``out_shardings``. The strategy's
PartitionSpecs (see ``parallel.strategies``) tell XLA where the collectives
go; donation of params + optimizer state makes the update in-place in HBM.

Gradient accumulation is *real* (a ``lax.scan`` over microbatches with fp32
accumulators) — the reference accepts ``--grad-accum`` but silently ignores it
for DDP/FSDP (reference ``train_harness.py:369-382``, SURVEY §2.1 C8).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp
import optax
from jax import lax
from jax.experimental.layout import Layout, with_layout_constraint
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..models import tinygpt
from ..parallel import strategies as strat
from ..utils import scopes

Params = Any


@dataclasses.dataclass
class TrainState:
    """Everything the benchmark loop needs, pre-placed on the mesh."""

    params: Params
    opt_state: Any
    # (params, opt_state, batch, step) -> (params, opt_state, loss)
    # — plus a trailing global grad-norm scalar when built with
    # make_train_step(sentinel=True) (the numerics sentinel's guard).
    step_fn: Callable
    # (params, opt_state, batch, step) -> jax.stages.Compiled for the step —
    # cache hit after the first execution; feeds measure_peak_hbm rung 2.
    aot_compile: Callable
    mesh: Mesh
    param_specs: Params
    opt_specs: Any
    batch_sharding: NamedSharding
    model_config: tinygpt.TinyGPTConfig
    strategy: strat.StrategyConfig
    n_params: int


def _resolve_model_config(
    model_config: tinygpt.TinyGPTConfig,
    strategy: strat.StrategyConfig,
    mesh: Optional[Mesh] = None,
) -> tinygpt.TinyGPTConfig:
    """Fold strategy-level knobs (remat, precision) into the model config.

    CPU + pipeline special case: XLA's CPU-only AllReducePromotion pass
    crashes ("Invalid binary instruction opcode copy") on the bf16
    all-reduces GSPMD emits around the partially-manual pipeline shard_map.
    TPU reduces bf16 natively and is unaffected; on CPU (tests, smoke) the
    pipelined arms run fp32 instead.
    """
    import jax as _jax

    compute_dtype = jnp.bfloat16 if strategy.precision == "bf16" else jnp.float32
    if (
        mesh is not None
        and mesh.shape.get("pipe", 1) > 1
        and _jax.default_backend() == "cpu"
    ):
        compute_dtype = jnp.float32
    # "auto" is resolved against the memory model by the benchmark loop
    # (utils.memory.resolve_auto_remat); a direct create_train_state caller
    # that skips that step gets the conservative policy.
    remat = "full" if strategy.remat == "auto" else strategy.remat
    if mesh is not None and mesh.shape.get("pipe", 1) > 1:
        model_config.refuse_pipeline()
    # bf16 parameter storage halves params+grads+Adam state — the knob that
    # fits tier B on one chip (see StrategyConfig.param_dtype).
    param_dtype = (
        jnp.bfloat16
        if getattr(strategy, "param_dtype", "f32") == "bf16"
        else jnp.float32
    )
    return dataclasses.replace(
        model_config, remat=remat, compute_dtype=compute_dtype,
        param_dtype=param_dtype,
    )


def _per_block_slice_specs(stacked_specs: Params):
    """(leaf name, layer-slice PartitionSpec) pairs for one block table.

    Shared by the zero2 grad rule and the fsdp/zero3 param rule: dropping
    the leading entry of each stacked spec is exactly the layer-slice
    layout (the stack axis disappears). Leaves whose shard landed on the
    stacked LAYERS axis (spec[0] non-None — the chooser's fallback when no
    in-layer axis divides) are skipped: their per-layer slice is genuinely
    replicated, and pinning it mid-loop would add a per-layer round-trip
    instead of hiding one. Returns None when nothing is armable.
    """
    # A leading dense stack holds leaves of the same names and shapes as
    # 'blocks' (same slice spec) plus its own MLP's: one table serves both. So
    # it does the KDA layers' stacks; a leaf two stacks place differently (one
    # name at two widths) is left out, as a leaf on the layers axis is.
    stacks = {}
    for stack in sorted(k for k in stacked_specs if k.endswith("blocks")):
        for name, spec in stacked_specs[stack].items():
            stacks.setdefault(name, set()).add(tuple(spec))
    per_block = tuple(sorted(
        (name, P(*spec[1:]))
        for name, (spec, *others) in stacks.items()
        if spec[0] is None and not others
    ))
    return per_block or None


def fsdp_block_param_spec(
    strategy: strat.StrategyConfig,
    param_specs: Params,
    pipelined: bool,
):
    """The per-layer-slice PARAM placement for the fsdp/zero3 forward-overlap
    path — the forward-side dual of :func:`zero2_block_grad_spec`.

    Handing the model this spec table (``TinyGPTConfig.block_param_spec``)
    pins each block's weight slice to its sharded placement INSIDE the
    forward layer loop (``tinygpt._constrain_layer_params``), so the weight
    all-gather each block's matmuls need issues per block right before those
    dots — instead of being free to bundle ahead of the whole layer stack,
    where nothing anchors it and the scheduler serializes it against the
    first layer. That per-block anchoring is what XLA's latency-hiding
    scheduler needs to overlap block i+1's gather with block i's compute
    (FSDP's prefetch-one-block schedule, GSPMD-native). The constraint
    transposes onto the cotangent, which for fsdp/zero3 is exactly the
    per-block grad placement — both halves of the frontier from one wrap.

    None for every other shape: ddp/zero2 params are replicated (nothing to
    gather), and pipeline schedules run inside a partially-manual shard_map
    where GSPMD constraints don't apply. Leaves whose shard landed on the
    stacked LAYERS axis (spec[0] non-None — the chooser's fallback when no
    in-layer axis divides) are skipped: their per-layer slice is genuinely
    replicated, and pinning it would add a per-layer round-trip. Composed
    dp x tp meshes arm too — the slice spec keeps both axes.
    """
    if not (strategy.shard_params and not pipelined):
        return None
    return _per_block_slice_specs(param_specs)


def scan_carry_spec(
    strategy: strat.StrategyConfig,
    mesh: Mesh,
    cfg: tinygpt.TinyGPTConfig,
    pipelined: bool,
):
    """The residual-stream placement pinned through the layer scan, or None.

    Armed exactly for SHARDED-PARAM (fsdp/zero3), scanned, non-pipelined
    arms on composed dp x tp meshes: there XLA otherwise picks its own
    layout for the scan's stacked activation stash — measured on
    llama-fsdp-dp4-tp2-scan as a batch-replicated,
    embed-sharded-over-'data' stash whose backward reconciles against the
    batch-sharded compute layout with collective-permute chains (the
    banked reshard residue). Pinning the (B, S, D) carry to the batch
    layout at the body boundary pins the stash with it (together with the
    _COMPOSED_CONTRACTION_DATA_SKIP spec rule: suspects 4 -> 0).
    Replicated-param strategies cannot exhibit the pathology (no weight
    leaf data-shards its contraction axis), so ddp/zero2 composed arms —
    e.g. the llama-tp2-gqa topology clients — keep their frozen lowerings
    byte-unchanged; so do pure-dp and single-axis meshes. The
    collective-matmul path owns its own residual layout (sequence-sharded
    over 'model') and is skipped.
    """
    if not strategy.shard_params:
        return None
    if not cfg.scan_layers or pipelined or cfg.tp_collective_matmul:
        return None
    if mesh.shape.get("data", 1) <= 1 or mesh.shape.get("model", 1) <= 1:
        return None
    batch = list(strat.batch_partition_spec(mesh))
    while len(batch) < 2:
        batch.append(None)
    return P(batch[0], batch[1], None)


def _axes(entry) -> tuple:
    """The mesh axes one PartitionSpec entry names (None, a name or a tuple)."""
    return (entry,) if isinstance(entry, str) else tuple(entry or ())


def mlp_hidden_spec(
    strategy: strat.StrategyConfig,
    mesh: Mesh,
    cfg: tinygpt.TinyGPTConfig,
    param_specs: Params,
    pipelined: bool,
):
    """P(batch, seq, hidden) for the MLP's F-wide intermediates, or None.

    Armed as ``block_param_spec`` is (sharded-param strategy, not pipelined)
    and only where the strategy's own table shards the first projection's
    weight (``wgu`` / ``wfc``) over 'data' along F, the largest-axis rule's
    choice on every pure-dp mesh: the hidden dim then takes that placement
    and the batch dims give the axis up, so each chip multiplies the F/n
    slice of ``wgu`` and ``wproj`` it already holds and only (B, S, D)
    activations travel. ddp / zero2 (replicated parameters), one-chip
    meshes, the pipeline schedules, the MoE branch (no ``wgu`` leaf),
    composed dp x tp meshes (F is 'model''s there and 'data' takes D) and
    the collective-matmul path (owns its layout) trace the same program as
    without the field.
    """
    if not strategy.shard_params or pipelined or cfg.tp_collective_matmul:
        return None
    spec = param_specs.get("blocks", {}).get("wgu" if cfg.mlp_act == "swiglu" else "wfc")
    hidden = None if spec is None else list(spec)[-1]
    if "data" not in _axes(hidden):
        return None
    batch = list(strat.batch_partition_spec(mesh)) + [None, None]
    free = tuple(ax for ax in _axes(batch[0]) if ax not in _axes(hidden))
    return P(free or None, batch[1], hidden)


def zero2_block_grad_spec(
    strategy: strat.StrategyConfig,
    grad_sharded_specs: Params,
    pipelined: bool,
):
    """The per-layer-slice grad placement for the zero2 overlap path.

    ZeRO-2 overlap (round 8): handing the model this spec table
    (``TinyGPTConfig.block_grad_spec``) makes each block's gradient adopt
    its reduce-scattered placement INSIDE the backward layer loop
    (``tinygpt._with_cotangent_spec``) instead of in the tail bundle —
    the structure XLA's latency-hiding scheduler needs to overlap grad
    comms with the next layer's backward compute. Dropping the leading
    entry of each stacked spec is exactly the layer-slice layout (the
    stack axis disappears).

    None for every other shape: fsdp/zero3 grads already equal the param
    layout (the tail constraint pins them), ddp has nothing to scatter,
    and pipeline schedules run their loss inside a partially-manual
    shard_map where GSPMD constraints don't apply. Leaves whose shard
    landed on the stacked LAYERS axis (spec[0] non-None — the chooser's
    fallback when no in-layer axis divides) are skipped: their per-layer
    slice is genuinely replicated, and pinning it mid-backward would add
    a gather/scatter round-trip per layer instead of hiding one; the
    tail constraint still places them.
    """
    if not (strategy.shard_grads and not strategy.shard_params
            and not pipelined):
        return None
    return _per_block_slice_specs(grad_sharded_specs)


def pipeline_schedule_meta(
    mesh: Mesh,
    grad_accum: int,
    pipeline_schedule: str = "gpipe",
    virtual_stages: int = 2,
) -> Optional[dict]:
    """The (schedule, stages, microbatches, virtual) the compiled step's
    pipeline actually runs, or None when the mesh has no >1 'pipe' axis.

    Single source of truth for the schedule auditor's closed-form laws:
    the microbatch count M IS ``grad_accum`` (the step feeds its whole
    accumulation axis to the schedule — the pipeline is the gradient
    accumulation), S is the 'pipe' mesh degree, and only the interleaved
    schedule has V > 1 virtual chunks. Deriving these anywhere else risks
    the laws drifting from what ``make_train_step`` compiles.
    """
    if mesh.shape.get("pipe", 1) <= 1:
        return None
    if pipeline_schedule not in ("gpipe", "1f1b", "interleaved"):
        raise ValueError(
            f"unknown pipeline schedule {pipeline_schedule!r} "
            "(expected 'gpipe', '1f1b' or 'interleaved')"
        )
    return {
        "schedule": pipeline_schedule,
        "stages": int(mesh.shape["pipe"]),
        "microbatches": int(grad_accum),
        "virtual": (
            int(virtual_stages) if pipeline_schedule == "interleaved" else 1
        ),
    }


def global_norm_f32(tree) -> jax.Array:
    """Global L2 norm of a pytree, accumulated in f32.

    The numerics sentinel's on-device guard primitive: for sharded trees
    the per-shard partial sums reduce through the mesh automatically (the
    scalar output is replicated), so the value is the GLOBAL norm on
    every strategy arm. f32 accumulation keeps ordinary magnitudes exact
    while a genuinely exploded tree still overflows to inf — which is a
    trip, not a rounding problem.
    """
    leaves = [l for l in jax.tree_util.tree_leaves(tree)
              if hasattr(l, "dtype")]
    if not leaves:
        return jnp.zeros((), jnp.float32)
    return jnp.sqrt(
        sum(jnp.sum(jnp.square(l.astype(jnp.float32))) for l in leaves)
    )


def make_param_norm_fn(mesh: Mesh) -> Callable:
    """Jitted parameter-tree checksum (global L2 norm) for the sentinel.

    One replicated f32 scalar per call; the loop invokes it only at
    sync-window boundaries every ``--sentinel-checksum-every`` steps
    (params are read-only here — a diagnostic reduction, not an update).
    """
    jitted = jax.jit(
        global_norm_f32,
        out_shardings=NamedSharding(mesh, P()),
    )

    def checksum(params):
        with jax.set_mesh(mesh):
            return jitted(params)

    return checksum


def _in_the_layouts_the_state_lives_in(grads, shardings):
    """``grads`` with every matrix and stack of matrices held to the device
    layout its parameter and moments live in: the default one for its shard's
    shape, as the device's client gives it.

    Left to itself the compiler runs AdamW in the layout the gradient's matmul
    writes, and where the state lives in another one it copies the new
    parameter and both moments back through a relayout at the step's boundary,
    every step: ``wgu`` (L, D, 2, F) lives in tiles of two rows over (2, F)
    and its gradient comes out in tiles over (D, F). Pinned here, the update
    runs in the layout the state lives in, and only the gradient crosses.
    Where the two agree already the compiled step is the one it was. A layout
    is not a value: the program's arithmetic is unchanged, and the compiler's
    agrees to rounding (it fuses on either side of a pin).
    """
    def pin(grad, sharding):
        if grad.ndim < 2:
            return grad
        device = sharding.mesh.devices.flat[0]
        default = device.client.get_default_layout(
            grad.dtype, sharding.shard_shape(grad.shape), device)
        return with_layout_constraint(grad, Layout.from_pjrt_layout(default))

    return jax.tree.map(pin, grads, shardings)


def _bytes_limit(mesh: Mesh) -> Optional[int]:
    """The smallest ``bytes_limit`` the allocators of this process's devices of
    ``mesh`` report; None where one of them keeps no statistics (the CPU) or
    is described and not attached (a compile for a chip that is not there)."""
    try:
        limits = [(d.memory_stats() or {}).get("bytes_limit") for d in mesh.local_devices]
    except jax.errors.JaxRuntimeError:
        return None
    return None if not limits or None in limits else min(limits)


def make_train_step(
    model_config: tinygpt.TinyGPTConfig,
    strategy: strat.StrategyConfig,
    optimizer: optax.GradientTransformation,
    mesh: Mesh,
    param_specs: Params,
    opt_specs: Any,
    grad_accum: int = 1,
    seed: int = 0,
    deterministic_dropout: bool = False,
    from_table: bool = False,
    global_micro: int = 1,
    seq_len: int = 0,
    pipeline_schedule: str = "gpipe",
    virtual_stages: int = 2,
    sentinel: bool = False,
) -> Callable:
    """Build the jitted train step for one strategy arm.

    batch layout: (grad_accum, global_microbatch, seq_len) int32; targets are
    the inputs themselves (parity: reference ``train_harness.py:359``).

    ``from_table=True`` switches the third argument from a per-step batch to
    the whole device-resident dataset table (size, seq_len); the step's batch
    rows are gathered *inside* the jitted step from the step index. This
    removes every per-step host->device transfer from the hot loop — the
    TPU-native answer to the reference's DataLoader (whose synthetic tensor
    also lives device-side after first touch). Requires ``global_micro`` and
    ``seq_len`` for the gather geometry.

    ``sentinel=True`` (numerics-sentinel round) makes the step return a
    FOURTH output: the global grad-norm (f32, replicated — see
    :func:`global_norm_f32`), computed inside the jitted step so the
    sentinel's explosion guard costs one fused reduction instead of a
    second device round-trip. Off by default: only sentinel-armed runs
    compile the extra all-reduce.
    """
    cfg = _resolve_model_config(model_config, strategy, mesh)
    params_shape = jax.eval_shape(functools.partial(tinygpt.init_params, cfg), jax.random.key(0))
    grad_sharded_specs = strat.param_partition_specs(
        params_shape,
        mesh,
        shard=True,
        kv_heads=cfg.kv_heads,
        scan_stacked=cfg.scan_layers,
    )
    batch_spec = strat.batch_partition_spec(mesh)
    # (accum, batch, seq): shard the *batch* dim, accum dim is sequential.
    full_batch_spec = P(None, *batch_spec)

    # What the config reports (``TinyGPTConfig.step_report``): the step
    # returns, after its loss, one float32 each, summed over layers and
    # micro-batches: the rows the held experts' buffers took and the held
    # assignments that did not fit (a bounded buffer), the masked tokens
    # (block diffusion, whose noise comes from ``key`` below: folded from
    # seed, step and micro-batch, as dropout's is); any other config traces
    # what it always did.
    reports = len(cfg.step_report)
    if reports and (sentinel or mesh.shape.get("pipe", 1) > 1):
        raise ValueError(
            f"a config that reports {cfg.step_report} does not compose with sentinel "
            "or pipe > 1")

    def loss_under(c, params: Params, micro: jax.Array, key: jax.Array) -> jax.Array:
        return (tinygpt.loss_and_report_fn if reports else tinygpt.loss_fn)(
            c,
            params,
            micro,
            micro,  # targets = inputs, unshifted (reference parity)
            dropout_key=key,
            deterministic=deterministic_dropout,
        )

    def micro_loss(params: Params, micro: jax.Array, key: jax.Array) -> jax.Array:
        return loss_under(cfg, params, micro, key)

    pipelined = mesh.shape.get("pipe", 1) > 1
    if pipelined:
        from ..parallel.interleaved import interleaved_loss_and_grads
        from ..parallel.pipeline import (
            pipeline_loss_and_grads_1f1b,
            pipeline_loss_fn,
        )

        if pipeline_schedule not in ("gpipe", "1f1b", "interleaved"):
            raise ValueError(
                f"unknown pipeline schedule {pipeline_schedule!r} "
                "(expected 'gpipe', '1f1b' or 'interleaved')"
            )

    block_spec = zero2_block_grad_spec(strategy, grad_sharded_specs, pipelined)
    if block_spec is not None:
        cfg = dataclasses.replace(cfg, block_grad_spec=block_spec)
    pblock_spec = fsdp_block_param_spec(strategy, param_specs, pipelined)
    if pblock_spec is not None:
        cfg = dataclasses.replace(cfg, block_param_spec=pblock_spec)
    carry_spec = scan_carry_spec(strategy, mesh, cfg, pipelined)
    if carry_spec is not None:
        cfg = dataclasses.replace(cfg, scan_carry_spec=carry_spec)
    hidden_spec = mlp_hidden_spec(strategy, mesh, cfg, param_specs, pipelined)
    if hidden_spec is not None:
        cfg = dataclasses.replace(cfg, mlp_hidden_spec=hidden_spec)

    def train_step(params, opt_state, batch, step):
        if from_table:
            # batch is the dataset table: gather this step's rows on-device.
            table = batch
            G = grad_accum * global_micro
            rows = (step * G + jnp.arange(G)) % table.shape[0]
            batch = jnp.take(table, rows, axis=0).reshape(
                grad_accum, global_micro, seq_len
            )
            batch = lax.with_sharding_constraint(
                batch, NamedSharding(mesh, full_batch_spec)
            )
        base_key = jax.random.fold_in(jax.random.key(seed), step)

        def one_micro(carry, inp):
            loss_acc, grad_acc = carry
            micro, key = inp
            loss, grads = jax.value_and_grad(micro_loss, has_aux=bool(reports))(params, micro, key)
            grad_acc = jax.tree.map(jnp.add, grad_acc, grads)
            return (jax.tree.map(jnp.add, loss_acc, loss), grad_acc), None

        if pipelined and pipeline_schedule == "interleaved":
            # Virtual stages (Megatron interleaved 1F1B): the bubble-shrinking
            # schedule — see parallel.interleaved. Requires params stacked in
            # layer_permutation order (create_train_state handles it).
            loss, grads = interleaved_loss_and_grads(
                cfg, mesh, params, batch, virtual=virtual_stages,
                base_key=None if deterministic_dropout else base_key,
                deterministic=deterministic_dropout,
            )
        elif pipelined and pipeline_schedule == "1f1b":
            # Hand-scheduled backward (O(P) residual liveness) — see
            # parallel.pipeline.pipeline_loss_and_grads_1f1b.
            loss, grads = pipeline_loss_and_grads_1f1b(
                cfg, mesh, params, batch,
                base_key=None if deterministic_dropout else base_key,
                deterministic=deterministic_dropout,
            )
        elif pipelined:
            # The microbatch axis feeds the GPipe schedule directly — the
            # pipeline IS the gradient accumulation.
            loss, grads = jax.value_and_grad(
                lambda p: pipeline_loss_fn(
                    cfg, mesh, p, batch,
                    base_key=None if deterministic_dropout else base_key,
                    deterministic=deterministic_dropout,
                )
            )(params)
        elif grad_accum == 1:
            key = jax.random.fold_in(base_key, 0)
            loss, grads = jax.value_and_grad(micro_loss, has_aux=bool(reports))(params, batch[0], key)
            if reports:
                loss, report = loss
        else:
            keys = jax.random.split(base_key, grad_accum)
            # Accumulator dtype follows the parameter dtype (cotangents
            # arrive in it anyway): fp32 for fp32 master weights — the
            # default, full-precision accumulation — and bf16 under
            # --param-dtype bf16, where fp32 accumulators alone would add a
            # params-sized 2x buffer and defeat the option's purpose (tier B
            # on one chip).
            zero_grads = jax.tree.map(
                lambda p: jnp.zeros(p.shape, p.dtype), params
            )
            zero = jnp.zeros((), jnp.float32)
            (loss_sum, grads), _ = lax.scan(
                one_micro,
                ((zero, jnp.zeros((reports,), jnp.float32)) if reports else zero, zero_grads),
                (batch, keys),
            )
            if reports:
                loss_sum, report = loss_sum
            loss = loss_sum / grad_accum
            grads = jax.tree.map(lambda g: g / grad_accum, grads)

        # Sentinel guard value: the global grad-norm, BEFORE any layout
        # constraint (the norm is layout-invariant; computing it here lets
        # XLA fuse the partial sums into the backward pass it just ran).
        with jax.named_scope(scopes.OPTIMIZER):
            gnorm = global_norm_f32(grads) if sentinel else None

        if strategy.shard_grads:
            # Pin the gradient layout for every sharded-grad strategy.
            # For zero2 this IS the semantics (reduce-scatter into the
            # optimizer shard; the per-BLOCK half is issued inside the
            # backward layer loop via cfg.block_grad_spec so each layer's
            # grad comms can overlap the next layer's backward compute).
            # For fsdp/zero3 the target equals the param layout and the
            # constraint looks redundant — but under the composed dp x tp
            # mesh it is load-bearing: without it GSPMD picks its own
            # layout for the stacked grad carry in the backward scan and
            # reconciles at the optimizer boundary with permute+all-to-all
            # chains (measured on llama-fsdp-dp4-tp2-scan: 12 -> 4
            # replication-reshard suspects from this line alone).
            grads = lax.with_sharding_constraint(grads, strat.named(mesh, grad_sharded_specs))

        grads = _in_the_layouts_the_state_lives_in(grads, strat.named(
            mesh, grad_sharded_specs if strategy.shard_grads else param_specs))
        with jax.named_scope(scopes.OPTIMIZER):
            updates, new_opt_state = optimizer.update(grads, opt_state, params)

            if strategy.shard_grads and not strategy.shard_params:
                # ZeRO-2: all-gather the (sharded) updates back onto replicated params.
                updates = lax.with_sharding_constraint(
                    updates, strat.named(mesh, param_specs)
                )

            new_params = optax.apply_updates(params, updates)
        if sentinel:
            return new_params, new_opt_state, loss, gnorm
        if reports:
            return new_params, new_opt_state, loss, report
        return new_params, new_opt_state, loss

    opt_shardings = strat.opt_state_shardings(mesh, opt_specs, strategy)
    scalar = NamedSharding(mesh, P())
    out_shardings = (
        strat.named(mesh, param_specs),
        opt_shardings,
        scalar,
    )
    if sentinel or reports:
        out_shardings = out_shardings + (scalar,)
    jitted = jax.jit(
        train_step,
        in_shardings=(
            strat.named(mesh, param_specs),
            opt_shardings,
            NamedSharding(mesh, P()) if from_table
            else NamedSharding(mesh, full_batch_spec),
            None,
        ),
        out_shardings=out_shardings,
        donate_argnums=(0, 1),
    )

    def step_with_mesh(params, opt_state, batch, step):
        # Trace/execute under the mesh context so mesh-aware ops (ring
        # attention's shard_map) can discover the axes via get_abstract_mesh.
        # The step's number goes into the record where the caller holds it
        # as a Python int; a device scalar is not fetched for it.
        args = {"step": step} if type(step) is int else {}
        with scopes.host_span(scopes.STEP_DISPATCH, **args), jax.set_mesh(mesh):
            return jitted(params, opt_state, batch, step)

    data_only = all(size == 1 for axis, size in mesh.shape.items() if axis != "data")

    @functools.lru_cache(maxsize=None)
    def saved_for_backward(micro_shape):
        """What one micro-batch's forward keeps for its backward on one chip:
        ``{"kept": [...], "all": [...], "left_out": {...}}``, entries
        ``(scope_path, name, shape, dtype, bytes)``, largest first. ``kept`` is
        under the step's own remat policy, ``all`` the same closure under
        ``remat="none"``; what is in ``all`` and not in ``kept`` is what the
        policy drops and remat runs again to have. Traced from shapes alone
        (``utils/residuals.py``), under the step's mesh, when asked and once.

        One chip's share: the trace is of the GLOBAL micro-batch
        (``global_micro`` x ``seq_len``; ``shape`` is the traced one) and
        ``bytes`` is that value's bytes over the ``data`` axis's size, since
        everything listed was computed from the batch and so carries it. What
        was computed from the parameters alone (``weights``) or from nothing
        (``constants``) is no activation and is summed in ``left_out``, whole.
        Only for a data-only mesh: with a ``pipe``, ``model``, ``seq`` or
        ``expert`` axis over 1 a chip's activations are not the trace's
        shapes over anything, and the record holds no callable."""
        from ..utils import residuals

        args = (jax.ShapeDtypeStruct(micro_shape, jnp.int32),
                jax.eval_shape(jax.random.key, jax.ShapeDtypeStruct((), jnp.uint32)))
        chips = mesh.shape.get("data", 1)

        def listed(remat):
            found = residuals.residuals(
                functools.partial(loss_under, dataclasses.replace(cfg, remat=remat)),
                params_shape, args, has_aux=bool(reports))
            return ([(*e[:4], e[4] // chips) for e in found["entries"]],
                    {k: found[k] for k in ("constants", "weights")})

        with jax.set_mesh(mesh):
            kept = listed(cfg.remat)
            everything = kept if tinygpt.normalize_remat(cfg.remat) == "none" else listed("none")
        return {"kept": kept[0], "all": everything[0],
                "left_out": {"kept": kept[1], "all": everything[1]}}

    def aot_compile(params, opt_state, batch, step=0):
        """AOT-compile for the given args and return the jax.stages.Compiled.

        After the jit has executed once this is a cache hit (<1ms) — the AOT
        path shares the jit executable cache — so it is the free way to get
        ``compiled.memory_analysis()`` (XLA's measured buffer-assignment
        peak) on runtimes whose allocator exposes no ``memory_stats()``.

        What the compiled step holds goes into the process's record
        (``scopes.step_memory()``): buffer assignment's classes, the smallest
        limit the mesh's devices' allocators report, and the callable that
        lists the micro-batch's residuals when someone asks.
        """
        from ..analysis.memory_anatomy import compile_memory_fields

        with jax.set_mesh(mesh):
            with scopes.host_span(scopes.STEP_LOWER):
                lowered = jitted.lower(params, opt_state, batch, step)
            with scopes.host_span(scopes.STEP_COMPILE):
                compiled = lowered.compile()
        micro_shape = (global_micro, seq_len) if from_table else tuple(batch.shape[1:])
        scopes.record_step_memory(
            compiled=compile_memory_fields(compiled),
            bytes_limit=_bytes_limit(mesh),
            saved=functools.partial(saved_for_backward, micro_shape) if data_only else None,
        )
        return compiled

    return step_with_mesh, aot_compile


def abstract_compile_step(
    model_config: tinygpt.TinyGPTConfig,
    strategy: strat.StrategyConfig,
    mesh: Mesh,
    grad_accum: int = 1,
    seed: int = 0,
    from_table: bool = True,
    global_micro: int = 1,
    seq_len: int = 0,
    dataset_size: int = 64,
    pipeline_schedule: str = "gpipe",
    virtual_stages: int = 2,
):
    """AOT-compile the exact train-step executable from ``ShapeDtypeStruct``s.

    No params are initialized and no device memory is touched — the inputs
    are abstract avals carrying their target shardings, so this is a pure
    compiler invocation. Raises on compile failure (callers that want a
    soft probe wrap it — see ``abstract_step_peak_bytes``). Shared by the
    auto-remat AOT probe and the ``analysis.static`` HLO auditor, which
    reads the compiled module's collective schedule off ``.as_text()``.
    """
    cfg = _resolve_model_config(model_config, strategy, mesh)
    optimizer = strat.make_optimizer(strategy)
    params_shape = jax.eval_shape(
        lambda key: tinygpt.init_params(cfg, key), jax.random.key(0)
    )
    param_specs = strat.param_partition_specs(
        params_shape, mesh, shard=strategy.shard_params, kv_heads=cfg.kv_heads,
        scan_stacked=cfg.scan_layers,
    )
    opt_specs = strat.opt_state_partition_specs(
        optimizer, params_shape, param_specs, mesh,
        shard=strategy.shard_opt_state, kv_heads=cfg.kv_heads,
        scan_stacked=cfg.scan_layers,
    )
    opt_shape = jax.eval_shape(optimizer.init, params_shape)

    step_fn, aot_compile = make_train_step(
        model_config, strategy, optimizer, mesh, param_specs, opt_specs,
        grad_accum=grad_accum, seed=seed, from_table=from_table,
        global_micro=global_micro, seq_len=seq_len,
        pipeline_schedule=pipeline_schedule, virtual_stages=virtual_stages,
    )

    def abstract(tree, specs):
        return jax.tree.map(
            lambda s, spec: jax.ShapeDtypeStruct(
                s.shape, s.dtype, sharding=NamedSharding(mesh, spec)
            ),
            tree, specs,
            is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct),
        )

    params_abs = abstract(params_shape, param_specs)
    opt_abs = abstract(opt_shape, opt_specs)
    if from_table:
        batch_abs = jax.ShapeDtypeStruct(
            (dataset_size, seq_len), jnp.int32,
            sharding=NamedSharding(mesh, P()),
        )
    else:
        batch_abs = jax.ShapeDtypeStruct(
            (grad_accum, global_micro, seq_len), jnp.int32,
            sharding=NamedSharding(mesh, P(None, *strat.batch_partition_spec(mesh))),
        )
    return aot_compile(params_abs, opt_abs, batch_abs, 0)


def abstract_step_peak_bytes(
    model_config: tinygpt.TinyGPTConfig,
    strategy: strat.StrategyConfig,
    mesh: Mesh,
    grad_accum: int = 1,
    seed: int = 0,
    from_table: bool = True,
    global_micro: int = 1,
    seq_len: int = 0,
    dataset_size: int = 64,
    pipeline_schedule: str = "gpipe",
    virtual_stages: int = 2,
) -> Optional[int]:
    """XLA's buffer-assignment peak for the train step, WITHOUT allocating.

    Lowers and compiles the exact train-step executable from
    ``ShapeDtypeStruct``s (via ``abstract_compile_step``) and reads
    ``memory_analysis().peak_memory_in_bytes`` — the measured
    compiled-program requirement, as opposed to the analytic
    ``utils.memory.estimate_hbm`` model. Returns None when the program
    cannot compile at all (e.g. the compiler itself reports HBM OOM) or the
    runtime exposes no memory analysis. Used by ``resolve_auto_remat``'s
    probe path to decide near-capacity remat policies by measurement; costs
    one XLA compile (the result is NOT reused by the later real step, whose
    jit cache keys on a different closure).
    """
    try:
        from ..utils import metrics as metrics_mod

        compiled = abstract_compile_step(
            model_config, strategy, mesh, grad_accum=grad_accum, seed=seed,
            from_table=from_table, global_micro=global_micro, seq_len=seq_len,
            dataset_size=dataset_size, pipeline_schedule=pipeline_schedule,
            virtual_stages=virtual_stages,
        )
        peak = metrics_mod.buffer_assignment_peak_bytes(compiled.memory_analysis())
        return peak if peak > 0 else None
    except Exception as e:
        # A compiler HBM-OOM here legitimately means "this policy does not
        # fit" — but a swallowed programming error would silently disable
        # the probe and quietly revert every near-capacity arm to the
        # conservative remat chain, so always say WHY the probe failed.
        msg = str(e)
        print(
            f"AOT probe: compile failed ({type(e).__name__}: "
            f"{msg[:300]}{'...' if len(msg) > 300 else ''})"
        )
        return None


def create_train_state(
    model_config: tinygpt.TinyGPTConfig,
    strategy: strat.StrategyConfig,
    mesh: Mesh,
    seed: int = 42,
    grad_accum: int = 1,
    deterministic_dropout: bool = False,
    from_table: bool = False,
    global_micro: int = 1,
    seq_len: int = 0,
    pipeline_schedule: str = "gpipe",
    virtual_stages: int = 2,
    sentinel: bool = False,
) -> TrainState:
    """Initialize params + optimizer state directly into their target shardings.

    Init is jitted with ``out_shardings`` so tier-B params materialize sharded
    across HBM — no single host/device ever holds the full replicated tree
    (the TPU analogue of FSDP's deferred/sharded init).
    """
    cfg = _resolve_model_config(model_config, strategy, mesh)
    optimizer = strat.make_optimizer(strategy)

    def init_fn(key):
        p = tinygpt.init_params(cfg, key)
        if (
            pipeline_schedule == "interleaved"
            and mesh.shape.get("pipe", 1) > 1
        ):
            # Interleaved virtual stages: device d owns chunks {v*P + d}, so
            # the stacked layer weights are permuted before the contiguous
            # 'pipe' sharding lands (parallel.interleaved.layer_permutation).
            # Params/grads/Adam state live in this layout for the whole run;
            # dropout keys use global layer indices, so the math is
            # layout-independent.
            from ..parallel.interleaved import layer_permutation

            perm = layer_permutation(
                cfg.n_layer, mesh.shape["pipe"], virtual_stages
            )
            p["blocks"] = jax.tree.map(lambda x: x[perm], p["blocks"])
        return p

    params_shape = jax.eval_shape(init_fn, jax.random.key(0))
    param_specs = strat.param_partition_specs(
        params_shape, mesh, shard=strategy.shard_params, kv_heads=cfg.kv_heads,
        scan_stacked=cfg.scan_layers,
    )
    opt_specs = strat.opt_state_partition_specs(
        optimizer, params_shape, param_specs, mesh,
        shard=strategy.shard_opt_state, kv_heads=cfg.kv_heads,
        scan_stacked=cfg.scan_layers,
    )

    with mesh:
        with scopes.host_span(scopes.INIT_PARAMS):
            params = jax.jit(
                init_fn,
                out_shardings=strat.named(mesh, param_specs),
            )(jax.random.key(seed))
        with scopes.host_span(scopes.INIT_OPT_STATE):
            opt_state = jax.jit(
                optimizer.init,
                out_shardings=strat.opt_state_shardings(mesh, opt_specs, strategy),
            )(params)

    step_fn, aot_compile = make_train_step(
        model_config,
        strategy,
        optimizer,
        mesh,
        param_specs,
        opt_specs,
        grad_accum=grad_accum,
        seed=seed,
        deterministic_dropout=deterministic_dropout,
        from_table=from_table,
        global_micro=global_micro,
        seq_len=seq_len,
        pipeline_schedule=pipeline_schedule,
        virtual_stages=virtual_stages,
        sentinel=sentinel,
    )
    return TrainState(
        params=params,
        opt_state=opt_state,
        step_fn=step_fn,
        aot_compile=aot_compile,
        mesh=mesh,
        param_specs=param_specs,
        opt_specs=opt_specs,
        batch_sharding=NamedSharding(mesh, P(None, *strat.batch_partition_spec(mesh))),
        model_config=cfg,
        strategy=strategy,
        n_params=tinygpt.count_params(params),
    )
