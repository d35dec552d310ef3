"""Distributed-training strategies as *sharding specifications*.

The reference implements its four strategy arms as four divergent wrapper code
paths — torch DDP, torch FSDP, and two DeepSpeed engines (reference
``benchmarking/train_harness.py:207-275``). On TPU/XLA the idiomatic design
collapses all four into data: one shared jitted train step, four
(param-sharding, grad-sharding, optimizer-state-sharding) specifications over
a ``jax.sharding.Mesh``. XLA/GSPMD then *derives* the collective schedule the
reference hand-picks libraries for:

- **ddp**   params+opt replicated, batch sharded on 'data'  -> XLA inserts a
  gradient all-reduce over ICI (what NCCL ring all-reduce does in DDP backward
  hooks, reference ``train_harness.py:217-222``).
- **fsdp**  params, grads and opt state all sharded on 'data' -> XLA inserts
  per-use all-gather of weights and reduce-scatter of grads (the FSDP
  schedule, reference ``train_harness.py:231-237``).
- **zero2** params replicated, grads+opt state sharded -> grads reduce-scatter
  into the shard, the Adam update runs on 1/N of the state, and the updates
  all-gather back into replicated params (DeepSpeed ZeRO stage-2 semantics,
  reference ``configs/deepspeed/zero2.json:10-25``). This is the arm XLA does
  not give you for free — the explicit sharding constraints below ask for it.
- **zero3** like fsdp plus per-layer rematerialization: DeepSpeed stage 3's
  live-parameter windowing (``configs/deepspeed/zero3.json:20-26``) trades
  memory for re-compute/re-gather; ``jax.checkpoint`` on the scanned block is
  the XLA-native expression of the same trade.

Every knob here is *live* (loaded from ``configs/strategies/*.json``) — unlike
the reference, where ``--fsdp-config`` is accepted but never read and
``--grad-accum`` is silently inert for DDP/FSDP (SURVEY §2.1 C8/C9).
"""

from __future__ import annotations

import dataclasses
import json
import os
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

Params = Any


@dataclasses.dataclass(frozen=True)
class StrategyConfig:
    """One strategy arm = optimizer recipe + sharding layout + remat policy."""

    name: str
    # optimizer (parity: AdamW lr=1e-4 wd=0.01, reference train_harness.py:328-331
    # and configs/deepspeed/zero2.json:27-36)
    learning_rate: float = 1e-4
    betas: Tuple[float, float] = (0.9, 0.999)
    eps: float = 1e-8
    weight_decay: float = 0.01
    # DeepSpeed arms use WarmupLR(5) + grad clip 1.0 (zero2.json:2,37-44);
    # the torch arms use neither.
    warmup_steps: int = 0
    grad_clip: Optional[float] = None
    # sharding layout over the 'data' mesh axis
    shard_params: bool = False
    shard_grads: bool = False
    shard_opt_state: bool = False
    # per-layer rematerialization policy inside the block scan:
    # "none" | "dots" (save matmul outputs and the named values of
    # models.tinygpt.remat_kept_names) | "full_keep_kernels" (the named
    # values alone) | "full" |
    # "auto" (pick the cheapest of none / dots / full whose memory estimate
    # fits the device — resolved by utils.memory.resolve_auto_remat before
    # training). Legacy bools accepted in JSON configs (True = "full").
    remat: str = "none"
    # compute precision for matmuls ('bf16' | 'f32')
    precision: str = "bf16"
    # parameter (and therefore Adam-state) storage dtype: 'f32' (default —
    # fp32 master weights, the training-quality choice) or 'bf16', which
    # halves params+grads+moments. bf16 state is what makes tier B (1.68B
    # params, ~25 GiB of fp32 state) runnable on a single 16 GiB chip —
    # DeepSpeed's fp16 master-weightless mode plays the same role. Expect
    # bf16-rounded Adam updates (a stress-tier trade, documented in
    # docs/TROUBLESHOOTING.md).
    param_dtype: str = "f32"
    def describe(self) -> str:
        bits = [
            f"params={'sharded' if self.shard_params else 'replicated'}",
            f"grads={'reduce-scatter' if self.shard_grads else 'all-reduce'}",
            f"opt_state={'sharded' if self.shard_opt_state else 'replicated'}",
        ]
        if self.remat != "none":
            bits.append(f"remat={self.remat}")
        if self.param_dtype != "f32":
            bits.append(f"param_dtype={self.param_dtype}")
        return f"{self.name}: " + ", ".join(bits)


STRATEGIES: Dict[str, StrategyConfig] = {
    "ddp": StrategyConfig(name="ddp"),
    "fsdp": StrategyConfig(
        name="fsdp", shard_params=True, shard_grads=True, shard_opt_state=True
    ),
    "zero2": StrategyConfig(
        name="zero2",
        shard_grads=True,
        shard_opt_state=True,
        warmup_steps=5,
        grad_clip=1.0,
    ),
    "zero3": StrategyConfig(
        name="zero3",
        shard_params=True,
        shard_grads=True,
        shard_opt_state=True,
        warmup_steps=5,
        grad_clip=1.0,
        # DeepSpeed stage 3 pays a recompute/gather tax only when memory
        # pressure demands it; blanket per-layer remat measured a ~20%
        # single-chip throughput tax where the arm fit comfortably without
        # it (docs/PERFORMANCE.md). "auto" picks the cheapest fitting policy.
        remat="auto",
    ),
}


def get_strategy(name: str) -> StrategyConfig:
    if name not in STRATEGIES:
        raise ValueError(f"Unknown strategy {name!r} (expected one of {sorted(STRATEGIES)})")
    return STRATEGIES[name]


def _normalize_remat_field(value: Any) -> str:
    """JSON remat field: bool (legacy, True="full"), a model policy string,
    or "auto" (resolved against the memory model before reaching the model —
    the one value tinygpt.normalize_remat deliberately rejects)."""
    if value == "auto":
        return value
    from ..models.tinygpt import normalize_remat

    try:
        return normalize_remat(value)
    except ValueError:
        raise ValueError(
            f"invalid remat value {value!r} in strategy config "
            "(expected bool or one of 'none'/'dots'/'full_keep_kernels'/'full'/'auto')"
        )


def load_strategy_config(path: str) -> StrategyConfig:
    """Load a strategy arm from a JSON config file (configs/strategies/*.json).

    File format (every field live — this replaces both the reference's
    DeepSpeed JSONs, which were loaded and mutated at runtime
    (train_harness.py:246-262), and its FSDP YAML, which was dead config):

        {"strategy": "zero2",
         "optimizer": {"lr": 1e-4, "betas": [0.9, 0.999], "eps": 1e-8,
                        "weight_decay": 0.01},
         "scheduler": {"warmup_steps": 5},
         "grad_clip": 1.0,
         "precision": "bf16",
         "sharding": {"params": false, "grads": true, "opt_state": true},
         "remat": false}
    """
    with open(path) as f:
        raw = json.load(f)
    offload_keys = sorted(k for k in raw if k.startswith("offload_"))
    if offload_keys:
        raise ValueError(
            f"strategy config {path}: key {offload_keys[0]!r} is not supported: "
            "the optimizer state lives in HBM here (there is no host offload). "
            f"Drop {', '.join(repr(k) for k in offload_keys)} from the file; "
            "\"param_dtype\": \"bf16\" is the memory relief."
        )
    name = raw.get("strategy")
    base = get_strategy(name) if name in STRATEGIES else StrategyConfig(name=name or os.path.basename(path))
    opt = raw.get("optimizer", {})
    sched = raw.get("scheduler", {})
    shard = raw.get("sharding", {})
    pdtype = raw.get("param_dtype", base.param_dtype)
    if pdtype not in ("f32", "bf16"):
        raise ValueError(
            f"invalid param_dtype {pdtype!r} in strategy config "
            "(expected 'f32' or 'bf16')"
        )
    return dataclasses.replace(
        base,
        learning_rate=float(opt.get("lr", base.learning_rate)),
        betas=tuple(opt.get("betas", base.betas)),
        eps=float(opt.get("eps", base.eps)),
        weight_decay=float(opt.get("weight_decay", base.weight_decay)),
        warmup_steps=int(sched.get("warmup_steps", base.warmup_steps)),
        grad_clip=raw.get("grad_clip", base.grad_clip),
        precision=raw.get("precision", base.precision),
        param_dtype=pdtype,
        shard_params=bool(shard.get("params", base.shard_params)),
        shard_grads=bool(shard.get("grads", base.shard_grads)),
        shard_opt_state=bool(shard.get("opt_state", base.shard_opt_state)),
        remat=_normalize_remat_field(raw.get("remat", base.remat)),
    )


def is_deepspeed_config(raw: Any) -> bool:
    """True when a JSON dict looks like a DeepSpeed config rather than our
    native strategy format (which always carries a "strategy" key)."""
    if not isinstance(raw, dict) or "strategy" in raw:
        return False
    return any(
        k in raw
        for k in (
            "zero_optimization",
            "train_micro_batch_size_per_gpu",
            "gradient_clipping",
            "bf16",
            "fp16",
        )
    )


def from_deepspeed_config(raw: Dict[str, Any], strategy_name: str) -> StrategyConfig:
    """Translate a DeepSpeed-format JSON into a live StrategyConfig.

    The reference *reads and mutates* its DeepSpeed JSONs at runtime
    (reference ``train_harness.py:246-262``) — so a user pointing
    ``--deepspeed-config`` at their own file expects its optimizer/scheduler/
    clipping values to take effect. Mapping (reference
    ``configs/deepspeed/zero2.json:2,7-9,27-44``):

    - ``optimizer.params.{lr,betas,eps,weight_decay}`` -> AdamW recipe;
    - ``scheduler.params.warmup_num_steps`` (WarmupLR) -> linear warmup;
    - ``gradient_clipping``                -> global-norm clip;
    - ``bf16.enabled`` / ``fp16.enabled``  -> bf16 compute (fp16 maps to bf16:
      the TPU fast path — same role the reference's AMP plays);
    - ``zero_optimization.stage``          -> cross-checked against the CLI
      strategy arm (stage 2 != zero3 is a user error worth failing loudly on).

    Batch-size keys (``train_micro_batch_size_per_gpu`` etc.) are *not* read:
    like the reference, batch geometry comes from the CLI and is injected over
    whatever the file says (reference ``train_harness.py:250-262``).
    """
    base = get_strategy(strategy_name)

    def section(key):
        """A config section must be a dict (or absent); fail naming the key
        rather than AttributeError-ing on shorthand like {"bf16": true}."""
        val = raw.get(key, {})
        if not isinstance(val, dict):
            raise ValueError(
                f"DeepSpeed config section {key!r} must be an object, got {val!r}"
            )
        return val

    def num(container, key, fallback, cast=float):
        """Read a numeric field; HF-Trainer-style "auto" (ubiquitous in real
        DeepSpeed JSONs) falls back to the arm default; anything else
        non-numeric fails naming the offending key."""
        val = container.get(key, None)
        if val is None or val == "auto":
            return fallback
        try:
            return cast(val)
        except (TypeError, ValueError):
            raise ValueError(
                f"DeepSpeed config field {key!r} has non-numeric value {val!r}"
            )

    zero = section("zero_optimization")
    stage = num(zero, "stage", None, int)
    expected = {"zero2": 2, "zero3": 3}.get(strategy_name)
    if stage is not None and expected is not None and stage != expected:
        raise ValueError(
            f"--strategy {strategy_name} but DeepSpeed config sets "
            f"zero_optimization.stage={stage}"
        )
    opt_section = section("optimizer")
    opt_type = opt_section.get("type", "AdamW")
    if str(opt_type).lower() not in ("adam", "adamw"):
        # The framework's optimizer recipe is AdamW (reference parity);
        # silently running AdamW under an SGD/Lamb config would be wrong
        # semantics at a likely-diverging lr.
        raise ValueError(
            f"DeepSpeed optimizer type {opt_type!r} is not supported "
            "(only Adam/AdamW map onto this framework's optimizer)"
        )
    opt = opt_section.get("params", {})
    if not isinstance(opt, dict):
        raise ValueError(
            f"DeepSpeed config field 'optimizer.params' must be an object, got {opt!r}"
        )
    sched = section("scheduler")
    sched_params = sched.get("params", {})
    if not isinstance(sched_params, dict):
        raise ValueError(
            f"DeepSpeed config field 'scheduler.params' must be an object, "
            f"got {sched_params!r}"
        )
    warmup = base.warmup_steps
    # Only warmup-family schedulers carry warmup_num_steps semantics we map.
    if sched.get("type", "WarmupLR") in ("WarmupLR", "WarmupDecayLR"):
        warmup = num(sched_params, "warmup_num_steps", base.warmup_steps, int)
    betas = opt.get("betas", None)
    if betas is None or betas == "auto":
        betas = base.betas
    elif not (
        isinstance(betas, (list, tuple))
        and len(betas) == 2
        and all(isinstance(b, (int, float)) for b in betas)
    ):
        raise ValueError(f"DeepSpeed config field 'betas' must be [b1, b2], got {betas!r}")
    precision = base.precision
    if section("bf16").get("enabled") or section("fp16").get("enabled"):
        precision = "bf16"
    grad_clip = num(raw, "gradient_clipping", base.grad_clip)
    if grad_clip is not None and grad_clip <= 0:
        # DeepSpeed semantics: gradient_clipping 0 means *disabled*, not
        # "clip everything to zero norm".
        grad_clip = None
    offload = zero.get("offload_optimizer")
    device = offload.get("device") if isinstance(offload, dict) else offload
    if device not in (None, "none"):
        raise ValueError(
            "DeepSpeed config sets zero_optimization.offload_optimizer.device="
            f"{device!r}: the optimizer state lives in HBM here (there is no "
            "host offload). Drop 'zero_optimization.offload_optimizer' (or set "
            "its device to \"none\"); --param-dtype bf16 is the memory relief."
        )
    return dataclasses.replace(
        base,
        learning_rate=num(opt, "lr", base.learning_rate),
        betas=tuple(betas),
        eps=num(opt, "eps", base.eps),
        weight_decay=num(opt, "weight_decay", base.weight_decay),
        warmup_steps=warmup,
        grad_clip=grad_clip,
        precision=precision,
    )


def make_optimizer(strategy: StrategyConfig) -> optax.GradientTransformation:
    """AdamW (+ optional global-norm clip + optional linear warmup).

    Mirrors the reference recipes: bare AdamW(1e-4, wd=0.01) for ddp/fsdp
    (train_harness.py:328-331); AdamW + WarmupLR(5) + clip 1.0 for the ZeRO
    arms (configs/deepspeed/zero2.json:2,27-44).
    """
    if strategy.warmup_steps > 0:
        lr = optax.linear_schedule(
            init_value=0.0,
            end_value=strategy.learning_rate,
            transition_steps=strategy.warmup_steps,
        )
    else:
        lr = strategy.learning_rate
    tx = optax.adamw(
        learning_rate=lr,
        b1=strategy.betas[0],
        b2=strategy.betas[1],
        eps=strategy.eps,
        weight_decay=strategy.weight_decay,
    )
    if strategy.grad_clip is not None:
        tx = optax.chain(optax.clip_by_global_norm(float(strategy.grad_clip)), tx)
    return tx


def opt_state_shardings(mesh: Mesh, opt_specs, strategy: StrategyConfig):
    """NamedShardings for the optimizer state: the whole of it (clip state,
    Adam moments, schedule count) lives in device HBM under every strategy
    (``strategy`` decides nothing here; the benchmark's builders pass it)."""
    return named(mesh, opt_specs)


# ---------------------------------------------------------------------------
# PartitionSpec derivation
# ---------------------------------------------------------------------------

# Megatron-style tensor-parallel layout over the 'model' mesh axis, keyed by
# parameter leaf path. Column-parallel QKV/FC1 (output features sharded),
# row-parallel attention-out/FC2 (input features sharded; XLA inserts the
# all-reduce the row-parallel matmul needs), vocab-sharded tied embedding
# (the logits einsum + cross-entropy become Megatron's parallel softmax —
# GSPMD derives the collectives from the sharding).
_TP_RULES = {
    "wte": (0,),        # vocab
    "lm_head": (0,),    # untied head: vocab-sharded like wte
    "blocks/wqkv": (3,),  # per-head output features
    "blocks/bqkv": (2,),
    # GQA split projections: column-parallel q and k/v (query heads share kv
    # heads in consecutive blocks, in the flash kernels' index maps and in
    # the other bodies' repeat, so each query-head shard is paired with its
    # own kv-head shard as long as the 'model' degree divides kv_heads)
    "blocks/wq": (2,),
    "blocks/bq": (1,),
    "blocks/wkv": (3,),
    "blocks/bkv": (2,),
    "blocks/wo": (1,),  # row-parallel input (merged heads)
    "blocks/wg": (2,),  # the output gate: one column a query head, as wq's
    "blocks/wfc": (2,),  # column-parallel output
    "blocks/bfc": (1,),
    # SwiGLU gate/up stack: column-parallel output features
    "blocks/wgu": (3,),
    "blocks/bgu": (2,),
    "blocks/wproj": (1,),  # row-parallel input
    # MoE experts: column-parallel w1, row-parallel w2 inside each expert
    "blocks/moe_w1": (3,),
    "blocks/moe_b1": (2,),
    "blocks/moe_w2": (2,),
    # Latent attention: the per-head expansion is column-parallel over heads;
    # the down projection and its norm serve every head and stay whole.
    "blocks/wkv_b": (2,),
    # Shared experts: column-parallel gate|up would split across the gate /
    # up boundary, so only the down projection's input rows are named.
    "blocks/shared_wd": (1,),
}

# Expert parallelism over the 'expert' mesh axis: each device group owns a
# slice of the expert set; the dispatch/combine einsums in models.moe become
# the all-to-all. The router stays replicated (it is tiny and every token
# needs all scores).
_EP_RULES = {
    "blocks/moe_w1": 1,
    "blocks/moe_b1": 1,
    "blocks/moe_w2": 1,
    "blocks/moe_b2": 1,
    "blocks/moe_wgu": 1,
    "blocks/moe_wu": 1,  # experts that are not gated: the up projection alone
    "blocks/moe_wd": 1,
}


def _leaf_name(path) -> str:
    """'blocks/<leaf>' for a leaf of any stack of layers (every name ends in
    'blocks': 'blocks', the leading dense 'dense_blocks', the stacks by a
    mixer's name, ``models/mixers/``'s ``STACKS``, and the stacks by kind of
    ``layer_heads`` and of ``block_halves``): a leaf of one name has the same
    shape but for its widths and the same role in every stack, and takes the
    same rules. Leaves that only ``tinygpt.PARAM_AXIS_RULES`` knows (a mixer's
    own that is not attention's; the relu2 experts' up projections) have no
    tensor-parallel rule: under a 'model' axis they stay whole."""
    name = "/".join(str(getattr(p, "key", p)) for p in path)
    stack, _, leaf = name.partition("/")
    return f"blocks/{leaf}" if leaf and stack.endswith("blocks") else name


#: Leaves smaller than this (total elements) are not worth FSDP-sharding in
#: a composed dp x tp mesh: norm scales and biases are a few hundred
#: elements per layer, and 'data'-sharding them buys ~nothing in HBM while
#: costing an all-gather per use — measured 10 extra all-gathers per step
#: on the llama-fsdp-dp4-tp2 arm (docs/PERFORMANCE.md round 8). Pure-dp
#: meshes keep the old behavior (their frozen budgets pin it, and without
#: a 'model' axis the gathers never risk the transposed-order permutes).
_COMPOSED_MIN_SHARD_ELEMENTS = 4096

#: Round-15 scan-carry kill: stacked column-parallel leaves whose ONLY
#: hygiene-legal 'data' axis is the embed (contraction) axis stay
#: model-only sharded in composed dp x tp meshes — under the SCANNED layer
#: loop only (``param_partition_specs(scan_stacked=True)``); the unrolled
#: lowering has no stacked stash and keeps the round-8 placement, so the
#: suite's measured llama-fsdp-dp4-tp2 budget stays byte-identical.
#: Data-sharding the
#: contraction dim makes GSPMD lower the projection as contraction-partial
#: matmuls whose scanned activation/grad stash reshards between tilings
#: with collective-permute chains — measured on llama-fsdp-dp4-tp2-scan,
#: where 'blocks/wq' was the source of the banked 4 reshard suspects
#: (together with the scan-carry pin, 4 -> 0). Scoped to the measured
#: leaf: wkv/wgu data-shard the same axis without tripping the stash
#: (and wgu is the largest block leaf — its fsdp split is the memory win
#: worth keeping); the unexercised tinygpt siblings (wqkv/wfc) keep the
#: old placement until a composed-mesh tinygpt arm joins the roster.
_COMPOSED_CONTRACTION_DATA_SKIP = frozenset({"blocks/wq"})


def _shard_largest_free_axis(
    spec: list, shape: Tuple[int, ...], n_shards: int, is_block_leaf: bool,
    composed: bool = False,
) -> None:
    """FSDP-style: put 'data' on the largest unsharded divisible axis.

    For stacked block leaves (leading 'layers' scan axis) we prefer a tensor
    axis over the layers axis: sharding inside the layer keeps the scan body's
    dynamic-slice local and lets XLA all-gather exactly one layer's shard per
    scan iteration (the FSDP/ZeRO-3 schedule). The layers axis is the fallback.

    ``composed`` (a >1 'model' axis coexists with >1 'data') adds the
    round-8 tile-order hygiene rules:

    - 'data' only lands on an axis BEFORE the leaf's 'model' axis. The mesh
      is data-major, so [.., 'data', .., 'model', ..] tiles enumerate
      devices in iota order while the reverse order enumerates them
      transposed — and GSPMD can only reshard between the two orders with
      collective-permute chains. Row-parallel and vocab-sharded leaves
      ('model' leads: wo/wproj/wte/lm_head) therefore keep model-only
      sharding; column-parallel leaves (wq/wgu/wfc: 'model' trails) keep
      their fsdp 'data' split. Measured on llama-fsdp-dp4-tp2 (unrolled):
      13 replication-reshard suspects -> 0.
    - vector-like leaves (< _COMPOSED_MIN_SHARD_ELEMENTS elements) stay
      replicated over 'data' (see the constant's comment).
    """
    if composed:
        # Vector-likeness is a PER-LAYER property: block leaves are
        # stacked (L, ...), and counting the layers axis would let a
        # deep model's norm scales (L x D elements) dodge the rule the
        # comment above sizes in per-layer units.
        per_layer = shape[1:] if is_block_leaf and len(shape) > 1 else shape
        size = 1
        for d in per_layer:
            size *= d
        if "model" not in spec and size < _COMPOSED_MIN_SHARD_ELEMENTS:
            return
    axes = list(range(len(shape)))
    candidates = axes[1:] + axes[:1] if is_block_leaf and len(shape) > 1 else axes
    if composed and "model" in spec:
        model_ax = spec.index("model")
        candidates = [ax for ax in candidates if ax < model_ax]
    best = None
    for ax in candidates:
        if spec[ax] is None and shape[ax] % n_shards == 0 and shape[ax] >= n_shards:
            if best is None or shape[ax] > shape[best]:
                best = ax
    if best is not None:
        spec[best] = "data"


def param_partition_specs(
    params: Params, mesh: Mesh, shard: bool, kv_heads: Optional[int] = None,
    scan_stacked: bool = False,
) -> Params:
    """PartitionSpec pytree for the params under a given strategy + mesh.

    Applies tensor-parallel rules first (when the mesh has a >1 'model' axis),
    then — for sharded strategies — FSDP-style 'data' sharding on the largest
    remaining axis of each leaf. The two compose: a 2-D (data, model) mesh
    gives e.g. wfc the spec P(None, 'data', 'model').

    ``kv_heads`` (the model config's KV-head count, passed by config-bearing
    callers) gates the GQA kv projections' 'model' sharding: the column
    split is only head-aligned when the 'model' degree divides ``kv_heads``.
    A misaligned split shards WITHIN each kv head's feature block, and the
    consecutive-block kv repeat (``mixers.attention._whole_heads``; in front of
    ``flash_attention``'s shard_map at such a degree) then needs a layout the
    partitioner cannot produce in place — it falls back to
    full-replicate-then-repartition of every per-layer k/v tensor (measured:
    +10 all-gathers and +6 collective-permutes per step on a tp=2 llama-S
    compile; on newer XLA the same fallback logs "[SPMD] Involuntary full
    rematerialization"). Keeping wkv/bkv replicated over 'model' instead
    duplicates only the small kv projection einsum (2/(2+q_heads/kv_heads)
    of one attention projection) and emits zero resharding collectives —
    the Megatron choice for tp > kv_heads.

    Composed dp x tp meshes additionally apply the round-8 tile-order
    hygiene rules (see ``_shard_largest_free_axis``): 'data' never lands
    after a leaf's 'model' axis (the transposed tile order is the
    llama-fsdp-dp4-tp2 collective-permute fallback) and vector-like leaves
    stay replicated over 'data'.

    ``scan_stacked`` (round 15) says the caller compiles the SCANNED layer
    loop: composed meshes then keep the
    :data:`_COMPOSED_CONTRACTION_DATA_SKIP` leaves model-only — the scan's
    stacked activation/grad stash is what reshards with permute chains
    when those leaves data-shard their contraction axis. The unrolled
    lowering has no stacked stash and keeps the round-8 placement (its
    frozen budgets stay byte-identical).
    """
    n_data = mesh.shape.get("data", 1)
    n_model = mesh.shape.get("model", 1)
    n_pipe = mesh.shape.get("pipe", 1)
    n_expert = mesh.shape.get("expert", 1)
    kv_misaligned = kv_heads is not None and kv_heads % n_model != 0

    def spec(path, leaf):
        s = [None] * len(leaf.shape)
        name = _leaf_name(path)
        is_block = name.startswith("blocks/")
        if n_pipe > 1 and is_block:
            # Pipeline stages own contiguous slices of the stacked layers axis.
            s[0] = "pipe"
        if n_expert > 1 and name in _EP_RULES:
            ax = _EP_RULES[name]
            if leaf.shape[ax] % n_expert == 0:
                s[ax] = "expert"
        if n_model > 1:
            for ax in _TP_RULES.get(name, ()):
                if name in ("blocks/wkv", "blocks/bkv") and kv_misaligned:
                    # kv-head-aligned rule (see docstring): replicate the kv
                    # projection over 'model' rather than split inside a head.
                    continue
                if name in ("wte", "lm_head") and n_pipe > 1:
                    # Pipeline runs keep the tied embedding replicated over
                    # 'model': the schedule already replicates embed/head
                    # across stages (every stage computes them for schedule
                    # uniformity), and a vocab-sharded embedding gather inside
                    # the partially-manual pipe region trips an XLA SPMD
                    # partitioner CHECK (spmd_partitioner_util.cc:495) when
                    # 'data' also shards the indices — the dp x tp x pp
                    # triple. Megatron-LM likewise special-cases the
                    # embedding's placement under pipeline parallelism.
                    continue
                if s[ax] is None and leaf.shape[ax] % n_model == 0:
                    s[ax] = "model"
        if shard and n_data > 1:
            if (
                scan_stacked
                and n_model > 1
                and name in _COMPOSED_CONTRACTION_DATA_SKIP
            ):
                # Round-15 scan-carry rule: keep the leaf model-only (see
                # _COMPOSED_CONTRACTION_DATA_SKIP) — the same posture the
                # hygiene rules already give the row-parallel leaves, whose
                # leading 'model' axis leaves no legal 'data' slot either.
                pass
            else:
                _shard_largest_free_axis(
                    s, leaf.shape, n_data, is_block, composed=n_model > 1
                )
        return P(*s)

    return jax.tree_util.tree_map_with_path(spec, params)


def opt_state_partition_specs(
    optimizer: optax.GradientTransformation,
    params: Params,
    param_specs: Params,
    mesh: Mesh,
    shard: bool,
    kv_heads: Optional[int] = None,
    scan_stacked: bool = False,
) -> Any:
    """PartitionSpec pytree for the optimizer state.

    Param-shaped leaves (Adam mu/nu, weight-decay masks, ...) inherit either
    the param's own spec (fsdp/zero3) or an FSDP-style sharded spec of their
    own (zero2: replicated params but *sharded* moments — the defining ZeRO-2
    layout). Non-param leaves (step counts) are replicated.
    """
    state_shapes = jax.eval_shape(optimizer.init, params)
    if shard:
        moment_specs = param_partition_specs(
            params, mesh, shard=True, kv_heads=kv_heads,
            scan_stacked=scan_stacked,
        )
    else:
        moment_specs = param_specs
    return optax.tree_map_params(
        optimizer,
        lambda _, spec: spec,
        state_shapes,
        moment_specs,
        transform_non_params=lambda _: P(),
    )


def batch_partition_spec(mesh: Mesh) -> P:
    """Global batch (batch, seq): batch dim sharded on 'data' — AND on
    'expert' when an expert-parallel axis exists — sequence dim on 'seq'
    when a sequence-parallel axis exists (ring attention consumes it).

    Expert parallelism rides the batch dim (DeepSpeed-MoE style): each of
    the dp x ep device groups processes a DISTINCT batch shard, and the MoE
    layer exchanges tokens across 'expert' with an explicit all-to-all
    (models.moe). The round-4 layout kept the batch replicated over
    'expert', which silently duplicated all non-expert compute ep times —
    half the machine re-deriving the same activations at ep=2."""
    axes = tuple(ax for ax in ("data", "expert") if mesh.shape.get(ax, 1) > 1)
    batch_axis = axes if axes else None
    seq_axis = "seq" if mesh.shape.get("seq", 1) > 1 else None
    if seq_axis is None:
        return P(batch_axis) if batch_axis else P()
    return P(batch_axis, seq_axis)


def named(mesh: Mesh, spec_tree: Any) -> Any:
    """PartitionSpec pytree -> NamedSharding pytree."""
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s),
        spec_tree,
        is_leaf=lambda x: isinstance(x, P),
    )
