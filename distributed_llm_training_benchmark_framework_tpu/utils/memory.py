"""Per-chip HBM footprint estimation — fail fast instead of OOM-ing.

The reference framework has no memory model at all: requesting its 1.68B
"stress tier" on hardware that cannot hold it dies in the allocator mid-run
(its own suite never ran tier B — reference ``scripts/run_all_benchmarks.sh``
keeps those lines commented out). Here the harness estimates the per-chip
footprint *before* initializing anything, prints the breakdown, and refuses
with an explanation when the estimate exceeds device capacity.

Method:

- **Parameter-shaped state is exact**: ``jax.eval_shape`` over ``init_params``
  and ``optimizer.init`` gives the true byte counts; each leaf is divided by
  the product of mesh-axis sizes its PartitionSpec shards over (the same
  specs the train step jits with), so DDP/FSDP/ZeRO/TP/PP layouts all read
  their real per-chip share. Gradients mirror params (fp32 accumulators),
  sharded when the strategy reduce-scatters them (ZeRO-2/3, FSDP).
- **Activations are analytic** (intentionally a model, not a measurement —
  the point is to predict before allocating): per-layer live tensors for the
  fwd+bwd of one microbatch, ``~14 * B * S * D`` compute-dtype bytes dense,
  plus the O(S^2) score/prob tensors ONLY for the materialized 'reference'
  attention (flash/ring never materialize them — their activation term is
  what makes long-context tier-A runs fit), plus the fp32 logits + cotangent
  at the head. Remat collapses the per-layer term to the boundary residual
  plus one layer's recompute peak.

Scope: single-host estimates for the dp/tp/pp axes the benchmark arms use.
Numbers are estimates (XLA fusion, padding and collective buffers move the
real peak ±20%); the capacity check applies a safety margin accordingly.
"""

from __future__ import annotations

import collections
import dataclasses
import functools
from typing import Any, Dict, Optional

import jax
import numpy as np

from .platform import chip_spec


def device_hbm_bytes(device_kind: str) -> Optional[int]:
    """Per-chip HBM capacity; None off a TPU (CPU hosts), an error for a
    TPU kind the hardware table does not hold (utils.platform)."""
    spec = chip_spec(device_kind)
    return int(spec.hbm_gib * 1024**3) if spec else None


def _sharded_bytes(shapes, specs, mesh) -> int:
    """Total bytes of a shape-tree, each leaf divided by its shard factor."""
    total = 0
    shape_leaves = jax.tree_util.tree_leaves(shapes)
    spec_leaves = jax.tree_util.tree_leaves(
        specs, is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec)
    )
    if len(shape_leaves) != len(spec_leaves):
        # A silent zip-truncation here would under-estimate HBM and defeat
        # the fail-fast pre-flight check — structure drift must fail loudly.
        raise ValueError(
            f"shape tree has {len(shape_leaves)} leaves but spec tree has "
            f"{len(spec_leaves)}; the trees must mirror each other"
        )
    for shape_leaf, spec_leaf in zip(shape_leaves, spec_leaves):
        nbytes = int(np.prod(shape_leaf.shape) or 1) * shape_leaf.dtype.itemsize
        factor = 1
        if isinstance(spec_leaf, jax.sharding.PartitionSpec):
            for entry in spec_leaf:
                for ax in (entry,) if isinstance(entry, str) else (entry or ()):
                    factor *= mesh.shape.get(ax, 1)
        total += nbytes // max(factor, 1)
    return total


@dataclasses.dataclass
class HBMEstimate:
    params: int
    grads: int
    opt_state: int
    activations: int
    logits: int
    dataset: int

    @property
    def total(self) -> int:
        return (
            self.params + self.grads + self.opt_state
            + self.activations + self.logits + self.dataset
        )

    def breakdown(self) -> Dict[str, float]:
        gib = 1024**3
        return {
            "params_gib": self.params / gib,
            "grads_gib": self.grads / gib,
            "opt_state_gib": self.opt_state / gib,
            "activations_gib": self.activations / gib,
            "logits_gib": self.logits / gib,
            "dataset_gib": self.dataset / gib,
            "total_gib": self.total / gib,
        }


def estimate_hbm(
    model_config: Any,
    strategy: Any,
    mesh: Any,
    per_device_batch: int,
    seq_len: int,
    dataset_size: int = 0,
) -> HBMEstimate:
    """Estimate the per-chip HBM footprint of one training arm."""
    from ..models import mixers, tinygpt
    from ..parallel import strategies as strat

    cfg = model_config
    params_shape = jax.eval_shape(
        functools.partial(tinygpt.init_params, cfg), jax.random.key(0)
    )
    scan_stacked = cfg.scan_layers
    param_specs = strat.param_partition_specs(
        params_shape, mesh, shard=strategy.shard_params, kv_heads=cfg.kv_heads,
        scan_stacked=scan_stacked,
    )
    grad_specs = strat.param_partition_specs(
        params_shape, mesh,
        shard=strategy.shard_params or strategy.shard_grads,
        kv_heads=cfg.kv_heads,
        scan_stacked=scan_stacked,
    )
    optimizer = strat.make_optimizer(strategy)
    opt_shape = jax.eval_shape(optimizer.init, params_shape)
    opt_specs = strat.opt_state_partition_specs(
        optimizer, params_shape, param_specs, mesh,
        shard=strategy.shard_opt_state, kv_heads=cfg.kv_heads,
        scan_stacked=scan_stacked,
    )

    params_b = _sharded_bytes(params_shape, param_specs, mesh)
    grads_b = _sharded_bytes(params_shape, grad_specs, mesh)
    opt_b = _sharded_bytes(opt_shape, opt_specs, mesh)

    # --- analytic activations for one microbatch's fwd+bwd on this chip ---
    B = per_device_batch  # per-data-parallel-shard batch
    S, D, L, V = seq_len, cfg.n_embd, cfg.n_layer, cfg.vocab_size
    # the widest kind's query heads where a kind has its own (layer_heads); the
    # output gate adds one (B, S, H) f32 a layer, which the coefficients below hold
    H = max([cfg.n_head] + [n for _, n in cfg.layer_heads or ()])
    # Block diffusion runs its layers over the stream of two copies of each
    # document; the head sees the noisy copy only (the logits below stay S).
    layer_S = 2 * S if cfg.block_diffusion is not None else S
    tp = mesh.shape.get("model", 1)
    pp = mesh.shape.get("pipe", 1)
    cbytes = jnp_itemsize(cfg.compute_dtype)
    # ln/qkv/attn-out residuals (~10·BSD) + the MLP hidden tensors: F/D
    # widths of it for GELU, 2F/D (gate+up) for SwiGLU. Default geometry
    # (F=4D, gelu) reproduces the original 14·BSD coefficient. GQA's k/v
    # are repeated to full H before attention (models.tinygpt), so no
    # activation credit is taken for kv_heads < n_head.
    F = cfg.mlp_dim
    mlp_widths = (2 if cfg.mlp_act == "swiglu" else 1) * F / D
    dense_per_layer = int((10 + mlp_widths) * B * layer_S * D) * cbytes
    # Megatron TP shards the head and MLP activations.
    dense_per_layer = dense_per_layer // max(tp, 1)
    if cfg.attention_impl == "reference":
        # scores + probs materialize per head, fp32 softmax: the O(S^2) term.
        # A sliding-window layer's too: the reference path masks a whole
        # (S, S) matrix, whatever the rule leaves of it (only the flash
        # kernels, which keep no score, visit the band alone).
        dense_per_layer += 2 * B * (H // max(tp, 1)) * layer_S * layer_S * 4
    layers_here = L // max(pp, 1)
    pol = tinygpt.normalize_remat("full" if cfg.remat == "auto" else cfg.remat)
    # What the layers' mixers keep for their backward beyond the coefficients
    # above (their kernels' residuals; the products 'full_keep_kernels' names
    # in them), each by its module (models/mixers/: kept_bytes).
    kinds = collections.Counter(k for k in cfg.layer_types or () if cfg.halves(k)[0])
    mixer_b = sum(layers * B * mixers.of(kind).kept_bytes(cfg, pol, S, cbytes)
                  for kind, layers in kinds.items())
    # What 'dots' and 'full_keep_kernels' keep by name beside those
    # (tinygpt._under_remat has the list): a routed layer's gate+up over the
    # rows its experts take, its router's float32 logits where the routing
    # trains and, under 'full_keep_kernels' alone ('dots' counts its matmul
    # results below), a dense SwiGLU layer's gate+up and the up product of a
    # shared expert that is not gated.
    named_b = 0
    if pol in ("dots", "full_keep_kernels"):
        tokens = B * layer_S
        moe_layers = cfg.n_moe_layers if cfg.capacity_factor is None else 0
        if moe_layers:
            from ..models.moe import held_buffer_rows

            rows = (tokens * cfg.expert_top_k if cfg.experts_held is None
                    else held_buffer_rows(cfg, tokens))
            named_b += moe_layers * rows * (2 if cfg.mlp_act == "swiglu" else 1) * F * cbytes
            if cfg.trains_routing:
                named_b += moe_layers * tokens * cfg.n_experts * 4
        if pol == "full_keep_kernels":
            if cfg.mlp_act == "relu2":  # a shared expert that is not gated (shared_dim 0: none)
                named_b += moe_layers * tokens * cfg.shared_dim * cbytes
            if cfg.mlp_act == "swiglu":
                dense_layers = L - cfg.n_moe_layers
                named_b += (dense_layers * tokens * 2 * (cfg.dense_mlp_hidden or F) * cbytes
                            // max(tp, 1))
        named_b //= max(pp, 1)
    if pol in ("full", "full_keep_kernels"):
        # Only the layer-boundary residual (+grad) survives (and, kept by
        # name, the attention kernel's output); one layer's working set is
        # live during its backward recompute.
        kept = 3 if pol == "full_keep_kernels" else 2
        act_b = layers_here * kept * B * layer_S * D * cbytes + dense_per_layer
    elif pol == "dots":
        # Matmul outputs are saved (~qkv 3BSD + attn-out BSD + mlp 5BSD +
        # boundary 2BSD ≈ 11·BSD per layer; the attention output is in
        # fact kept under flash too: apply_blocks saves the kernel's two
        # results by name); elementwise intermediates are recomputed
        # within one layer's working set.
        act_b = layers_here * 11 * B * layer_S * D * cbytes + dense_per_layer
    else:
        act_b = layers_here * dense_per_layer
    # fp32 logits + cotangent at the LM head.
    logits_b = 2 * B * S * V * 4

    dataset_b = dataset_size * seq_len * 4  # device-resident int32 table

    return HBMEstimate(
        params=params_b, grads=grads_b, opt_state=opt_b,
        activations=act_b + mixer_b + named_b, logits=logits_b, dataset=dataset_b,
    )


def jnp_itemsize(dtype: Any) -> int:
    return int(np.dtype(jax.numpy.dtype(dtype)).itemsize)


def format_breakdown(est: HBMEstimate, device_kind: str) -> str:
    b = est.breakdown()
    cap = device_hbm_bytes(device_kind)
    lines = [
        "Estimated per-chip HBM footprint:",
        f"  params:      {b['params_gib']:7.2f} GiB",
        f"  grads:       {b['grads_gib']:7.2f} GiB",
        f"  opt state:   {b['opt_state_gib']:7.2f} GiB",
        f"  activations: {b['activations_gib']:7.2f} GiB (analytic)",
        f"  logits:      {b['logits_gib']:7.2f} GiB",
        f"  dataset:     {b['dataset_gib']:7.2f} GiB",
        f"  total:       {b['total_gib']:7.2f} GiB"
        + (f" / {cap / 1024**3:.0f} GiB {device_kind}" if cap else ""),
    ]
    return "\n".join(lines)


# Headroom for remat-policy selection (resolve_auto_remat): the analytic
# estimate must stay below this fraction of HBM before a cheaper policy is
# chosen. Derived from the measured est->actual bias (docs/PERFORMANCE.md).
AUTO_REMAT_MARGIN = 0.70
# When the analytic margin rejects a policy but the estimate still fits
# nominal capacity, the resolver can ask XLA directly (an abstract AOT
# compile of the real step — train.step.abstract_step_peak_bytes) and
# accept on the MEASURED buffer-assignment peak. 0.96 of nominal keeps
# ~4% runtime headroom below XLA's own usable limit (~98.4% of nominal on
# v5e: "15.75G of 16G" in compiler OOM reports).
AOT_PROBE_ACCEPT_MARGIN = 0.96


def check_fits(
    est: HBMEstimate, device_kind: str, margin: float = 0.95
) -> Optional[str]:
    """Return a refusal message if the estimate exceeds usable capacity.

    ``margin`` reserves headroom for XLA scratch/fragmentation. Unknown
    device kinds (CPU hosts) are never refused.
    """
    cap = device_hbm_bytes(device_kind)
    if cap is None or est.total <= cap * margin:
        return None
    b = est.breakdown()
    hints = []
    if b["opt_state_gib"] + b["grads_gib"] > 0.4 * b["total_gib"]:
        hints.append("a sharded arm (fsdp/zero3) or more chips")
    if b["activations_gib"] > 0.3 * b["total_gib"]:
        hints.append("--remat, a smaller --per-device-batch, or --attention flash")
    hint = f" Try {' and '.join(hints)}." if hints else ""
    return (
        f"Estimated footprint {b['total_gib']:.1f} GiB exceeds "
        f"{cap / 1024**3:.0f} GiB on {device_kind} "
        f"(margin {margin:.0%}).{hint}\n{format_breakdown(est, device_kind)}"
    )


def resolve_auto_remat(
    model_config: Any,
    strategy: Any,
    mesh: Any,
    per_device_batch: int,
    seq_len: int,
    dataset_size: int = 0,
    device_kind: str = "",
    aot_probe: Optional[Any] = None,
) -> Any:
    """Resolve a strategy's remat="auto" to the cheapest policy that fits:
    "none" -> "dots" -> "full" against :func:`estimate_hbm` + :func:`check_fits`
    for this arm's (batch, seq, mesh); the strategy unchanged unless remat ==
    "auto". Unknown device kinds (CPU) are never refused, so they resolve to
    "none". The analytic choice uses a STRICTER margin than the go/no-go
    pre-flight (AUTO_REMAT_MARGIN vs check_fits' 0.95): measured peaks run up
    to ~13% above the estimate (XLA temp buffers the model ignores), so a
    nominal fit near capacity cannot be trusted; nor can a rejection there (a
    cheaper policy that does fit is a quarter faster). So with ``aot_probe`` (a
    callable (remat_policy) -> Optional[peak_bytes]: the harness wires
    train.step.abstract_step_peak_bytes) a policy in the ambiguous band (the
    margin rejects, the estimate still fits nominal capacity) is decided by an
    abstract AOT compile of the real step: accepted iff XLA's buffer-assignment
    peak fits AOT_PROBE_ACCEPT_MARGIN. One extra compile a probed policy."""
    if getattr(strategy, "remat", None) != "auto":
        return strategy
    cap = device_hbm_bytes(device_kind)
    for pol in ("none", "dots", "full"):
        cand = dataclasses.replace(strategy, remat=pol)
        cfg = dataclasses.replace(model_config, remat=pol)
        est = estimate_hbm(
            cfg, cand, mesh, per_device_batch, seq_len, dataset_size=dataset_size
        )
        if check_fits(est, device_kind, margin=AUTO_REMAT_MARGIN) is None:
            return cand
        # Probe band capped at the downstream pre-flight's own margin
        # (0.95): a probe-accepted policy must also pass check_fits in the
        # benchmark loop, or the resolver would hand back an arm the
        # pre-flight immediately refuses (where escalating would have run).
        if (
            aot_probe is not None and cap is not None
            and check_fits(est, device_kind) is None
        ):
            peak = aot_probe(pol)
            if peak is not None and peak <= cap * AOT_PROBE_ACCEPT_MARGIN:
                return cand
    # Nothing fits; return the most memory-frugal policy and let the
    # pre-flight check downstream produce the refusal message.
    return dataclasses.replace(strategy, remat="full")
