"""Softmax attention, the mixer of the ``global`` and ``window`` kinds and of
a stack of one kind: the projections (fused, grouped-query or latent), QK-norm
and rotary, the dispatch to the attention bodies (``ops/flash_attention.py``,
ring, Ulysses, the ``jnp`` reference), the per-head output gate, and the
counters that read its mask rules. ``models/mixers/__init__.py`` has the table
and what each name here is asked for."""

from __future__ import annotations

import functools
from typing import TYPE_CHECKING, Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ...ops.flash_attention import FLASH_RESIDUAL_NAMES
from ...utils import scopes
from ..common import Params, _dropout, _norm, _rms_norm, normal

if TYPE_CHECKING:
    from ..tinygpt import TinyGPTConfig, YarnScaling

#: Its kinds of layer; a stack of one kind (no ``layer_types``: kind None) is its too.
KINDS = (scopes.GLOBAL, scopes.WINDOW)

#: Its stacks of the parameter tree, in the order ``init_params`` draws them
#: (the embedding and the head between the two).
STACKS = ("blocks", "dense_blocks")

CAST_NAMES = ()  # 'dots' and 'full_keep_kernels' keep the kernel's results, not a projection's
RESIDUAL_NAMES = FLASH_RESIDUAL_NAMES

AXIS_RULES = {
    # qkv is stored (layers, embed, 3, heads*head_dim) — the q/k/v axis is its
    # own dimension so sharding 'heads' on a tensor-parallel mesh axis never
    # crosses a q/k/v boundary.
    "blocks/wqkv": ("layers", "embed", "qkv3", "heads"),
    "blocks/bqkv": ("layers", "qkv3", "heads"),
    # GQA split projections (present instead of wqkv/bqkv when kv_heads <
    # n_head): q keeps its own matrix; k/v stack on a 'kv2' axis so sharding
    # 'kv_heads' never crosses the k/v boundary (same reasoning as qkv3).
    "blocks/wq": ("layers", "embed", "heads"),
    "blocks/bq": ("layers", "heads"),
    "blocks/wkv": ("layers", "embed", "kv2", "kv_heads"),
    "blocks/bkv": ("layers", "kv2", "kv_heads"),
    "blocks/wo": ("layers", "heads_merged", "embed"),
    "blocks/bo": ("layers", "embed"),
    # QK-norm scales (present when qk_norm): one per projected q / k feature,
    # or under qk_norm="head" one (head_dim,) vector that every head shares
    # (no strategy splits it over 'model': parallel/strategies._TP_RULES).
    "blocks/q_norm": ("layers", "heads"),
    "blocks/k_norm": ("layers", "kv_heads"),
    # Latent attention (present instead of wqkv / wkv when kv_lora_rank): wq
    # as above with heads of qk_dim; the shared down projection to
    # [latent | rotary key], the latent's norm scale, and the per-head
    # expansion to [k_nope | v].
    "blocks/wkv_a": ("layers", "embed", "latent_rope"),
    "blocks/kv_norm": ("layers", "latent"),
    "blocks/wkv_b": ("layers", "latent", "heads"),
    # The per-head output gate (present when attn_gate): one column a query
    # head, so it splits over 'model' as wq's columns do.
    "blocks/wg": ("layers", "embed", "gate_heads"),
}


NEEDS, check = "", lambda c: True  # its fields are TinyGPTConfig.__post_init__'s, one by one


def own(kinds) -> set:
    """Those of ``kinds`` that are this module's: they have a mask rule, a
    rotary table and the flash kernels (the others' counters are their own)."""
    return set(kinds) & {None, *KINDS}


def leaves(c, k, L: int, kind: Optional[str] = None) -> Params:
    """One stack's norm scales and attention leaves, L layers at the kind's
    head count (under ``block_halves`` the mixer's one norm), drawn from the
    key iterator ``k``."""
    D, H, Hkv, Dh = c.n_embd, c.heads(kind), c.kv_heads, c.head_dim
    ones = lambda shape: jnp.ones(shape, c.param_dtype)
    zeros = lambda shape: jnp.zeros(shape, c.param_dtype)
    blocks = {"ln1_scale": ones((L, D))}
    if not c.block_halves:
        blocks["ln2_scale"] = ones((L, D))
    if c.norm == "layernorm":
        blocks.update({f"{name[:3]}_bias": zeros((L, D)) for name in list(blocks)})
    if c.latent_attention:
        R, Dr = c.kv_lora_rank, c.qk_rope_head_dim
        blocks.update(
            wq=normal(c, next(k), (L, D, H * c.qk_dim)),
            wkv_a=normal(c, next(k), (L, D, R + Dr)),
            kv_norm=ones((L, R)),
            wkv_b=normal(c, next(k), (L, R, H * (c.qk_nope_head_dim + c.v_dim))),
        )
    elif Hkv == H:
        blocks["wqkv"] = normal(c, next(k), (L, D, 3, D))
        if c.bias:
            blocks["bqkv"] = zeros((L, 3, D))
    else:
        blocks["wq"] = normal(c, next(k), (L, D, H * Dh))
        blocks["wkv"] = normal(c, next(k), (L, D, 2, Hkv * Dh))
        if c.bias:
            blocks["bq"] = zeros((L, H * Dh))
            blocks["bkv"] = zeros((L, 2, Hkv * Dh))
    if c.qk_norm == "head":
        blocks.update(q_norm=ones((L, Dh)), k_norm=ones((L, Dh)))
    elif c.qk_norm:
        blocks.update(q_norm=ones((L, H * Dh)), k_norm=ones((L, Hkv * Dh)))
    blocks["wo"] = normal(c, next(k), (L, H * c.v_dim, D))
    if c.bias:
        blocks["bo"] = zeros((L, D))
    if c.attn_gate:
        blocks["wg"] = normal(c, next(k), (L, D, H))
    return blocks


def _rope(
    x: jax.Array,  # (B, S, H, Dh)
    positions: jax.Array,  # (S,) int32 global token positions
    theta: float,
    scaling: Optional[YarnScaling] = None,
    rotary_dim: Optional[int] = None,
) -> jax.Array:
    """Rotary position embedding, HF-Llama rotate-half convention.

    ``cos``/``sin`` are built over pairs (i, i + Dh/2) — x1 = first half,
    x2 = second half, x' = x*cos + cat(-x2, x1)*sin — matching HF
    ``apply_rotary_pos_emb`` exactly so the transformers parity test can
    load identical weights. fp32 rotation math, cast back to x.dtype.
    ``rotary_dim``: only the leading lanes rotate (rotate-half inside them,
    the frequencies over ``rotary_dim``); the rest pass as they are.
    """
    from ...ops.rotary import rope_angles

    if rotary_dim is not None and rotary_dim != x.shape[-1]:
        turned = _rope(x[..., :rotary_dim], positions, theta, scaling)
        return jnp.concatenate((turned, x[..., rotary_dim:]), axis=-1)
    Dh = x.shape[-1]
    half = Dh // 2
    freqs = rope_angles(positions, Dh, theta, scaling)  # (S, Dh/2)
    cos = jnp.cos(freqs)[None, :, None, :]  # (1, S, 1, Dh/2)
    sin = jnp.sin(freqs)[None, :, None, :]
    if scaling is not None and scaling.cos_sin_factor != 1.0:
        cos, sin = cos * scaling.cos_sin_factor, sin * scaling.cos_sin_factor
    xf = x.astype(jnp.float32)
    x1, x2 = xf[..., :half], xf[..., half:]
    out = jnp.concatenate((x1 * cos - x2 * sin, x2 * cos + x1 * sin), axis=-1)
    return out.astype(x.dtype)


def _whole_heads(q: jax.Array, k: jax.Array, v: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """k and v (B, S, KV, .) broadcast to q's head count, each kv head to its
    query group, for the attention bodies that take a k and a v a query head.
    Consecutive-block repetition matches the TP layout: query-head shard j
    needs exactly kv-head shard j when the 'model' degree divides kv_heads;
    when it does not, the kv-head-aligned spec rule keeps wkv replicated over
    'model' (strategies.param_partition_specs) so this never needs the
    partitioner's full-replicate resharding fallback."""
    rep = q.shape[2] // k.shape[2]
    if rep == 1:
        return k, v
    return jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)


def _attention(
    config: TinyGPTConfig,
    q: jax.Array,  # (B, S, H, Dh)
    k: jax.Array,  # (B, S, KV, Dh): the model's kv heads, H a multiple
    v: jax.Array,
    dropout_key: Optional[jax.Array],
    deterministic: bool,
    kind: Optional[str] = None,
) -> jax.Array:
    """Dispatch to the configured attention implementation. Returns (B,S,H,Dh).
    ``kind`` is the layer's (``TinyGPTConfig.layer_types``): its mask rule.

    'flash' takes k and v at their own head count (its kernels' index maps
    find a query head's kv head); every other body takes them broadcast to
    the query heads (``_whole_heads``).

    Attention-probability dropout (reference train_harness.py:116) applies in
    ALL THREE impls: materialized bernoulli in 'reference', and the shared
    global-coordinate hash mask in 'flash' (in-kernel) and 'ring' (per
    rotating K/V block) — the probabilities still never materialize in HBM
    for the latter two, and flash/ring produce bitwise-identical masks for
    equal seeds. 'reference' draws from a different RNG stream (bernoulli),
    so with dropout > 0 its parity vs flash/ring is statistical, not
    per-step exact; set dropout=0 for exact cross-impl loss comparison.
    """
    seed = None
    if not deterministic and config.dropout > 0.0 and dropout_key is not None:
        seed = jax.random.bits(dropout_key, (), jnp.uint32)
    # Which attention, never how: tiles and the backward's choice belong to
    # ops/flash_attention.py, which picks them from S, D and VMEM.
    kwargs = dict(
        causal=config.causal,
        dropout_rate=config.dropout if seed is not None else 0.0,
        dropout_seed=seed,
    )
    rule = config.mask_rule(q.shape[1], kind)
    if config.attention_impl != "flash":
        k, v = _whole_heads(q, k, v)
    if config.latent_attention and (
        config.seq_manual_axis is not None
        or config.attention_impl not in ("flash", "reference")
    ):
        raise ValueError(
            "latent attention runs attention_impl 'flash' or 'reference' outside "
            "the pipeline schedules; the ring and Ulysses bodies take one head "
            "width and their own scale"
        )
    if config.seq_manual_axis is not None:
        # Inside a shard_map that is manual over the sequence axis (the
        # pipeline schedules): q/k/v hold LOCAL sequence chunks, so dispatch
        # straight to the sharded attention bodies, which communicate over
        # that axis. The dropout seed is deliberately NOT per-shard here —
        # ring masks are keyed by global coordinates (all ring participants
        # must agree on the seed); Ulysses folds its own shard index.
        ax = config.seq_manual_axis
        if config.attention_impl == "ring":
            from ...ops.ring_attention import ring_attention_sharded

            return ring_attention_sharded(
                q, k, v, axis_name=ax, zigzag=config.ring_zigzag, **kwargs
            )
        if config.attention_impl == "ulysses":
            from ...ops.ulysses_attention import ulysses_attention_sharded

            return ulysses_attention_sharded(q, k, v, axis_name=ax, **kwargs)
        raise ValueError(
            "sequence-parallel pipeline needs attention_impl 'ring' or "
            f"'ulysses' (local '{config.attention_impl}' attention over a "
            "sequence chunk would silently compute blockwise attention)"
        )
    if config.attention_impl == "flash":
        # Pallas TPU kernel; fp32 online-softmax accumulation internally.
        from ...ops.flash_attention import flash_attention

        if config.attn_scale is not None:
            kwargs["scale"] = config.attn_scale
        kwargs["causal"] = rule
        return flash_attention(q, k, v, **kwargs)
    if config.attention_impl == "ring":
        from ...ops.ring_attention import ring_attention

        return ring_attention(q, k, v, zigzag=config.ring_zigzag, **kwargs)
    if config.attention_impl == "ulysses":
        from ...ops.ulysses_attention import ulysses_attention

        return ulysses_attention(q, k, v, **kwargs)

    # Reference jnp implementation: softmax(QK^T/sqrt(d))V with fp32 softmax.
    scale = config.attn_scale or 1.0 / (q.shape[-1] ** 0.5)
    scores = jnp.einsum(
        "bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32
    ) * scale
    if not isinstance(rule, bool):  # a rule that is an object says which pairs
        pos = jnp.arange(q.shape[1], dtype=jnp.int32)
        scores = jnp.where(
            rule.allowed(pos[:, None], pos[None, :]), scores, jnp.finfo(jnp.float32).min
        )
    elif config.causal:
        s = q.shape[1]
        mask = jnp.tril(jnp.ones((s, s), bool))
        scores = jnp.where(mask, scores, jnp.finfo(jnp.float32).min)
    probs = jax.nn.softmax(scores, axis=-1)
    # Parity: nn.MultiheadAttention applies dropout to attention probabilities
    # (reference train_harness.py:116).
    probs = _dropout(probs, config.dropout, dropout_key, deterministic)
    out = jnp.einsum(
        "bhqk,bkhd->bqhd", probs.astype(q.dtype), v, preferred_element_type=jnp.float32
    )
    return out.astype(q.dtype)


def _rotary_positions(c: TinyGPTConfig, S: int) -> jax.Array:
    """(S,) int32: the positions the S rows of a layer's q and k are rotated
    at. Global token positions; under a sequence-manual pipeline this shard
    holds positions [shard*S, shard*S + S) (same offset rule as the learned
    table's dynamic slice in embed()). The zigzag ring redistribution happens
    INSIDE ring_attention, after rotation, so the rotated rows travel with
    their tokens. Under block diffusion both copies of the document are at
    0..L-1."""
    pos = jnp.arange(S, dtype=jnp.int32)
    if c.seq_manual_axis is not None:
        pos = pos + S * lax.axis_index(c.seq_manual_axis)
    if c.block_diffusion is not None:
        pos = pos % (S // 2)
    return pos


def _takes_qk_prologue(c: TinyGPTConfig, S: int, kind: Optional[str] = None) -> bool:
    """Whether q and k of the stack's layers of ``kind``, S rows of them, are
    ``ops.rotary``'s operand: rotary over heads of whole 128-lane vregs, all of
    a head's lanes or its leading ``rotary_dim``. The per-head norm is the
    pass's first stage; a norm over all of a layer's features (OLMoE) stays in
    ``jnp`` before it. Not latent attention's, which rotates 64 of 192 lanes of
    q and a one-head key."""
    from ...ops import rotary as rotary_ops

    return (c.pos_embed == "rope" and not c.latent_attention
            and rotary_ops.fits(c.head_dim, S, c.rotary(kind).rotary_dim))


def qk_prologue_tables(c: TinyGPTConfig, S: int) -> Dict:
    """{kind of layer: the (S, head_dim) f32 table of cos and sin
    ``ops.rotary.qk_prologue`` rotates by} (``layer_types``; the one key None
    for a stack of one kind), made once for the whole stack and handed down
    to its layers. Empty where the stack's layers keep the
    ``jnp`` chain: another operand (``_takes_qk_prologue``), or a backend
    without the kernels."""
    from ...ops import rotary as rotary_ops

    if rotary_ops.kernel_mode() is None:
        return {}
    pos = _rotary_positions(c, S)
    kinds = sorted(own(c.layer_types)) if c.layer_types else (None,)
    return {
        kind: rotary_ops.table(pos, c.head_dim, c.rotary(kind).theta, c.rotary(kind).scaling,
                               c.rotary(kind).rotary_dim)
        for kind in kinds if _takes_qk_prologue(c, S, kind)
    }


def qk_prologue_stats(config: TinyGPTConfig, seq_len: int) -> Dict[str, Any]:
    """Counters of the pass between the projections and the flash kernels
    over sequences of ``seq_len`` tokens (a block-diffusion stream is twice
    that), from the config and the backend at trace time: ``rotary_layers``
    that rotate q and k at all, ``pass_layers`` of them that take
    ``ops.rotary``'s one pass here (the rest run the ``jnp`` chain: a cell
    that fell back says so), ``norm_stage_layers`` of those with the per-head
    norm inside the pass, and the bytes one layer's pass moves a sequence,
    ``forward_bytes`` and ``backward_bytes`` (of the first kind that takes
    it). ``by_kind`` has the same a kind of attention layer (the one key
    ``global`` for a stack of one kind), with its ``heads`` and the
    ``rotary_lanes`` of a head that rotate."""
    from ...ops import rotary as rotary_ops

    c = config
    S = seq_len * (2 if c.block_diffusion is not None else 1)
    head = c.qk_norm == "head"
    kinds = c.layer_types or (None,) * c.n_layer
    by_kind = {}
    for kind in sorted(own(kinds), key=str):
        layers = kinds.count(kind) if c.pos_embed == "rope" else 0
        taken = (layers > 0 and _takes_qk_prologue(c, S, kind)
                 and rotary_ops.kernel_mode() is not None)
        moved = rotary_ops.pass_bytes(
            S, c.heads(kind) * c.head_dim, c.kv_heads * c.head_dim,
            jnp.dtype(c.compute_dtype).itemsize, head) if taken else {"forward": 0, "backward": 0}
        by_kind[kind or scopes.GLOBAL] = {
            "heads": c.heads(kind),
            "rotary_lanes": (c.rotary(kind).rotary_dim or c.head_dim) if layers else 0,
            "rotary_layers": layers,
            "pass_layers": layers if taken else 0,
            "norm_stage_layers": layers if taken and head else 0,
            "forward_bytes": moved["forward"], "backward_bytes": moved["backward"],
        }
    total = lambda key: sum(entry[key] for entry in by_kind.values())
    first = next((e for e in by_kind.values() if e["pass_layers"]), None) or {}
    return {
        "rotary_layers": total("rotary_layers"),
        "pass_layers": total("pass_layers"),
        "norm_stage_layers": total("norm_stage_layers"),
        "forward_bytes": first.get("forward_bytes", 0),
        "backward_bytes": first.get("backward_bytes", 0),
        "by_kind": by_kind,
    }


def sublayer(
    c: TinyGPTConfig,
    x: jax.Array,
    layer: Params,
    dropout_key: Optional[jax.Array],
    deterministic: bool,
    kind: Optional[str] = None,
    qk_tables: Optional[Dict] = None,
) -> jax.Array:
    """Norm -> q/k/v projections -> QK-norm -> rope -> attention -> (the
    per-head output gate) -> output projection -> residual: the first half of
    ``_block``, at the kind's head count (``TinyGPTConfig.heads``). Where the stack's
    q and k are ``ops.rotary``'s operand and the backend runs its kernels
    (``qk_prologue_tables`` has this kind's tables), the per-head norm and
    the rotation are its one pass; else the ``jnp`` chain ``_rms_norm`` ->
    ``_rope``, which is also what the pass is tested against."""
    B, S, D = x.shape
    cd = c.compute_dtype
    H = c.heads(kind)
    use_cmm = c.tp_collective_matmul
    if use_cmm:
        from ...ops import collective_matmul as _cm

    h = _norm(c, x, layer["ln1_scale"], layer.get("ln1_bias"))
    if c.latent_attention:
        return x + _latent_attention(c, h, layer, dropout_key, deterministic)
    if "wqkv" in layer:  # fused MHA projection (kv_heads == n_head)
        if use_cmm:
            qkv = _cm.ag_proj(h, layer["wqkv"].astype(cd)).astype(cd)
        else:
            qkv = jnp.einsum(
                "bsd,dce->bsce", h, layer["wqkv"].astype(cd), preferred_element_type=jnp.float32
            ).astype(cd)
        if "bqkv" in layer:
            qkv = qkv + layer["bqkv"].astype(cd)
        q, k, v = (qkv[:, :, i] for i in range(3))
    else:  # GQA: separate q and stacked k/v projections
        if use_cmm:
            q = _cm.ag_proj(h, layer["wq"].astype(cd)).astype(cd)
            # kv rides the kv-head-aligned rule (aligned_units): with a
            # misaligned 'model' degree the weight enters replicated and
            # the ring produces replicated full-kv outputs.
            kv = _cm.ag_proj(
                h, layer["wkv"].astype(cd), aligned_units=c.kv_heads
            ).astype(cd)
        else:
            q = jnp.einsum(
                "bsd,de->bse", h, layer["wq"].astype(cd), preferred_element_type=jnp.float32
            ).astype(cd)
            kv = jnp.einsum(
                "bsd,dce->bsce", h, layer["wkv"].astype(cd), preferred_element_type=jnp.float32
            ).astype(cd)
        if "bq" in layer:
            q = q + layer["bq"].astype(cd)
            kv = kv + layer["bkv"].astype(cd)
        k, v = kv[:, :, 0], kv[:, :, 1]
    if c.qk_norm and c.qk_norm != "head":
        q = _rms_norm(q, layer["q_norm"], c.norm_eps)
        k = _rms_norm(k, layer["k_norm"], c.norm_eps)
    if qk_tables is None:
        qk_tables = qk_prologue_tables(c, S)
    head_norm = c.qk_norm == "head"
    v = v.reshape(B, S, c.kv_heads, c.head_dim)
    if kind in qk_tables:
        from ...ops import rotary as rotary_ops

        scales = (layer["q_norm"], layer["k_norm"]) if head_norm else (None, None)
        with jax.named_scope(scopes.QK_PROLOGUE):
            q, k = rotary_ops.qk_prologue(  # -> (B, S, heads, head_dim)
                q, k, *scales, qk_tables[kind], c.norm_eps,
                interpret=rotary_ops.kernel_mode(), rotary_dim=c.rotary(kind).rotary_dim)
    else:  # the jnp chain
        q = q.reshape(B, S, H, c.head_dim)
        k = k.reshape(B, S, c.kv_heads, c.head_dim)
        if head_norm:
            q = _rms_norm(q, layer["q_norm"], c.norm_eps)
            k = _rms_norm(k, layer["k_norm"], c.norm_eps)
        if c.pos_embed == "rope":
            rotary = c.rotary(kind)
            pos = _rotary_positions(c, S)
            q = _rope(q, pos, rotary.theta, rotary.scaling, rotary.rotary_dim)
            k = _rope(k, pos, rotary.theta, rotary.scaling, rotary.rotary_dim)
    attn = _attention(c, q, k, v, dropout_key, deterministic, kind)
    if "wg" in layer:
        with jax.named_scope(scopes.ATTN_GATE):
            gate = jax.nn.sigmoid(jnp.einsum(  # (B, S, H) f32: one scalar a head a token
                "bsd,dh->bsh", h, layer["wg"].astype(cd), preferred_element_type=jnp.float32))
            attn = (attn.astype(jnp.float32) * gate[..., None]).astype(cd)
    attn = attn.reshape(B, S, H * c.head_dim)
    if use_cmm:
        attn = _cm.rs_proj(attn, layer["wo"].astype(cd)).astype(cd)
    else:
        attn = jnp.einsum(
            "bsd,de->bse", attn, layer["wo"].astype(cd), preferred_element_type=jnp.float32
        ).astype(cd)
    if "bo" in layer:
        attn = attn + layer["bo"].astype(cd)
    return x + attn


def _latent_attention(
    c: TinyGPTConfig,
    h: jax.Array,  # (B, S, D), the normed input
    layer: Params,
    dropout_key: Optional[jax.Array],
    deterministic: bool,
) -> jax.Array:
    """MLA as DeepSeek-V2 computes it in training (no absorbed matrices: k and
    v are expanded per head), in three scopes: ``mla_proj`` (the three
    projections, the latent's norm, rotary, assembling k), ``mla_core`` (the
    attention itself: the flash kernels at qk_dim over v_dim) and
    ``mla_out``. One departure from the source's ``modeling_deepseek.py``: it
    de-interleaves q_pe / k_pe before rotate-half; with seeded weights that is
    one fixed permutation of both and leaves q k^T unchanged."""
    B, S, _ = h.shape
    cd = c.compute_dtype
    H, Dn, Dr, Dv, R = (c.n_head, c.qk_nope_head_dim, c.qk_rope_head_dim,
                        c.v_dim, c.kv_lora_rank)
    proj = functools.partial(jnp.einsum, preferred_element_type=jnp.float32)
    with jax.named_scope(scopes.MLA_PROJ):
        q = proj("bsd,de->bse", h, layer["wq"].astype(cd)).astype(cd)
        q = q.reshape(B, S, H, Dn + Dr)
        kv_a = proj("bsd,de->bse", h, layer["wkv_a"].astype(cd)).astype(cd)
        latent = _rms_norm(kv_a[..., :R], layer["kv_norm"], c.norm_eps)
        kv_b = proj("bsr,re->bse", latent, layer["wkv_b"].astype(cd)).astype(cd)
        kv_b = kv_b.reshape(B, S, H, Dn + Dv)
        if c.mla_nope:  # the 64 shared columns as they are: nothing rotates q or k
            k_pe = kv_a[:, :, None, R:]
        else:
            pos = jnp.arange(S, dtype=jnp.int32)
            q_pe = _rope(q[..., Dn:], pos, c.rope_theta, c.rope_scaling)
            k_pe = _rope(kv_a[:, :, None, R:], pos, c.rope_theta, c.rope_scaling)
            q = jnp.concatenate((q[..., :Dn], q_pe), axis=-1)
        k = jnp.concatenate(
            (kv_b[..., :Dn], jnp.broadcast_to(k_pe, (B, S, H, Dr))), axis=-1
        )
        v = kv_b[..., Dn:]
    with jax.named_scope(scopes.MLA_CORE):
        attn = _attention(c, q, k, v, dropout_key, deterministic)
    with jax.named_scope(scopes.MLA_OUT):
        return proj(
            "bse,ed->bsd", attn.reshape(B, S, H * Dv), layer["wo"].astype(cd)
        ).astype(cd)


def _kv_heads_in_kernel(config: TinyGPTConfig, kind: Optional[str] = None) -> int:
    """The head count k and v enter a ``kind`` layer's attention body with:
    the model's ``kv_heads`` where the flash kernels read them as they are
    (``ops.flash_attention.kv_heads_in_kernel``: under the mesh this is called
    in), the query heads' where a body takes whole heads (``_whole_heads``)
    or the layer makes a k and a v a head itself (latent attention)."""
    from ...ops import flash_attention as fa

    heads = config.heads(kind)
    if config.attention_impl != "flash" or config.latent_attention:
        return heads
    return fa.kv_heads_in_kernel(heads, config.kv_heads)


def bd_mask_stats(config: TinyGPTConfig, seq_len: int) -> Dict[str, int]:
    """Counters of one head's attention over documents of ``seq_len`` tokens
    under ``block_diffusion``, from the mask rule (no array is made): the true
    pairs, and what the forward and the backward kernel visit of all there
    is, at the tiles and pieces ``ops.flash_attention`` picks for the stream
    and in the unit each kernel skips by (``visited_units``): the (piece,
    piece) piece where the rule gives its tiles shapes (``*_live_tiles``
    pieces visited of ``*_tiles``, ``*_tile_pairs`` pairs a piece), the
    whole tile where it does not; ``*_grid_steps``, the steps a head's grid
    makes, and ``*_tile_fetches``, the times its walk changes the tile its
    inner operand's blocks address (K forward, q backward: the copies the
    pipeline issues; ``ops.flash_attention.tile_fetches``, from the index
    maps' own function: a dead step addresses a live tile of its row and
    brings nothing); and ``kv_heads_in_kernel``, the heads of k and v the
    kernels were handed (``_kv_heads_in_kernel``)."""
    from ...ops import flash_attention as fa

    S = 2 * seq_len
    rule = config.mask_rule(S)
    bq, bk, bk_bwd, _ = fa.pick_tiles(S, config.qk_dim, config.compute_dtype, causal=rule)
    live_fwd, all_fwd, unit_fwd = fa.visited_units(rule, S, bq, bk, fa._fwd_sub_k(bk))
    live_bwd, all_bwd, unit_bwd = fa.visited_units(
        rule, S, bq, bk_bwd, fa._bwd_sub_q(bq, config.dropout))
    return {
        "true_pairs": rule.tile_counts(bq, bk)[2],
        "fwd_live_tiles": live_fwd, "fwd_tiles": all_fwd, "fwd_tile_pairs": unit_fwd,
        "bwd_live_tiles": live_bwd, "bwd_tiles": all_bwd, "bwd_tile_pairs": unit_bwd,
        **_walk_counts(rule, S, bq, bk, bk_bwd),
        "kv_heads_in_kernel": _kv_heads_in_kernel(config),
    }


def _walk_counts(rule, S: int, bq: int, bk: int, bk_bwd: int) -> Dict[str, int]:
    """``*_grid_steps`` and ``*_tile_fetches`` of one head's forward and
    fused-backward walk under ``rule`` (``bd_mask_stats``)."""
    from ...ops import flash_attention as fa

    return {f"{name}_{count}": fn(rule, S, bq, keys, name == "fwd")
            for name, keys in (("fwd", bk), ("bwd", bk_bwd))
            for count, fn in (("grid_steps", fa.grid_steps), ("tile_fetches", fa.tile_fetches))}


def attn_mask_stats(config: TinyGPTConfig, seq_len: int) -> Dict[str, Dict[str, int]]:
    """Counters of one head's attention over ``seq_len`` positions by kind of
    layer (``layer_types``; one entry, ``global``, for a stack of one kind),
    from each kind's mask rule at the tiles and pieces ``ops.flash_attention``
    picks (no array is made): ``layers`` of the kind, ``true_pairs`` the rule
    allows, the kind's query ``heads`` and the ``kv_heads_in_kernel`` its k and v
    entered the kernels with (``_kv_heads_in_kernel``: the model's kv heads
    where the index maps do the sharing, ``heads`` where k and v were
    repeated or nothing is shared), the (queries, keys) ``fwd_tile`` and
    ``bwd_tile`` taken, and for the forward and the fused backward kernel ``*_live_tiles``
    (tiles that hold a pair), ``*_grid_steps`` (steps a head's grid makes: the
    square's under causal, the band's under a window; the difference
    multiplies nothing and brings nothing: it addresses a live tile of its
    row), ``*_tile_fetches`` (``bd_mask_stats``: the live tiles, or fewer
    where a row starts on the tile the row before ended on) and
    ``*_pairs_multiplied`` (the area of what the bodies walk: a *lower* tile's
    pieces on and below its piece diagonal, else whole tiles)."""
    from ...ops import flash_attention as fa

    kinds = config.layer_types or (scopes.GLOBAL,) * config.n_layer
    stats = {}
    for kind in sorted(own(kinds)):
        rule = config.mask_rule(seq_len, kind if config.layer_types else None)
        bq, bk, bk_bwd, _ = fa.pick_tiles(
            seq_len, config.qk_dim, config.compute_dtype, causal=rule)
        window = isinstance(rule, fa.SlidingWindow)
        entry = {"layers": kinds.count(kind),
                 "heads": config.heads(kind if config.layer_types else None),
                 "kv_heads_in_kernel": _kv_heads_in_kernel(
                     config, kind if config.layer_types else None),
                 "fwd_tile": (bq, bk), "bwd_tile": (bq, bk_bwd),
                 "true_pairs": (rule.true_pairs(seq_len) if window
                                else seq_len * (seq_len + 1) // 2 if rule else seq_len ** 2)}
        for name, keys, piece in (("fwd", bk, fa._fwd_sub_k(bk)),
                                  ("bwd", bk_bwd, fa._bwd_sub_q(bq, config.dropout))):
            units, _, unit_pairs = fa.visited_units(rule, seq_len, bq, keys, piece)
            tiles = fa.tiles_by_shape(rule, seq_len, bq, keys, piece)
            entry[f"{name}_live_tiles"] = int(sum(t.sum() for t in tiles.values()))
            entry[f"{name}_pairs_multiplied"] = units * unit_pairs
        entry.update(_walk_counts(rule, seq_len, bq, bk, bk_bwd))
        stats[kind] = entry
    return stats


def _window_tokens(c) -> float:
    """Keys a token of a ``window`` layer meets, the mean over a sequence."""
    S = c.block_size
    W = min(c.sliding_window, S)
    return (W * (W + 1) / 2 + (S - W) * W) / S


def forward_flops_per_token(c, kind: Optional[str] = None) -> float:
    """One attention layer of ``kind``, a token: the projections at the kind's
    head count (latent attention's three at their own widths; the output
    gate's 2 D H) and the scores and values over the keys a token meets: all
    S, S / 2 under a causal mask (the kernels skip masked tiles; the exact
    share is (S + tile) / 2S, and 1 / 2 keeps rows comparable across tiles), a
    ``window`` layer's true pairs. Under block diffusion by the DATA token: 2 x
    the matmuls (the stream's two copies) and S + block keys a data token."""
    D, S = c.n_embd, c.block_size
    tokens, copies = (S / 2 if c.causal else S), 1
    if c.block_diffusion is not None:
        tokens, copies = S + c.block_diffusion.block, 2
    if kind == scopes.WINDOW:
        tokens = _window_tokens(c)
    if c.latent_attention:
        H, R, Dn, Dr, Dv = c.n_head, c.kv_lora_rank, c.qk_nope_head_dim, c.qk_rope_head_dim, c.v_dim
        projections = (2 * D * H * (Dn + Dr) + 2 * D * (R + Dr) + 2 * R * H * (Dn + Dv)
                       + 2 * H * Dv * D)
        scores = 2 * tokens * H * (Dn + Dr + Dv)
    else:
        H, Dh = c.heads(kind), c.head_dim
        projections = (2 * D * (H + 2 * c.kv_heads) * Dh + 2 * H * Dh * D
                       + (2 * D * H if c.attn_gate else 0))
        scores = 4 * tokens * H * Dh
    return copies * projections + scores


def kept_bytes(c, pol: str, S: int, cbytes: int) -> int:
    """Nothing beyond ``utils.memory.estimate_hbm``'s coefficients a layer,
    which are attention's (the flash kernel's output is among them)."""
    return 0
