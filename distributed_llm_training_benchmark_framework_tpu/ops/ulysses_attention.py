"""Ulysses-style sequence parallelism — all-to-all head redistribution.

The second of the two canonical sequence-parallel attention schemes (the
DeepSpeed-Ulysses construction; ring attention in ``ops.ring_attention`` is
the other). Nothing like either exists in the reference (SURVEY §2.3: no
sequence parallelism anywhere; 16K+ contexts are future-work prose).

Mechanism: with the sequence dimension sharded over mesh axis ``seq`` (size
n), an ``all_to_all`` re-shards each of Q/K/V from sequence-sharded
(B, S/n, H, D) to head-sharded (B, S, H/n, D). Every device then runs the
ordinary *local* flash kernel over the FULL sequence for its 1/n of the
heads — no attention math changes at all — and a reverse all-to-all restores
sequence sharding on the output.

Trade-off vs ring (why both exist):
- Ulysses moves 4 all-to-alls of S*H*D/n elements each per call and reuses
  the peak-tuned flash kernel unchanged; parallelism is capped at
  n <= H (heads must divide).
- Ring moves (n-1) neighbor hops of 2*S*D/n (K,V) overlapped with compute,
  scales past the head count, but runs its own online-softmax merge.
On ICI both patterns map well (all_to_all uses the full torus bisection;
ppermute uses neighbor links); for moderate n and head-rich models Ulysses
usually wins on simplicity and kernel efficiency.

Attention-probability dropout: the local flash call uses the shared
coordinate-hash mask with a per-shard seed fold — the fold covers the seq
axis index AND any data/model shard indices (each attention shard in the
whole mesh draws from its own stream), so masks are unbiased and
decorrelated across head groups, batch shards, and tp shards alike, and
reproducible: the exact global mask is a pure function of (seed, shard ids)
the tests materialize and check against. (It is NOT bitwise-equal to the
mask the unsharded flash kernel would draw for the same seed — the
head-group seeding differs; flash<->ring keep that property instead.)
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P


def _shard_seed(seed: jax.Array, shard: jax.Array) -> jax.Array:
    """Per-shard dropout seed: decorrelate attention shards across the mesh."""
    return (seed + (shard.astype(jnp.uint32) + jnp.uint32(1))
            * jnp.uint32(0x9E3779B9)).astype(jnp.uint32)


def resolve_seq_mesh(
    mesh: Optional[jax.sharding.Mesh], axis_name: str
) -> Tuple[Optional[jax.sharding.Mesh], Optional[str], Optional[str]]:
    """Shared mesh resolution for the sequence-parallel wrappers (ring and
    Ulysses): discover the ambient mesh if none given, and name the axes the
    batch and head dims ride (for specs and dropout decorrelation). Returns
    (mesh-or-None, batch_axis, heads_axis); mesh None means "no seq axis in
    scope — fall back to plain flash"."""
    if mesh is None:
        m = jax.sharding.get_abstract_mesh()
        mesh = m if m is not None and axis_name in getattr(m, "axis_names", ()) else None
    if mesh is None or mesh.shape.get(axis_name, 1) == 1:
        return None, None, None
    batch_ax = "data" if mesh.shape.get("data", 1) > 1 else None
    model_ax = "model" if mesh.shape.get("model", 1) > 1 else None
    return mesh, batch_ax, model_ax


def _global_shard_index(axis_names) -> jax.Array:
    """Flatten this device's position along the given (present) mesh axes
    into one index — a unique per-attention-shard id for seed folding."""
    idx = jnp.zeros((), jnp.uint32)
    for ax in axis_names:
        if ax is None:
            continue
        idx = idx * jnp.uint32(lax.axis_size(ax)) + lax.axis_index(ax).astype(jnp.uint32)
    return idx


def ulysses_attention_sharded(
    q: jax.Array,  # (B, S_local, H, D) — this device's sequence shard
    k: jax.Array,
    v: jax.Array,
    axis_name: str = "seq",
    causal: bool = False,
    dropout_rate: float = 0.0,
    dropout_seed: Optional[jax.Array] = None,
    batch_axis: Optional[str] = None,
    heads_axis: Optional[str] = None,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Ulysses body; call inside shard_map with seq sharded on axis_name.

    ``batch_axis``/``heads_axis`` name the mesh axes (if any) the batch and
    head dims are sharded over — folded into the dropout seed so shards at
    the same local coordinates on different dp/tp shards do NOT share masks
    (the same hazard ring_attention_sharded's global offsets prevent).
    """
    from .flash_attention import flash_attention

    n = lax.axis_size(axis_name)
    B, Sl, H, D = q.shape
    if H % n != 0:
        raise ValueError(
            f"Ulysses needs heads % seq_parallel == 0, got H={H}, n={n} "
            "(use ring attention past the head count)"
        )

    def to_heads(t):  # (B, S/n, H, D) -> (B, S, H/n, D)
        return lax.all_to_all(t, axis_name, split_axis=2, concat_axis=1, tiled=True)

    qg, kg, vg = to_heads(q), to_heads(k), to_heads(v)

    seed = None
    rate = 0.0
    if dropout_rate > 0.0 and dropout_seed is not None:
        shard = _global_shard_index((batch_axis, heads_axis, axis_name))
        seed = _shard_seed(
            jnp.asarray(dropout_seed, jnp.uint32).reshape(()), shard
        )
        rate = dropout_rate
    out = flash_attention(
        qg, kg, vg, causal=causal, interpret=interpret,
        dropout_rate=rate, dropout_seed=seed,
    )  # (B, S, H/n, D)
    # heads-sharded -> seq-sharded
    return lax.all_to_all(out, axis_name, split_axis=1, concat_axis=2, tiled=True)


def ulysses_attention(
    q: jax.Array,  # (B, S, H, D) — full (mesh-visible) arrays
    k: jax.Array,
    v: jax.Array,
    causal: bool = False,
    axis_name: str = "seq",
    mesh: Optional[jax.sharding.Mesh] = None,
    dropout_rate: float = 0.0,
    dropout_seed: Optional[jax.Array] = None,
) -> jax.Array:
    """Shard the sequence over ``axis_name`` and run Ulysses. Falls back to
    plain flash when no such mesh axis is in scope (mirrors ring_attention's
    contract, so attention_impl='ulysses' runs anywhere)."""
    mesh, batch_ax, model_ax = resolve_seq_mesh(mesh, axis_name)
    if mesh is None:
        from .flash_attention import flash_attention

        return flash_attention(
            q, k, v, causal=causal,
            dropout_rate=dropout_rate, dropout_seed=dropout_seed,
        )

    spec = P(batch_ax, axis_name, model_ax, None)
    if dropout_seed is None:
        from .flash_attention import _warn_seedless_dropout

        _warn_seedless_dropout(dropout_rate, "ulysses_attention")
        seed = jnp.zeros((), jnp.uint32)
        dropout_rate = 0.0
    else:
        seed = jnp.asarray(dropout_seed, jnp.uint32).reshape(())

    def body(qs, ks, vs, seed_s):
        return ulysses_attention_sharded(
            qs, ks, vs, axis_name=axis_name, causal=causal,
            dropout_rate=dropout_rate, dropout_seed=seed_s,
            batch_axis=batch_ax, heads_axis=model_ax,
        )

    fn = jax.shard_map(
        body, mesh=mesh, in_specs=(spec, spec, spec, P()), out_specs=spec,
        # The Pallas kernel's out_shape carries no varying-axes annotation;
        # skip the vma checker for this map (the all_to_alls fix the types).
        check_vma=False,
    )
    return fn(q, k, v, seed)
