"""Collective matmul — tp projection comms decomposed into a ppermute ring.

Overlap round 3 (docs/PERFORMANCE.md §20). The plain tensor-parallel
lowering keeps the residual stream replicated over 'model' and pays a bare
activation all-gather (and a bare partial-sum all-reduce) at the
projections — collectives the scheduler can only overlap with *unrelated*
work, because the gather's consumer is the very dot waiting on it. The
collective-matmul formulation (Wang et al., ASPLOS'23 "Overlap
Communication with Dependent Computation via Decomposition"; the t5x/praxis
``collective_matmul`` passes) restructures the projection itself:

- the residual stream between projections rides SEQUENCE-sharded over the
  'model' axis (Megatron sequence-parallel layout — norms, residual adds
  and dropout are elementwise over the feature dim, so they stay local);
- entering a column-parallel projection (attention qkv, MLP up), the
  activation all-gather is split into per-shard sequence chunks rotated by
  ``ppermute``: each hop's chunk feeds one partial dot while the next chunk
  is in flight, so the comms hide INSIDE the matmul
  (:func:`ag_proj`);
- leaving a row-parallel projection (attention out, MLP down), the
  reduce-scatter is likewise a rotating-accumulator ring: each hop adds the
  partial product destined for the accumulator's current owner
  (:func:`rs_proj`).

Per projection that turns one bulk collective into n-1 neighbor
``ppermute`` hops interleaved with n dots — ICI-neighbor traffic with a
dependent-compute shadow to hide in, instead of a bisection-wide barrier.
The HLO signature (pinned by the ``llama-tp2-gqa-cmm`` graftcheck budget):
tp all-gathers at the projections -> 0, replaced by the ppermute ring,
reshard suspects 0.

Usable two ways, like ``ops.ring_attention``:
- ``ag_proj``/``rs_proj`` inside a jitted function running under a mesh
  with a >1 ``axis_name`` axis (they shard_map themselves over it, and
  fall back to the plain einsum when the axis is absent or 1 — so a
  ``tp_collective_matmul`` model still runs on a pure-dp mesh);
- ``ag_proj_sharded``/``rs_proj_sharded`` directly inside an existing
  shard_map.

Numerics: every dot accumulates in fp32 (``preferred_element_type``), the
ring accumulator is fp32, and the result downcasts once at the end — at
least as accurate as the plain path, whose partial-sum all-reduce runs on
the fp32 einsum output. Equivalence against the plain tp lowering (forward
AND grads) is pinned by ``tests/test_overlap.py`` on the 8-virtual-device
CPU mesh.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

def _tp_mesh(axis_name: str, mesh) -> Optional[jax.sharding.Mesh]:
    """The mesh in scope when ``axis_name`` is a >1 axis, else None."""
    if mesh is None:
        m = jax.sharding.get_abstract_mesh()
        mesh = (
            m if m is not None and axis_name in getattr(m, "axis_names", ())
            else None
        )
    if mesh is None or mesh.shape.get(axis_name, 1) <= 1:
        return None
    return mesh


def _batch_axes(mesh) -> Optional[Tuple[str, ...]]:
    """Mesh axes the activation batch dim is sharded over (cf.
    strategies.batch_partition_spec) — the ring only ever communicates
    along ``axis_name``; batch stays sharded on 'data'/'expert'."""
    axes = tuple(
        ax for ax in ("data", "expert") if mesh.shape.get(ax, 1) > 1
    )
    return axes or None


def _proj_einsum(x: jax.Array, w: jax.Array) -> jax.Array:
    """The projection contraction, fp32 accumulation, both weight ranks."""
    eq = "bsd,dcf->bscf" if w.ndim == 3 else "bsd,df->bsf"
    return jnp.einsum(eq, x, w, preferred_element_type=jnp.float32)


def ag_proj_sharded(
    x: jax.Array,  # (B, S_local, D) — this shard's sequence chunk
    w: jax.Array,  # (D, F_local) or (D, C, F_local) — local feature shard
    axis_name: str = "model",
) -> jax.Array:
    """All-gather-side collective matmul body: full-sequence output rows
    for the local feature shard, comms as a ppermute ring.

    Each of the n ring steps multiplies the currently-held sequence chunk
    with the local weight shard and writes the product into its global row
    slot; the chunk rotates one neighbor hop per step, so after n steps
    every device has computed all S rows of its F_local columns without a
    bulk all-gather ever materializing. Returns (B, S_total, F_local...)
    in x.dtype (fp32 accumulation internally).
    """
    n = lax.axis_size(axis_name)
    if n == 1:
        return _proj_einsum(x, w).astype(x.dtype)
    idx = lax.axis_index(axis_name)
    perm = [(j, (j + 1) % n) for j in range(n)]
    s_local = x.shape[1]
    out = jnp.zeros(
        (x.shape[0], s_local * n) + w.shape[1:], jnp.float32
    )
    chunk = x
    for i in range(n):
        # After i hops along j -> j+1, the chunk this device holds
        # originated at device (idx - i) mod n — that is its row slot.
        src = (idx - i) % n
        out = lax.dynamic_update_slice_in_dim(
            out, _proj_einsum(chunk, w), src * s_local, axis=1
        )
        if i < n - 1:
            chunk = lax.ppermute(chunk, axis_name, perm)
    return out.astype(x.dtype)


def rs_proj_sharded(
    y: jax.Array,  # (B, S_total, F_local) — full rows, local features
    w: jax.Array,  # (F_local, D) — local row shard
    axis_name: str = "model",
) -> jax.Array:
    """Reduce-scatter-side collective matmul body: the row-parallel
    partial sums accumulate around the ring instead of in a bulk
    reduce-scatter. Returns (B, S_local, D) — this shard's sequence chunk
    of the summed projection, in y.dtype (fp32 ring accumulator).

    Schedule: at step i device j contracts the sequence chunk
    ``(j - i + n - 1) mod n`` — chosen so each accumulator hop lands on
    the device that computes the SAME chunk next, and after n-1 hops the
    accumulator sits on its destination with all n partials folded in.
    """
    n = lax.axis_size(axis_name)
    if n == 1:
        return _proj_einsum(y, w).astype(y.dtype)
    if y.shape[1] % n != 0:
        # A non-dividing sequence would silently drop the trailing rows
        # from the ring's partial sums (the rs_proj wrapper guards this;
        # the sharded entry point must be loud too — it is documented
        # public API).
        raise ValueError(
            f"rs_proj_sharded: sequence length {y.shape[1]} does not "
            f"divide the '{axis_name}' ring size {n}"
        )
    idx = lax.axis_index(axis_name)
    perm = [(j, (j + 1) % n) for j in range(n)]
    s_local = y.shape[1] // n
    acc = jnp.zeros((y.shape[0], s_local, w.shape[-1]), jnp.float32)
    for i in range(n):
        if i:
            acc = lax.ppermute(acc, axis_name, perm)
        ci = (idx - i + n - 1) % n
        chunk = lax.dynamic_slice_in_dim(y, ci * s_local, s_local, axis=1)
        acc = acc + _proj_einsum(chunk, w)
    return acc.astype(y.dtype)


def _feature_sharded(
    w: jax.Array, n: int, aligned_units: Optional[int]
) -> bool:
    """Whether the projection's feature dim shards over the tp axis —
    MUST agree with strategies.param_partition_specs: 'model' lands on the
    feature axis iff it divides, and the GQA kv projection additionally
    demands the 'model' degree divide ``kv_heads`` (the kv-head-aligned
    rule; a misaligned split has no in-place reshard)."""
    if w.shape[-1] % n != 0:
        return False
    return aligned_units is None or aligned_units % n == 0


def ag_proj(
    x: jax.Array,  # (B, S, D) global activations
    w: jax.Array,  # (D, F) or (D, C, F) global weight
    *,
    axis_name: str = "model",
    mesh: Optional[jax.sharding.Mesh] = None,
    aligned_units: Optional[int] = None,
) -> jax.Array:
    """Column-parallel projection as a collective matmul.

    The activation enters sequence-sharded over ``axis_name`` (GSPMD
    reshards it there — a local slice when the producer was replicated,
    exact when the producer was the previous block's :func:`rs_proj`), the
    weight enters feature-sharded, and the output leaves feature-sharded
    with FULL sequence rows — what attention / the MLP nonlinearity needs.

    ``aligned_units`` gates feature sharding beyond plain divisibility
    (pass ``kv_heads`` for the GQA kv projection — the kv-head-aligned
    rule): a non-shardable weight enters replicated and the ring computes
    replicated full-feature outputs instead (each device still does one
    S x F worth of dot work — the chunks just cover all features).

    Falls back to the plain einsum when no >1 ``axis_name`` axis is in
    scope, or the sequence does not divide by it.
    """
    m = _tp_mesh(axis_name, mesh)
    n = 1 if m is None else m.shape[axis_name]
    if m is None or x.shape[1] % n != 0:
        return _proj_einsum(x, w).astype(x.dtype)
    b = _batch_axes(m)
    sharded = _feature_sharded(w, n, aligned_units)
    w_spec = P(*([None] * (w.ndim - 1)), axis_name if sharded else None)
    out_spec = P(b, None, *([None] * (w.ndim - 2)),
                 axis_name if sharded else None)
    fn = jax.shard_map(
        lambda xs, ws: ag_proj_sharded(xs, ws, axis_name=axis_name),
        mesh=m,
        in_specs=(P(b, axis_name, None), w_spec),
        out_specs=out_spec,
        check_vma=False,
    )
    return fn(x, w)


def rs_proj(
    y: jax.Array,  # (B, S, F) global, feature-sharded activations
    w: jax.Array,  # (F, D) global row-parallel weight
    *,
    axis_name: str = "model",
    mesh: Optional[jax.sharding.Mesh] = None,
) -> jax.Array:
    """Row-parallel projection as a collective matmul.

    The feature-sharded activation (a :func:`ag_proj` output, through the
    elementwise middle) contracts against the row-sharded weight; the
    partial sums fold around the ppermute ring and the output leaves
    sequence-sharded over ``axis_name`` — exactly the layout the next
    residual add and :func:`ag_proj` consume, so the stream between
    projections never re-replicates.

    Falls back to the plain einsum when no >1 ``axis_name`` axis is in
    scope, the contraction dim does not shard, or the sequence does not
    divide.
    """
    m = _tp_mesh(axis_name, mesh)
    n = 1 if m is None else m.shape[axis_name]
    if m is None or y.shape[1] % n != 0 or w.shape[0] % n != 0:
        return _proj_einsum(y, w).astype(y.dtype)
    b = _batch_axes(m)
    fn = jax.shard_map(
        lambda ys, ws: rs_proj_sharded(ys, ws, axis_name=axis_name),
        mesh=m,
        in_specs=(P(b, None, axis_name), P(axis_name, None)),
        out_specs=P(b, axis_name, None),
        check_vma=False,
    )
    return fn(y, w)
