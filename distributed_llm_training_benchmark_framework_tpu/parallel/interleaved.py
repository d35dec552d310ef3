"""Interleaved 1F1B — virtual pipeline stages that actually shrink the bubble.

The plain 1F1B schedule in ``parallel.pipeline`` is the lockstep variant: its
fill/drain bubble is identical to GPipe's (2*(P-1) full-stage units); the win
is memory only. This module implements the Megatron-LM *interleaved* schedule
(Narayanan et al. 2021, "Efficient Large-Scale Language Model Training on GPU
Clusters"): each device owns V non-contiguous layer chunks — global pipeline
position j in [0, P*V) maps to device j % P, chunk j // P — so a microbatch
rides the ring V times through chunks 1/V the size. Fill/drain cost drops to
2*(P-1) *chunk* units versus the non-interleaved 2*(P-1)*V: the bubble
fraction falls by ~V.

TPU-native construction (nothing like Megatron's process-per-stage runtime):

- **Static schedule, SPMD execution.** A greedy list scheduler
  (``build_schedule``, plain numpy at trace time) simulates the whole run —
  each device executes ONE chunk-forward or ONE chunk-backward per tick,
  messages take one tick per ring hop — and emits per-(tick, stage) tables:
  which (microbatch, chunk) to run, which buffer slots to read/write, what to
  send. The executor replays the tables with a ``lax.scan`` over the stacked
  table rows inside a ``shard_map`` manual over 'pipe' — ONE compiled tick
  body regardless of how long the accumulation chain is. Per tick, a
  ``lax.switch`` on the device's scheduled kind runs exactly one unit
  (device-varying control flow — legal in the manual region), then ONE fwd
  ``ppermute`` and ONE bwd ``ppermute`` move whatever was produced (zeros on
  idle links). Collectives stay unconditional and uniform — no deadlock
  surface.
- **Rolling buffers, slot-allocated by the scheduler.** Arriving activations
  / gradients park in pending buffers; forward inputs persist in a residual
  buffer until their backward rematerializes the chunk under ``jax.vjp``
  (same per-stage recompute policy as the plain 1F1B). Smallest-free-slot
  allocation bounds every buffer at its true max concurrency — O(P*V),
  independent of M (tests assert both properties).
- **No forward unit at the last position.** The final chunk's output is only
  ever consumed by its own backward, which rematerializes the chunk from its
  input anyway — so position P*V-1 schedules no F unit at all: its backward
  (the "head" unit) consumes the parked incoming activation directly and
  computes loss value + chunk/head/input cotangents in ONE vjp. Saves M
  chunk-forwards per step and their schedule slots.
- **Permuted layer stacking.** Device d must own global layers of chunks
  {v*P + d}: ``layer_permutation`` reorders the stacked block weights so the
  contiguous 'pipe' sharding of ``pipeline_param_specs`` lands each chunk on
  its device. Params (and grads, and Adam state) live in this layout for the
  whole run — checkpoints record the layout and refuse a mismatched resume.
  Dropout keys use GLOBAL layer indices, so the math is layout-independent.

Constraints: n_layer % (pipe * virtual) == 0. MoE composes: each chunk's
forward returns its layers' Switch load-balance aux alongside the
activation, F units (and the head unit, whose chunk has no F) accumulate
the primal aux, and every chunk backward seeds the constant aux cotangent
coef/(n_layer*n_micro) — the same accounting gpipe/1f1b use, per chunk
instead of per stage. Sequence parallelism composes the same way as the
other schedules (manual
over ('pipe','seq'), sharded ring/Ulysses attention, CE psum over 'seq') —
with one backend-specific execution detail. With sp>1 the unit bodies
contain 'seq'-axis collectives, and the per-tick ``lax.switch`` index varies
across pipe stages. Each 'seq' collective's participants all share a pipe
stage, so every participant takes the same branch — uniform-across-
participants, which is what the SPMD model requires — but XLA:CPU's thunk
runtime rendezvouses ALL local devices per collective instruction, so pipe
stage 0 sitting in the FWD branch's ring ppermute while stage 1 sits in the
BWD branch's CE psum aborts the process (rendezvous timeout, observed as
SIGABRT with "Expected 4 threads to join the rendezvous, but only 2
arrived"). On CPU with sp>1 the executor therefore runs every unit kind
unconditionally and selects outputs by mask — one uniform collective
sequence on every device, at the price of ~2-3x per-tick compute. That
price is paid only where it buys testability; the TPU path keeps the
single-unit switch.
"""

from __future__ import annotations

import dataclasses
import heapq
from typing import Dict, List, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from ..models import tinygpt
from .pipeline import (
    AXIS, _key_data_or_none, _rebuild_key, _seq_setup, _stage_iota,
    pipeline_param_specs,
)

IDLE, FWD, BWD = 0, 1, 2

# Table names stacked into the executor's lax.scan xs, in order.
_TABLES = (
    "kind", "unit_m", "unit_v", "f_src", "b_src", "b_head",
    "resid_rw", "park_f", "park_b", "send_f", "send_b",
)


@dataclasses.dataclass(frozen=True)
class Schedule:
    """Static interleaved-1F1B schedule for (P stages, V chunks, M micro).

    All tables are (T, P) int32; -1 means "not applicable this tick".
    """

    P: int
    V: int
    M: int
    ticks: int
    kind: np.ndarray          # IDLE/FWD/BWD
    unit_m: np.ndarray        # microbatch index of this tick's unit
    unit_v: np.ndarray        # chunk index of this tick's unit
    f_src: np.ndarray         # FWD: pend_f slot to read (-2 = embed injection)
    b_src: np.ndarray         # BWD: pend_b slot (b_head=0) / pend_f slot (=1)
    b_head: np.ndarray        # 1 iff this BWD unit is the last position
    resid_rw: np.ndarray      # FWD: slot to write x_in / BWD: slot to read
    park_f: np.ndarray        # slot to park the arriving fwd message (-1 none)
    park_b: np.ndarray        # slot to park the arriving bwd message (-1 none)
    send_f: np.ndarray        # 1 iff this tick's F output goes on the fwd ring
    send_b: np.ndarray        # 1 iff this tick's B output goes on the bwd ring
    pend_f_slots: int
    pend_b_slots: int
    resid_slots: int

    @property
    def bubble_fraction(self) -> float:
        """Idle fraction of the schedule (unit-ticks wasted / total)."""
        work = self.M * (self.P * self.V - 1) + self.M * self.P * self.V
        return 1.0 - work / float(self.ticks * self.P)


def build_schedule(P: int, V: int, M: int) -> Schedule:
    """Greedy lockstep list-scheduler (the 'alternate' policy).

    Per tick each device picks one ready unit: after a backward it prefers a
    forward (the 1F1B steady-state alternation — strict backward-greedy
    measures 1-14 ticks worse at P=4); forwards prefer the DEEPEST ready
    position (drain in-flight microbatches before injecting new ones, which
    bounds residual liveness), backwards the oldest microbatch.

    Readiness: F(m,0) is always ready (embed is local); F(m,j) one tick after
    F(m,j-1) ran on the previous ring device. Position PV-1 has NO forward
    unit — B(m, PV-1) becomes ready one tick after F(m, PV-2) (its input has
    arrived) and does loss + chunk vjp in place; B(m,j) one tick after
    B(m,j+1).
    """
    PV = P * V
    fwd_done: Dict[Tuple[int, int], int] = {}
    bwd_done: Dict[Tuple[int, int], int] = {}
    last_was_b = [False] * P

    rows: List[dict] = []  # per tick: {d: (kind, m, j)}
    t = 0
    while len(bwd_done) < M * PV:
        if t > 8 * (2 * M * V + 4 * PV) + 64:
            raise RuntimeError(
                f"interleaved schedule did not converge (P={P}, V={V}, M={M})"
            )
        sel = {}
        for d in range(P):
            fcands, bcands = [], []
            for m in range(M):
                for v in range(V):
                    j = v * P + d
                    if j != PV - 1 and (m, j) not in fwd_done:
                        if j == 0:
                            fcands.append((m, j))
                        else:
                            pm = fwd_done.get((m, j - 1))
                            if pm is not None and pm + 1 <= t:
                                fcands.append((m, j))
                    if (m, j) not in bwd_done:
                        if j == PV - 1:
                            pm = fwd_done.get((m, j - 1))
                            if pm is not None and pm + 1 <= t:
                                bcands.append((m, j))
                        elif (m, j) in fwd_done:
                            nb = bwd_done.get((m, j + 1))
                            if nb is not None and nb + 1 <= t:
                                bcands.append((m, j))
            fcands.sort(key=lambda mj: (-mj[1], mj[0]))
            bcands.sort(key=lambda mj: (mj[0], -mj[1]))
            if last_was_b[d] and fcands:
                sel[d] = (FWD, *fcands[0])
            elif bcands:
                sel[d] = (BWD, *bcands[0])
            elif fcands:
                sel[d] = (FWD, *fcands[0])
        for d, (kind, m, j) in sel.items():
            if kind == FWD:
                fwd_done[(m, j)] = t
                last_was_b[d] = False
            else:
                bwd_done[(m, j)] = t
                last_was_b[d] = True
        rows.append(sel)
        t += 1
    T = t

    # --- second pass: buffer-slot allocation from the committed schedule ---
    shape = (T, P)
    kind = np.zeros(shape, np.int32)
    unit_m = np.full(shape, -1, np.int32)
    unit_v = np.full(shape, -1, np.int32)
    f_src = np.full(shape, -1, np.int32)
    b_src = np.full(shape, -1, np.int32)
    b_head = np.zeros(shape, np.int32)
    resid_rw = np.full(shape, -1, np.int32)
    park_f = np.full(shape, -1, np.int32)
    park_b = np.full(shape, -1, np.int32)
    send_f = np.zeros(shape, np.int32)
    send_b = np.zeros(shape, np.int32)

    # Smallest-free-slot allocation so the high-watermark equals the true
    # max concurrency (the buffer-size claim tests assert O(P*V)).
    pend_f_free = [list(range(4 * PV + 4)) for _ in range(P)]
    pend_b_free = [list(range(4 * PV + 4)) for _ in range(P)]
    resid_free = [list(range(4 * PV + 4)) for _ in range(P)]
    pend_f_of: Dict[Tuple[int, int], int] = {}  # (m, j-consumer) -> slot
    pend_b_of: Dict[Tuple[int, int], int] = {}
    resid_of: Dict[Tuple[int, int], int] = {}
    hi_f = hi_b = hi_r = 0

    for t, sel in enumerate(rows):
        # arrivals first: a message sent at t-1 parks at t (possibly consumed
        # later the same tick).
        if t > 0:
            for d, (k, m, j) in rows[t - 1].items():
                if k == FWD:  # every scheduled F unit sends (PV-1 has none)
                    dst = (d + 1) % P
                    slot = heapq.heappop(pend_f_free[dst])
                    hi_f = max(hi_f, slot + 1)
                    pend_f_of[(m, j + 1)] = slot
                    park_f[t, dst] = slot
                elif k == BWD and j != 0:
                    dst = (d - 1) % P
                    slot = heapq.heappop(pend_b_free[dst])
                    hi_b = max(hi_b, slot + 1)
                    pend_b_of[(m, j - 1)] = slot
                    park_b[t, dst] = slot
        for d, (k, m, j) in sel.items():
            kind[t, d] = k
            unit_m[t, d] = m
            unit_v[t, d] = j // P
            if k == FWD:
                if j == 0:
                    f_src[t, d] = -2
                else:
                    slot = pend_f_of.pop((m, j))
                    f_src[t, d] = slot
                    heapq.heappush(pend_f_free[d], slot)
                rslot = heapq.heappop(resid_free[d])
                hi_r = max(hi_r, rslot + 1)
                resid_of[(m, j)] = rslot
                resid_rw[t, d] = rslot
                send_f[t, d] = 1
            elif j == PV - 1:
                # Head unit: consumes the parked incoming activation directly
                # (no residual, no F unit existed for this position).
                slot = pend_f_of.pop((m, j))
                b_src[t, d] = slot
                b_head[t, d] = 1
                heapq.heappush(pend_f_free[d], slot)
                send_b[t, d] = 1
            else:
                slot = pend_b_of.pop((m, j))
                b_src[t, d] = slot
                heapq.heappush(pend_b_free[d], slot)
                rslot = resid_of.pop((m, j))
                resid_rw[t, d] = rslot
                heapq.heappush(resid_free[d], rslot)
                send_b[t, d] = int(j != 0)

    return Schedule(
        P=P, V=V, M=M, ticks=T, kind=kind, unit_m=unit_m, unit_v=unit_v,
        f_src=f_src, b_src=b_src, b_head=b_head, resid_rw=resid_rw,
        park_f=park_f, park_b=park_b, send_f=send_f, send_b=send_b,
        pend_f_slots=max(hi_f, 1), pend_b_slots=max(hi_b, 1),
        resid_slots=max(hi_r, 1),
    )


def layer_permutation(n_layer: int, P: int, V: int) -> np.ndarray:
    """perm such that stacked row r holds global layer perm[r] when the stack
    is contiguously sharded over 'pipe': device d's rows (v*Lc + i within its
    shard) hold chunk (v*P + d)'s layers."""
    if n_layer % (P * V) != 0:
        raise ValueError(
            f"n_layer={n_layer} not divisible by pipe*virtual={P}*{V}"
        )
    Lc = n_layer // (P * V)
    perm = np.empty(n_layer, np.int64)
    for d in range(P):
        for v in range(V):
            for i in range(Lc):
                r = d * (n_layer // P) + v * Lc + i
                perm[r] = (v * P + d) * Lc + i
    return perm


def interleaved_loss_and_grads(
    config: tinygpt.TinyGPTConfig,
    mesh: Mesh,
    params,
    batch: jax.Array,  # (M, mb, S) microbatches; targets are the inputs
    virtual: int = 2,
    base_key: Optional[jax.Array] = None,
    deterministic: bool = True,
):
    """Run one interleaved-1F1B step -> (loss, grads).

    ``params['blocks']`` must already be stacked in ``layer_permutation``
    order (create_train_state does this for pipeline_schedule='interleaved');
    returned grads are in the same layout.
    """
    n_stages = mesh.shape[AXIS]
    V = virtual
    if config.n_layer % (n_stages * V) != 0:
        raise ValueError(
            f"n_layer={config.n_layer} not divisible by pipe*virtual="
            f"{n_stages}*{V}"
        )
    config, seq_ax, sp, manual_axes, batch_spec = _seq_setup(config, mesh)
    # See the module docstring: XLA:CPU's collective rendezvous spans all
    # local devices per instruction, so 'seq' collectives inside the
    # device-varying switch deadlock there. Run all unit kinds and mask.
    # Keyed on backend != 'tpu' (not == 'cpu'): only TPU's per-core SPMD
    # rendezvous is validated for collectives inside lax.switch, so any
    # other backend (e.g. GPU) gets the conservative uniform path too.
    uniform_units = sp > 1 and jax.default_backend() != "tpu"
    PV = n_stages * V
    Lc = config.n_layer // PV
    n_micro = batch.shape[0]
    sched = build_schedule(n_stages, V, n_micro)
    perm_fwd = [(i, (i + 1) % n_stages) for i in range(n_stages)]
    perm_bwd = [(i, (i - 1) % n_stages) for i in range(n_stages)]
    # Mean over microbatches; the hand-seeded loss cotangent uses the same
    # scale.
    inv_m = 1.0 / n_micro
    # Head/embed grads and activations vary over every manual axis; scalar
    # (loss/aux) terms over 'pipe' only — CE/aux are already seq-invariant
    # when sp>1 (psum'd inside).
    var_axes = (AXIS,) + ((seq_ax,) if seq_ax else ())
    moe = config.n_experts > 0
    key_data = _key_data_or_none(base_key)

    def staged(params, batch, stage_arr):
        stage = stage_arr[0]
        # The typed key must not cross the shard_map boundary (the seed-old
        # u32 tile-assignment compile failure — see _key_data_or_none);
        # rebuild it from the raw data inside the manual region.
        base_key = _rebuild_key(key_data)
        blocks = params["blocks"]  # local rows: V chunks x Lc layers
        mb, S = batch.shape[1], batch.shape[2]
        D = config.n_embd
        cd = config.compute_dtype

        from ..utils.vma import pcast_missing

        def var(x):
            # Activations and head/embed cotangents vary over every manual
            # axis (pipe, and seq when sequence-parallel).
            return pcast_missing(x, var_axes)

        def var_p(x):
            # Block grads and scalar loss terms are pipe-varying only: the
            # block-param primal is seq-invariant (its vjp psums over 'seq'
            # implicitly) and the CE psums over 'seq' explicitly.
            return pcast_missing(x, (AXIS,))

        zeros_act = lambda n: var(jnp.zeros((n, mb, S, D), cd))
        pend_f = zeros_act(sched.pend_f_slots)
        pend_b = zeros_act(sched.pend_b_slots)
        resid = zeros_act(sched.resid_slots)
        fwd_msg = var(jnp.zeros((mb, S, D), cd))
        bwd_msg = var(jnp.zeros((mb, S, D), cd))
        d_blocks = jax.tree.map(lambda x: var_p(jnp.zeros_like(x)), blocks)
        loss_sum = var_p(jnp.zeros((), jnp.float32))
        # MoE: chunk forwards return their layers' Switch load-balance aux;
        # F units (and the head unit, whose chunk never runs an F) add the
        # primal aux, every chunk backward seeds the constant cotangent —
        # the weight of the aux term in the final loss. Every scheduled
        # unit is a real (microbatch, chunk), so no validity masking is
        # needed (unlike the lockstep schedules' fill/drain ticks).
        aux_sum = var_p(jnp.zeros((), jnp.float32))
        aux_ct_const = (
            config.router_aux_coef / (config.n_layer * n_micro)
            if moe else 0.0
        )

        hp = {k: params[k] for k in tinygpt.head_param_names(config)}
        ep = {k: params[k] for k in tinygpt.embed_param_names(config)}
        # Pre-cast the head/embed params to device-varying so their vjps stay
        # collective-free inside the switch branches (an invariant primal
        # would make the transpose insert a psum there — deadlock inside
        # divergent control flow); ONE psum after the tick loop re-reduces.
        hp_in = jax.tree.map(var, hp)
        ep_in = jax.tree.map(var, ep)
        d_hp = jax.tree.map(lambda x: var(jnp.zeros(x.shape, x.dtype)), hp)
        d_ep = jax.tree.map(lambda x: var(jnp.zeros(x.shape, x.dtype)), ep)

        live_keys = base_key is not None and not deterministic
        emb_key = (
            jax.random.fold_in(base_key, 1_000_003) if live_keys else None
        )

        def chunk_slice(tree, v):
            return jax.tree.map(
                lambda x: lax.dynamic_slice_in_dim(x, v * Lc, Lc, axis=0), tree
            )

        def chunk_update_add(tree, upd, v):
            def one(x, u):
                cur = lax.dynamic_slice_in_dim(x, v * Lc, Lc, axis=0)
                return lax.dynamic_update_slice_in_dim(
                    x, cur + u, v * Lc, axis=0
                )
            return jax.tree.map(one, tree, upd)

        def chunk_fwd(blk_c, x, m, v):
            # Dropout keys: base fold m + (gpipe stage owning these layers) +
            # per-layer fold of the GLOBAL layer index inside apply_blocks —
            # exactly the keys the GPipe/plain-1F1B schedules derive for the
            # same (microbatch, layer), so the three schedules produce
            # bit-identical dropout masks; the backward rematerialization
            # derives the same key from (m, j), replaying the forward exactly.
            j = v * n_stages + stage
            key = (
                jax.random.fold_in(base_key, m + j // V) if live_keys
                else None
            )
            y, aux = tinygpt.apply_blocks(
                config, blk_c, x, key, deterministic,
                layer_offset=j * Lc,
            )
            if moe:
                if seq_ax is not None:
                    # Shard-local aux averaged over sequence shards
                    # (seq-invariant, matching pipeline.stage_fwd).
                    aux = lax.psum(aux, seq_ax) / sp
            else:
                # Dense: apply_blocks' zero aux carries the activation's
                # full (seq,pipe) vma, which would widen the aux carry and
                # the final loss; a fresh zero stays pipe-varying only.
                # Its vjp cotangent (constant 0.0) reaches nothing.
                aux = jnp.zeros((), jnp.float32)
            # Always (y, aux): the uniform shape keeps the dense and MoE
            # vjp/seeding code identical (one copy, not four).
            return y, var_p(aux)

        def tick(carry, row):
            (pend_f, pend_b, resid, fwd_msg, bwd_msg,
             d_blocks, d_hp, d_ep, loss_sum, aux_sum) = carry
            t = dict(zip(_TABLES, [r[stage] for r in row]))

            # Park arrivals (messages sent on the rings last tick).
            pend_f = jnp.where(
                t["park_f"] >= 0,
                lax.dynamic_update_index_in_dim(
                    pend_f, fwd_msg, jnp.maximum(t["park_f"], 0), 0
                ),
                pend_f,
            )
            pend_b = jnp.where(
                t["park_b"] >= 0,
                lax.dynamic_update_index_in_dim(
                    pend_b, bwd_msg, jnp.maximum(t["park_b"], 0), 0
                ),
                pend_b,
            )

            m_s = jnp.maximum(t["unit_m"], 0)
            v_s = jnp.maximum(t["unit_v"], 0)
            blk_c = chunk_slice(blocks, v_s)
            tgt = jnp.take(batch, m_s, axis=0)
            zero_out = var(jnp.zeros((mb, S, D), cd))
            zb = jax.tree.map(lambda x: var_p(jnp.zeros_like(x)), blk_c)
            zh = jax.tree.map(lambda x: var(jnp.zeros(x.shape, x.dtype)), hp)
            ze = jax.tree.map(lambda x: var(jnp.zeros(x.shape, x.dtype)), ep)
            zl = var_p(jnp.zeros((), jnp.float32))

            def f_unit():
                inject = tinygpt.embed(
                    config, ep_in, tgt,
                    jax.random.fold_in(emb_key, m_s) if live_keys else None,
                    deterministic,
                )
                parked = lax.dynamic_index_in_dim(
                    pend_f, jnp.maximum(t["f_src"], 0), 0, keepdims=False
                )
                x_in = jnp.where(t["f_src"] == -2, inject, parked)
                resid2 = lax.dynamic_update_index_in_dim(
                    resid, x_in, jnp.maximum(t["resid_rw"], 0), 0
                )
                y, aux_t = chunk_fwd(blk_c, x_in, m_s, v_s)
                return (resid2, y, zero_out, zb, zh, ze, zl, aux_t)

            def b_unit():
                is_head = t["b_head"] == 1
                from_pend_f = lax.dynamic_index_in_dim(
                    pend_f, jnp.maximum(t["b_src"], 0), 0, keepdims=False
                )
                from_resid = lax.dynamic_index_in_dim(
                    resid, jnp.maximum(t["resid_rw"], 0), 0, keepdims=False
                )
                x_saved = jnp.where(is_head, from_pend_f, from_resid)
                g_parked = lax.dynamic_index_in_dim(
                    pend_b, jnp.maximum(t["b_src"], 0), 0, keepdims=False
                )
                ek = (
                    jax.random.fold_in(emb_key, m_s) if live_keys else None
                )

                def head_vjp():
                    # The head position (PV-1) never runs an F unit, so its
                    # chunk's primal aux is accumulated HERE, alongside the
                    # loss; every other chunk's aux came from its F unit.
                    def fn(blk_a, hp_a, x):
                        y, aux = chunk_fwd(blk_a, x, m_s, v_s)
                        l = tinygpt._cross_entropy(
                            tinygpt.head(config, hp_a, y), tgt, seq_axis=seq_ax
                        )
                        return l, aux
                    (l, aux_p), vjp = jax.vjp(fn, blk_c, hp_in, x_saved)
                    dl = var_p(jnp.asarray(inv_m, jnp.float32))
                    d_blk, d_hp_t, d_x = vjp(
                        (dl, jnp.zeros_like(aux_p) + aux_ct_const)
                    )
                    return l, d_blk, d_hp_t, d_x, aux_p

                def plain_vjp():
                    # Chunk backward: seed the constant aux cotangent (its
                    # weight in the final loss — 0.0 for dense); the primal
                    # aux was already counted by this unit's F.
                    (_, aux_p), vjp = jax.vjp(
                        lambda blk_a, x: chunk_fwd(blk_a, x, m_s, v_s),
                        blk_c, x_saved,
                    )
                    d_blk, d_x = vjp(
                        (g_parked, jnp.zeros_like(aux_p) + aux_ct_const)
                    )
                    return zl, d_blk, zh, d_x, zl

                if uniform_units:
                    l, d_blk, d_hp_t, d_x, aux_p = jax.tree.map(
                        lambda h, p: jnp.where(is_head, h, p),
                        head_vjp(), plain_vjp(),
                    )
                else:
                    l, d_blk, d_hp_t, d_x, aux_p = lax.cond(
                        is_head, head_vjp, plain_vjp
                    )

                # Position 0's input cotangent belongs to the embedding
                # (compute-and-mask: embed is cheap, and ep_in is pre-cast
                # varying so the vjp is collective-free).
                is_embed = (v_s == 0) & (stage == 0) & (t["b_head"] == 0)
                _, vjp_emb = jax.vjp(
                    lambda ep_a: tinygpt.embed(
                        config, ep_a, tgt, ek, deterministic
                    ),
                    ep_in,
                )
                (d_ep_t,) = vjp_emb(
                    jnp.where(is_embed, d_x, jnp.zeros((), d_x.dtype))
                )
                return (resid, zero_out, d_x, d_blk, d_hp_t, d_ep_t, l,
                        aux_p)

            def idle_unit():
                return (resid, zero_out, zero_out, zb, zh, ze, zl, zl)

            if uniform_units:
                k = t["kind"]
                (resid, f_out, b_out, d_blk_t, d_hp_t, d_ep_t, l_t,
                 aux_t) = jax.tree.map(
                    lambda i, f, b: jnp.where(
                        k == FWD, f, jnp.where(k == BWD, b, i)
                    ),
                    idle_unit(), f_unit(), b_unit(),
                )
            else:
                (resid, f_out, b_out, d_blk_t, d_hp_t, d_ep_t, l_t,
                 aux_t) = lax.switch(t["kind"], [idle_unit, f_unit, b_unit])
            d_blocks = chunk_update_add(d_blocks, d_blk_t, v_s)
            d_hp = jax.tree.map(jnp.add, d_hp, d_hp_t)
            d_ep = jax.tree.map(jnp.add, d_ep, d_ep_t)
            loss_sum = loss_sum + l_t
            aux_sum = aux_sum + aux_t

            fwd_msg = lax.ppermute(
                jnp.where(t["send_f"] == 1, f_out, jnp.zeros((), cd)),
                AXIS, perm_fwd,
            )
            bwd_msg = lax.ppermute(
                jnp.where(t["send_b"] == 1, b_out, jnp.zeros((), cd)),
                AXIS, perm_bwd,
            )
            return (pend_f, pend_b, resid, fwd_msg, bwd_msg,
                    d_blocks, d_hp, d_ep, loss_sum, aux_sum), None

        carry = (pend_f, pend_b, resid, fwd_msg, bwd_msg,
                 d_blocks, d_hp, d_ep, loss_sum, aux_sum)
        xs = tuple(jnp.asarray(getattr(sched, n)) for n in _TABLES)
        carry, _ = lax.scan(tick, carry, xs)

        (_, _, _, _, _, d_blocks, d_hp, d_ep, loss_sum, aux_sum) = carry
        loss = lax.psum(loss_sum, AXIS) * inv_m
        if moe:
            # Every (microbatch, chunk) contributed its layers' aux exactly
            # once; normalize as gpipe/1f1b do: coef * mean per layer per
            # microbatch.
            loss = loss + config.router_aux_coef * lax.psum(
                aux_sum, AXIS
            ) / (config.n_layer * n_micro)
        d_hp = jax.tree.map(lambda x: lax.psum(x, var_axes), d_hp)
        d_ep = jax.tree.map(lambda x: lax.psum(x, var_axes), d_ep)
        # Block grads stay per-stage (out_spec P('pipe', ...)); their 'seq'
        # sum happens implicitly inside the vjp.
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
