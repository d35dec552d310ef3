"""Mixture-of-Experts MLP: two families of routing.

The reference lists "Mistral/Mixtral architectures" and MoE only as future
work (reference ``README.md:1025``); here sparse expert layers are a
first-class model family with their own mesh axis.

**Dropless** (``capacity_factor=None``; ``_moe_mlp_dropless``) is what
OLMoE-class models train with: every token's ``expert_top_k`` assignments are
computed, none dropped and none padded away. The N x K assignments are sorted
by expert, the rows gathered into expert order, the experts run as grouped
matmuls over the per-expert row counts (the Pallas ``gmm`` / ``tgmm`` that
ship with jax, ``jax.experimental.pallas.ops.tpu.megablox``; they show in a
trace under those names), and the rows go back to token order weighted by
their gates. Every grouped matmul takes its tiles from its own widths
(``gmm_tiling``: the contraction and column tiles are multiples of 128 that
divide what they multiply, the pair nearest the (1024, 1024) PR 26 swept at
OLMoE's sizes that fits the kernel's VMEM; megablox pads a width to whole tiles
and multiplies the padding, which at Mellum's 2304 x 1792 was a third of the
work: PERF.md, PR 42). Experts are SwiGLU without bias (``moe_wgu`` (E, D, 2F), gate
columns then up columns, and ``moe_wd`` (E, F, D); the config pairs
``mlp_act='swiglu'`` with this path) or, under ``mlp_act='relu2'``, not gated:
``W_down relu(W_up h)^2`` (``moe_wu`` (E, D, F) in ``moe_wgu``'s place, the shared
expert's ``shared_wu`` at its own width, ``shared_expert_hidden``; Nemotron-H);
gates are renormalised only if
``norm_topk_prob``; the auxiliary channel carries the load-balance term over
all K choices plus the router z-loss. Its parts carry the scopes ``router`` /
``dispatch`` / ``experts`` / ``combine`` (``utils/scopes.MOE_SCOPES``). What
of it is dear to make again and cheap to hold carries a ``checkpoint_name``
(``MOE_RESIDUAL_NAMES``: the gate+up grouped matmul's result, the router's
logits and choice, the sorts' plan), which remat ``dots`` and
``full_keep_kernels`` keep. It runs on one chip's tokens: the 'expert' axis
all-to-all around it is not written yet.

**A chip's share of the experts** (``experts_held = (first, count)``;
``_moe_mlp_held``) is the dropless layer told which experts it holds, as
expert parallelism tells it: the router scores all ``n_experts`` and keeps its
``expert_top_k`` choices a token, the leaves ``moe_wgu`` / ``moe_wd`` hold
``count`` experts, and the layer computes the part of the routed sum its own
experts give. Assignments on experts held elsewhere add nothing here; no code
stands in for the chips that hold them or for the exchange between them. How
many assignments land on the held experts is data, so the rows go into a
static buffer: N x K rows (nothing can overflow) or, with
``held_rows_factor``, that multiple of the expected N x K x count / n_experts,
in which case the layer also counts its rows and the held assignments that
did not fit, and the train step returns both after its loss. Every pass over
the held rows costs what the M buffer rows cost, not what the N x K
assignments would (seven in eight of them are held elsewhere): tokens go to
rows by a gather of M rows (``_rows_from_tokens``), and rows come back to
tokens (``_tokens_from_rows``: the weighted sum in ``combine`` and the
transpose of the gather in ``dispatch``'s backward are the same operation)
sorted by token, where a tile of tokens owns one contiguous span of rows, as
one more grouped matmul: a one-hot of the token inside its tile times the
span's rows (``tgmm``, f32 accumulation). A part of the
experts does not train its routing (``TinyGPTConfig.trains_routing``: the
gradient through the gates is the held experts' part only, and its sum over
the chips is the exchange's to make). ``n_shared_experts``
adds one SwiGLU of that many experts' width that every token passes (scope
``shared``): every chip of the deployment computes it alike.

**Capacity** (a number for ``capacity_factor``; the GShard-style paths below)
is the E = 8 top-2 GELU toy with biases and the only path across an 'expert'
mesh axis today. It drops what overflows an expert's buffer, and its one-hot
dispatch tensors are (N, E, C): fine at toy sizes, 0.67 G elements each at
OLMoE's.

Two capacity formulations share the same routing math:

1. **Explicit all-to-all** (``_moe_mlp_a2a``) — the expert-parallel path.
   The batch is sharded over ``('data', 'expert')``
   (``strategies.batch_partition_spec``), so each of the dp x ep members
   routes its OWN tokens; inside a ``shard_map`` the dispatched
   ``(experts, capacity, d_model)`` buffer is exchanged across the
   'expert' axis with ``lax.all_to_all`` (one hop out, expert FFN on local
   experts, one hop back). This is the DeepSpeed-MoE/Tutel schedule, and
   the collective is *guaranteed* in the lowering because we emit it.

2. **GSPMD einsum** (``_moe_mlp_einsum``) — routing as two dense einsums
   against a one-hot dispatch tensor: static shapes, MXU-friendly, used on
   meshes without a >1 'expert' axis and inside the pipeline schedules'
   manual regions.

Round-5 finding (the reason the explicit path exists): the SPMD
partitioner does NOT lower the dispatch/combine einsums to all-to-all —
AOT-compiling the einsum formulation for an 8-chip v5e topology shows 0
``all-to-all`` ops; the partitioner picks all-gather/all-reduce
strategies, which move the full token buffer across the expert axis. An
earlier docstring claimed the opposite; ``tests/test_collective_lowering.py``
now pins the all-to-all in the compiled HLO of the explicit path.

Top-k routing with capacity: each token picks its top-k experts by router
probability; each expert accepts at most C = ceil(capacity_factor * k * N / E)
tokens (token order breaks ties); overflowing tokens are dropped for that
expert (their combine weight is zero) — the standard capacity discipline that
keeps every shape static under jit. In the all-to-all path N and C are
per-member quantities (capacity is provisioned per source shard), so drop
decisions are shard-local; total capacity ep * C_local matches the global
formulation's budget.

The load-balance auxiliary loss is Switch-style: E * sum_e f_e * P_e, where
f_e is the fraction of tokens dispatched to expert e (top-1 assignment) and
P_e the mean router probability — minimized at uniform routing. The
all-to-all path ``pmean``s f and P over the token-sharding axes so both
formulations optimize the same global statistic.
"""

from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.experimental.pallas.ops.tpu.megablox import ops as megablox
from jax.experimental.pallas.ops.tpu.megablox.gmm import tgmm
from jax.sharding import PartitionSpec as P

from ..utils import scopes
from .common import SHARED_U, _dropout


#: ``jax.ad_checkpoint.checkpoint_name``s of what the dropless routed layer makes
#: that is dear to make again and cheap to hold (``tinygpt._under_remat`` has the
#: rule; remat ``dots`` and ``full_keep_kernels`` keep them): the experts' gate+up
#: grouped matmul's result (a Mosaic call, which no ``dot_general`` rule sees);
#: the router's float32 logits (at ``Precision.HIGHEST``); its choice (``top_k``'s
#: results and the counts an expert: a sort); and the plan that moves rows (the
#: sorts' integer arrays and the gate a row, kilobytes to a MB a layer).
MOE_GU, ROUTER_LOGITS, ROUTER_CHOICE, MOE_PLAN = MOE_RESIDUAL_NAMES = (
    "moe_gu", "router_logits", "router_choice", "moe_plan")


def capacity(n_tokens: int, n_experts: int, top_k: int, factor: float) -> int:
    c = int(factor * top_k * n_tokens / n_experts + 0.999)
    return max(c, top_k)


def _route(c, xt: jax.Array, router: jax.Array, C: int):
    """Shared routing math -> (dispatch (N,E,C), combine (N,E,C), probs,
    expert_idx). fp32 router numerics (discipline as for softmax/LN)."""
    N = xt.shape[0]
    E, K = c.n_experts, c.expert_top_k
    logits = jnp.einsum(
        "nd,de->ne", xt, router.astype(jnp.float32),
        preferred_element_type=jnp.float32,
    )
    probs = jax.nn.softmax(logits, axis=-1)  # (N, E)

    gate_vals, expert_idx = jax.lax.top_k(probs, K)  # (N, K)
    # Renormalize the chosen gates so they sum to 1 per token.
    gate_vals = gate_vals / jnp.maximum(
        jnp.sum(gate_vals, axis=-1, keepdims=True), 1e-9
    )

    # Position of each (token, choice) in its expert's capacity buffer:
    # count prior assignments to the same expert in (token-major, choice-major)
    # order via a cumulative sum over one-hots.
    onehot = jax.nn.one_hot(expert_idx, E, dtype=jnp.int32)  # (N, K, E)
    flat = onehot.reshape(N * K, E)
    pos_flat = jnp.cumsum(flat, axis=0) - flat  # prior count per expert
    pos = jnp.sum(pos_flat.reshape(N, K, E) * onehot, axis=-1)  # (N, K)
    keep = pos < C  # overflowing assignments are dropped

    # dispatch (N, E, C): 1 where token n occupies slot c of expert e.
    disp = (
        jax.nn.one_hot(expert_idx, E, dtype=xt.dtype)[:, :, :, None]
        * jax.nn.one_hot(jnp.where(keep, pos, C), C + 1, dtype=xt.dtype)[:, :, None, :C]
    )  # (N, K, E, C); pos>=C one-hots into the dropped C+1th slot, sliced off
    dispatch = jnp.sum(disp, axis=1)  # (N, E, C)
    combine = jnp.sum(disp * gate_vals[:, :, None, None].astype(xt.dtype), axis=1)
    drop_frac = jnp.mean(1.0 - keep.astype(jnp.float32))
    return dispatch, combine, probs, expert_idx, drop_frac


def _expert_ffn(c, xin: jax.Array, w1, b1, w2, b2) -> jax.Array:
    """(E', C', D) -> (E', C', D) batched expert MLP, bf16 MXU / fp32 accum."""
    h = jnp.einsum(
        "ecd,edf->ecf", xin, w1.astype(c.compute_dtype),
        preferred_element_type=jnp.float32,
    ).astype(c.compute_dtype) + b1.astype(c.compute_dtype)[:, None, :]
    h = jax.nn.gelu(h, approximate=False)
    return jnp.einsum(
        "ecf,efd->ecd", h, w2.astype(c.compute_dtype),
        preferred_element_type=jnp.float32,
    ).astype(c.compute_dtype) + b2.astype(c.compute_dtype)[:, None, :]


def _aux_stats(probs: jax.Array, expert_idx: jax.Array, E: int):
    """Switch load-balance statistics on the top-1 assignment -> (f, p)."""
    top1 = jax.nn.one_hot(expert_idx[:, 0], E, dtype=jnp.float32)
    f = jnp.mean(top1, axis=0)  # fraction of tokens per expert
    p = jnp.mean(probs, axis=0)  # mean router prob per expert
    return f, p


def _moe_mlp_einsum(c, layer, x, dropout_key, deterministic):
    """GSPMD formulation: dense einsums, sharding left to the partitioner."""
    B, S, D = x.shape
    N = B * S
    E = c.n_experts
    C = capacity(N, E, c.expert_top_k, c.capacity_factor)
    xt = x.reshape(N, D)

    dispatch, combine, probs, expert_idx, drop_frac = _route(
        c, xt, layer["router"], C
    )

    # Expert compute on (E, C, D) buffers — batched over the expert axis.
    xin = jnp.einsum("nd,nec->ecd", xt, dispatch, preferred_element_type=jnp.float32)
    out_e = _expert_ffn(
        c, xin.astype(c.compute_dtype),
        layer["moe_w1"], layer["moe_b1"], layer["moe_w2"], layer["moe_b2"],
    )
    y = jnp.einsum(
        "ecd,nec->nd", out_e, combine, preferred_element_type=jnp.float32
    ).astype(x.dtype)
    y = _dropout(y, c.dropout, dropout_key, deterministic)

    if c.moe_aux_mode == "overflow":
        return y.reshape(B, S, D), drop_frac
    f, p = _aux_stats(probs, expert_idx, E)
    aux = E * jnp.sum(f * p)
    return y.reshape(B, S, D), aux


def _moe_mlp_a2a(c, layer, x, dropout_key, deterministic, mesh, ep, dp):
    """Expert-parallel formulation: explicit all-to-all inside shard_map.

    Token layout: batch dim sharded over ('data', 'expert') — every member
    routes B*S/(dp*ep) tokens. Expert layout: weight tensors sharded over
    'expert' on their leading experts axis (strategies._EP_RULES), E/ep
    local experts per member. Two ``lax.all_to_all`` hops exchange the
    per-source-capacity buffers; the expert FFN runs on (E/ep, ep*C, D).
    """
    B, S, D = x.shape
    E, K = c.n_experts, c.expert_top_k
    E_loc = E // ep
    batch_ax = ("data", "expert") if dp > 1 else ("expert",)
    xspec = P(batch_ax, None, None)
    have_key = dropout_key is not None
    key = dropout_key if have_key else jax.random.key(0)

    def body(x_loc, router, w1, b1, w2, b2, key):
        Bl, S_, D_ = x_loc.shape
        N = Bl * S_
        C = capacity(N, E, K, c.capacity_factor)
        xt = x_loc.reshape(N, D_)

        dispatch, combine, probs, expert_idx, drop_frac = _route(
            c, xt, router, C
        )

        xin = jnp.einsum(
            "nd,nec->ecd", xt, dispatch, preferred_element_type=jnp.float32
        ).astype(c.compute_dtype)  # (E, C, D)

        # Hop out: split the experts axis into ep destination groups; after
        # the exchange dim 0 indexes the SOURCE member, so member m holds
        # its E_loc experts' slices from every source.
        xin = xin.reshape(ep, E_loc, C, D_)
        xin = lax.all_to_all(xin, "expert", split_axis=0, concat_axis=0)
        xe = xin.transpose(1, 0, 2, 3).reshape(E_loc, ep * C, D_)

        out = _expert_ffn(c, xe, w1, b1, w2, b2)  # (E_loc, ep*C, D)

        # Hop back: regroup by source and return each member its slots.
        out = out.reshape(E_loc, ep, C, D_).transpose(1, 0, 2, 3)
        out = lax.all_to_all(out, "expert", split_axis=0, concat_axis=0)
        out_full = out.reshape(E, C, D_)

        y = jnp.einsum(
            "ecd,nec->nd", out_full, combine, preferred_element_type=jnp.float32
        ).astype(x_loc.dtype)
        if have_key:
            # Distinct dropout stream per token shard (same discipline as
            # the pipeline schedules' per-shard fold, tinygpt.py).
            member = lax.axis_index("expert") + (
                ep * lax.axis_index("data") if dp > 1 else 0
            )
            y = _dropout(
                y, c.dropout, jax.random.fold_in(key, member), deterministic
            )

        if c.moe_aux_mode == "overflow":
            return y.reshape(Bl, S_, D_), lax.pmean(drop_frac, batch_ax)
        f, p = _aux_stats(probs, expert_idx, E)
        # Both statistics are means over the GLOBAL token set in the einsum
        # formulation; average over the token-sharding axes to match.
        f = lax.pmean(f, batch_ax)
        p = lax.pmean(p, batch_ax)
        aux = E * jnp.sum(f * p)
        return y.reshape(Bl, S_, D_), aux

    fn = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(
            xspec,
            P(None, None),            # router replicated (tiny; all tokens need all scores)
            P("expert", None, None),  # moe_w1 (E, D, F)
            P("expert", None),        # moe_b1 (E, F)
            P("expert", None, None),  # moe_w2 (E, F, D)
            P("expert", None),        # moe_b2 (E, D)
            P(),
        ),
        out_specs=(xspec, P()),
    )
    return fn(
        x, layer["router"], layer["moe_w1"], layer["moe_b1"],
        layer["moe_w2"], layer["moe_b2"], key,
    )


@jax.custom_vjp
def _permute_rows(x: jax.Array, perm: jax.Array, inverse: jax.Array) -> jax.Array:
    """``x[perm]`` for a permutation of the rows. Its transpose is the gather
    by ``inverse``; autodiff of the indexing alone would emit a scatter-add
    over rows it cannot know are distinct."""
    return x[perm]


def _permute_rows_fwd(x, perm, inverse):
    return x[perm], inverse


def _permute_rows_bwd(inverse, g):
    return g[inverse], None, None


_permute_rows.defvjp(_permute_rows_fwd, _permute_rows_bwd)


def _route_dropless(c, xt: jax.Array, router: jax.Array, sequences: int = 1, bias=None):
    """Token-choice routing with nothing dropped -> (gates (N, K) fp32,
    expert_idx (N, K), counts (E,) int32, aux). ``sequences`` is how many
    equal sequences the N tokens are, read only under ``seq_aux``.

    The scoring rule is the config's (``router_score``): a softmax over the
    experts, or each logit's sigmoid, where the K are chosen by score +
    ``bias`` (the layer's (E,) ``router_bias``, which moves the choice and no
    gate) and the load-balance term is 0: the bias is that family's balancer,
    and its update between steps is not part of the step. Either way the gates
    are renormalised under ``norm_topk_prob`` and then multiplied by
    ``routed_scaling_factor``.

    fp32 throughout, the logits at the highest matmul precision (a TPU's
    default would round the router to bfloat16, and near-ties in the top-k
    flip on less). ``counts[e]`` is how many of the N x K assignments chose
    expert e; they sum to N x K. ``aux`` is in units of ``router_aux_coef``,
    the one scalar the layer loop carries: E * sum_e f_e * P_e over all K
    choices (f_e = counts / (N K), P_e the mean probability) plus
    ``router_z_coef / router_aux_coef`` times the z-loss, the mean over tokens
    of logsumexp(logits)^2.
    """
    N = xt.shape[0]
    E, K = c.n_experts, c.expert_top_k
    logits = checkpoint_name(jnp.einsum(
        "nd,de->ne", xt.astype(jnp.float32), router.astype(jnp.float32),
        precision=lax.Precision.HIGHEST, preferred_element_type=jnp.float32,
    ), ROUTER_LOGITS)
    sigmoid = c.router_score == "sigmoid"
    if sigmoid:
        probs = jax.nn.sigmoid(logits)
        expert_idx = checkpoint_name(
            lax.top_k(probs + bias.astype(jnp.float32), K)[1], ROUTER_CHOICE)
        gates = jnp.take_along_axis(probs, expert_idx, axis=-1)
    else:
        probs = jax.nn.softmax(logits, axis=-1)
        gates, expert_idx = checkpoint_name(lax.top_k(probs, K), ROUTER_CHOICE)
    if c.norm_topk_prob:
        gates = gates / jnp.maximum(jnp.sum(gates, axis=-1, keepdims=True), 1e-9)
    if c.routed_scaling_factor != 1.0:
        gates = gates * c.routed_scaling_factor
    counts = checkpoint_name(
        jnp.sum(jax.nn.one_hot(expert_idx, E, dtype=jnp.int32), axis=(0, 1)), ROUTER_CHOICE)
    if sigmoid:
        aux = jnp.zeros((), jnp.float32)
    elif c.seq_aux:
        # DeepSeek: the same statistic a sequence (f its share of the S x K
        # assignments, P its mean probability), averaged over sequences.
        S = N // sequences
        onehot = jax.nn.one_hot(expert_idx.reshape(sequences, S * K), E, dtype=jnp.float32)
        share = jnp.sum(onehot, axis=1) / (S * K)  # (sequences, E)
        mean_prob = jnp.mean(probs.reshape(sequences, S, E), axis=1)
        aux = E * jnp.mean(jnp.sum(share * mean_prob, axis=-1))
    else:
        aux = E * jnp.sum(counts.astype(jnp.float32) / (N * K) * jnp.mean(probs, axis=0))
    if c.router_z_coef:
        z = jnp.mean(jnp.square(jax.nn.logsumexp(logits, axis=-1)))
        aux = aux + (c.router_z_coef / c.router_aux_coef) * z
    return gates, expert_idx, counts, aux


# (contraction, columns) tile the grouped matmuls aim at and their row tile: the
# best of a sweep on the v5e at OLMoE's sizes, whose widths it divides
# (scripts/microbench_moe_experts.py; PERF.md, PR 26). At other widths
# ``gmm_tiling`` takes the dividing tiles nearest to it: the rule was read from a
# sweep of each kernel alone at the DeepSeek, SDAR and Mellum cells' widths (the
# same script with ``--calls``; PERF.md, PR 42).
_GMM_TILE = (1024, 1024)
_ROW_TILE = 512
# What a grouped matmul's tiles may take of the kernel's 16 MiB of VMEM, counted
# as ``_tiles_vmem`` counts: on a described v5e the compiler's own allocation read
# up to 1.1 MiB over that count (PR 42; tests/test_tpu_compile.py compiles the
# cells' stacks).
_GMM_VMEM = 14 * 2**20


def _dividing_tiles(width: int) -> list[int]:
    """The multiples of 128 that divide ``width``."""
    return [t for t in range(128, width + 1, 128) if width % t == 0]


def _tiles_vmem(tm: int, tk: int, tn: int) -> int:
    """Bytes of VMEM a (tm, tk, tn) tiling asks for in bf16: both operand tiles
    and the result tile twice each (the pipeline's double buffers) and the f32
    accumulator, in whichever of megablox's kernels asks for more: ``gmm``
    accumulates (tm, tn), ``tgmm`` (tk, tn). A tiling serves both: megablox
    looks it up by (m, k, n), which the forward and ``tgmm`` share."""
    return 4 * (tm * tk + tk * tn + tm * tn) + 4 * max(tm, tk) * tn


def _nearest_fitting(tm: int, k: int, n: int, near: Tuple[int, int]) -> Tuple[int, int]:
    """(tk, tn) that **divide** k and n, so that no tile is part padding
    (megablox rounds a width up to whole tiles, multiplies them whole and masks
    afterwards): of the pairs that fit ``_GMM_VMEM`` the nearest to ``near`` by
    ratio. A width no multiple of 128 divides, and both where no pair fits, keep
    what the kernel pads: ``near`` or the whole width."""
    padded = (min(near[0], k), min(near[1], n))
    distance = lambda pair: sum(abs(math.log(t / to)) for t, to in zip(pair, near))
    pairs = [(tk, tn) for tk in _dividing_tiles(k) or padded[:1]
             for tn in _dividing_tiles(n) or padded[1:] if _tiles_vmem(tm, tk, tn) <= _GMM_VMEM]
    return min(pairs, key=distance, default=padded)


def gmm_tiling(m: int, k: int, n: int) -> Tuple[int, int, int]:
    """(tm, tk, tn) for a grouped matmul of (m, k) rows by (k, n) weights, from
    the call's own widths: megablox looks it up for the forward ``gmm``, for the
    rows' gradient (k and n changed places) and for the weights' ``tgmm``. One
    function, named here: it is a static argument of megablox's jits."""
    tm = math.gcd(m, _ROW_TILE)
    return (tm,) + _nearest_fitting(tm, k, n, _GMM_TILE)


def gmm_tile_fill(m: int, k: int, n: int, tiling=gmm_tiling) -> float:
    """Share of what a grouped matmul's tiles cover of its (k, n) that is
    operand, under ``tiling`` (a tuple, or a function of (m, k, n) as megablox
    takes one): 1.0 where the tiles divide the widths; the rest the MXU
    multiplies and the kernel masks away."""
    _, tk, tn = tiling(m, k, n) if callable(tiling) else tiling
    return k * n / (-(-k // tk) * tk * -(-n // tn) * tn)


def _grouped_matmul(c, rows: jax.Array, weights: jax.Array, counts: jax.Array) -> jax.Array:
    """(M, K) rows sorted by expert x (E, K, N) -> (M, N): the rows of group e
    times ``weights[e]``, bf16 MXU / fp32 accumulation. The Pallas grouped
    matmul that ships with jax; its backward is one more ``gmm`` (the rows'
    gradient) and a ``tgmm`` (the weights'). Off a TPU it runs interpreted."""
    return megablox.gmm(
        rows, weights.astype(c.compute_dtype), counts, c.compute_dtype, gmm_tiling,
        interpret=jax.default_backend() != "tpu",
    )


def _relu2(u: jax.Array) -> jax.Array:
    """relu(u)^2, squared in float32, in u's dtype: the activation of experts
    that are not gated (``mlp_act='relu2'``)."""
    return jnp.square(jax.nn.relu(u.astype(jnp.float32))).astype(u.dtype)


def _experts_dropless(c, layer, rows: jax.Array, counts: jax.Array) -> jax.Array:
    """The experts over rows in expert order, (M, D) -> (M, D): SwiGLU (leaf
    ``moe_wgu``), or not gated, W_down relu(W_up h)^2 (leaf ``moe_wu``)."""
    F = c.mlp_dim
    if "moe_wu" in layer:  # the first grouped matmul's result keeps its name
        u = checkpoint_name(_grouped_matmul(c, rows, layer["moe_wu"], counts), MOE_GU)
        return _grouped_matmul(c, _relu2(u), layer["moe_wd"], counts)
    # gate and up in one matmul; kept through remat by name: its re-run is a ``gmm``
    gu = checkpoint_name(_grouped_matmul(c, rows, layer["moe_wgu"], counts), MOE_GU)
    h = jax.nn.silu(gu[:, :F]) * gu[:, F:]
    return _grouped_matmul(c, h, layer["moe_wd"], counts)


@jax.named_scope(scopes.SHARED)
def _shared_experts(c, layer, x: jax.Array) -> jax.Array:
    """The shared experts as one SwiGLU, (B, S, D) -> (B, S, D): gate columns
    then up columns in one matrix, as the routed experts store theirs; or one
    expert that is not gated, whose up product keeps a name (``common.SHARED_U``:
    remat ``full_keep_kernels`` keeps it; the gated one's gate+up stays dropped,
    ``tinygpt._under_remat`` has both readings)."""
    cd = c.compute_dtype
    Fs = layer["shared_wd"].shape[0]
    if "shared_wu" in layer:  # not gated: W_down relu(W_up h)^2
        u = checkpoint_name(jnp.einsum(
            "bsd,df->bsf", x, layer["shared_wu"].astype(cd), preferred_element_type=jnp.float32
        ).astype(cd), SHARED_U)
        return jnp.einsum(
            "bsf,fd->bsd", _relu2(u), layer["shared_wd"].astype(cd),
            preferred_element_type=jnp.float32).astype(cd)
    gu = jnp.einsum(
        "bsd,df->bsf", x, layer["shared_wgu"].astype(cd), preferred_element_type=jnp.float32
    ).astype(cd)
    h = jax.nn.silu(gu[..., :Fs]) * gu[..., Fs:]
    return jnp.einsum(
        "bsf,fd->bsd", h, layer["shared_wd"].astype(cd), preferred_element_type=jnp.float32
    ).astype(cd)


# What the held experts' buffer is rounded up to: whole row tiles of the grouped
# matmuls as swept. Its own number: the buffers' sizes are part of the cells.
_HELD_ROWS_MULTIPLE = 512


def held_buffer_rows(c, n_tokens: int) -> int:
    """Rows of the held experts' static buffer for ``n_tokens`` tokens: all N x
    K assignments, or ``held_rows_factor`` times the N x K x count / E expected
    on the held experts, up to a whole row tile of the grouped matmuls."""
    assignments = n_tokens * c.expert_top_k
    if c.held_rows_factor is None:
        return assignments
    expected = assignments * c.experts_held[1] / c.n_experts
    tile = math.gcd(assignments, _HELD_ROWS_MULTIPLE)
    return min(assignments, -(-math.ceil(c.held_rows_factor * expected) // tile) * tile)


def _held_plan(c, expert_idx: jax.Array, counts: jax.Array, gates: jax.Array):
    """Where the held experts' assignments go -> (gate (M,) each buffer row's
    gate, live (M,) whether the row holds an assignment, rows_of (token (M,)
    each row's token, by_token (M,) the rows in token order, sorted_token (M,)
    their tokens, spans (N / T,) the rows of each tile of T tokens: what moves
    rows, see ``_rows_from_tokens``), sizes (count,) rows an expert, rows the
    number filled, overflow the held assignments that did not fit).

    Assignments sort by held expert (stable: token order inside an expert),
    everything held elsewhere behind them, and their tokens and gates ride
    along; the first M of that order are the buffer. Past ``rows`` the buffer
    is padding: never read, by anyone.
    """
    first, count = c.experts_held
    K = c.expert_top_k
    flat = expert_idx.reshape(-1)
    N = flat.shape[0] // K
    M = held_buffer_rows(c, N)
    local = flat - first
    _, token, gate = lax.sort(
        (jnp.where((local >= 0) & (local < count), local, count),
         jnp.arange(N * K, dtype=jnp.int32) // K, gates.reshape(-1)),
        num_keys=1, is_stable=True)
    ends = jnp.minimum(jnp.cumsum(counts[first:first + count]), M)
    rows = ends[-1]
    sizes = jnp.diff(ends, prepend=0).astype(jnp.int32)
    overflow = jnp.sum(counts[first:first + count]) - rows
    live = jnp.arange(M) < rows
    token = jnp.where(live, token[:M], N)  # padding sorts behind every token
    sorted_token, by_token = lax.sort_key_val(token, jnp.arange(M, dtype=jnp.int32))
    tile = _token_tile(N)
    spans = jnp.sum(jax.nn.one_hot(token // tile, N // tile, dtype=jnp.int32), axis=0)
    # what the backward reads of the plan, by name: remat then re-runs neither sort
    gate, live, rows_of, sizes = checkpoint_name(
        (gate[:M], live, (token, by_token, sorted_token, spans), sizes), MOE_PLAN)
    return gate, live, rows_of, sizes, rows, overflow


def routing_rows(config, layer: dict, x: jax.Array):
    """One layer's routing of ``x`` (B, S, D), the MLP's normed input ->
    ((E,) int32 assignments an expert, (2,) int32 or None: the rows the
    dispatch puts into the held experts' buffer and the held assignments that
    do not fit it)."""
    gates, expert_idx, counts, _ = _route_dropless(
        config, x.reshape(-1, x.shape[-1]), layer["router"], bias=layer.get("router_bias"))
    if config.experts_held is None:
        return counts, None
    *_, rows, overflow = _held_plan(config, expert_idx, counts, gates)
    return counts, jnp.stack([rows, overflow]).astype(jnp.int32)


# Tokens a group and (buffer rows, columns) tile the sum back to tokens aims at,
# from a sweep on the v5e at DeepSeek-V2-Lite's sizes, whose 2048 columns it
# divides (scripts/microbench_moe_rows.py; PERF.md, PR 32).
_SUM_TOKENS, _SUM_TILING = 128, (256, 2048)


def _token_tile(n_tokens: int) -> int:
    return math.gcd(n_tokens, _SUM_TOKENS)


def _sum_tiling(rows: int, n_tokens: int, columns: int) -> Tuple[int, int, int]:
    """``tgmm``'s tiling for the sum of ``rows`` buffer rows back to tokens:
    the column tile divides the columns as the experts' tiles do (at 2304 two
    2048-wide tiles would read the rows twice for 2304 columns)."""
    tm, tile = math.gcd(rows, _SUM_TILING[0]), _token_tile(n_tokens)
    return (tm,) + _nearest_fitting(tm, tile, columns, (tile, _SUM_TILING[1]))


@jax.custom_vjp
def _rows_from_tokens(xt, rows_of):
    """(N, D) tokens -> (M, D) buffer rows: each live row its token's; a row
    of the padding holds the last token's, for nobody to read (a mask would be
    one more pass over the rows: XLA's gather fuses with nothing).
    ``rows_of`` is ``_held_plan``'s. Its transpose is ``_tokens_from_rows``
    (autodiff of the indexing would scatter-add row by row), and that one's is
    this."""
    return xt.at[rows_of[0]].get(mode="clip")


def _rows_from_tokens_fwd(xt, rows_of):
    return _rows_from_tokens(xt, rows_of), (rows_of, xt.shape[0])


def _rows_from_tokens_bwd(res, g):
    rows_of, n_tokens = res
    return _tokens_from_rows(g, rows_of, n_tokens), None


_rows_from_tokens.defvjp(_rows_from_tokens_fwd, _rows_from_tokens_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _tokens_from_rows(rows, rows_of, n_tokens: int):
    """(M, D) buffer rows -> (N, D): each token's sum of its live rows (at most
    K, on average K x count / E), f32 accumulation. It costs what the M rows
    cost, not what the N x K assignments would: with the rows in token order a
    tile of T tokens owns one contiguous span of them, so the sum is a grouped
    matmul over the spans, (T, span) one-hot of the token inside its tile x
    (span, D) rows: the ``tgmm`` the experts' backward already runs. The
    padding sorts behind every span, and ``tgmm`` selects a span's rows out of
    what it loads before it multiplies: whatever the padding holds, NaN too,
    reaches no sum."""
    _, by_token, sorted_token, spans = rows_of
    (M, D), tile = rows.shape, _token_tile(n_tokens)
    inside = (sorted_token % tile)[None, :] == jnp.arange(tile)[:, None]  # (T, M)
    out = tgmm(
        inside.astype(rows.dtype), rows[by_token], spans, rows.dtype,
        _sum_tiling(M, n_tokens, D), interpret=jax.default_backend() != "tpu")
    return out.reshape(n_tokens, D)


def _tokens_from_rows_fwd(rows, rows_of, n_tokens):
    return _tokens_from_rows(rows, rows_of, n_tokens), rows_of


def _tokens_from_rows_bwd(n_tokens, rows_of, g):
    return _rows_from_tokens(g, rows_of), None


_tokens_from_rows.defvjp(_tokens_from_rows_fwd, _tokens_from_rows_bwd)


def _moe_mlp_held(c, layer, x, dropout_key, deterministic):
    """The dropless layer over the experts this chip holds (module docstring)
    -> (y, aux): aux is the load-balance scalar, or with ``held_rows_factor``
    (3,): that, the rows the buffer took and the held assignments that did
    not fit."""
    B, S, D = x.shape
    N = B * S
    xt = x.reshape(N, D)
    with jax.named_scope(scopes.ROUTER):
        gates, expert_idx, counts, aux = _route_dropless(
            c, xt, layer["router"], B, layer.get("router_bias"))
        if not c.trains_routing:
            gates, aux = lax.stop_gradient((gates, aux))
    with jax.named_scope(scopes.DISPATCH):
        gate, live, rows_of, sizes, n_rows, overflow = _held_plan(c, expert_idx, counts, gates)
        rows = _rows_from_tokens(xt, rows_of)
    with jax.named_scope(scopes.EXPERTS):
        out = _experts_dropless(c, layer, rows, sizes)
    with jax.named_scope(scopes.COMBINE):
        out = jnp.where(live[:, None], out, jnp.zeros((), out.dtype))  # padding may hold anything
        y = _tokens_from_rows(
            (out.astype(jnp.float32) * gate[:, None]).astype(out.dtype), rows_of, N)
    y = _dropout(y, c.dropout, dropout_key, deterministic).reshape(B, S, D)
    if c.moe_aux_mode == "overflow":
        aux = jnp.zeros((), jnp.float32)
    if c.reports_held_overflow:
        aux = jnp.concatenate([aux[None], lax.stop_gradient(
            jnp.stack([n_rows, overflow])).astype(jnp.float32)])
    return y, aux


def _moe_mlp_dropless(c, layer, x, dropout_key, deterministic):
    """Sort by expert, grouped matmuls, weighted sum back (module docstring)."""
    B, S, D = x.shape
    N, K = B * S, c.expert_top_k
    xt = x.reshape(N, D)
    with jax.named_scope(scopes.ROUTER):
        gates, expert_idx, counts, aux = _route_dropless(
            c, xt, layer["router"], B, layer.get("router_bias"))
    with jax.named_scope(scopes.DISPATCH):
        # Assignment n*K + k is token n's k-th choice; ``order`` lists the
        # assignments expert by expert, ``inverse`` is where each one went.
        order = jnp.argsort(expert_idx.reshape(N * K), stable=True)
        order, inverse = checkpoint_name((order, jnp.argsort(order)), MOE_PLAN)
        rows = _permute_rows(jnp.repeat(xt, K, axis=0), order, inverse)
    with jax.named_scope(scopes.EXPERTS):
        out = _experts_dropless(c, layer, rows, counts)
    with jax.named_scope(scopes.COMBINE):
        back = _permute_rows(out, inverse, order).reshape(N, K, D)
        y = jnp.sum(back.astype(jnp.float32) * gates[:, :, None], axis=1).astype(x.dtype)
    y = _dropout(y, c.dropout, dropout_key, deterministic)
    if c.moe_aux_mode == "overflow":
        aux = jnp.zeros((), jnp.float32)  # nothing is ever dropped here
    return y.reshape(B, S, D), aux


def expert_counts(config, layer: dict, x: jax.Array) -> jax.Array:
    """(E,) int32: how many of the N x K assignments of ``x`` (B, S, D), the
    MLP's normed input, chose each expert at this layer's router."""
    return routing_rows(config, layer, x)[0]


def moe_mlp(
    config,
    layer: dict,  # one layer's params: router, moe_w1/b1 + moe_w2/b2 or moe_wgu + moe_wd
    x: jax.Array,  # (B, S, D) compute dtype
    dropout_key: Optional[jax.Array],
    deterministic: bool,
) -> Tuple[jax.Array, jax.Array]:
    """-> (output (B,S,D), aux load-balance loss scalar fp32).

    ``capacity_factor=None`` is the dropless path. Otherwise picks the
    capacity formulation per ``config.moe_dispatch`` (module docstring): the
    explicit all-to-all path needs a mesh in scope with a >1 'expert' axis,
    divisible geometry, and no manual/sequence/tensor/pipeline axes in play;
    anything else falls back to the GSPMD einsums.
    """
    c = config
    B, S, D = x.shape
    if c.capacity_factor is None:
        routed = _moe_mlp_dropless if c.experts_held is None else _moe_mlp_held
        y, aux = routed(c, layer, x, dropout_key, deterministic)
        if c.n_shared_experts:
            y = y + _shared_experts(c, layer, x)
        return y, aux
    mesh = None
    if c.moe_dispatch != "einsum" and c.seq_manual_axis is None:
        m = jax.sharding.get_abstract_mesh()
        if m is not None and "expert" in getattr(m, "axis_names", ()):
            mesh = m
    ep = mesh.shape.get("expert", 1) if mesh is not None else 1
    dp = mesh.shape.get("data", 1) if mesh is not None else 1
    geometry_ok = (
        ep > 1
        and c.n_experts % ep == 0
        and B % (dp * ep) == 0
        and mesh.shape.get("model", 1) == 1
        and mesh.shape.get("seq", 1) == 1
        and mesh.shape.get("pipe", 1) == 1
    )
    if c.moe_dispatch == "alltoall" and not geometry_ok:
        raise ValueError(
            "moe_dispatch='alltoall' needs an in-scope mesh with a >1 "
            "'expert' axis, n_experts % ep == 0, batch % (dp*ep) == 0, and "
            f"no model/seq/pipe axes > 1 (got mesh={mesh}, B={B})"
        )
    if geometry_ok:
        return _moe_mlp_a2a(c, layer, x, dropout_key, deterministic, mesh, ep, dp)
    return _moe_mlp_einsum(c, layer, x, dropout_key, deterministic)
