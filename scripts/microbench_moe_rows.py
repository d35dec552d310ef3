#!/usr/bin/env python3
"""The row passes of a chip's share of the routed experts, alone, on the chip:
tokens -> buffer rows (``dispatch``) and buffer rows -> tokens (``combine``;
``dispatch``'s transpose is the same operation without the gates), in the form
``models/moe.py::_moe_mlp_held`` had until PR 32 and in each candidate that PR
timed, at the sizes of ``deepseek-v2-lite.share8-seq8192``.

    chiprun -- python3 scripts/microbench_moe_rows.py [tokens top_k hidden experts held factor]

N tokens route over ``experts`` with ``top_k`` choices each from a seeded
router; the first ``held`` experts are this chip's; the buffer has M =
``factor`` x N x top_k x held / experts rows, in expert order, token order
inside an expert. Prints one JSON line a measurement (ms a call: 20 calls enqueued
back to back, the median of 10 such batches after 3 warm-ups; ``floor`` is what
an empty call reads that way):

* ``plan``: the index work alone (the sorts), today's and the new one's;
* ``to_rows``: the M-row gather every form shares;
* ``to_tokens``: rows -> tokens, gate-weighted, f32 accumulation, one line a
  form, with GB/s of what the pass has to move (live rows read once, N x D
  written once, bf16) against the HBM's 819;
* ``layer_forward_ms``: plan + to_rows + to_tokens; ``layer_backward_ms``: what
  the gradient of the input runs (the plan, ``combine``'s backward: a gather of
  M rows times their gates, ``dispatch``'s: rows -> tokens; the experts stand in
  as a multiplication by 2, so no forward result is needed again).

Standalone on purpose: the program keeps one form (PR 32: ``segment`` at 128
tokens a group, tile (256, 2048)), the others live here.
"""

import functools
import json
import statistics
import sys
import time

import jax
import jax.numpy as jnp
from jax.experimental.pallas.ops.tpu.megablox.gmm import tgmm

HBM_GBS = 819.0  # TPU v5e
INTERPRET = jax.default_backend() != "tpu"  # a CPU rehearsal at a small size


REPEATS = 20  # calls enqueued back to back before the host waits: the device's time a call,


def timed(f, *args):  # not the host's round trip (0.5 ms and more on a one-chip machine)
    for _ in range(3):
        jax.block_until_ready(f(*args))
    times = []
    for _ in range(10):
        t = time.perf_counter()
        for _ in range(REPEATS):
            out = f(*args)
        jax.block_until_ready(out)
        times.append((time.perf_counter() - t) / REPEATS)
    return 1e3 * statistics.median(times)


def route(xt, router, top_k):
    logits = jnp.einsum("nd,de->ne", xt.astype(jnp.float32), router,
                        precision=jax.lax.Precision.HIGHEST)
    gates, expert_idx = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), top_k)
    counts = jnp.sum(jax.nn.one_hot(expert_idx, router.shape[1], dtype=jnp.int32), axis=(0, 1))
    return gates, expert_idx, counts


# ---------------------------------------------------------------- the plans

def plan_today(expert_idx, counts, held, M):
    """Until PR 32: two sorts of all N x K keys; ``slot`` serves the gathers
    back."""
    order = jnp.argsort(jnp.where(expert_idx.reshape(-1) < held, expert_idx.reshape(-1), held),
                        stable=True)
    rows = jnp.minimum(jnp.sum(counts[:held]), M)
    position = jnp.argsort(order)
    return order[:M], jnp.where(position < rows, position, M), rows


def plan_rows(expert_idx, counts, held, M):
    """One sort of the N x K keys; nothing needs ``slot``."""
    order = jnp.argsort(jnp.where(expert_idx.reshape(-1) < held, expert_idx.reshape(-1), held),
                        stable=True)
    return order[:M], None, jnp.minimum(jnp.sum(counts[:held]), M)


# ------------------------------------------------- rows -> tokens, each form
# f(rows (M, D), token (M,), valid (M,), slot or None, N) -> (N, D), the f32
# sum over a token's rows in the rows' dtype.

def tokens_today(rows, token, valid, slot, N):
    rows = jnp.where(valid[:, None], rows, jnp.zeros((), rows.dtype))
    padded = jnp.concatenate([rows, jnp.zeros((1, rows.shape[1]), rows.dtype)])
    back = padded[slot].reshape(N, -1, rows.shape[1])
    return jnp.sum(back.astype(jnp.float32), axis=1).astype(rows.dtype)


def tokens_scatter(rows, token, valid, slot, N):
    live = jnp.where(valid[:, None], rows.astype(jnp.float32), 0.0)
    return jnp.zeros((N, rows.shape[1]), jnp.float32).at[token].add(live).astype(rows.dtype)


def tokens_scatter_sorted(rows, token, valid, slot, N):
    token = jnp.where(valid, token, N)  # padding behind every token, and dropped
    by_token = jnp.argsort(token)
    return jnp.zeros((N, rows.shape[1]), jnp.float32).at[token[by_token]].add(
        rows[by_token].astype(jnp.float32), indices_are_sorted=True, mode="drop").astype(rows.dtype)


def tokens_segment(tile, tm, tn, dtype=None):
    """Rows in token order; a tile of ``tile`` tokens owns one contiguous span
    of them, so the sum is ``tgmm``'s: (tile, span) one-hot x (span, D) rows.
    What the program kept (``models/moe.py::_tokens_from_rows``, with the sort
    in its plan)."""
    def f(rows, token, valid, slot, N):
        M, D = rows.shape
        token = jnp.where(valid, token, N)  # padding behind every span, in none
        sorted_token, by_token = jax.lax.sort_key_val(token, jnp.arange(M, dtype=jnp.int32))
        operand = dtype or rows.dtype
        inside = (sorted_token % tile)[None, :] == jnp.arange(tile)[:, None]
        spans = jnp.sum(jax.nn.one_hot(token // tile, N // tile, dtype=jnp.int32), axis=0)
        out = tgmm(inside.astype(operand), rows[by_token].astype(operand), spans, rows.dtype,
                   (min(tm, M), tile, min(tn, D)), interpret=INTERPRET)
        return out.reshape(N, D)
    return f


FORMS = {
    "today": tokens_today,
    "scatter": tokens_scatter,
    "scatter_sorted": tokens_scatter_sorted,
    "segment_t128_m512_n1024": tokens_segment(128, 512, 1024),
    "segment_t128_m512_n2048": tokens_segment(128, 512, 2048),
    "segment_t128_m256_n2048": tokens_segment(128, 256, 2048),
    "segment_t256_m512_n1024": tokens_segment(256, 512, 1024),
    "segment_t256_m512_n2048": tokens_segment(256, 512, 2048),
    "segment_t512_m512_n1024": tokens_segment(512, 512, 1024),
    "segment_t512_m1024_n1024": tokens_segment(512, 1024, 1024),
    "segment_t128_m512_n1024_f32": tokens_segment(128, 512, 1024, jnp.float32),
}


def pair(to_tokens_form):
    """(tokens -> rows, rows -> tokens), each the other's transpose."""
    @jax.custom_vjp
    def to_rows(xt, token, valid, slot):
        return jnp.where(valid[:, None], xt[token], jnp.zeros((), xt.dtype))

    def to_rows_fwd(xt, token, valid, slot):
        return to_rows(xt, token, valid, slot), (token, valid, slot, xt.shape[0])

    def to_rows_bwd(res, g):
        token, valid, slot, N = res
        return to_tokens(g, token, valid, slot, N), None, None, None

    to_rows.defvjp(to_rows_fwd, to_rows_bwd)

    @functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
    def to_tokens(rows, token, valid, slot, N):
        return to_tokens_form(rows, token, valid, slot, N)

    def to_tokens_fwd(rows, token, valid, slot, N):
        return to_tokens(rows, token, valid, slot, N), (token, valid, slot)

    def to_tokens_bwd(N, res, g):
        token, valid, slot = res
        return to_rows(g, token, valid, slot), None, None, None

    to_tokens.defvjp(to_tokens_fwd, to_tokens_bwd)
    return to_rows, to_tokens


def layer(form, plan, xt, gates, expert_idx, counts, held, M):
    """The row passes of one layer around a stand-in for the experts."""
    N, K = gates.shape
    take, slot, n_rows = plan(expert_idx, counts, held, M)
    token, valid = take // K, jnp.arange(M) < n_rows
    if form is tokens_today:
        return combine_today(2 * gather_today(xt, token, slot, valid), gates, take, slot, valid)
    to_rows, to_tokens = pair(form)
    out = 2 * to_rows(xt, token, valid, slot)  # the experts
    weighted = (out.astype(jnp.float32) * gates.reshape(-1)[take][:, None]).astype(out.dtype)
    return to_tokens(weighted, token, valid, slot, N)


# ------------------- until PR 32, as ``models/moe.py`` had them (commit 8b0bd1d)

@jax.custom_vjp
def gather_today(xt, take_token, slot, valid):
    return jnp.where(valid[:, None], xt[take_token], jnp.zeros((), xt.dtype))


def _gather_today_fwd(xt, take_token, slot, valid):
    return gather_today(xt, take_token, slot, valid), (slot, valid, xt.shape[0])


def _gather_today_bwd(res, g):
    slot, valid, N = res
    return tokens_today(g, None, valid, slot, N), None, None, None


gather_today.defvjp(_gather_today_fwd, _gather_today_bwd)


@jax.custom_vjp
def combine_today(out, gates, take, slot, valid):
    N, K = gates.shape
    out = jnp.where(valid[:, None], out, jnp.zeros((), out.dtype))
    padded = jnp.concatenate([out, jnp.zeros((1, out.shape[1]), out.dtype)])
    back = padded[slot].reshape(N, K, out.shape[1])
    return jnp.sum(back.astype(jnp.float32) * gates[:, :, None], axis=1).astype(out.dtype)


def _combine_today_fwd(out, gates, take, slot, valid):
    return combine_today(out, gates, take, slot, valid), (out, gates, take, slot, valid)


def _combine_today_bwd(res, dy):
    out, gates, take, slot, valid = res
    N, K = gates.shape
    dy_rows = dy[take // K].astype(jnp.float32)
    live = valid[:, None]
    d_out = jnp.where(live, dy_rows * gates.reshape(-1)[take][:, None], 0.0).astype(out.dtype)
    d_gate_rows = jnp.sum(jnp.where(live, out.astype(jnp.float32) * dy_rows, 0.0), axis=1)
    d_gates = jnp.concatenate([d_gate_rows, jnp.zeros((1,), jnp.float32)])[slot]
    return d_out, d_gates.reshape(N, K), None, None, None


combine_today.defvjp(_combine_today_fwd, _combine_today_bwd)


def main(argv):
    N, K, D, E, held, factor = (
        [int(x) for x in argv[:5]] + [float(argv[5])] if argv else [16384, 6, 2048, 64, 8, 1.5])
    M = int(factor * N * K * held / E)
    keys = jax.random.split(jax.random.key(0), 4)
    xt = jax.random.normal(keys[0], (N, D), jnp.bfloat16)
    router = 0.02 * jax.random.normal(keys[1], (D, E), jnp.float32)
    cotangent = jax.random.normal(keys[2], (N, D), jnp.bfloat16)
    gates, expert_idx, counts = jax.jit(route, static_argnums=2)(xt, router, K)
    take, slot, n_rows = jax.jit(plan_today, static_argnums=(2, 3))(expert_idx, counts, held, M)
    token, valid = take // K, jnp.arange(M) < n_rows
    live = int(n_rows)
    print(json.dumps({"device": jax.devices()[0].device_kind, "tokens": N, "top_k": K,
                      "hidden": D, "experts": E, "held": held, "buffer_rows": M,
                      "live_rows": live, "slots": N * K}), flush=True)

    print(json.dumps({"floor": "an empty call", "ms": timed(jax.jit(lambda v: v), valid)}),
          flush=True)
    for name, plan in (("today", plan_today), ("rows", plan_rows)):
        f = jax.jit(lambda e, c, plan=plan: plan(e, c, held, M))
        print(json.dumps({"plan": name, "ms": timed(f, expert_idx, counts)}), flush=True)
    sort_m = jax.jit(lambda t, v: jnp.argsort(jnp.where(v, t, N)))
    print(json.dumps({"plan": "sort of the M rows by token", "ms": timed(sort_m, token, valid)}),
          flush=True)

    gather = jax.jit(lambda x, t, v: jnp.where(v[:, None], x[t], jnp.zeros((), x.dtype)))
    rows = gather(xt, token, valid)
    ms = timed(gather, xt, token, valid)
    print(json.dumps({"to_rows": "gather of M rows", "ms": ms,
                      "GB/s": 2 * M * D * 2 / ms / 1e6}), flush=True)

    weights = gates.reshape(-1)[take]
    need_gb = (live * D * 2 + N * D * 2) / 1e9
    want = None
    for name, form in FORMS.items():
        f = jax.jit(lambda r, w, t, v, s, form=form: form(
            (r.astype(jnp.float32) * w[:, None]).astype(r.dtype), t, v, s, N))
        if form is tokens_today:  # its gates multiply the gathered (N, K, D) rows in f32
            f = jax.jit(lambda r, w, t, v, s: combine_today(r, gates, take, s, v))
        both = jax.jit(jax.grad(lambda x, g, e, c, ct, form=form: jnp.sum(
            layer(form, plan_today if form is tokens_today else plan_rows, x, g, e, c, held, M)
            .astype(jnp.float32) * ct.astype(jnp.float32))))
        forward = jax.jit(lambda x, g, e, c, form=form: layer(
            form, plan_today if form is tokens_today else plan_rows, x, g, e, c, held, M))
        try:
            got = f(rows, weights, token, valid, slot).astype(jnp.float32)
            want = got if want is None else want
            ms = timed(f, rows, weights, token, valid, slot)
            fwd_ms = timed(forward, xt, gates, expert_idx, counts)
            both_ms = timed(both, xt, gates, expert_idx, counts, cotangent)
        except Exception as e:  # a form the compiler refuses is a result too
            print(json.dumps({"to_tokens": name, "error": repr(e)[:300]}),
                  flush=True)
            continue
        print(json.dumps({
            "to_tokens": name, "ms": ms, "GB/s": need_gb / ms * 1e3,
            "of_hbm_pct": 100 * need_gb / ms * 1e3 / HBM_GBS,
            "layer_forward_ms": fwd_ms, "layer_backward_ms": both_ms,
            "max_abs_diff_from_today": float(jnp.max(jnp.abs(got - want))),
            "largest_output": float(jnp.max(jnp.abs(want))),
        }), flush=True)


if __name__ == "__main__":
    main(sys.argv[1:])
