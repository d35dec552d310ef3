#!/usr/bin/env python
"""The KDA recurrence (``ops/kda.py``) alone on the chip: the forward kernel
and the forward + backward at a cell's operand, a line a chunk size, and the
kernels' distance from the position-by-position recurrence at a short length.

    chiprun -- python scripts/microbench_kda.py [--rows 16384] [--heads 32]
        [--chunks 32,64,128] [--heads-per-step 1,2,4] [--iters 5]
        [--out chiprun_out/kda.jsonl]

``--prep``: what stands between a layer's q, k, v projection and the recurrence
instead, at (1, rows, 3 x heads x 128): the chain as it ran before the
convolution's kernels had an epilogue (``causal_conv``'s two calls, then
``silu_l2norm``, XLA's ``jnp`` chain) against ``qkv_prologue``'s calls, a line
each: ms a layer forward and forward + backward, the GB/s of the bytes one pass
needs (x in and q, k, v out; x and three cotangents in and dx out), and
``out_err`` / ``grad_err`` against the chain in float32 on the same operands.

``--gated``: a gated short-convolution mixer's middle (``ops.short_conv.gated_conv``)
at (2, rows, 3 x 2048) and 3 taps: its two Mosaic calls (``sconv_fwd`` /
``sconv_bwd``, a line a tile height of ``--gated-rows``) against the ``jnp``
chain (the two gates and the taps as shifted products, XLA's fusions) and
against the gates in ``jnp`` around ``causal_conv``'s kernels: ms forward and
forward + backward, the GB/s of the bytes one pass needs (the operand in and
the result out; the operand and the cotangent in and the operand's gradient
out), and ``out_err`` / ``grad_err`` against the chain in float32.

ms a layer's call (the mean of ``--iters`` after a warm-up) and |kernel -
recurrence| / |recurrence| of the output and the five gradients at
``--check-rows`` positions with bfloat16 operands. A pair the chip's compiler
refuses (a backward whose spilled registers pass the kernel's 16 MiB of VMEM)
gives a line with its ``error`` and the sweep goes on.
"""

import argparse
import json
import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rows", type=int, default=16384)
    ap.add_argument("--heads", type=int, default=32)
    ap.add_argument("--chunks", default="32,64,128")
    ap.add_argument("--heads-per-step", default="1,2,4")
    ap.add_argument("--iters", type=int, default=5)
    ap.add_argument("--check-rows", type=int, default=1024)
    ap.add_argument("--prep", action="store_true", help="the chain in front of the recurrence")
    ap.add_argument("--gated", action="store_true", help="a gated short convolution's middle")
    ap.add_argument("--gated-rows", default="128", help="tile heights of the gated kernels")
    ap.add_argument("--out")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    from distributed_llm_training_benchmark_framework_tpu.ops import kda
    from perfbench.harness import reference_kda

    def recurrent(q, k, v, g, beta):  # the benchmark's scan over the positions, float32
        f32 = lambda x: x.astype(jnp.float32)
        rule = lambda *a: reference_kda.delta_rule({"state_dtype": "float32"}, *a)
        with jax.default_matmul_precision("highest"):
            return jax.vmap(rule)(f32(q), f32(k), f32(v), g, beta)

    def operands(S, H, d=128):
        ks = jax.random.split(jax.random.PRNGKey(0), 6)
        unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
        q, k = (unit(jax.random.normal(key, (1, S, H, d))) for key in ks[:2])
        v = jax.random.normal(ks[2], (1, S, H, d))
        a = jnp.exp(jax.random.uniform(ks[3], (H, 1), maxval=jnp.log(16.0)))
        g = -a * jax.nn.softplus(jax.random.normal(ks[4], (1, S, H, d)) - 3.0)
        beta = jax.nn.sigmoid(jax.random.normal(ks[5], (1, S, H)))
        bf = lambda x: x.astype(jnp.bfloat16)
        return bf(q), bf(k), bf(v), g, beta

    def timed(f, *a):
        jax.block_until_ready(f(*a))
        t = time.perf_counter()
        for _ in range(args.iters):
            out = f(*a)
        jax.block_until_ready(out)
        return 1e3 * (time.perf_counter() - t) / args.iters

    lines = []
    rel = lambda a, b: float(jnp.linalg.norm(a.astype(jnp.float32) - b) / jnp.linalg.norm(b))
    ints = lambda text: [int(c) for c in text.split(",")]
    if args.prep or args.gated:
        lines = (gated_lines if args.gated else prep_lines)(args, timed, rel)
        return write(args.out, lines)
    loss = lambda f: (lambda *a: jnp.sum(f(*a).astype(jnp.float32) ** 2))
    small, big = operands(args.check_rows, 4), operands(args.rows, args.heads)
    want = recurrent(*small)
    want_grads = jax.grad(loss(recurrent), argnums=(0, 1, 2, 3, 4))(*small)
    for chunk, per_step in ((c, h) for c in ints(args.chunks) for h in ints(args.heads_per_step)):
        op = lambda *a: kda.kda(*a, chunk=chunk, interpret=False, heads_per_step=per_step)
        line = {"chunk": chunk, "heads_per_step": per_step, "rows": args.rows, "heads": args.heads}
        try:
            got_grads = jax.jit(jax.grad(loss(op), argnums=(0, 1, 2, 3, 4)))(*small)
            line["out_err"] = rel(jax.jit(op)(*small), want)
            line["grad_err"] = [rel(a, b) for a, b in zip(got_grads, want_grads)]
            line["fwd_ms"] = timed(jax.jit(op), *big)
            line["fwd_bwd_ms"] = timed(jax.jit(jax.grad(loss(op), argnums=(0, 1, 2, 3, 4))), *big)
        except jax.errors.JaxRuntimeError as e:  # the compiler's refusal: say it, go on
            text = " ".join(str(e).split())
            line["error"] = text if len(text) < 600 else text[:200] + " ... " + text[-350:]
        print(json.dumps(line), flush=True)
        lines.append(line)
    write(args.out, lines)


def prep_lines(args, timed, rel):
    import jax
    import jax.numpy as jnp

    from distributed_llm_training_benchmark_framework_tpu.ops import short_conv

    H, d, K = args.heads, 128, 4

    def operands(S, heads, dtype=jnp.bfloat16):
        ks = jax.random.split(jax.random.PRNGKey(0), 2)
        x = jax.random.normal(ks[0], (1, S, 3 * heads * d)).astype(dtype)
        bound = K ** -0.5  # the model's initialisation
        return x, jax.random.uniform(ks[1], (K, 3 * heads * d), minval=-bound, maxval=bound)

    def float32_chain(heads):  # tap by tap, SiLU, the l2norms: no kernel, nothing rounded
        def f(x, taps):
            S = x.shape[1]
            xf = jnp.pad(x.astype(jnp.float32), ((0, 0), (K - 1, 0), (0, 0)))
            return short_conv.silu_l2norm(sum(xf[:, i:i + S] * taps[i] for i in range(K)), heads)
        return f

    paths = {
        "parent_chain": lambda heads: lambda x, taps: short_conv.silu_l2norm(
            short_conv.causal_conv(x, taps, interpret=False), heads),
        "qkv_prologue": lambda heads: lambda x, taps: short_conv.qkv_prologue(
            x, taps, heads, interpret=False),
    }
    loss = lambda f: lambda x, taps: sum(jnp.sum(o.astype(jnp.float32) ** 2) for o in f(x, taps))
    small, big = operands(args.check_rows, 4), operands(args.rows, H)
    want = float32_chain(4)(*small)
    want_grads = jax.grad(loss(float32_chain(4)), argnums=(0, 1))(*small)
    third = args.rows * H * d * 2  # bytes of one of q, k, v in bfloat16
    lines = []
    for name, path in paths.items():
        line = {"path": name, "rows": args.rows, "heads": H}
        grads = jax.jit(jax.grad(loss(path(4)), argnums=(0, 1)))(*small)
        line["out_err"] = [rel(a, b) for a, b in zip(jax.jit(path(4))(*small), want)]
        line["grad_err"] = [rel(a, b) for a, b in zip(grads, want_grads)]
        line["fwd_ms"] = timed(jax.jit(path(H)), *big)
        line["fwd_bwd_ms"] = timed(jax.jit(jax.grad(loss(path(H)), argnums=(0, 1))), *big)
        line["fwd_gb_s"] = 6 * third / line["fwd_ms"] / 1e6
        line["bwd_gb_s"] = 9 * third / (line["fwd_bwd_ms"] - line["fwd_ms"]) / 1e6
        print(json.dumps(line), flush=True)
        lines.append(line)
    return lines


def gated_lines(args, timed, rel):
    import jax
    import jax.numpy as jnp

    from distributed_llm_training_benchmark_framework_tpu.ops import short_conv

    C, K, B = 2048, 3, 2

    def operands(batch, S, dtype=jnp.bfloat16):
        ks = jax.random.split(jax.random.PRNGKey(0), 2)
        bcx = jax.random.normal(ks[0], (batch, S, 3 * C)).astype(dtype)
        bound = K ** -0.5  # the model's initialisation
        return bcx, jax.random.uniform(ks[1], (K, C), minval=-bound, maxval=bound)

    def chain(dtype):  # the two gates and the taps as shifted products: no kernel
        def f(bcx, taps):
            S = bcx.shape[1]
            b, c, x = (bcx[..., i * C:(i + 1) * C].astype(jnp.float32) for i in range(3))
            v = jnp.pad(b * x, ((0, 0), (K - 1, 0), (0, 0)))
            return (c * sum(v[:, i:i + S] * taps[i] for i in range(K))).astype(dtype)
        return f

    def around_causal_conv(bcx, taps):  # the gates in jnp around the bare convolution's kernels
        b, c, x = (bcx[..., i * C:(i + 1) * C] for i in range(3))
        return c * short_conv.causal_conv(b * x, taps, interpret=False)

    kernels = lambda bcx, taps: short_conv.gated_conv(bcx, taps, interpret=False)
    # (name, path, the kernels' tile height where it is theirs)
    paths = [("jnp_chain", chain(jnp.bfloat16), None),
             ("gates_around_causal_conv", around_causal_conv, None),
             *((f"gated_conv_rows{r}", kernels, int(r)) for r in args.gated_rows.split(","))]
    loss = lambda f: lambda bcx, taps: jnp.sum(f(bcx, taps).astype(jnp.float32) ** 2)
    small, big = operands(1, args.check_rows), operands(B, args.rows)
    want = chain(jnp.float32)(*small)
    want_grads = jax.grad(loss(chain(jnp.float32)), argnums=(0, 1))(*small)
    third = B * args.rows * C * 2  # bytes of one of b, c, x in bfloat16
    lines = []
    for name, path, tile_rows in paths:
        line = {"path": name, "batch": B, "rows": args.rows, "columns": 3 * C}
        if tile_rows is not None:
            short_conv._GATED_ROWS = tile_rows
            short_conv._gated_call.cache_clear()
        try:
            grads = jax.jit(jax.grad(loss(path), argnums=(0, 1)))(*small)
            line["out_err"] = rel(jax.jit(path)(*small), want)
            line["grad_err"] = [rel(a, b) for a, b in zip(grads, want_grads)]
            line["fwd_ms"] = timed(jax.jit(path), *big)
            line["fwd_bwd_ms"] = timed(jax.jit(jax.grad(loss(path), argnums=(0, 1))), *big)
            line["fwd_gb_s"] = 4 * third / line["fwd_ms"] / 1e6
            line["bwd_gb_s"] = 7 * third / (line["fwd_bwd_ms"] - line["fwd_ms"]) / 1e6
        except jax.errors.JaxRuntimeError as e:  # the compiler's refusal: say it, go on
            text = " ".join(str(e).split())
            line["error"] = text if len(text) < 600 else text[:200] + " ... " + text[-350:]
        print(json.dumps(line), flush=True)
        lines.append(line)
    return lines


def write(out, lines):
    if out:
        os.makedirs(os.path.dirname(out), exist_ok=True)
        with open(out, "w") as f:
            f.writelines(json.dumps(line) + "\n" for line in lines)


if __name__ == "__main__":
    main()
