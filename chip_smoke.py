#!/usr/bin/env python3
"""The quickest proof that the trainer still starts on the chip.

    python3 chip_smoke.py               # one TPU chip (what the driver runs)
    python3 chip_smoke.py --four-chips  # the four-chip phase, and only that

One process, no child: a chip belongs to one process at a time. It fails
unless ``jax.devices()[0].platform`` is ``"tpu"``, and then

(a) runs the flash-attention forward and both backward paths (the XLA
    einsum backward and the fused Pallas backward kernel) as Mosaic kernels,
    never interpret mode, at TinyGPT tier-A widths, and compares outputs
    and gradients with ``reference_attention`` on the device;
(b) drives ``train.harness.main`` — the CLI behind
    ``benchmarking/train_harness.py`` — in this process at the full width of
    tier A (236M parameters, seq 2048, zero2, flash, unrolled, b1 x accum4),
    a few steps after warm-up, random weights from the harness's seed;
(c) reads the result row the harness wrote and checks it: a TPU backend,
    flash attention, loss finite and falling, tokens/s > 0, an MFU from a
    known device kind, a peak-HBM number and the rung that gave it, and no
    ``WARNING: ... skipped/failed`` line in the run's output.

With ``--four-chips`` it runs instead, through the harness at tier A: fsdp
over dp=4 with flash at seq 2048, then ring attention over sp=4 at seq 8192,
each beside the same seed and global batch on one device. It checks loss
parity between the two, that parameter and optimizer shards live on four
distinct devices, and that the compiled step holds the collectives the arm
needs.

Everything it writes goes under ``chiprun_out/chip_smoke/`` (plus the compile
cache, ``utils.platform.enable_compile_cache``). The last line of stdout is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}`` with
the device as jax reports it; any failure exits non-zero with no such line.
"""

import argparse
import contextlib
import io
import json
import os
import re
import sys

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, ROOT)

OUT_DIR = os.path.join(ROOT, "chiprun_out", "chip_smoke")

# What a passing run is held to. tests/test_chip_smoke.py steers these to
# run the same code at tier S on the CPU; the script has no option for it.
PLATFORM = "tpu"
INTERPRET = False  # the kernels run as Mosaic kernels, not interpreted
TIER = "A"
SEQ_LEN = 2048
KERNEL_SHAPE = (1, 2048, 16, 64)  # (batch, seq, heads, head_dim): tier A
STEPS, WARMUP_STEPS = 15, 3
# Loss parity of a sharded run against one device (__graft_entry__.py's).
PARITY_RTOL = 2e-2


class _Tee(io.TextIOBase):
    """stdout that also keeps what passed through it."""

    def __init__(self, stream):
        self.stream, self.kept = stream, io.StringIO()

    def write(self, text):
        self.kept.write(text)
        return self.stream.write(text)

    def flush(self):
        self.stream.flush()


def check(condition, message):
    if not condition:
        raise SystemExit(f"chip_smoke: FAILED — {message}")


def device_report():
    import jax

    devices = jax.devices()
    return {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }


def kernel_phase():
    """(a): fwd, einsum-bwd and Pallas-bwd against the reference."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from distributed_llm_training_benchmark_framework_tpu.ops.flash_attention import (
        flash_attention,
        reference_attention,
    )

    keys = jax.random.split(jax.random.key(0), 4)
    q, k, v, w = (
        jax.random.normal(key, KERNEL_SHAPE, jnp.bfloat16) for key in keys
    )

    def value_and_grads(attention):
        def loss(q, k, v):
            out = attention(q, k, v)
            # A fixed random projection makes the cotangent non-trivial.
            return jnp.sum((out * w).astype(jnp.float32)), out

        return jax.jit(
            jax.value_and_grad(loss, argnums=(0, 1, 2), has_aux=True)
        )

    def rel_err(got, want):
        got, want = got.astype(jnp.float32), want.astype(jnp.float32)
        return float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))

    (_, want_out), want_grads = value_and_grads(reference_attention)(q, k, v)
    for name, pallas_backward in (("einsum", False), ("pallas", True)):
        step = value_and_grads(lambda q, k, v: flash_attention(
            q, k, v, interpret=INTERPRET, pallas_backward=pallas_backward
        ))
        compiled = step.lower(q, k, v).compile()
        if not INTERPRET:
            n = compiled.as_text().count('custom_call_target="tpu_custom_call"')
            check(n == (2 if pallas_backward else 1),
                  f"{name}: expected Mosaic kernels in the HLO, found {n}")
        (_, got_out), got_grads = compiled(q, k, v)
        err_out = rel_err(got_out, want_out)
        err_grad = max(rel_err(g, r) for g, r in zip(got_grads, want_grads))
        print(f"kernels[{name}-bwd] shape={KERNEL_SHAPE}: max|out-ref|/"
              f"max|ref|={err_out:.3e}, grads {err_grad:.3e}", flush=True)
        # bf16 operands, fp32 accumulation on both sides: a few bf16 ulps.
        check(np.isfinite(err_out) and err_out < 3e-2,
              f"{name}: flash output differs from the reference ({err_out})")
        check(np.isfinite(err_grad) and err_grad < 3e-2,
              f"{name}: flash gradient differs from the reference ({err_grad})")


def run_harness(name, *, strategy, world_size, per_device_batch, grad_accum,
                attention="flash", seq_len=None, extra=()):
    """Drive train.harness.main in-process; return (row, its stdout)."""
    from distributed_llm_training_benchmark_framework_tpu.train import harness

    results_dir = os.path.join(OUT_DIR, name)
    argv = [
        "--tier", TIER, "--seq-len", str(seq_len or SEQ_LEN),
        "--strategy", strategy,
        "--attention", attention, "--layer-loop", "unrolled",
        "--per-device-batch", str(per_device_batch),
        "--grad-accum", str(grad_accum), "--world-size", str(world_size),
        "--steps", str(STEPS), "--warmup-steps", str(WARMUP_STEPS),
        "--results-dir", results_dir, *extra,
    ]
    print(f"--- harness[{name}]: {' '.join(argv)}", flush=True)
    tee = _Tee(sys.stdout)
    with contextlib.redirect_stdout(tee):
        rc = harness.main(argv)
    check(rc == 0, f"{name}: harness exited {rc}")
    rows = [f for f in os.listdir(results_dir) if f.startswith("result_")]
    check(len(rows) == 1, f"{name}: expected one result file, found {rows}")
    with open(os.path.join(results_dir, rows[0])) as f:
        return json.load(f), tee.kept.getvalue()


def check_row(name, row, output, device, attention="flash"):
    """(c): the row is a TPU measurement of the run that was asked for."""
    import math

    check(row["platform"] == row["backend"] == device["platform"] == PLATFORM,
          f"{name}: row platform {row['platform']!r}/{row['backend']!r}")
    check(row["device_kind"] == device["kind"], f"{name}: device_kind")
    check(row["device_count"] == device["count"], f"{name}: device_count")
    check(row["attention_impl"] == attention, f"{name}: attention_impl")
    check(row["tokens_per_sec"] > 0, f"{name}: tokens_per_sec")
    check(math.isfinite(row["mean_loss"]), f"{name}: loss not finite")
    check(row["loss_last_window"] < row["loss_first_window"],
          f"{name}: loss not falling ({row['loss_first_window']} -> "
          f"{row['loss_last_window']})")
    check(row["mfu_pct"] > 0, f"{name}: mfu_pct {row['mfu_pct']}")
    check(row["peak_hbm_gb"] > 0 and row["peak_hbm_method"] != "unavailable",
          f"{name}: peak HBM {row['peak_hbm_gb']} ({row['peak_hbm_method']})")
    check(row["hbm_attribution"] is not None, f"{name}: no memory anatomy")
    swallowed = [
        line for line in output.splitlines()
        if line.startswith("WARNING") and re.search("skipped|failed", line)
    ]
    check(not swallowed, f"{name}: swallowed failures: {swallowed}")


def print_row(name, row, output, cache_dir):
    clocks = re.search(
        r"Window clock, median s/step: (\S+) stopped at block_until_ready, "
        r"(\S+) stopped after the loss fetch", output,
    )
    check(clocks is not None, f"{name}: the loop printed no window clocks")
    print(f"smoke[{name}] tokens/s/chip: "
          f"{row['tokens_per_sec'] / row['world_size']:.1f}")
    print(f"smoke[{name}] step time median: {row['step_time_p50_sec']:.6f} s "
          f"(mean {row['mean_step_time_sec']:.6f} s, cv "
          f"{row['step_time_cv_pct']:.2f}%, {STEPS - WARMUP_STEPS} steps)")
    print(f"smoke[{name}] step time, loop's clock (block_until_ready): "
          f"{clocks.group(1)} s; stopped after the loss fetch: "
          f"{clocks.group(2)} s")
    print(f"smoke[{name}] MFU: {row['mfu_pct']:.2f}% of "
          f"{row['device_kind']!r} bf16 peak")
    print(f"smoke[{name}] peak HBM: {row['peak_hbm_gb']:.3f} GB "
          f"(method: {row['peak_hbm_method']})")
    print(f"smoke[{name}] compile (first step, incl. cache read): "
          f"{row['time_in_compile_sec']:.2f} s; cache dir: {cache_dir}")
    print(f"smoke[{name}] loss: {row['loss_first_window']:.4f} -> "
          f"{row['loss_last_window']:.4f} (mean {row['mean_loss']:.4f})")


def one_chip(device, cache_dir):
    kernel_phase()
    row, output = run_harness(
        "zero2_1chip", strategy="zero2", world_size=1, per_device_batch=1,
        grad_accum=4,
    )
    check_row("zero2_1chip", row, output, device)
    print_row("zero2_1chip", row, output, cache_dir)


def compare_with_one_device(name, device, cache_dir, *, sharded, single,
                            collectives, state_is_split):
    """One sharded arm on four chips through the harness, and the same seed
    and global batch on one device: loss parity, where the state lives, and
    which collectives the compiled step holds."""
    import jax

    from distributed_llm_training_benchmark_framework_tpu.analysis.static.hlo_audit import (
        count_collectives,
    )
    from distributed_llm_training_benchmark_framework_tpu.train import loop

    # Look at the train state the harness builds (its arrays are donated
    # into the first step, so the shard placement is read at creation) and
    # keep it, for the compiled step's HLO after the run.
    seen = {}
    create = loop.create_train_state

    def observing_create(*args, **kwargs):
        state = create(*args, **kwargs)
        seen["state"] = state
        seen["avals"] = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=x.sharding),
            (state.params, state.opt_state),
        )
        for part in ("params", "opt_state"):
            leaves = [
                leaf for leaf in jax.tree.leaves(getattr(state, part))
                if leaf.ndim > 0
            ]
            seen[part] = {
                "devices": {s.device for leaf in leaves
                            for s in leaf.addressable_shards},
                "split": sum(
                    leaf.addressable_shards[0].data.size < leaf.size
                    for leaf in leaves
                ),
                "leaves": len(leaves),
            }
        return state

    # threefry: dropout bits that do not depend on the sharding, so the two
    # runs draw the same masks and the comparison is exact math.
    threefry = ("--prng-impl", "threefry")
    loop.create_train_state = observing_create
    try:
        row4, out4 = run_harness(
            name, world_size=4, grad_accum=1,
            **{**sharded, "extra": threefry + sharded.get("extra", ())},
        )
    finally:
        loop.create_train_state = create
    state = seen["state"]
    # The step the run just executed (a cache hit), over the harness's
    # default 1000-row dataset table.
    table = jax.ShapeDtypeStruct(
        (1000, row4["seq_len"]), "int32",
        sharding=jax.sharding.NamedSharding(
            state.mesh, jax.sharding.PartitionSpec()
        ),
    )
    hlo = state.aot_compile(*seen["avals"], table).as_text()
    row1, out1 = run_harness(f"{name}_vs_1chip", world_size=1, grad_accum=1,
                             extra=threefry, **single)

    check_row(name, row4, out4, device, sharded.get("attention", "flash"))
    check_row(f"{name}_vs_1chip", row1, out1, device)
    print_row(name, row4, out4, cache_dir)
    print_row(f"{name}_vs_1chip", row1, out1, cache_dir)

    for part in ("params", "opt_state"):
        info = seen[part]
        print(f"four-chips[{name}] {part}: {info['split']}/{info['leaves']} "
              f"leaves split, shards on {len(info['devices'])} devices")
        check(len(info["devices"]) == 4,
              f"{part} shards live on {len(info['devices'])} devices, not 4")
        check(info["split"] > 0 or not state_is_split,
              f"no {part} leaf is split across devices")
    counts = count_collectives(hlo)
    kernels = hlo.count('custom_call_target="tpu_custom_call"')
    print(f"four-chips[{name}] compiled step: {counts}, {kernels} Mosaic "
          "kernels")
    for any_of in collectives:
        check(sum(counts[op] for op in any_of) > 0,
              f"compiled {name} step holds none of {any_of}")
    check(kernels > 0 or INTERPRET, f"compiled {name} step has no kernel")

    for key in ("loss_first_window", "loss_last_window", "mean_loss"):
        a, b = row4[key], row1[key]
        delta = abs(a - b) / max(abs(b), 1e-6)
        print(f"four-chips[{name}] parity {key}: {a:.6f} vs one device "
              f"{b:.6f}, rel delta {delta:.2e}")
        check(delta <= PARITY_RTOL,
              f"{name} {key}: {a} vs one device {b} (rel {delta:.2e})")


def four_chips(device, cache_dir):
    check(device["count"] >= 4, f"--four-chips needs 4 chips, found {device}")
    # The headline multi-chip arm: fsdp over dp=4 with flash attention.
    compare_with_one_device(
        "fsdp_dp4", device, cache_dir,
        sharded=dict(strategy="fsdp", per_device_batch=1),
        single=dict(strategy="fsdp", per_device_batch=4),
        collectives=(("all-gather",), ("reduce-scatter", "all-reduce")),
        state_is_split=True,
    )
    # A sequence four times as long, split over the chips by the ring
    # (same dropout masks as flash, so one device's flash is its reference).
    compare_with_one_device(
        "ring_sp4", device, cache_dir,
        sharded=dict(strategy="zero2", per_device_batch=1, attention="ring",
                     seq_len=4 * SEQ_LEN, extra=("--sequence-parallel", "4")),
        single=dict(strategy="zero2", per_device_batch=1,
                    seq_len=4 * SEQ_LEN),
        collectives=(("collective-permute",),),
        state_is_split=False,
    )


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument(
        "--four-chips", action="store_true",
        help="run the four-chip phase (fsdp dp=4 + flash, then ring sp=4, "
             "each vs one device) and no other",
    )
    args = parser.parse_args(argv)

    from distributed_llm_training_benchmark_framework_tpu.utils.platform import (
        enable_compile_cache,
    )

    cache_dir = enable_compile_cache()
    device = device_report()
    if device["platform"] != PLATFORM:
        print(f"chip_smoke: no accelerator — jax came up on {device}; this "
              "script proves the TPU path and reports nothing elsewhere",
              file=sys.stderr)
        return 1
    os.makedirs(OUT_DIR, exist_ok=True)
    print(f"chip_smoke: {device}, compile cache at {cache_dir}", flush=True)
    (four_chips if args.four_chips else one_chip)(device, cache_dir)
    sys.stdout.flush()
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
