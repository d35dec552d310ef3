#!/usr/bin/env python3
"""Where ``mla_loop.TOLERANCE`` comes from: on the chip, at a DeepSeek-V2-class
cell's real sizes, the cell's own initial check (``mla_loop.check_initial``)
on the program against the reference, and against the reference in lower
precisions and references that are wrong on purpose: each has to come out not
correct. Run once when such a configuration is added.

    python3 perfbench/tools/calibrate_correct_mla.py <cell> [seed ...]

One JSON line a seed: for every variant the check's verdict, the readings the
limits are on (``per_position_err``, ``expert_grad_err``, ``held_rows_err``)
and which of them refused it. ``program`` is what a run's initial check
reads; ``reference_fp8_weights`` the reference with every weight rounded to
float8_e4m3fn (the nearest precision below the cell's bfloat16 compute);
``reference_in_bf16_passes`` the reference at the TPU's default matmul
precision (forward only: not a wrong model); the others leave one part of the
mathematics out or get it wrong. ``assignments_moved`` counts, layer by layer,
the assignments whose expert in the program is not the reference's (a flip
moves a whole expert's term in or out of the held sum);
``held_rows_over_expected`` the rows that landed on the held experts, layer by
layer.
"""

import dataclasses
import json
import math
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))


def main(argv):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from perfbench.harness import build, build_mla, correct, manifest, mla_loop, reference_mla

    _, workload, config = manifest.load_cell(argv[0])
    if jax.devices()[0].platform != "tpu":  # a rehearsal of the control flow
        workload, config = build.tiny(workload, config)
        config = build_mla.tiny_mla(config)
    jax.config.update("jax_default_prng_impl", "rbg")
    shape = build_mla.mla_shape(workload, config)
    first, count = shape["held"]
    plain_scale = (shape["qk_nope"] + shape["qk_rope"]) ** -0.5
    wrong = {
        "yarn_off": {"yarn": None},
        "scale_without_m2": {"softmax_scale": plain_scale},
        "latent_norm_left_out": {"latent_norm": False},
        "rotary_over_the_whole_head": {"rope_whole_head": True},
        "shared_experts_left_out": {"shared_width": 0},
        "one_held_expert_fewer": {"held": (first, count - 1)},
    }
    limits = {"per_position_err": "per_position", "expert_grad_err": "expert_grad",
              "held_rows_err": "held_rows", "mean_loss_rel_err": "mean_loss"}
    for seed in [int(s) for s in argv[1:]] or [0]:
        state, _, tokens = build.build_state(workload, config, jax.devices()[: workload["chips"]], seed)
        batch = correct.first_micro_batch(state, tokens, workload)
        # nothing steps here: the moments' 5 GB make room for the float8 copy of the weights
        state = dataclasses.replace(state, opt_state=None)
        with jax.set_mesh(state.mesh):
            program = jax.jit(mla_loop.program_side(state.model_config))(state.params, batch)
        counts, held = np.asarray(program[2]), np.asarray(program[3])

        def check(shape, reference_params=None):
            ok, numbers = mla_loop.check_initial(state, shape, batch, program, reference_params)
            refused = [k for k, limit in limits.items() if numbers[k] > mla_loop.TOLERANCE[limit]]
            return {"ok": ok, "refused_by": refused, **{k: numbers[k] for k in limits}}

        def forward(precision):
            f = jax.jit(lambda params, batch: jax.lax.map(
                lambda t: reference_mla.token_losses_and_counts(shape, params, t), batch))
            with jax.set_mesh(state.mesh), jax.default_matmul_precision(precision):
                losses, counts = f(state.params, batch)
            return np.asarray(losses, np.float64), np.asarray(counts).sum(0)

        want, want_counts = forward("highest")
        err = lambda x: math.sqrt(np.mean((x - want) ** 2)) / want.std()
        expected = batch.size * shape["experts_per_token"] * count / shape["experts"]
        out = {"cell": argv[0], "seed": seed, "reference_mean": want.mean(),
               "reference_spread": want.std(), "positions": int(want.size),
               # half the L1 distance of the per-expert counts: assignments that moved
               "assignments_moved": (np.abs(counts - want_counts).sum(-1) // 2).tolist(),
               "held_rows_over_expected": (held[:, 0] / expected).round(3).tolist(),
               "held_overflow": int(held[:, 1].sum()),
               "reference_in_bf16_passes": err(forward("default")[0]),
               "program": check(shape)}
        fp8 = jax.tree.map(lambda t: t.astype(jnp.float8_e4m3fn).astype(t.dtype), state.params)
        out["reference_fp8_weights"] = check(shape, fp8)
        del fp8
        for name, change in wrong.items():
            out[name] = check({**shape, **change})
        print(json.dumps(out), flush=True)
        del state, program


if __name__ == "__main__":
    main(sys.argv[1:])
