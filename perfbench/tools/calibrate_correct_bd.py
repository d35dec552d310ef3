#!/usr/bin/env python3
"""Where ``bd_loop.TOLERANCE`` comes from: on the chip, at an SDAR-class cell's
real sizes, the cell's own initial check (``bd_loop.check_initial``: the timed
program fed the reference's hidden states a sublayer at a time, at the cell's
own weights) on the program against the reference, against the reference in a
lower precision, against references that are wrong on purpose and on a program
that is: each has to come out not correct. Run once when such a configuration
is added.

    python3 perfbench/tools/calibrate_correct_bd.py <cell> [seed ...]

One JSON line a seed: for every variant the check's verdict, every reading a
limit is on and which limits refused it. ``program`` is what a run's initial
check reads;
``reference_fp8_weights`` the reference with every weight rounded to
float8_e4m3fn (the nearest precision below the cell's bfloat16 compute); the
others get one part of the mathematics wrong: a causal mask over the stream;
the noisy copy seeing its own clean block (``<=`` for ``<``); rotary positions
0 .. 2L-1 along the stream; QK-norm over the whole projected vector; gates not
renormalised; one held expert fewer; the loss without its 1 / t; the loss
divided by the masked count and not by L; and ``a_buffer_too_short`` is the
program itself with a held-rows buffer of 0.8 of the expected rows (its
overflow, which alone fails a run, is left out of ``refused_by``: the limits
have to see the rows that were dropped).
"""

import dataclasses
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))


def main(argv):
    import jax
    import jax.numpy as jnp

    from perfbench.harness import bd_loop, build, build_bd, correct, manifest

    _, workload, config = manifest.load_cell(argv[0])
    if jax.devices()[0].platform != "tpu":  # a rehearsal of the control flow
        workload, config = build.tiny(workload, config)
        config = build_bd.tiny_bd(config)
    jax.config.update("jax_default_prng_impl", "rbg")
    if jax.devices()[0].platform == "tpu" and not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        # a wrong model changes a few of the check's programs: the others are read back
        jax.config.update("jax_compilation_cache_dir", os.path.join(manifest.BENCH_DIR, ".jax_cache"))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    shape = build_bd.bd_shape(workload, config)
    first, count = shape["held"]
    wrong = {
        "a_causal_mask": {"mask": "causal"},
        "the_own_clean_block_seen": {"mask": "block_diffusion_le"},
        "positions_along_the_stream": {"positions": "stream"},
        "qk_norm_over_the_whole_vector": {"qk_norm": "whole"},
        "gates_not_renormalised": {"norm_topk_prob": False},
        "one_held_expert_fewer": {"held": (first, count - 1)},
        "the_loss_without_its_1_over_t": {"loss_weight": "one"},
        "the_loss_over_the_masked_count": {"loss_over": "masked"},
    }
    for seed in [int(s) for s in argv[1:]] or [0]:
        state, _, tokens = bd_loop.build_state(
            workload, config, shape, jax.devices()[: workload["chips"]], seed)
        batch = correct.first_micro_batch(state, tokens, workload)
        # nothing steps here: the moments' 5 GB make room for the float8 copy of the weights
        state = dataclasses.replace(state, opt_state=None)

        def check(parts, shape, **other):
            numbers = {}
            for part in parts:
                numbers.update(part(state, shape, batch, **other))
            return {"ok": not bd_loop.refused_by(numbers) and not numbers.get("held_overflow"),
                    "refused_by": bd_loop.refused_by(numbers),
                    **{k: v for k, v in numbers.items() if "_err" in k or "held_" in k
                       or k == "clear_tokens_share_min"}}

        layers, objective = (bd_loop.check_layers,), (bd_loop.check_objective,)
        out = {"cell": argv[0], "seed": seed, "program": check(layers + objective, shape)}
        fp8 = jax.tree.map(lambda t: t.astype(jnp.float8_e4m3fn).astype(t.dtype), state.params)
        out["reference_fp8_weights"] = check(layers + objective, shape, reference_params=fp8)
        del fp8
        for name, change in wrong.items():  # each through the part of the check it can change
            out[name] = check(objective if "loss" in name else layers, {**shape, **change})
        out["a_buffer_too_short"] = check(layers, shape, model_config=dataclasses.replace(
            state.model_config, held_rows_factor=0.8))
        print(json.dumps(out), flush=True)
        del state


if __name__ == "__main__":
    main(sys.argv[1:])
