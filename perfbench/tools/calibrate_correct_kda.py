#!/usr/bin/env python3
"""Where ``kda_loop.TOLERANCE`` comes from: on the chip, at a Kimi-Linear-class
cell's real sizes, the cell's own initial check (``kda_loop.check_initial``:
the timed program fed the reference's hidden states a sublayer at a time, at
the cell's own weights) on the program against the reference, against the
reference in a lower precision, against references that are wrong on purpose
and on programs that are: each has to come out not correct. Run once when such
a configuration is added.

    python3 perfbench/tools/calibrate_correct_kda.py <cell> [--only variant,...] [seed ...]
    python3 perfbench/tools/calibrate_correct_kda.py <cell> --rows [factor,...] [seed ...]

One JSON line a seed: for every variant the check's verdict, every reading a
limit is on and which limits refused it. ``program`` is what a run's initial
check reads; ``reference_fp8_weights`` the reference with every weight rounded
to float8_e4m3fn (the nearest precision below the cell's bfloat16 compute over
float32 accumulation), ``reference_bf16_weights`` the same in bfloat16 (the
cell's own precision: it has to pass); ``a_bfloat16_state_in_the_reference``
rounds the recurrence's state to bfloat16 after every position (the program
has no such switch: its state is float32); the
others get one part of the mathematics wrong: gates not renormalised; no
scaling factor; one held expert fewer; no shared expert; an l2norm eps of 1 (the
sum of a head's 128 squares is about 11 here);
filters of three taps (the first tap zeroed in the reference's weights); and
``a_buffer_too_short`` is the program with a held-rows buffer of 0.8 of the
expected rows (its overflow, which alone fails a run, is left out of
``refused_by``: the limits have to see the rows that were dropped).

``--rows`` reads no reference: for each seed and each ``factor@scale`` of the
comma-separated list (a ``held_rows_factor`` and where the KDA layers' head-norm
scales start: a number, or ``file`` for the config file's own
``kda_norm_scale_init``) the program's routing at the seeded weights, one line
a seed: the busiest expert's load over the mean and the held experts' rows
over the expected by routed layer, and the assignments over the buffer.
"""

import dataclasses
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))


def rows_only(workload, config, factors, seeds):
    import jax
    import numpy as np

    from perfbench.harness import build, build_kda, correct, kda_loop

    for seed in seeds:
        for factor, _, scale in (f.partition("@") for f in factors):
            job = {**workload, "held_rows_factor": float(factor)}
            start = config if scale in ("", "file") else {**config, "kda_norm_scale_init": float(scale)}
            shape = build_kda.kda_shape(job, config)
            expected = (job["micro_batch_per_chip"] * job["seq_len"] * shape["experts_per_token"]
                        * shape["held"][1] / shape["experts"])
            state, _, tokens = kda_loop.build_state(job, start, jax.devices()[: job["chips"]], seed)
            batch = correct.first_micro_batch(state, tokens, job)
            with jax.set_mesh(state.mesh):
                counts, held = kda_loop._programs(state.model_config)["routing"](state.params, batch)
            counts, held = np.asarray(counts, np.float64), np.asarray(held, np.float64)
            print(json.dumps({
                "seed": seed, "held_rows_factor": float(factor), "kda_norm_scale": scale or "file",
                "load_max_over_mean": [round(float(x), 3) for x in counts.max(-1) / counts.mean(-1)],
                "held_rows_over_expected": [round(float(x), 4) for x in held[:, 0] / expected],
                "overflow": float(held[:, 1].sum()),
            }), flush=True)
            del state


def main(argv):
    import jax
    import jax.numpy as jnp

    from perfbench.harness import build, build_kda, correct, kda_loop, manifest

    cell, argv = argv[0], argv[1:]
    _, workload, config = manifest.load_cell(cell)
    if jax.devices()[0].platform != "tpu":  # a rehearsal of the control flow
        workload, config = build_kda.tiny_kda(*build.tiny(workload, config))
    jax.config.update("jax_default_prng_impl", "rbg")
    if jax.devices()[0].platform == "tpu" and not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        # a wrong model changes a few of the check's programs: the others are read back
        jax.config.update("jax_compilation_cache_dir", os.path.join(manifest.BENCH_DIR, ".jax_cache"))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    if argv[:1] == ["--rows"]:
        return rows_only(workload, config, argv[1].split(","), [int(s) for s in argv[2:]] or [0])
    only = None
    if argv[:1] == ["--only"]:
        only, argv = set(argv[1].split(",")), argv[2:]
    wanted = lambda name: only is None or name in only
    shape = build_kda.kda_shape(workload, config)
    first, count = shape["held"]
    wrong = {
        "a_bfloat16_state_in_the_reference": {"state_dtype": "bfloat16"},
        "gates_not_renormalised": {"norm_topk_prob": False},
        "no_scaling_factor": {"routed_scaling": 1.0},
        "one_held_expert_fewer": {"held": (first, count - 1)},
        "no_shared_expert": {"shared": False},
        "an_l2norm_eps_of_1": {"l2_eps": 1.0},
    }
    for seed in [int(s) for s in argv] or [0]:
        state, _, tokens = kda_loop.build_state(workload, config, jax.devices()[: workload["chips"]], seed)
        batch = correct.first_micro_batch(state, tokens, workload)
        # nothing steps here: the moments' bytes make room for the rounded copy of the weights
        state = dataclasses.replace(state, opt_state=None)

        def check(shape, **other):
            numbers = kda_loop.check_initial_numbers(state, shape, batch, **other)
            return {"ok": not kda_loop.refused_by(numbers) and not numbers.get("held_overflow"),
                    "refused_by": kda_loop.refused_by(numbers),
                    **{k: v for k, v in numbers.items() if "_err" in k or "held_" in k
                       or k in ("clear_tokens_share_min", "expert_load_max_over_mean")}}

        out = {"cell": cell, "seed": seed, "program": check(shape)}
        print(json.dumps({"seed": seed, "program": out["program"]}), flush=True)  # should the rest be cut
        for name, dtype in (("reference_fp8_weights", jnp.float8_e4m3fn),
                            ("reference_bf16_weights", jnp.bfloat16)):
            if not wanted(name):
                continue
            rounded = jax.tree.map(lambda t: t.astype(dtype).astype(t.dtype), state.params)
            out[name] = check(shape, reference_params=rounded)
            del rounded
        if wanted("filters_of_three_taps"):
            three_taps = jax.tree_util.tree_map_with_path(
                lambda path, t: t.at[:, :, 0].set(0.0) if path[-1].key == "kda_conv" else t,
                state.params)
            out["filters_of_three_taps"] = check(shape, reference_params=three_taps)
            del three_taps
        for name, change in wrong.items():
            if wanted(name):
                out[name] = check({**shape, **change})
        if wanted("a_buffer_too_short"):
            out["a_buffer_too_short"] = check(shape, model_config=dataclasses.replace(
                state.model_config, held_rows_factor=0.8))
        print(json.dumps(out), flush=True)
        del state


if __name__ == "__main__":
    main(sys.argv[1:])
