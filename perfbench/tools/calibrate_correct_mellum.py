#!/usr/bin/env python3
"""Where ``mellum_loop.TOLERANCE`` comes from: on the chip, at a Mellum-2-class
cell's real sizes, the cell's own initial check (``mellum_loop.check_initial``:
the timed program fed the reference's hidden states a sublayer at a time, at
the cell's own weights) on the program against the reference, against the
reference in a lower precision, against references that are wrong on purpose
and on a program that is: each has to come out not correct. Run once when such
a configuration is added.

    python3 perfbench/tools/calibrate_correct_mellum.py <cell> [seed ...]
    python3 perfbench/tools/calibrate_correct_mellum.py <cell> --rows [qk scale,...] [seed ...]

One JSON line a seed: for every variant the check's verdict, every reading a
limit is on and which limits refused it. ``program`` is what a run's initial
check reads; ``reference_fp8_weights`` the reference with every weight rounded
to float8_e4m3fn (the nearest precision below the cell's bfloat16 compute over
float32 accumulation), ``reference_bf16_weights`` the same in bfloat16 (the
cell's own precision: it has to pass); the others get one part of the
mathematics wrong: a window of 1023 or 1025 keys; the window on the global
layer too; no window on the sliding layers; the sliding layers' plain table on
the global layer; YaRN without its ``attention_factor``; gates not
renormalised; one held expert fewer; and ``a_buffer_too_short`` is the program
itself with a held-rows buffer of 0.8 of the expected rows (its overflow, which
alone fails a run, is left out of ``refused_by``: the limits have to see the
rows that were dropped).

``--rows`` reads no reference: for each seed (and each QK-norm scale of the
comma-separated list, ``file`` for the cell's own start) the program's routing
at the seeded weights, one line a seed: the busiest expert's load over the mean
and the held experts' rows over the expected, by layer. What the builder reads
before anything is timed (PERF.md section 6, PR 36: a routed stack with no
shared expert can collapse onto the same experts at the program's start).
"""

import dataclasses
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))


def rows_only(workload, config, scales, seeds):
    import jax
    import numpy as np

    from perfbench.harness import build_mellum, correct, mellum_loop

    shape = build_mellum.mellum_shape(workload, config)
    expected = (workload["micro_batch_per_chip"] * workload["seq_len"] * shape["experts_per_token"]
                * shape["held"][1] / shape["experts"])
    for seed in seeds:
        for scale in scales:
            start = config if scale == "file" else {**config, "qk_norm_scale_init": float(scale)}
            state, _, tokens = mellum_loop.build_state(
                workload, start, jax.devices()[: workload["chips"]], seed)
            batch = correct.first_micro_batch(state, tokens, workload)
            with jax.set_mesh(state.mesh):
                counts, held = mellum_loop._programs(state.model_config)["routing"](state.params, batch)
            counts, held = np.asarray(counts, np.float64), np.asarray(held, np.float64)
            print(json.dumps({
                "seed": seed, "qk_norm_scale": scale,
                "load_max_over_mean": [round(float(x), 3) for x in counts.max(-1) / counts.mean(-1)],
                "held_rows_over_expected": [round(float(x), 4) for x in held[:, 0] / expected],
                "overflow": float(held[:, 1].sum()),
            }), flush=True)
            del state


def main(argv):
    import jax
    import jax.numpy as jnp

    from perfbench.harness import build, build_mellum, correct, manifest, mellum_loop

    cell, argv = argv[0], argv[1:]
    _, workload, config = manifest.load_cell(cell)
    if jax.devices()[0].platform != "tpu":  # a rehearsal of the control flow
        workload, config = build.tiny(workload, config)
        workload, config = build_mellum.tiny_mellum(workload, config)
    jax.config.update("jax_default_prng_impl", "rbg")
    if jax.devices()[0].platform == "tpu" and not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        # a wrong model changes a few of the check's programs: the others are read back
        jax.config.update("jax_compilation_cache_dir", os.path.join(manifest.BENCH_DIR, ".jax_cache"))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    if argv[:1] == ["--rows"]:
        scales = argv[1].split(",")
        return rows_only(workload, config, scales, [int(s) for s in argv[2:]] or [0])
    shape = build_mellum.mellum_shape(workload, config)
    first, count = shape["held"]
    kinds, tables = shape["kinds"], dict(shape["rotary"])
    theta, yarn = tables["global"]
    wrong = {
        "a_window_of_1023": {"window": shape["window"] - 1},
        "a_window_of_1025": {"window": shape["window"] + 1},
        "the_window_on_the_global_layer": {"mask_kinds": ("window",) * len(kinds)},
        "no_window_on_a_sliding_layer": {"mask_kinds": ("global",) * len(kinds)},
        "the_sliding_table_on_the_global_layer": {
            "rotary": tuple(sorted({**tables, "global": tables["window"]}.items()))},
        "yarn_without_its_attention_factor": {
            "rotary": tuple(sorted({**tables, "global": (theta, yarn[:4] + (1.0,))}.items()))},
        "gates_not_renormalised": {"norm_topk_prob": False},
        "one_held_expert_fewer": {"held": (first, count - 1)},
    }
    for seed in [int(s) for s in argv] or [0]:
        state, _, tokens = mellum_loop.build_state(
            workload, config, jax.devices()[: workload["chips"]], seed)
        batch = correct.first_micro_batch(state, tokens, workload)
        # nothing steps here: the moments' bytes make room for the rounded copy of the weights
        state = dataclasses.replace(state, opt_state=None)

        def check(shape, **other):
            numbers = mellum_loop.check_initial_numbers(state, shape, batch, **other)
            return {"ok": not mellum_loop.refused_by(numbers) and not numbers.get("held_overflow"),
                    "refused_by": mellum_loop.refused_by(numbers),
                    **{k: v for k, v in numbers.items() if "_err" in k or "held_" in k
                       or k in ("clear_tokens_share_min", "expert_load_max_over_mean")}}

        out = {"cell": cell, "seed": seed, "program": check(shape)}
        print(json.dumps({"seed": seed, "program": out["program"]}), flush=True)  # should the rest be cut
        for name, dtype in (("reference_fp8_weights", jnp.float8_e4m3fn),
                            ("reference_bf16_weights", jnp.bfloat16)):
            rounded = jax.tree.map(lambda t: t.astype(dtype).astype(t.dtype), state.params)
            out[name] = check(shape, reference_params=rounded)
            del rounded
        for name, change in wrong.items():
            out[name] = check({**shape, **change})
        out["a_buffer_too_short"] = check(shape, model_config=dataclasses.replace(
            state.model_config, held_rows_factor=0.8))
        print(json.dumps(out), flush=True)
        del state


if __name__ == "__main__":
    main(sys.argv[1:])
