#!/usr/bin/env python3
"""Where ``check_laguna.TOLERANCE`` comes from: on the chip, at a Laguna-class
cell's real sizes, the cell's own initial check (``check_laguna.check_initial``:
the timed program fed the reference's hidden states a sublayer at a time, at
the cell's own weights) on the program against the reference, against the
reference in a lower precision, against references that are wrong on purpose
and on programs that are: each has to come out not correct. Run once when such
a configuration is added.

    python3 perfbench/tools/calibrate_correct_laguna.py <cell> [--only variant,...] [seed ...]
    python3 perfbench/tools/calibrate_correct_laguna.py <cell> --rows [factor,...] [seed ...]

One JSON line a seed (and one a variant as it ends, should the rest be cut):
for every variant the check's verdict, every reading a limit is on and which
limits refused it. ``program`` is what a run's initial check reads;
``reference_fp8_weights`` the reference with every weight rounded to
float8_e4m3fn (the nearest precision below the cell's bfloat16 compute over
float32 accumulation), ``reference_bf16_weights`` the same in bfloat16 (the
cell's own precision: it has to pass). The others get one part of the
mathematics wrong (``WRONG``): a window of 511 or 513 keys, no window, the gate
left out, the gate from the un-normed input, all 128 lanes rotated on a full
layer, lanes paired j with j + 64 inside the half, ``attention_factor`` left
out, the other kind's theta or table, the query heads' K / V grouping one off,
gates not times 2.5, gates not renormalised, no shared expert, one held expert
fewer; and ``a_buffer_too_short`` is the program with a held-rows buffer of 0.8
of the expected rows (its overflow, which alone fails a run, is left out of
``refused_by``: the limits have to see the rows that were dropped).

``--rows`` reads no reference: for each seed and each ``held_rows_factor`` of
the comma-separated list the program's routing at the seeded weights, one line
a seed: the busiest expert's load over the mean and the held experts' rows
over the expected by routed layer, and the assignments over the buffer.
"""

import dataclasses
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))


def wrong_models(shape):
    """{name: the change to ``shape`` that makes the reference that model}."""
    first, count = shape["held"]
    table = dict(shape["rotary"])
    full, sliding = table["global"], table["window"]
    both = lambda g, w: (("global", g), ("window", w))
    return {
        "a_window_of_511_keys": {"window": shape["window"] - 1},
        "a_window_of_513_keys": {"window": shape["window"] + 1},
        "no_window_on_the_sliding_layers": {"mask_kinds": ("global",) * shape["layers"]},
        "the_gate_left_out": {"gate": None},
        "the_gate_from_the_un_normed_input": {"gate": "raw"},
        "all_128_lanes_of_a_full_layers_head_rotated": {
            "rotary": both((full[0], shape["head_dim"], full[2]), sliding)},
        "lanes_paired_j_with_j_plus_64_inside_the_half": {"pairing": "head"},
        "yarn_without_its_attention_factor": {
            "rotary": both((*full[:2], full[2][:4] + (1.0,)), sliding)},
        "the_sliding_theta_on_the_full_layers": {"rotary": both((sliding[0], *full[1:]), sliding)},
        "the_full_theta_on_the_sliding_layers": {"rotary": both(full, (full[0], *sliding[1:]))},
        "the_full_table_on_the_sliding_layers": {
            "rotary": both(full, (full[0], shape["head_dim"], full[2]))},
        "the_query_heads_grouped_one_off": {"kv_shift": 1},
        "gates_not_times_the_scaling_factor": {"routed_scaling": 1.0},
        "gates_not_renormalised": {"norm_topk_prob": False},
        "no_shared_expert": {"shared": False},
        "one_held_expert_fewer": {"held": (first, count - 1)},
    }


def rows_only(workload, config, factors, seeds):
    import jax
    import numpy as np

    from distributed_llm_training_benchmark_framework_tpu.models import tinygpt
    from perfbench.harness import build, build_laguna, correct

    for seed in seeds:
        for factor in factors:
            job = {**workload, "held_rows_factor": float(factor)}
            shape = build_laguna.laguna_shape(job, config)
            expected = (job["micro_batch_per_chip"] * job["seq_len"] * shape["experts_per_token"]
                        * shape["held"][1] / shape["experts"])
            state, _, tokens = build.build_state(job, config, jax.devices()[: job["chips"]], seed)
            batch = correct.first_micro_batch(state, tokens, job)
            with jax.set_mesh(state.mesh):
                counts, held = jax.jit(lambda p, b: tinygpt.moe_routing_rows(
                    state.model_config, p, b))(state.params, batch)
            counts, held = np.asarray(counts, np.float64), np.asarray(held, np.float64)
            print(json.dumps({
                "seed": seed, "held_rows_factor": float(factor),
                "load_max_over_mean": [round(float(x), 3) for x in counts.max(-1) / counts.mean(-1)],
                "held_rows_over_expected": [round(float(x), 4) for x in held[:, 0] / expected],
                "overflow": float(held[:, 1].sum()),
            }), flush=True)
            del state


def main(argv):
    import jax
    import jax.numpy as jnp

    from perfbench.harness import build, build_laguna, check_laguna, correct, manifest

    cell, argv = argv[0], argv[1:]
    _, workload, config = manifest.load_cell(cell)
    if jax.devices()[0].platform != "tpu":  # a rehearsal of the control flow
        workload, config = build_laguna.tiny_laguna(*build.tiny(workload, config))
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
    shape = build_laguna.laguna_shape(workload, config)
    for seed in [int(s) for s in argv] or [0]:
        state, _, tokens = build.build_state(workload, config, jax.devices()[: workload["chips"]], seed)
        batch = correct.first_micro_batch(state, tokens, workload)
        # nothing steps here: the moments' bytes make room for the rounded copy of the weights
        state = dataclasses.replace(state, opt_state=None)

        def check(name, shape, **other):
            numbers = check_laguna.check_initial_numbers(state, shape, batch, **other)
            refused = check_laguna.refused_by(numbers)
            found = {"ok": not refused and not numbers.get("held_overflow"), "refused_by": refused,
                     **{k: v for k, v in numbers.items() if "_err" in k or "held_" in k
                        or k in ("clear_tokens_share_min", "expert_load_max_over_mean",
                                 "attention_input_scale_min")}}
            print(json.dumps({"seed": seed, name: found}), flush=True)  # should the rest be cut
            return found

        out = {"cell": cell, "seed": seed, "program": check("program", shape)}
        for name, dtype in (("reference_fp8_weights", jnp.float8_e4m3fn),
                            ("reference_bf16_weights", jnp.bfloat16)):
            if not wanted(name):
                continue
            rounded = jax.tree.map(lambda t: t.astype(dtype).astype(t.dtype), state.params)
            out[name] = check(name, shape, reference_params=rounded)
            del rounded
        for name, change in wrong_models(shape).items():
            if wanted(name):
                out[name] = check(name, {**shape, **change})
        if wanted("a_buffer_too_short"):
            out["a_buffer_too_short"] = check("a_buffer_too_short", shape, model_config=dataclasses.replace(
                state.model_config, held_rows_factor=0.8))
        print(json.dumps(out), flush=True)
        del state


if __name__ == "__main__":
    main(sys.argv[1:])
