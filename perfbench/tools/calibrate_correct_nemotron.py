#!/usr/bin/env python3
"""Where ``check_nemotron.TOLERANCE`` comes from: on the chip, at a
Nemotron-H-class cell's real sizes, the cell's own initial check
(``check_nemotron.check_initial``: the timed program fed the reference's hidden
states a block at a time, at the cell's own weights) on the program against
the reference, against the reference in a lower precision, against references
that are wrong on purpose and on programs that are: each has to come out not
correct. Run once when such a configuration is added.

    python3 perfbench/tools/calibrate_correct_nemotron.py <cell> [--only variant,...] [seed ...]
    python3 perfbench/tools/calibrate_correct_nemotron.py <cell> --rows [scale,...] [seed ...]

One JSON line a seed (and one a variant as it ends, should the rest be cut):
for every variant the check's verdict, every reading a limit is on and which
limits refused it. ``program`` is what a run's initial check reads;
``reference_fp8_weights`` the reference with every weight rounded to
float8_e4m3fn (the nearest precision below the cell's bfloat16 compute over
float32 accumulation), ``reference_bf16_weights`` the same in bfloat16 (the
cell's own precision: it has to pass). The others get one part of the
mathematics wrong (``WRONG``): a bfloat16 state in the scan, the norm before
the gate, one RMS over all 4096 channels... (``wrong_models``); and
``a_buffer_too_short`` is the program with a held-rows buffer of 0.8 of the
expected rows (its overflow, which alone fails a run, is left out of
``refused_by``: the limits have to see the rows that were dropped).

``--rows`` reads no reference: for each seed and each stand-in scale of the
comma-separated list (what multiplies the seeded start of the embedding's
rows; ``file`` takes the config file's ``embedding_scale_init``, 1 is the
program's own start) the program's routing at the seeded weights, one line
a seed: the busiest expert's load over the mean and the held experts' rows
over the expected by routed block, and the assignments over the buffer.
"""

import dataclasses
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))


def wrong_models(shape):
    """{name: the change to ``shape`` that makes the reference that model}."""
    first, count = shape["held"]
    return {
        "a_bfloat16_state_in_the_reference": {"state_dtype": "bfloat16"},
        "the_norm_before_the_gate": {"gate_first": False},
        "no_skip": {"skip": False},
        "no_bias_on_the_convolution": {"conv_bias": False},
        "heads_reading_the_next_groups_b_and_c": {"group_shift": 1},
        "rotary_on_the_attention_block": {"rotary": 10000.0},
        "relu_not_squared": {"squared": False},
        "gates_not_times_the_scaling_factor": {"routed_scaling": 1.0},
        "gates_not_renormalised": {"norm_topk_prob": False},
        "no_shared_expert": {"shared": False},
        "one_held_expert_fewer": {"held": (first, count - 1)},
    }


def rows_only(workload, config, scales, seeds):
    import jax
    import numpy as np

    from distributed_llm_training_benchmark_framework_tpu.models import tinygpt
    from perfbench.harness import build_nemotron, check_nemotron, correct

    for seed in seeds:
        for scale in scales:
            file = config if scale == "file" else {**config, "embedding_scale_init": float(scale)}
            shape = build_nemotron.nemotron_shape(workload, file)
            expected = (workload["micro_batch_per_chip"] * workload["seq_len"]
                        * shape["experts_per_token"] * shape["held"][1] / shape["experts"])
            state, _, tokens = check_nemotron.build_state(
                workload, file, jax.devices()[: workload["chips"]], seed)
            batch = correct.first_micro_batch(state, tokens, workload)
            with jax.set_mesh(state.mesh):
                counts, held = jax.jit(lambda p, b: tinygpt.moe_routing_rows(
                    state.model_config, p, b))(state.params, batch)
            counts, held = np.asarray(counts, np.float64), np.asarray(held, np.float64)
            print(json.dumps({
                "seed": seed, "embedding_scale_init": file.get("embedding_scale_init", 1.0),
                "load_max_over_mean": [round(float(x), 3) for x in counts.max(-1) / counts.mean(-1)],
                "held_rows_over_expected": [round(float(x), 4) for x in held[:, 0] / expected],
                "overflow": float(held[:, 1].sum()),
            }), flush=True)
            del state


def main(argv):
    import jax
    import jax.numpy as jnp

    from perfbench.harness import build, build_nemotron, check_nemotron, correct, manifest

    cell, argv = argv[0], argv[1:]
    _, workload, config = manifest.load_cell(cell)
    if jax.devices()[0].platform != "tpu":  # a rehearsal of the control flow
        workload, config = build_nemotron.tiny_nemotron(*build.tiny(workload, config))
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
    shape = build_nemotron.nemotron_shape(workload, config)
    for seed in [int(s) for s in argv] or [0]:
        state, _, tokens = check_nemotron.build_state(
            workload, config, jax.devices()[: workload["chips"]], seed)
        batch = correct.first_micro_batch(state, tokens, workload)
        # nothing steps here: the moments' bytes make room for the rounded copy of the weights
        state = dataclasses.replace(state, opt_state=None)

        def check(name, shape, **other):
            numbers = check_nemotron.check_initial_numbers(state, shape, batch, **other)
            refused = check_nemotron.refused_by(numbers)
            found = {"ok": not refused and not numbers.get("held_overflow"), "refused_by": refused,
                     **{k: v for k, v in numbers.items() if "_err" in k or "held_" in k
                        or k in ("clear_tokens_share_min", "expert_load_max_over_mean",
                                 "mixer_input_scale_min")}}
            print(json.dumps({"seed": seed, name: found}), flush=True)  # should the rest be cut
            return found

        out = {"cell": cell, "seed": seed}
        if wanted("program"):
            out["program"] = check("program", shape)
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
