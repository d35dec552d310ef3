#!/usr/bin/env python3
"""Where ``check_lfm2.TOLERANCE`` comes from: on the chip, at an LFM2-MoE-class
cell's real sizes, the cell's own initial check (``check_lfm2.check_initial``:
the timed program fed the reference's hidden states a sublayer at a time, at
the cell's own weights) on the program against the reference, against the
reference in a lower precision, against references that are wrong on purpose
and on programs that are: each has to come out not correct. Run once when such
a configuration is added.

    python3 perfbench/tools/calibrate_correct_lfm2.py <cell> [--only variant,...] [seed ...]
    python3 perfbench/tools/calibrate_correct_lfm2.py <cell> --rows [seed ...]

One JSON line a seed (and one a variant as it ends, should the rest be cut): for
every variant the check's verdict, every reading a limit is on and which limits
refused it. ``program`` is what a run's initial check reads;
``reference_fp8_weights`` the reference with every weight rounded to
float8_e4m3fn (the nearest precision below the cell's bfloat16 compute over
float32 accumulation), ``reference_bf16_weights`` the same in bfloat16 (the
cell's own precision: it has to pass). The others get one part of the
mathematics wrong (``wrong_models``: a bfloat16 accumulation in the
convolution, a gate left out, a tap more or fewer, taps one position late, no
QK-norm, ...). Those that differ from the right model only where the expert
bias is not zero (the choice by the unbiased score, the gates from the biased
one) run with the bias of both sides drawn from the seed at ``BIAS`` (uniform
within it), and ``program_with_a_bias`` is the right model there: it has to
pass. ``a_buffer_too_short`` is the program with a held-rows buffer of 0.8 of
the expected rows (its overflow, which alone fails a run, is left out of
``refused_by``: the limits have to see the rows that were dropped).

``--rows`` reads no reference: for each seed the program's routing at the seeded
weights, one line a seed: the busiest expert's load over the mean and the held
experts' rows over the expected by routed layer, and the assignments over the
buffer.
"""

import dataclasses
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))

BIAS = 0.05  # a sigmoid score at the seeded start is 0.5 +- 0.2: a bias of this size moves choices


def wrong_models(shape):
    """{name: the change to ``shape`` that makes the reference that model}."""
    first, count = shape["held"]
    return {
        "a_bfloat16_convolution": {"conv_dtype": "bfloat16"},
        "no_b_gate": {"gate_b": False},
        "no_c_gate": {"gate_c": False},
        "two_taps": {"taps_used": 2},
        "four_taps": {"taps_used": 4},
        "taps_one_position_later": {"tap_shift": 1},
        "gates_not_renormalised": {"norm_topk_prob": False},
        "no_qk_norm": {"qk_norm": None},
        "the_norm_after_rotary": {"qk_norm": "after"},
        "no_rotary": {"rotary": False},
        "an_untied_head": {"tied": False},
        "bfloat16_router_logits": {"router_dtype": "bfloat16"},
        "one_held_expert_fewer": {"held": (first, count - 1)},
    }


WITH_A_BIAS = {
    "selection_by_the_unbiased_score": {"select_by": "score"},
    "gates_from_the_biased_score": {"gates_from": "biased"},
}


def with_a_bias(params, seed):
    """``params`` with every routed stack's expert bias drawn uniform within BIAS."""
    import jax

    out = dict(params)
    for i, (name, stack) in enumerate(sorted(params.items())):
        if isinstance(stack, dict) and "router_bias" in stack:
            bias = stack["router_bias"]
            drawn = jax.random.uniform(jax.random.fold_in(jax.random.key(seed % 2**31), i),
                                       bias.shape, bias.dtype, -BIAS, BIAS)
            out[name] = {**stack, "router_bias": jax.device_put(drawn, bias.sharding)}
    return out


def rows_only(workload, config, seeds):
    import jax
    import numpy as np

    from distributed_llm_training_benchmark_framework_tpu.models import tinygpt
    from perfbench.harness import build, build_lfm2, correct

    shape = build_lfm2.lfm2_shape(workload, config)
    expected = (workload["micro_batch_per_chip"] * workload["seq_len"]
                * shape["experts_per_token"] * shape["held"][1] / shape["experts"])
    for seed in seeds:
        state, _, tokens = build.build_state(
            workload, config, jax.devices()[: workload["chips"]], seed)
        batch = correct.first_micro_batch(state, tokens, workload)
        with jax.set_mesh(state.mesh):
            counts, held = jax.jit(lambda p, b: tinygpt.moe_routing_rows(
                state.model_config, p, b))(state.params, batch)
        counts, held = np.asarray(counts, np.float64), np.asarray(held, np.float64)
        print(json.dumps({
            "seed": seed,
            "load_max_over_mean": [round(float(x), 3) for x in counts.max(-1) / counts.mean(-1)],
            "held_rows_over_expected": [round(float(x), 4) for x in held[:, 0] / expected],
            "overflow": float(held[:, 1].sum()),
        }), flush=True)
        del state


def main(argv):
    import jax
    import jax.numpy as jnp

    from perfbench.harness import build, build_lfm2, check_lfm2, correct, manifest

    cell, argv = argv[0], argv[1:]
    _, workload, config = manifest.load_cell(cell)
    if jax.devices()[0].platform != "tpu":  # a rehearsal of the control flow
        workload, config = build_lfm2.tiny_lfm2(*build.tiny(workload, config))
    jax.config.update("jax_default_prng_impl", "rbg")
    if jax.devices()[0].platform == "tpu" and not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        # a wrong model changes a few of the check's programs: the others are read back
        jax.config.update("jax_compilation_cache_dir", os.path.join(manifest.BENCH_DIR, ".jax_cache"))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    if argv[:1] == ["--rows"]:
        return rows_only(workload, config, [int(s) for s in argv[1:]] or [0])
    only = None
    if argv[:1] == ["--only"]:
        only, argv = set(argv[1].split(",")), argv[2:]
    wanted = lambda name: only is None or name in only
    shape = build_lfm2.lfm2_shape(workload, config)
    for seed in [int(s) for s in argv] or [0]:
        state, _, tokens = build.build_state(
            workload, config, jax.devices()[: workload["chips"]], seed)
        batch = correct.first_micro_batch(state, tokens, workload)
        # nothing steps here: the moments' bytes make room for the rounded copy of the weights
        state = dataclasses.replace(state, opt_state=None)

        def check(name, shape, **other):
            numbers = check_lfm2.check_initial_numbers(state, shape, batch, **other)
            refused = check_lfm2.refused_by(numbers)
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
                other = {}
                if name == "an_untied_head":  # a head of its own: the embedding's rows in reverse
                    other["reference_params"] = {**state.params, "lm_head": state.params["wte"][::-1]}
                out[name] = check(name, {**shape, **change}, **other)
        if any(wanted(name) for name in ("program_with_a_bias", *WITH_A_BIAS)):
            biased = with_a_bias(state.params, seed)
            for name, change in {"program_with_a_bias": {}, **WITH_A_BIAS}.items():
                if wanted(name):
                    out[name] = check(name, {**shape, **change}, params=biased)
            del biased
        if wanted("a_buffer_too_short"):
            out["a_buffer_too_short"] = check("a_buffer_too_short", shape, model_config=dataclasses.replace(
                state.model_config, held_rows_factor=0.8))
        print(json.dumps(out), flush=True)
        del state


if __name__ == "__main__":
    main(sys.argv[1:])
