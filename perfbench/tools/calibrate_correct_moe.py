#!/usr/bin/env python3
"""Where ``moe_loop.TOLERANCE`` comes from: on the chip, at an OLMoE-class
cell's real sizes, the program's per-position losses against the routed
reference, beside the reference in lower precisions and references that are
wrong on purpose. Run once when such a configuration is added.

    python3 perfbench/tools/calibrate_correct_moe.py <cell> [seed ...]

One JSON line a seed. ``program`` is what a run's initial check reads;
``reference_in_bf16_passes`` the reference at the TPU's default matmul
precision; ``reference_fp8_weights`` the reference with every weight rounded
to float8_e4m3fn (the nearest precision below the cell's bfloat16 compute:
it has to come out as not correct); ``top_k_sets_moved`` counts the positions
whose set of chosen experts in the program (bfloat16 activations) is not the
reference's, layer by layer.
"""

import json
import math
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))


def main(argv):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from distributed_llm_training_benchmark_framework_tpu.models import moe, tinygpt
    from perfbench.harness import build, build_moe, correct, manifest, moe_loop, reference_moe

    _, workload, config = manifest.load_cell(argv[0])
    jax.config.update("jax_default_prng_impl", "rbg")
    shape = build_moe.moe_shape(workload, config)
    for seed in [int(s) for s in argv[1:]] or [0]:
        state, _, tokens = build.build_state(workload, config, jax.devices()[: workload["chips"]], seed)
        batch = correct.first_micro_batch(state, tokens, workload)
        cfg = state.model_config

        def reference_losses(shape, precision, params=state.params):
            f = jax.jit(lambda params, batch: jax.vmap(
                lambda t: reference_moe.token_losses(shape, params, t))(batch))
            with jax.set_mesh(state.mesh), jax.default_matmul_precision(precision):
                return np.asarray(f(params, batch), np.float64)

        def chosen_sets(params, batch):
            """Per layer, whether each position's chosen experts are the same
            set in the program (its own activations) and in the reference."""
            moved, x = [], tinygpt.embed(cfg, params, batch)
            p32 = jax.tree.map(lambda t: t.astype(jnp.float32), params)
            xr = p32["wte"][batch]
            for i in range(cfg.n_layer):
                layer = jax.tree.map(lambda t: t[i], params["blocks"])
                x = tinygpt._attention_sublayer(cfg, x, layer, None, True)
                h = tinygpt._norm(cfg, x, layer["ln2_scale"], None)
                _, index, _, _ = moe._route_dropless(cfg, h.reshape(-1, h.shape[-1]), layer["router"])
                x, _ = tinygpt._mlp_sublayer(cfg, x, layer, None, True)
                with jax.default_matmul_precision("highest"):
                    w = jax.tree.map(lambda t: t[i], p32["blocks"])
                    hr = jax.vmap(lambda s: reference_moe._norm(shape, reference_moe._block_attention(shape, s, w), w["ln2_scale"], None))(xr)
                    probs = jax.nn.softmax(hr @ w["router"], -1)
                    _, want = jax.lax.top_k(probs, shape["experts_per_token"])
                    xr = jax.vmap(lambda s: reference_moe._block(shape, s, w)[0])(xr)
                same = jnp.all(jnp.sort(index.reshape(want.shape), -1) == jnp.sort(want, -1), -1)
                moved.append(jnp.sum(~same))
            return jnp.stack(moved)

        want = reference_losses(shape, "highest")
        with jax.set_mesh(state.mesh):
            got = np.asarray(jax.jit(moe_loop.token_losses(cfg, shape))(state.params, batch)[0],
                             np.float64)
            moved = np.asarray(jax.jit(chosen_sets)(state.params, batch))
        fp8 = jax.tree.map(lambda t: t.astype(jnp.float8_e4m3fn).astype(t.dtype), state.params)
        err = lambda x: math.sqrt(np.mean((x - want) ** 2)) / want.std()
        rel = lambda x: abs(x.mean() - want.mean()) / want.mean()
        out = {"cell": argv[0], "seed": seed, "reference_mean": want.mean(),
               "reference_spread": want.std(), "positions": int(want.size),
               "program": err(got), "program_mean_rel": rel(got),
               "top_k_sets_moved": moved.tolist()}
        for name, losses in {
            "reference_in_bf16_passes": reference_losses(shape, "default"),
            "reference_fp8_weights": reference_losses(shape, "default", fp8),
            "gates_renormalised": reference_losses({**shape, "norm_topk_prob": True}, "highest"),
            "qk_norm_left_out": reference_losses({**shape, "qk_norm": False}, "highest"),
            "one_expert_fewer": reference_losses(
                {**shape, "experts_per_token": shape["experts_per_token"] - 1}, "highest"),
            "wrong_mask": reference_losses({**shape, "causal": False}, "highest"),
        }.items():
            out[name], out[name + "_mean_rel"] = err(losses), rel(losses)
        print(json.dumps(out), flush=True)
        del state, fp8


if __name__ == "__main__":
    main(sys.argv[1:])
