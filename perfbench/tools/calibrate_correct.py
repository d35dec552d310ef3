#!/usr/bin/env python3
"""Where ``correct.TOLERANCE`` comes from: on the chip, at a cell's real
sizes, the program's per-position losses against the reference, beside
references that are wrong on purpose. Run once when a configuration is added.

    python3 perfbench/tools/calibrate_correct.py <cell> [seed]
"""

import json
import math
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))


def main(argv):
    import jax
    import numpy as np

    from perfbench.harness import build, correct, manifest, reference

    _, workload, config = manifest.load_cell(argv[0])
    seed = int(argv[1]) if len(argv) > 1 else 0
    jax.config.update("jax_default_prng_impl", "rbg")
    state, _, tokens = build.build_state(workload, config, jax.devices()[: workload["chips"]], seed)
    shape = build.model_shape(workload, config)
    batch = correct.first_micro_batch(state, tokens, workload)

    def reference_losses(shape, precision):
        f = jax.jit(lambda params, batch: jax.vmap(
            lambda t: reference.token_losses(shape, params, t))(batch))
        with jax.set_mesh(state.mesh), jax.default_matmul_precision(precision):
            return np.asarray(f(state.params, batch), np.float64)

    want = reference_losses(shape, "highest")
    with jax.set_mesh(state.mesh):
        got = np.asarray(jax.jit(correct.token_losses(state.model_config, shape))(
            state.params, batch)[0], np.float64)
    wrong = reference_losses({**shape, "causal": not shape["causal"]}, "highest")
    err = lambda x: math.sqrt(np.mean((x - want) ** 2)) / want.std()
    out = {"cell": argv[0], "seed": seed, "reference_mean": want.mean(),
           "reference_spread": want.std(), "program": err(got),
           "program_mean_rel": abs(got.mean() - want.mean()) / want.mean(),
           "wrong_mask": err(wrong),
           "wrong_mask_mean_rel": abs(wrong.mean() - want.mean()) / want.mean(),
           "reference_in_bf16_passes": err(reference_losses(shape, "default"))}
    if shape["positions"] == "rope":
        out["no_rope"] = err(reference_losses({**shape, "positions": "none"}, "highest"))
    print(json.dumps(out))


if __name__ == "__main__":
    main(sys.argv[1:])
