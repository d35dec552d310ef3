#!/usr/bin/env python3
"""``describe_compile.py`` for a cell whose config names its own ``builder``:
compile the real-size step for a described (not attached) v5e:2x2 and print
the bytes a chip needs.

    JAX_PLATFORMS=cpu python3 perfbench/tools/describe_cell.py <cell> [key=value ...]

``key=value`` overrides a key of the workload file for this compile (``remat=dots
depth=5 held_rows_factor=2.0``). Nothing runs. One such process at a time.
"""

import dataclasses
import json
import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))


def main(argv):
    import jax
    from jax.experimental import topologies

    from distributed_llm_training_benchmark_framework_tpu.parallel import get_strategy, make_mesh
    from distributed_llm_training_benchmark_framework_tpu.train.step import abstract_compile_step
    from perfbench.harness import build, manifest

    _, workload, config = manifest.load_cell(argv[0])
    for override in argv[1:]:
        key, _, value = override.partition("=")
        workload[key] = json.loads(value) if value[:1].isdigit() else value
    jax.config.update("jax_enable_compilation_cache", False)
    jax.config.update("jax_default_prng_impl", "rbg")
    jax.default_backend = lambda: "tpu"  # the program asks it whether to interpret its kernels
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    mesh = make_mesh(tuple(workload["mesh"][a] for a in build.MESH_AXES), build.MESH_AXES,
                     devices=topo.devices[: workload["chips"]])
    strategy = dataclasses.replace(get_strategy(workload["strategy"]), remat=workload["remat"])
    builder = manifest.resolve(config.get("builder", "perfbench.harness.build:tinygpt_config"))
    t = time.perf_counter()
    compiled = abstract_compile_step(
        builder(workload, config), strategy, mesh, grad_accum=workload["grad_accum"],
        global_micro=workload["micro_batch_per_chip"] * workload["mesh"]["data"],
        seq_len=workload["seq_len"], dataset_size=workload["dataset_rows"],
    )
    ma, text = compiled.memory_analysis(), compiled.as_text()
    print(json.dumps({
        "cell": argv[0], "overrides": argv[1:], "compile_s": round(time.perf_counter() - t, 1),
        "peak_gb": ma.peak_memory_in_bytes / 1e9,
        "arguments_gb": ma.argument_size_in_bytes / 1e9,
        "temporaries_gb": ma.temp_size_in_bytes / 1e9,
        "mosaic_kernels": text.count('custom_call_target="tpu_custom_call"'),
    }))


if __name__ == "__main__":
    main(sys.argv[1:])
