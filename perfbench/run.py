#!/usr/bin/env python3
"""Run one cell of BENCHMARK.json once, in this process, on this machine.

    python3 perfbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The last line of stdout is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics``, ``device`` (and ``breakdown`` when traced). With
``--trace 0`` the metrics are the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics. It exits non-zero and prints no result
unless jax came up on a TPU with exactly the chips the cell asks for;
``--allow-cpu`` is the dry run (tiny sizes, no metric, ``"platform": "cpu"``).
"""

import time

PROCESS_START = time.perf_counter()  # set-up is counted from here

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    parser.add_argument("--allow-cpu", action="store_true",
                        help="dry run on the CPU at tiny sizes; reports no metric")
    args = parser.parse_args(argv)

    from perfbench.harness import build, manifest

    entry, workload, config = manifest.load_cell(args.workload)

    import jax

    # The cache's place is fixed: the path is part of its key. A dry run keeps none.
    if args.allow_cpu:
        jax.config.update("jax_enable_compilation_cache", False)
    elif not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(manifest.BENCH_DIR, ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    # What train/loop.py::run_benchmark sets around the step, and nothing else.
    jax.config.update("jax_default_prng_impl", "rbg")

    devices = jax.devices()
    platform, chips = devices[0].platform, workload["chips"]
    if args.allow_cpu and platform == "cpu" and len(devices) >= chips:
        devices = devices[:chips]
        workload, config = build.tiny(workload, config)
    elif platform != "tpu" or len(devices) != chips:
        print(f"perfbench: {args.workload} needs {chips} TPU chip(s); jax came up on "
              f"{len(devices)} x {platform!r}", file=sys.stderr)
        return 3
    import jaxlib

    print(f"perfbench: {args.workload} seed {args.seed}: jax {jax.__version__}, jaxlib "
          f"{jaxlib.__version__}, {len(devices)} x {devices[0].device_kind!r}, "
          f"XLA_FLAGS={os.environ.get('XLA_FLAGS', '')!r}, LIBTPU_INIT_ARGS="
          f"{os.environ.get('LIBTPU_INIT_ARGS', '')!r}", flush=True)

    driver = manifest.resolve(workload.get("driver", "perfbench.harness.step_loop:run"))
    result = driver(entry, workload, config, args, devices, PROCESS_START)
    sys.stdout.flush()
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
