#!/usr/bin/env python3
"""What the flash kernels hand the TPU compiler, as text: a file a kernel and
configuration, the Mosaic module printed without source locations.

    JAX_PLATFORMS=cpu python scripts/flash_mosaic_modules.py <out dir> [filter ...]
    diff -r <out dir of one checkout> <out dir of another>

No chip: the calls are lowered for a described v5e. Two checkouts whose files
are equal run the same kernels, whatever their Python looks like: that is how
PR 37 moved the bodies' chains from ``jnp`` operators to ``lax`` primitives
(a third of the trace's cost) without a chip run of the kernels, and how a
call with no mask is shown to be the parent's. The configurations: forward
and fused backward x no mask / causal at S 4096 / ``BlockDiffusion(8192, 4)``
over a stream of 16,384 x dropout 0 / 0.1 x widths 128, 64 and 192 over 128,
at the Mosaic path's tiles. ``filter`` keeps the names that hold one of the
strings (``fwd.causal``, ``0.1.192``). One such process at a time.
"""

import base64
import itertools
import json
import os
import re
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from distributed_llm_training_benchmark_framework_tpu.ops import (  # noqa: E402
    flash_attention as fa,
)

MASKS = {"none": (False, 4096), "causal": (True, 4096), "bd": (fa.BlockDiffusion(8192, 4), 16384)}
RATES = (0.0, 0.1)
WIDTHS = ((128, 128), (64, 64), (192, 128))
BH, TILE = 2, 1024


def mosaic_modules(lowered_text):
    """[text] of the Mosaic modules in a lowered computation: each
    ``tpu_custom_call``'s serialized body parsed and printed without debug
    info (a body keeps the file and line of every op), under its other
    settings."""
    from jax._src.lib import tpu
    from jax._src.lib.mlir import ir

    found = []
    for config in re.findall(r'backend_config = "((?:[^"\\]|\\.)*)"', lowered_text):
        config = json.loads(config.replace("\\22", '"').replace("\\5C", "\\"))["custom_call_config"]
        context = ir.Context()
        tpu.register_dialect(context)
        context.allow_unregistered_dialects = True
        with context:
            module = ir.Module.parse(base64.b64decode(config.pop("body")))
            found.append(json.dumps(config, sort_keys=True) + "\n"
                         + module.operation.get_asm(enable_debug_info=False))
    return found


def kernels(device):
    """(name, jitted call, its abstract operands) of every configuration."""
    from jax.sharding import SingleDeviceSharding

    one = SingleDeviceSharding(device)

    def array(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    for (mask_name, (mask, S)), rate, (D, Dv) in itertools.product(MASKS.items(), RATES, WIDTHS):
        q, v = array((BH, S, D), jnp.bfloat16), array((BH, S, Dv), jnp.bfloat16)
        seed, bhv = array((1,), jnp.uint32), array((BH,), jnp.int32)
        stat = array((BH, 8, S), jnp.float32)

        def forward(q, k, v, seed, bhv, mask=mask, rate=rate):
            return fa._flash_forward(q, k, v, mask, False, TILE, TILE, rate, seed, bhv)

        def backward(q, k, v, do, lse3, delta3, seed, bhv, mask=mask, rate=rate):
            return fa._fused_backward(
                q, k, v, do, lse3, delta3, seed, bhv, mask, rate, TILE, TILE, False)

        tag = f"{mask_name}.{rate}.{D}.{Dv}"
        yield f"fwd.{tag}", jax.jit(forward), (q, q, v, seed, bhv)
        yield f"bwd.{tag}", jax.jit(backward), (q, q, v, v, stat, stat, seed, bhv)


def main(argv):
    from jax.experimental import topologies

    if not argv:
        sys.exit(__doc__)
    out, filters = argv[0], argv[1:]
    os.makedirs(out, exist_ok=True)
    topology = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    for name, call, operands in kernels(topology.devices[0]):
        if filters and not any(f in name for f in filters):
            continue
        (module,) = mosaic_modules(call.lower(*operands).as_text())
        with open(os.path.join(out, name + ".mlir"), "w") as f:
            f.write(module)
        print(name, len(module), flush=True)


if __name__ == "__main__":
    main(sys.argv[1:])
