"""TPU-native distributed LLM training benchmark framework.

A brand-new, TPU-first re-design of the capabilities of
``deepaksatna/Distributed-LLM-Training-Benchmark-Framework`` (the reference):
four distributed-training strategy arms (ddp / fsdp / zero2 / zero3) expressed
as *sharding specifications* over a ``jax.sharding.Mesh`` applied to a single
shared, jitted train step — instead of the reference's four divergent
wrapper-object code paths (reference ``benchmarking/train_harness.py:207-275``).

Subpackages
-----------
- ``models``    TinyGPT decoder-only transformer (pure functional JAX)
- ``ops``       attention kernels (jnp reference + Pallas flash / ring attention)
- ``parallel``  mesh construction, strategy sharding specs, collectives
- ``train``     unified train step, timed benchmark loop, CLI harness
- ``data``      synthetic dataset (seeded, zero-I/O)
- ``utils``     metrics/result schema, HBM probes, config files
- ``analysis``  parse -> metrics.csv -> plots -> Markdown report pipeline
- ``runtime``   multi-host init (jax.distributed), profiling, checkpointing
"""

__version__ = "0.1.0"
