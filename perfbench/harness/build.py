"""From a cell's two data files to the program's own objects.

The program's builders take a config *object*, so any width is data. This is
the generic builder, for every model ``models/tinygpt.TinyGPTConfig`` can
express (both knob sets: LayerNorm/learned/GELU/MHA and RMSNorm/RoPE/SwiGLU/
GQA). A config file may name another ``builder`` for a model it cannot.
"""

import dataclasses

MESH_AXES = ("data", "seq", "model", "pipe", "expert")  # train/loop.py's mesh

# What --allow-cpu runs instead of the published sizes: control flow only.
TINY = {"hidden_size": 64, "intermediate_size": 128, "vocab_size": 512,
        "depth": 2, "seq_len": 128, "dataset_rows": 32}


def tiny(workload, config):
    """The cell cut to a size the CPU interpreter can run (never a measurement)."""
    heads = 4
    kv = heads if config["num_key_value_heads"] == config["num_attention_heads"] else 2
    config = {**config, "hidden_size": TINY["hidden_size"],
              "intermediate_size": TINY["intermediate_size"],
              "vocab_size": TINY["vocab_size"], "num_attention_heads": heads,
              "num_key_value_heads": kv, "head_dim": TINY["hidden_size"] // heads}
    workload = {**workload, "depth": TINY["depth"], "seq_len": TINY["seq_len"],
                "dataset_rows": TINY["dataset_rows"], "warmup_steps": 1}
    return workload, config


def model_shape(workload, config):
    """The sizes the arithmetic in ``flops.py`` and ``reference.py`` reads."""
    return {
        "hidden": config["hidden_size"],
        "heads": config["num_attention_heads"],
        "kv_heads": config["num_key_value_heads"],
        "head_dim": config["head_dim"],
        "mlp_hidden": config["intermediate_size"],
        "mlp": config["mlp"],
        "norm": config["norm"],
        "norm_eps": config["norm_eps"],
        "positions": config["positions"],
        "rope_theta": config.get("rope_theta"),
        "tied_head": config["tie_word_embeddings"],
        "causal": config["causal"],
        "vocab": config["vocab_size"],
        "layers": workload.get("depth", config["num_hidden_layers"]),
        "seq_len": workload["seq_len"],
    }


def tinygpt_config(workload, config):
    """The generic builder: a ``TinyGPTConfig`` from the files' sizes."""
    from distributed_llm_training_benchmark_framework_tpu.models.tinygpt import (
        TinyGPTConfig,
    )

    m = model_shape(workload, config)
    if m["hidden"] != m["heads"] * m["head_dim"]:
        raise ValueError(
            f"TinyGPTConfig derives head_dim as hidden/heads; {m['hidden']} != "
            f"{m['heads']} x {m['head_dim']} needs a builder of its own"
        )
    if config.get("sliding_window") is not None:
        raise ValueError("TinyGPTConfig has no sliding window")
    return TinyGPTConfig(
        vocab_size=m["vocab"], n_embd=m["hidden"], n_head=m["heads"],
        n_layer=m["layers"], block_size=m["seq_len"], dropout=config["dropout"],
        causal=m["causal"], attention_impl=workload["attention"],
        scan_layers={"scan": True, "unrolled": False}[workload["layer_loop"]],
        norm=m["norm"], norm_eps=m["norm_eps"], pos_embed=m["positions"],
        rope_theta=m["rope_theta"] or 10000.0, mlp_act=m["mlp"],
        mlp_hidden=m["mlp_hidden"],
        n_kv_head=None if m["kv_heads"] == m["heads"] else m["kv_heads"],
        bias=config["bias"], tie_embeddings=m["tied_head"],
    )


def build_state(workload, config, devices, seed, dropout_seed=0):
    """Mesh, strategy, the seeded state on the device, the step and the tokens
    (on the device and on the host).

    Weights and tokens come from ``seed``. The step is built with a fixed
    ``dropout_seed``: the program folds its seed into the jitted step as a
    constant, so a step built from ``--seed`` would be another program, and
    another compilation, in every run.
    """
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from distributed_llm_training_benchmark_framework_tpu.parallel import (
        get_strategy, make_mesh,
    )
    from distributed_llm_training_benchmark_framework_tpu.parallel.strategies import (
        make_optimizer,
    )
    from distributed_llm_training_benchmark_framework_tpu.train.step import (
        create_train_state, make_train_step,
    )

    from .manifest import resolve

    builder = resolve(config.get("builder", "perfbench.harness.build:tinygpt_config"))
    model_config = builder(workload, config)
    degrees = tuple(workload["mesh"][axis] for axis in MESH_AXES)
    mesh = make_mesh(degrees, MESH_AXES, devices=devices)
    strategy = dataclasses.replace(
        get_strategy(workload["strategy"]), remat=workload["remat"]
    )
    step_shape = dict(
        grad_accum=workload["grad_accum"], from_table=True,
        global_micro=workload["micro_batch_per_chip"] * workload["mesh"]["data"],
        seq_len=workload["seq_len"],
    )
    state = create_train_state(model_config, strategy, mesh, seed=seed, **step_shape)
    step_fn, aot_compile = make_train_step(
        model_config, strategy, make_optimizer(strategy), mesh,
        state.param_specs, state.opt_specs, seed=dropout_seed, **step_shape,
    )
    state = dataclasses.replace(state, step_fn=step_fn, aot_compile=aot_compile)
    tokens = token_table(model_config.vocab_size, workload, seed)
    table = jax.device_put(tokens, NamedSharding(mesh, P()))
    jax.block_until_ready((state.params, state.opt_state, table))
    return state, table, tokens


def token_table(vocab, workload, seed):
    """The benchmark's own generator of the job's input: ``dataset_rows``
    sequences of ``seq_len`` tokens drawn uniformly from the vocabulary, from
    the seed, as the program's ``data/synthetic.SyntheticDataset`` draws them
    (which takes a minute to compile its ``randint`` for the TPU under the
    rbg generator). The step gathers its rows from the table on the device."""
    import numpy as np

    return np.random.default_rng(seed).integers(
        0, vocab, (workload["dataset_rows"], workload["seq_len"]), dtype=np.int32
    )
