"""The builder for Nemotron-H-class configurations (``model_type``
``nemotron_h``): a stack whose blocks are each one sublayer alone, by the
letters of ``hybrid_override_pattern`` (M a Mamba-2 mixer, E a routed
feed-forward part of experts that are not gated, * attention over few KV heads
without positions), sigmoid routing with a selection bias over the published
experts, one shared expert of its own width, with one chip's share of the
routed experts and of the vocabulary; and the sizes the arithmetic in
``flops_nemotron.py`` and ``reference_nemotron.py`` reads. A config file names
``nemotron_config`` under ``builder``."""

KINDS = {"M": "ssd", "E": "mlp", "*": "global"}


def nemotron_shape(workload, config):
    """What the reference, the FLOP count and the readers read; every value
    hashable. ``kinds`` the blocks' kinds in the published order (the first
    ``layers`` letters of the pattern); ``held`` the chip's (first, count) of
    the ``experts`` the router scores; ``vocab`` its slice. The wrong models of
    the calibration and of the tests are changes to this dict."""
    layers = workload.get("depth", config["num_hidden_layers"])
    kinds = tuple(KINDS[letter] for letter in config["hybrid_override_pattern"][:layers])
    return {
        "hidden": config["hidden_size"],
        "heads": config["num_attention_heads"],
        "kv_heads": config["num_key_value_heads"],
        "head_dim": config["head_dim"],
        "norm_eps": config["norm_eps"],
        "kinds": kinds,
        "ssd_heads": config["mamba_num_heads"],
        "ssd_head_dim": config["mamba_head_dim"],
        "ssd_groups": config["n_groups"],
        "ssd_state": config["ssm_state_size"],
        "ssd_conv": config["conv_kernel"],
        "chunk": config["chunk_size"],
        "state_dtype": "float32",
        "expert_width": config["moe_intermediate_size"],
        "shared_width": config["n_shared_experts"] * config["moe_shared_expert_intermediate_size"],
        "experts": config["n_routed_experts_published"],
        "held": (config["experts_held_first"], config["n_routed_experts"]),
        # a part of the experts, alone, does not train its routing (reference_nemotron, departure 2)
        "routing_trained": config["n_routed_experts"] == config["n_routed_experts_published"],
        "experts_per_token": config["num_experts_per_tok"],
        "norm_topk_prob": config["norm_topk_prob"],
        "routed_scaling": config["routed_scaling_factor"],
        "router_score": "sigmoid",
        "held_rows_factor": workload["held_rows_factor"],
        "vocab": config["vocab_size"],
        "layers": layers,
        "moe_layers": kinds.count("mlp"),
        "seq_len": workload["seq_len"],
    }


def tiny_nemotron(workload, config):
    """The widths ``--allow-cpu`` runs beside ``build.tiny``'s (hidden 64, 4
    heads of 16 over 2 KV heads): control flow only. The cell's nine blocks, 4
    scan heads of 16 channels in 2 groups over a state of 16, chunks of 32, 4
    of 8 experts held, 3 a token."""
    return ({**workload, "depth": 9},
            {**config, "mamba_num_heads": 4, "mamba_head_dim": 16, "n_groups": 2,
             "ssm_state_size": 16, "chunk_size": 32, "moe_intermediate_size": 32,
             "moe_shared_expert_intermediate_size": 48, "n_routed_experts_published": 8,
             "n_routed_experts": 4, "experts_held_first": 2, "num_experts_per_tok": 3})


def nemotron_config(workload, config):
    from distributed_llm_training_benchmark_framework_tpu.models.tinygpt import TinyGPTConfig

    m = nemotron_shape(workload, config)
    if (config["attention_bias"] or config["mamba_proj_bias"] or config["mlp_bias"]
            or config["use_bias"] or not config["use_conv_bias"]
            or config["mamba_hidden_act"] != "silu" or config["mlp_hidden_act"] != "relu2"
            or config["n_group"] != 1 or config["topk_group"] != 1
            or config["tie_word_embeddings"] or config["sliding_window"] is not None
            or config["n_shared_experts"] != 1):
        raise ValueError("the program computes nemotron_h with a bias on the convolution and "
                         "nowhere else, SiLU in the mixer, relu2 experts that are not gated, one "
                         "group of experts (no group step), one shared expert, full attention "
                         "and an untied head only")
    return TinyGPTConfig(
        vocab_size=m["vocab"], n_embd=m["hidden"], n_head=m["heads"], n_kv_head=m["kv_heads"],
        head_width=m["head_dim"], n_layer=m["layers"], block_size=m["seq_len"],
        dropout=config["dropout"], causal=True, attention_impl=workload["attention"],
        scan_layers={"scan": True, "unrolled": False}[workload["layer_loop"]],
        norm="rmsnorm", norm_eps=m["norm_eps"], pos_embed="none",
        mlp_act="relu2", mlp_hidden=m["expert_width"], bias=False, tie_embeddings=False,
        n_experts=m["experts"], expert_top_k=m["experts_per_token"], capacity_factor=None,
        norm_topk_prob=m["norm_topk_prob"], router_score="sigmoid",
        routed_scaling_factor=m["routed_scaling"], router_aux_coef=0.0,
        n_shared_experts=config["n_shared_experts"], shared_expert_hidden=m["shared_width"],
        experts_held=tuple(m["held"]), held_rows_factor=m["held_rows_factor"],
        layer_types=m["kinds"], block_halves=True,
        ssd_heads=m["ssd_heads"], ssd_head_dim=m["ssd_head_dim"], ssd_groups=m["ssd_groups"],
        ssd_state=m["ssd_state"], ssd_conv=m["ssd_conv"], ssd_chunk=m["chunk"],
    )
