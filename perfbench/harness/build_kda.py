"""The builder for Kimi-Linear-class configurations (``model_type``
``kimi_linear``): Kimi-Delta-Attention layers (the gated delta rule with a
decay a key channel, short convolutions, a gated head norm) beside NoPE
latent-attention ones in one stack, a leading dense layer that is itself a KDA
layer, sigmoid routing with a selection bias over the published experts, one
shared expert, with one chip's share of the routed experts and of the
vocabulary; and the sizes the arithmetic in ``flops_kda.py`` and
``reference_kda.py`` reads. A config file names ``kimi_config`` under
``builder``."""


def kda_shape(workload, config):
    """What the reference, the FLOP count and the readers read; every value
    hashable. ``kinds`` the layers' kinds in the published order (layer i + 1
    of ``linear_attn_config``'s two lists, which count from 1); ``held`` the
    chip's (first, count) of the ``experts`` the router scores; ``vocab`` its
    slice. ``head_dim`` is for the readers that know one width, as
    ``build_mla``'s. The wrong models of the calibration and of the tests are
    changes to this dict."""
    layers = workload.get("depth", config["num_hidden_layers"])
    linear = config["linear_attn_config"]
    kinds = tuple("kda" if i + 1 in linear["kda_layers"] else "global" for i in range(layers))
    if any(i + 1 not in linear["kda_layers"] + linear["full_attn_layers"] for i in range(layers)):
        raise ValueError("linear_attn_config names every layer in kda_layers or full_attn_layers")
    qk = config["qk_nope_head_dim"] + config["qk_rope_head_dim"]
    return {
        "hidden": config["hidden_size"],
        "heads": config["num_attention_heads"],
        "qk_nope": config["qk_nope_head_dim"],
        "qk_rope": config["qk_rope_head_dim"],
        "v_head": config["v_head_dim"],
        "head_dim": (qk + config["v_head_dim"]) // 2,
        "kv_lora": config["kv_lora_rank"],
        "nope": config["mla_use_nope"],
        "softmax_scale": qk ** -0.5,  # rope_scaling null: no factor
        "yarn": None,
        "causal": True,
        "kinds": kinds,
        "kda_heads": linear["num_heads"],
        "kda_head_dim": linear["head_dim"],
        "kda_conv": linear["short_conv_kernel_size"],
        "l2_eps": config["kda_l2norm_eps"],
        "state_dtype": "float32",
        "norm_eps": config["rms_norm_eps"],
        "dense_layers": config["first_k_dense_replace"],
        "dense_width": config["intermediate_size"],
        "expert_width": config["moe_intermediate_size"],
        "shared_width": config["num_shared_experts"] * config["moe_intermediate_size"],
        "experts": config["num_experts_published"],
        "held": (config["experts_held_first"], config["num_experts"]),
        # a part of the experts, alone, does not train its routing (reference_kda, departure 2)
        "routing_trained": config["num_experts"] == config["num_experts_published"],
        "experts_per_token": config["num_experts_per_token"],
        "norm_topk_prob": config["moe_renormalize"],
        "routed_scaling": config["routed_scaling_factor"],
        "router_score": config["moe_router_activation_func"],
        "held_rows_factor": workload["held_rows_factor"],
        "vocab": config["vocab_size"],
        "layers": layers,
        "moe_layers": layers - config["first_k_dense_replace"],
        "seq_len": workload["seq_len"],
    }


def tiny_kda(workload, config):
    """The widths ``--allow-cpu`` runs beside ``build.tiny``'s (hidden 64, 4
    heads): control flow only. The first five layers of the pattern, KDA heads
    of 16, 4 of 8 experts held, 3 a token."""
    return ({**workload, "depth": 5, "kda_chunk": 32},
            {**config, "kv_lora_rank": 32, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
             "v_head_dim": 16, "moe_intermediate_size": 32, "num_experts_published": 8,
             "num_experts": 4, "experts_held_first": 2, "num_experts_per_token": 3,
             "linear_attn_config": {**config["linear_attn_config"], "head_dim": 16, "num_heads": 4}})


def kimi_config(workload, config):
    from distributed_llm_training_benchmark_framework_tpu.models.tinygpt import TinyGPTConfig

    m = kda_shape(workload, config)
    if (config["q_lora_rank"] is not None or config["rope_scaling"] is not None
            or not config["use_grouped_topk"] or config["num_expert_group"] != 1
            or config["topk_group"] != 1 or config["moe_layer_freq"] != 1
            or config["num_nextn_predict_layers"] or config["hidden_act"] != "silu"
            or config["tie_word_embeddings"] or m["router_score"] != "sigmoid"
            or config["num_key_value_heads"] != config["num_attention_heads"]):
        raise ValueError("the program computes kimi_linear with whole-rank q, no rope_scaling, "
                         "one group of experts (no group step), every layer after the dense ones "
                         "routed by sigmoid scores, no MTP module, SwiGLU and an untied head only")
    return TinyGPTConfig(
        vocab_size=m["vocab"], n_embd=m["hidden"], n_head=m["heads"], n_layer=m["layers"],
        block_size=m["seq_len"], dropout=config["dropout"], causal=True,
        attention_impl=workload["attention"],
        scan_layers={"scan": True, "unrolled": False}[workload["layer_loop"]],
        norm="rmsnorm", norm_eps=m["norm_eps"], pos_embed="rope", rope_theta=config["rope_theta"],
        mlp_act="swiglu", mlp_hidden=m["expert_width"], bias=False, tie_embeddings=False,
        kv_lora_rank=m["kv_lora"], qk_nope_head_dim=m["qk_nope"], qk_rope_head_dim=m["qk_rope"],
        v_head_dim=m["v_head"], mla_nope=m["nope"],
        first_k_dense=m["dense_layers"], dense_mlp_hidden=m["dense_width"],
        n_experts=m["experts"], expert_top_k=m["experts_per_token"], capacity_factor=None,
        norm_topk_prob=m["norm_topk_prob"], router_score=m["router_score"],
        routed_scaling_factor=m["routed_scaling"], router_aux_coef=0.0,
        n_shared_experts=config["num_shared_experts"], experts_held=tuple(m["held"]),
        held_rows_factor=m["held_rows_factor"],
        layer_types=m["kinds"], kda_heads=m["kda_heads"], kda_head_dim=m["kda_head_dim"],
        kda_conv=m["kda_conv"],
        # the chunk is the op's own (measured there) unless a workload file names another
        **({"kda_chunk": workload["kda_chunk"]} if "kda_chunk" in workload else {}),
    )
