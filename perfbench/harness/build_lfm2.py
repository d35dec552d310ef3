"""The builder for LFM2-MoE-class configurations (``model_type`` ``lfm2_moe``):
a stack whose layers are by ``layer_types`` a gated short convolution (``conv``)
or grouped-query attention under per-head QK-norm and rotary
(``full_attention``), each before a feed-forward part: a dense SwiGLU MLP in the
first ``num_dense_layers`` layers, then experts chosen by sigmoid score + a
selection bias, no shared expert; a tied head; with one chip's share of the
routed experts and of the vocabulary; and the sizes the arithmetic in
``flops_lfm2.py`` and ``reference_lfm2.py`` reads. A config file names
``lfm2_config`` under ``builder``."""

KINDS = {"conv": "conv", "full_attention": "global"}


def lfm2_shape(workload, config):
    """What the reference, the FLOP count and the readers read; every value
    hashable. ``kinds`` the kept layers' kinds in the published order (``layers``
    entries of the file's ``layer_types`` from ``first_layer_kept``),
    ``dense_layers`` those of them among the published ``num_dense_layers``
    leading ones, ``held`` the chip's (first, count) of the ``experts`` the
    router scores, ``vocab`` its slice. The wrong models of the calibration and
    of the tests are changes to this dict."""
    layers = workload.get("depth", config["num_hidden_layers"])
    first = config["first_layer_kept"]
    kinds = tuple(KINDS[t] for t in config["layer_types"][first:first + layers])
    dense = max(0, min(layers, config["num_dense_layers"] - first))
    return {
        "hidden": config["hidden_size"],
        "heads": config["num_attention_heads"],
        "kv_heads": config["num_key_value_heads"],
        "head_dim": config["hidden_size"] // config["num_attention_heads"],
        "norm_eps": config["norm_eps"],
        "rope_theta": float(config["rope_theta"]),
        "kinds": kinds,
        "taps": config["conv_L_cache"],
        "dense_layers": dense,
        "dense_width": config["intermediate_size"],
        "expert_width": config["moe_intermediate_size"],
        "shared_width": 0,
        "experts": config["num_experts_published"],
        "held": (config["experts_held_first"], config["num_experts"]),
        # a part of the experts, alone, does not train its routing (reference_lfm2, departure 2)
        "routing_trained": config["num_experts"] == config["num_experts_published"],
        "experts_per_token": config["num_experts_per_tok"],
        "norm_topk_prob": config["norm_topk_prob"],
        "routed_scaling": float(config["routed_scaling_factor"]),
        "router_score": "sigmoid",
        "held_rows_factor": workload["held_rows_factor"],
        "vocab": config["vocab_size"],
        "layers": layers,
        "moe_layers": layers - dense,
        "seq_len": workload["seq_len"],
    }


def tiny_lfm2(workload, config):
    """The widths ``--allow-cpu`` runs beside ``build.tiny``'s (hidden 64, 4
    heads of 16 over 2 KV heads): control flow only. The cell's five layers, 4
    of 8 experts held, 3 a token. At 64 channels a convolution mixer (a product
    of three projections of a normed input) adds an RMS of 3e-4 at the program's
    seeded start, under sqrt(eps) at the published 1e-5: the check's scaled
    inputs would be eps's and not the mixer's, so the dry run's eps is 1e-10."""
    return ({**workload, "depth": 5},
            {**config, "norm_eps": 1e-10,
             "moe_intermediate_size": 32, "num_experts_published": 8,
             "num_experts": 4, "experts_held_first": 2, "num_experts_per_tok": 3})


def lfm2_config(workload, config):
    from distributed_llm_training_benchmark_framework_tpu.models.tinygpt import TinyGPTConfig

    m = lfm2_shape(workload, config)
    if (config["conv_bias"] or not config["use_expert_bias"] or not config["tie_word_embeddings"]
            or config["qk_norm"] != "head_before_rotary" or config["conv_columns"] != "B,C,x"
            or config["router_aux_loss"] is not None):
        raise ValueError("the program computes lfm2_moe with no bias on the convolution, the "
                         "columns B | C | x~, a selection bias on the sigmoid router and no "
                         "auxiliary term, QK-norm per head before rotary and a tied head only")
    return TinyGPTConfig(
        vocab_size=m["vocab"], n_embd=m["hidden"], n_head=m["heads"], n_kv_head=m["kv_heads"],
        n_layer=m["layers"], block_size=m["seq_len"], dropout=config["dropout"], causal=True,
        attention_impl=workload["attention"],
        scan_layers={"scan": True, "unrolled": False}[workload["layer_loop"]],
        norm="rmsnorm", norm_eps=m["norm_eps"], pos_embed="rope", rope_theta=m["rope_theta"],
        qk_norm="head", mlp_act="swiglu", mlp_hidden=m["expert_width"], bias=False,
        tie_embeddings=True, n_experts=m["experts"], expert_top_k=m["experts_per_token"],
        capacity_factor=None, norm_topk_prob=m["norm_topk_prob"], router_aux_coef=0.0,
        router_score="sigmoid", routed_scaling_factor=m["routed_scaling"], n_shared_experts=0,
        first_k_dense=m["dense_layers"], dense_mlp_hidden=m["dense_width"],
        experts_held=tuple(m["held"]), held_rows_factor=m["held_rows_factor"],
        layer_types=m["kinds"], conv_taps=m["taps"],
    )
