"""The builder for DeepSeek-V2-class configurations: latent attention under
YaRN, leading dense layers, shared experts and one chip's share of the routed
experts, none of which the generic builder (``build.tinygpt_config``) reads;
and the sizes the arithmetic in ``flops_mla.py`` and ``reference_mla.py``
reads. A config file names it under ``builder``."""

import math


def yarn_softmax_scale(config):
    """1 / sqrt(width of q) times YaRN's m^2, m = 0.1 mscale_all_dim ln(factor) + 1."""
    scale = (config["qk_nope_head_dim"] + config["qk_rope_head_dim"]) ** -0.5
    yarn = config["rope_scaling"]
    if yarn and yarn["factor"] > 1:
        scale *= (0.1 * yarn["mscale_all_dim"] * math.log(yarn["factor"]) + 1.0) ** 2
    return scale


def mla_shape(workload, config):
    """What the reference, the FLOP count and the readers read. ``held`` is the
    chip's (first, count) of the ``experts`` the router scores; ``vocab`` its
    slice. ``head_dim`` is for the readers that know one width: (192 + 128) /
    2, which makes their 4 S^2 head_dim the true 2 S^2 (192 + 128)."""
    layers = workload.get("depth", config["num_hidden_layers"])
    qk = config["qk_nope_head_dim"] + config["qk_rope_head_dim"]
    return {
        "hidden": config["hidden_size"],
        "heads": config["num_attention_heads"],
        "kv_heads": config["num_key_value_heads"],
        "qk_nope": config["qk_nope_head_dim"],
        "qk_rope": config["qk_rope_head_dim"],
        "v_head": config["v_head_dim"],
        "head_dim": (qk + config["v_head_dim"]) // 2,
        "kv_lora": config["kv_lora_rank"],
        "latent_norm": True,
        "rope_whole_head": False,
        "rope_theta": config["rope_theta"],
        "yarn": config["rope_scaling"],
        "softmax_scale": yarn_softmax_scale(config),
        "norm": "rmsnorm",
        "norm_eps": config["rms_norm_eps"],
        "dense_layers": config["first_k_dense_replace"],
        "dense_width": config["intermediate_size"],
        "expert_width": config["moe_intermediate_size"],
        "shared_width": config["n_shared_experts"] * config["moe_intermediate_size"],
        "experts": config["n_routed_experts_published"],
        "held": (config["experts_held_first"], config["n_routed_experts"]),
        # a part of the experts, alone, does not train its routing (reference_mla, departure 3)
        "routing_trained": config["n_routed_experts"] == config["n_routed_experts_published"],
        "experts_per_token": config["num_experts_per_tok"],
        "norm_topk_prob": config["norm_topk_prob"],
        "routed_scaling": config["routed_scaling_factor"],
        "aux_coef": config["aux_loss_alpha"],
        "seq_aux": config["seq_aux"],
        "held_rows_factor": workload["held_rows_factor"],
        "tied_head": config["tie_word_embeddings"],
        "causal": config["causal"],
        "vocab": config["vocab_size"],
        "layers": layers,
        "moe_layers": layers - config["first_k_dense_replace"],
        "seq_len": workload["seq_len"],
    }


def tiny_mla(config):
    """The widths ``--allow-cpu`` runs beside ``build.tiny``'s: control flow only."""
    return {
        **config, "kv_lora_rank": 32, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
        "v_head_dim": 16, "moe_intermediate_size": 32, "n_routed_experts_published": 8,
        "n_routed_experts": 4, "experts_held_first": 2, "num_experts_per_tok": 3,
        "rope_scaling": {**config["rope_scaling"], "original_max_position_embeddings": 32},
    }


def deepseek_config(workload, config):
    from distributed_llm_training_benchmark_framework_tpu.models.tinygpt import (
        TinyGPTConfig, YarnScaling,
    )

    m = mla_shape(workload, config)
    if config["q_lora_rank"] is not None or config["scoring_func"] != "softmax" or (
            config["topk_method"] != "greedy" or config["routed_scaling_factor"] != 1):
        raise ValueError("the program computes whole-rank q, softmax scores, greedy top-k "
                         "and routed_scaling_factor 1 only")
    yarn = m["yarn"]
    return TinyGPTConfig(
        vocab_size=m["vocab"], n_embd=m["hidden"], n_head=m["heads"], n_layer=m["layers"],
        block_size=m["seq_len"], dropout=config["dropout"], causal=m["causal"],
        attention_impl=workload["attention"],
        scan_layers={"scan": True, "unrolled": False}[workload["layer_loop"]],
        norm="rmsnorm", norm_eps=m["norm_eps"], pos_embed="rope", rope_theta=m["rope_theta"],
        mlp_act="swiglu", mlp_hidden=m["expert_width"], bias=False,
        tie_embeddings=m["tied_head"],
        kv_lora_rank=m["kv_lora"], qk_nope_head_dim=m["qk_nope"],
        qk_rope_head_dim=m["qk_rope"], v_head_dim=m["v_head"],
        rope_scaling=None if yarn is None else YarnScaling(
            factor=yarn["factor"],
            original_max_position_embeddings=yarn["original_max_position_embeddings"],
            beta_fast=yarn["beta_fast"], beta_slow=yarn["beta_slow"],
            mscale=yarn["mscale"], mscale_all_dim=yarn["mscale_all_dim"]),
        first_k_dense=m["dense_layers"], dense_mlp_hidden=m["dense_width"],
        n_experts=m["experts"], expert_top_k=m["experts_per_token"], capacity_factor=None,
        norm_topk_prob=m["norm_topk_prob"], router_aux_coef=m["aux_coef"],
        n_shared_experts=config["n_shared_experts"], experts_held=tuple(m["held"]),
        held_rows_factor=m["held_rows_factor"], seq_aux=m["seq_aux"],
    )
