"""The builder for Mellum-2-class configurations (``model_type`` ``mellum``): a
Qwen3-MoE block (GQA, per-head QK-norm, softmax-top-k experts with renormalised
gates, no shared expert) in a stack that mixes kinds of layer: three
sliding-window layers to every global one, each kind with a rotary table of its
own (YaRN on the global layers only), with one chip's share of the routed
experts and of the vocabulary; and the sizes the arithmetic in
``flops_mellum.py`` and ``reference_mellum.py`` reads. A config file names
``mellum_config`` under ``builder``."""

KINDS = {"sliding_attention": "window", "full_attention": "global"}


def _rotary(group):
    """One group of ``rope_parameters`` -> (theta, None) or (theta, (factor,
    original positions, beta_fast, beta_slow, attention_factor))."""
    if group["rope_type"] == "default":
        return (float(group["rope_theta"]), None)
    if group["rope_type"] != "yarn":
        raise ValueError(f"rope_type {group['rope_type']!r}: default or yarn")
    return (float(group["rope_theta"]), (
        float(group["factor"]), int(group["original_max_position_embeddings"]),
        float(group["beta_fast"]), float(group["beta_slow"]), float(group["attention_factor"])))


def mellum_shape(workload, config):
    """What the reference, the FLOP count and the readers read; every value
    hashable. ``held`` is the chip's (first, count) of the ``experts`` the
    router scores; ``vocab`` its slice; ``kinds`` the layers' kinds in order
    (the first ``layers`` entries of the file's ``layer_types``); ``rotary``
    each kind's table. The wrong models of the calibration and of the tests
    are changes to this dict."""
    layers = workload.get("depth", config["num_hidden_layers"])
    return {
        "hidden": config["hidden_size"],
        "heads": config["num_attention_heads"],
        "kv_heads": config["num_key_value_heads"],
        "head_dim": config["head_dim"],
        "qk_norm": config["qk_norm"],
        "norm_eps": config["rms_norm_eps"],
        "expert_width": config["moe_intermediate_size"],
        "experts": config["num_experts_published"],
        "held": (config["experts_held_first"], config["num_experts"]),
        # a part of the experts, alone, does not train its routing (reference_mellum, departure 2)
        "routing_trained": config["num_experts"] == config["num_experts_published"],
        "experts_per_token": config["num_experts_per_tok"],
        "norm_topk_prob": config["norm_topk_prob"],
        "aux_coef": config["router_aux_loss_coef"],
        "held_rows_factor": workload["held_rows_factor"],
        "vocab": config["vocab_size"],
        "layers": layers,
        "seq_len": workload["seq_len"],
        "kinds": tuple(KINDS[t] for t in config["layer_types"][:layers]),
        "window": config["sliding_window"],
        "rotary": tuple(sorted(
            (KINDS[t], _rotary(group)) for t, group in config["rope_parameters"].items())),
    }


def tiny_mellum(workload, config):
    """The widths ``--allow-cpu`` runs beside ``build.tiny``'s: control flow
    only. One whole period of the pattern, a window shorter than the sequence."""
    rope = {k: {**g, **({"original_max_position_embeddings": 64} if g["rope_type"] == "yarn" else {})}
            for k, g in config["rope_parameters"].items()}
    return ({**workload, "depth": 4},
            {**config, "moe_intermediate_size": 32, "num_experts_published": 8, "num_experts": 4,
             "experts_held_first": 2, "num_experts_per_tok": 3, "sliding_window": 48,
             "rope_parameters": rope})


def mellum_config(workload, config):
    from distributed_llm_training_benchmark_framework_tpu.models.tinygpt import (
        Rotary, TinyGPTConfig, YarnScaling,
    )

    m = mellum_shape(workload, config)
    if (set(config["mlp_layer_types"]) != {"sparse"} or not config["use_sliding_window"]
            or config["attention_bias"] or config["tie_word_embeddings"]
            or config["hidden_act"] != "silu" or config.get("mtp_head")):
        raise ValueError("the program computes mellum with every layer routed, sliding and full "
                         "layers as layer_types says, SwiGLU experts, no bias, an untied head "
                         "and no MTP head only")

    def rotary(theta, yarn):
        if yarn is None:
            return Rotary(theta)
        factor, original, fast, slow, attention_factor = yarn
        # mscale 1 over mscale_all_dim 0 is 0.1 ln(factor) + 1 on cos and sin and
        # nothing on the softmax scale: the file's attention_factor, checked
        scaling = YarnScaling(factor, original, fast, slow, mscale=1.0, mscale_all_dim=0.0)
        if abs(scaling.cos_sin_factor - attention_factor) > 1e-12 or scaling.softmax_factor != 1.0:
            raise ValueError(f"attention_factor {attention_factor} is not 0.1 ln({factor}) + 1")
        return Rotary(theta, scaling)

    tables = {kind: rotary(*table) for kind, table in m["rotary"]}
    plain = tables["window"].theta
    return TinyGPTConfig(
        vocab_size=m["vocab"], n_embd=m["hidden"], n_head=m["heads"], n_kv_head=m["kv_heads"],
        head_width=m["head_dim"], n_layer=m["layers"], block_size=m["seq_len"],
        dropout=config["dropout"], causal=True, attention_impl=workload["attention"],
        scan_layers={"scan": True, "unrolled": False}[workload["layer_loop"]],
        norm="rmsnorm", norm_eps=m["norm_eps"], pos_embed="rope", rope_theta=plain,
        mlp_act="swiglu", mlp_hidden=m["expert_width"], bias=False, tie_embeddings=False,
        qk_norm=m["qk_norm"], n_experts=m["experts"], expert_top_k=m["experts_per_token"],
        capacity_factor=None, norm_topk_prob=m["norm_topk_prob"], router_aux_coef=m["aux_coef"],
        experts_held=tuple(m["held"]), held_rows_factor=m["held_rows_factor"],
        layer_types=m["kinds"],
        sliding_window=m["window"] if "window" in m["kinds"] else None,
        layer_rotary=tuple(sorted((kind, table) for kind, table in tables.items()
                                  if kind in m["kinds"] and table != Rotary(plain))) or None,
    )
