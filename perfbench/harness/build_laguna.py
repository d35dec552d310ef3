"""The builder for Laguna-class configurations (``model_type`` ``laguna``):
full-attention and sliding-window layers in one stack with a head count a kind
(``num_attention_heads_per_layer``: the kind decides the shapes of wq, wg and
wo), a per-head sigmoid gate on the attention's output, a rotary table a kind
(the full layers rotate half of each head under YaRN, the sliding ones whole
heads at another theta), a leading dense layer, then routed layers scored by
sigmoid with one shared expert, with one chip's share of the routed experts
and of the vocabulary; and the sizes the arithmetic in ``flops_laguna.py`` and
``reference_laguna.py`` reads. A config file names ``laguna_config`` under
``builder``."""

KINDS = {"sliding_attention": "window", "full_attention": "global"}


def _rotary(group, head_dim):
    """One group of ``rope_parameters`` -> (theta, lanes of a head that rotate,
    None or (factor, original positions, beta_fast, beta_slow, attention_factor))."""
    lanes = int(round(group.get("partial_rotary_factor", 1) * head_dim))
    if group["rope_type"] == "default":
        return (float(group["rope_theta"]), lanes, None)
    if group["rope_type"] != "yarn":
        raise ValueError(f"rope_type {group['rope_type']!r}: default or yarn")
    return (float(group["rope_theta"]), lanes, (
        float(group["factor"]), int(group["original_max_position_embeddings"]),
        float(group["beta_fast"]), float(group["beta_slow"]), float(group["attention_factor"])))


def laguna_shape(workload, config):
    """What the reference, the FLOP count and the readers read; every value
    hashable. ``kinds`` the layers' kinds in order (the first ``layers``
    entries of the file's ``layer_types``), ``heads`` each kind's query heads
    (from ``num_attention_heads_per_layer``), ``rotary`` each kind's table,
    ``held`` the chip's (first, count) of the ``experts`` the router scores,
    ``vocab`` its slice. The wrong models of the calibration and of the tests
    are changes to this dict."""
    layers = workload.get("depth", config["num_hidden_layers"])
    kinds = tuple(KINDS[t] for t in config["layer_types"][:layers])
    per_layer = config["num_attention_heads_per_layer"][:layers]
    heads = {}
    for kind, count in zip(kinds, per_layer):
        if heads.setdefault(kind, count) != count:
            raise ValueError("num_attention_heads_per_layer gives one count a kind of layer")
    dense = sum(t == "dense" for t in config["mlp_layer_types"][:layers])
    if config["mlp_layer_types"][:dense] != ["dense"] * dense:
        raise ValueError("the dense layers lead the stack")
    return {
        "hidden": config["hidden_size"],
        "heads": tuple(sorted(heads.items())),
        "kv_heads": config["num_key_value_heads"],
        "head_dim": config["head_dim"],
        "norm_eps": config["rms_norm_eps"],
        "gate": {True: "head", False: None}[bool(config["gating"])],
        "kinds": kinds,
        "window": config["sliding_window"],
        "rotary": tuple(sorted(
            (KINDS[t], _rotary(group, config["head_dim"]))
            for t, group in config["rope_parameters"].items() if t in KINDS)),
        "pairing": "part",  # rotate-half inside the lanes that rotate; a wrong model: "head"
        "kv_shift": 0,  # a wrong model groups the query heads one off
        "dense_layers": dense,
        "dense_width": config["intermediate_size"],
        "expert_width": config["moe_intermediate_size"],
        "shared_width": config["n_shared_experts"] * config["shared_expert_intermediate_size"],
        "experts": config["num_experts_published"],
        "held": (config["experts_held_first"], config["num_experts"]),
        # a part of the experts, alone, does not train its routing (reference_laguna, departure 2)
        "routing_trained": config["num_experts"] == config["num_experts_published"],
        "experts_per_token": config["num_experts_per_tok"],
        "router_score": config["router_score"],
        "norm_topk_prob": config["norm_topk_prob"],
        "routed_scaling": config["moe_routed_scaling_factor"],
        "held_rows_factor": workload["held_rows_factor"],
        "vocab": config["vocab_size"],
        "layers": layers,
        "moe_layers": layers - dense,
        "seq_len": workload["seq_len"],
    }


def tiny_laguna(workload, config):
    """The widths ``--allow-cpu`` runs beside ``build.tiny``'s: control flow
    only. The dense layer and one whole period, 6 / 8 heads of 16 over 2 KV
    heads, a window shorter than the sequence, 4 of 8 experts held, 3 a token."""
    rope = {k: ({**g, "original_max_position_embeddings": 64}
                if isinstance(g, dict) and g.get("rope_type") == "yarn" else g)
            for k, g in config["rope_parameters"].items()}
    fewest = min(config["num_attention_heads_per_layer"])  # the full layers' count
    return ({**workload, "depth": 5},
            {**config, "head_dim": 16, "num_attention_heads": 6, "num_key_value_heads": 2,
             "num_attention_heads_per_layer": [
                 6 if n == fewest else 8 for n in config["num_attention_heads_per_layer"]],
             "intermediate_size": 128, "moe_intermediate_size": 32,
             "shared_expert_intermediate_size": 32, "num_experts_published": 8, "num_experts": 4,
             "experts_held_first": 2, "num_experts_per_tok": 3, "sliding_window": 48,
             "rope_parameters": rope})


def laguna_config(workload, config):
    from distributed_llm_training_benchmark_framework_tpu.models.tinygpt import (
        Rotary, TinyGPTConfig, YarnScaling,
    )

    m = laguna_shape(workload, config)
    if (config["attention_bias"] or config["tie_word_embeddings"] or config["hidden_act"] != "silu"
            or config["qk_norm"] or config["moe_apply_router_weight_on_input"]
            or config["gating_granularity"] != "per_head" or config["rotary_lanes"] != "leading"
            or config["router_aux_loss_coef"] or m["router_score"] != "sigmoid"):
        raise ValueError("the program computes laguna with a per-head output gate, no QK-norm, "
                         "the leading lanes of a head rotated, sigmoid routing without an "
                         "auxiliary term, the gates on the experts' outputs, SwiGLU, no bias "
                         "and an untied head only")

    def rotary(theta, lanes, yarn):
        part = None if lanes == m["head_dim"] else lanes
        if yarn is None:
            return Rotary(theta, rotary_dim=part)
        factor, original, fast, slow, attention_factor = yarn
        # mscale 1 over mscale_all_dim 0 is 0.1 ln(factor) + 1 on cos and sin and
        # nothing on the softmax scale: the file's attention_factor, checked
        scaling = YarnScaling(factor, original, fast, slow, mscale=1.0, mscale_all_dim=0.0)
        if abs(scaling.cos_sin_factor - attention_factor) > 1e-12 or scaling.softmax_factor != 1.0:
            raise ValueError(f"attention_factor {attention_factor} is not 0.1 ln({factor}) + 1")
        return Rotary(theta, scaling, rotary_dim=part)

    tables = {kind: rotary(*table) for kind, table in m["rotary"]}
    plain = float(config["rope_theta"])
    return TinyGPTConfig(
        vocab_size=m["vocab"], n_embd=m["hidden"], n_head=config["num_attention_heads"],
        n_kv_head=m["kv_heads"], head_width=m["head_dim"], n_layer=m["layers"],
        block_size=m["seq_len"], dropout=config["dropout"], causal=True,
        attention_impl=workload["attention"],
        scan_layers={"scan": True, "unrolled": False}[workload["layer_loop"]],
        norm="rmsnorm", norm_eps=m["norm_eps"], pos_embed="rope", rope_theta=plain,
        mlp_act="swiglu", mlp_hidden=m["expert_width"], bias=False, tie_embeddings=False,
        n_experts=m["experts"], expert_top_k=m["experts_per_token"], capacity_factor=None,
        norm_topk_prob=m["norm_topk_prob"], router_aux_coef=0.0,
        router_score="sigmoid", routed_scaling_factor=m["routed_scaling"],
        n_shared_experts=config["n_shared_experts"],
        first_k_dense=m["dense_layers"], dense_mlp_hidden=m["dense_width"],
        experts_held=tuple(m["held"]), held_rows_factor=m["held_rows_factor"],
        layer_types=m["kinds"],
        sliding_window=m["window"] if "window" in m["kinds"] else None,
        layer_rotary=tuple(sorted((kind, table) for kind, table in tables.items()
                                  if kind in m["kinds"] and table != Rotary(plain))) or None,
        layer_heads=tuple((kind, n) for kind, n in m["heads"]
                          if n != config["num_attention_heads"]) or None,
        attn_gate=m["gate"] == "head",
    )
