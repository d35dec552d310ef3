"""The builder for SDAR-class configurations (``model_type`` ``sdar_moe``): a
Qwen3-MoE block (GQA, per-head QK-norm, softmax-top-k experts with
renormalised gates, no shared expert) trained by block diffusion, with one
chip's share of the routed experts and of the vocabulary; the sizes the
arithmetic in ``flops_bd.py`` and ``reference_bd.py`` reads; and the cell's own
generator of documents. A config file names ``sdar_config`` under ``builder``."""

import numpy as np


def bd_shape(workload, config):
    """What the reference, the FLOP count and the readers read. ``held`` is the
    chip's (first, count) of the ``experts`` the router scores; ``vocab`` its
    slice, whose last id is the mask token. The wrong models of the
    calibration and of the tests are changes to this dict."""
    return {
        "hidden": config["hidden_size"],
        "heads": config["num_attention_heads"],
        "kv_heads": config["num_key_value_heads"],
        "head_dim": config["head_dim"],
        "qk_norm": config["qk_norm"],
        "rope_theta": config["rope_theta"],
        "norm_eps": config["rms_norm_eps"],
        "expert_width": config["moe_intermediate_size"],
        "experts": config["num_experts_published"],
        "held": (config["experts_held_first"], config["num_experts"]),
        # a part of the experts, alone, does not train its routing (reference_bd, departure 2)
        "routing_trained": config["num_experts"] == config["num_experts_published"],
        "experts_per_token": config["num_experts_per_tok"],
        "norm_topk_prob": config["norm_topk_prob"],
        "aux_coef": config["router_aux_loss_coef"],
        "held_rows_factor": workload["held_rows_factor"],
        "vocab": config["vocab_size"],
        "layers": workload.get("depth", config["num_hidden_layers"]),
        "seq_len": workload["seq_len"],  # L: a document; the stream is 2 L
        "block": config["block_length"],
        "mask_id": config["mask_token_id"],
        "t_range": (config["noise"]["t_min"], config["noise"]["t_max"]),
        "mask": "block_diffusion",
        "positions": "per_copy",
        "loss_weight": config["noise"]["loss_weight"],  # "1/t"
        "loss_over": "document",  # the weighted sum is divided by L
    }


def tiny_bd(config):
    """The widths ``--allow-cpu`` runs beside ``build.tiny``'s: control flow only."""
    return {
        **config, "moe_intermediate_size": 32, "num_experts_published": 8, "num_experts": 4,
        "experts_held_first": 2, "num_experts_per_tok": 3,
        "mask_token_id": config["vocab_size"] - 1,
    }


def sdar_config(workload, config):
    from distributed_llm_training_benchmark_framework_tpu.models.tinygpt import (
        BlockDiffusionObjective, TinyGPTConfig,
    )

    m = bd_shape(workload, config)
    if (config["mlp_only_layers"] or config["decoder_sparse_step"] != 1
            or config["use_sliding_window"] or config["rope_scaling"] is not None
            or config["attention_bias"] or config["tie_word_embeddings"]):
        raise ValueError("the program computes sdar_moe with every layer routed, full "
                         "attention, plain rotary, no bias and an untied head only")
    # The cell's file says which kernels it runs ("flash_block_diffusion": the
    # flash kernels under the block-diffusion rule); the program's word is "flash".
    attention = {"flash_block_diffusion": "flash", "reference": "reference"}[workload["attention"]]
    return TinyGPTConfig(
        vocab_size=m["vocab"], n_embd=m["hidden"], n_head=m["heads"], n_kv_head=m["kv_heads"],
        head_width=m["head_dim"], n_layer=m["layers"], block_size=m["seq_len"],
        dropout=config["dropout"], causal=False, attention_impl=attention,
        scan_layers={"scan": True, "unrolled": False}[workload["layer_loop"]],
        norm="rmsnorm", norm_eps=m["norm_eps"], pos_embed="rope", rope_theta=m["rope_theta"],
        mlp_act="swiglu", mlp_hidden=m["expert_width"], bias=False, tie_embeddings=False,
        qk_norm=m["qk_norm"], n_experts=m["experts"], expert_top_k=m["experts_per_token"],
        capacity_factor=None, norm_topk_prob=m["norm_topk_prob"], router_aux_coef=m["aux_coef"],
        experts_held=tuple(m["held"]), held_rows_factor=m["held_rows_factor"],
        block_diffusion=BlockDiffusionObjective(
            block=m["block"], mask_id=m["mask_id"], t_min=m["t_range"][0], t_max=m["t_range"][1]),
    )


def token_table(shape, workload, seed):
    """The cell's own generator of the job's input: ``dataset_rows`` documents
    of ``seq_len`` tokens, each id drawn independently from a Zipf law over the
    data ids (every id of the slice but the mask token): P(id = r) ~ 1 /
    (r + 1) ^ exponent, id 0 the commonest. Real text's unigram law: it makes
    the routing uneven as topics do, and gives the masked-token loss
    something to learn (uniform ids are unpredictable under this objective)."""
    law = workload["token_law"]
    data_ids = shape["vocab"] - 1  # the mask token is the slice's last id
    if law["kind"] != "zipf" or shape["mask_id"] != data_ids:
        raise ValueError("the generator draws Zipf ids below a mask token that is the last id")
    weights = 1.0 / np.arange(1, data_ids + 1, dtype=np.float64) ** law["exponent"]
    cumulative = np.cumsum(weights / weights.sum())
    draws = np.random.default_rng(seed).random((workload["dataset_rows"], workload["seq_len"]))
    return np.minimum(np.searchsorted(cumulative, draws), data_ids - 1).astype(np.int32)
