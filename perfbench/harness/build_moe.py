"""The builder for OLMoE-class configurations: a ``TinyGPTConfig`` with the
routed-MLP and QK-norm facts the generic builder (``build.tinygpt_config``)
does not read, and the sizes the arithmetic in ``flops_moe.py`` and
``reference_moe.py`` reads. A config file names it under ``builder``."""

import dataclasses

from . import build


def moe_shape(workload, config):
    """``build.model_shape`` plus the routed layer's facts."""
    return {
        **build.model_shape(workload, config),
        "experts": config["num_experts"],
        "experts_per_token": config["num_experts_per_tok"],
        "norm_topk_prob": config["norm_topk_prob"],
        "qk_norm": config["qk_norm"],
        "aux_coef": config["router_aux_loss_coef"],
        "z_coef": config["router_z_loss_coef"],
    }


def olmoe_config(workload, config):
    """Dropless top-k routing over SwiGLU experts of width ``intermediate_size``."""
    m = moe_shape(workload, config)
    return dataclasses.replace(
        build.tinygpt_config(workload, config),
        n_experts=m["experts"], expert_top_k=m["experts_per_token"], capacity_factor=None,
        norm_topk_prob=m["norm_topk_prob"], router_aux_coef=m["aux_coef"],
        router_z_coef=m["z_coef"], qk_norm=m["qk_norm"],
    )
