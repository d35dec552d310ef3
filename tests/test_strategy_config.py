"""Strategy-config resolution tests, incl. DeepSpeed-format translation.

The reference reads and mutates its DeepSpeed JSON at runtime
(``train_harness.py:246-262``); our ``--deepspeed-config`` alias must honor
the file's optimizer/scheduler/clipping values rather than discarding them.
The fixture file mirrors the shape of ``configs/deepspeed/zero2.json:27-44``
without copying it (different values on purpose, so the test proves the
values flow through).
"""

import argparse
import json

import pytest

from distributed_llm_training_benchmark_framework_tpu.parallel.strategies import (
    from_deepspeed_config,
    is_deepspeed_config,
    get_strategy,
    load_strategy_config,
)
from distributed_llm_training_benchmark_framework_tpu.train.harness import (
    resolve_strategy,
)


DS_STYLE = {
    "train_batch_size": "auto",
    "train_micro_batch_size_per_gpu": "auto",
    "gradient_accumulation_steps": "auto",
    "gradient_clipping": 0.5,
    "bf16": {"enabled": True},
    "zero_optimization": {
        "stage": 2,
        "overlap_comm": True,
        "reduce_scatter": True,
        "allgather_bucket_size": 5e8,
    },
    "optimizer": {
        "type": "AdamW",
        "params": {
            "lr": 3e-4,
            "betas": [0.85, 0.97],
            "eps": 1e-7,
            "weight_decay": 0.05,
        },
    },
    "scheduler": {
        "type": "WarmupLR",
        "params": {"warmup_min_lr": 0, "warmup_max_lr": 3e-4, "warmup_num_steps": 11},
    },
}


def test_is_deepspeed_config_detection():
    assert is_deepspeed_config(DS_STYLE)
    assert not is_deepspeed_config({"strategy": "zero2"})
    assert not is_deepspeed_config({"random": 1})
    assert not is_deepspeed_config([1, 2])


def test_translation_maps_all_fields():
    sc = from_deepspeed_config(DS_STYLE, "zero2")
    assert sc.learning_rate == 3e-4
    assert sc.betas == (0.85, 0.97)
    assert sc.eps == 1e-7
    assert sc.weight_decay == 0.05
    assert sc.warmup_steps == 11
    assert sc.grad_clip == 0.5
    assert sc.precision == "bf16"
    # Sharding layout still comes from the arm, not the file.
    base = get_strategy("zero2")
    assert sc.shard_grads == base.shard_grads
    assert sc.shard_opt_state == base.shard_opt_state
    assert sc.shard_params == base.shard_params


def test_stage_mismatch_fails_loudly():
    with pytest.raises(ValueError, match="stage=2"):
        from_deepspeed_config(DS_STYLE, "zero3")


def test_auto_values_fall_back_to_arm_defaults():
    # HF-Trainer DeepSpeed configs routinely set "auto" everywhere.
    raw = {
        "gradient_clipping": "auto",
        "zero_optimization": {"stage": "auto"},
        "optimizer": {"params": {"lr": "auto", "betas": "auto",
                                 "eps": "auto", "weight_decay": "auto"}},
        "scheduler": {"type": "WarmupLR", "params": {"warmup_num_steps": "auto"}},
    }
    base = get_strategy("zero2")
    sc = from_deepspeed_config(raw, "zero2")
    assert sc.learning_rate == base.learning_rate
    assert sc.betas == base.betas
    assert sc.grad_clip == base.grad_clip
    assert sc.warmup_steps == base.warmup_steps


def test_non_numeric_field_fails_naming_the_key():
    with pytest.raises(ValueError, match="'lr'"):
        from_deepspeed_config(
            {"bf16": {"enabled": True}, "optimizer": {"params": {"lr": "fast"}}},
            "zero2",
        )
    with pytest.raises(ValueError, match="betas"):
        from_deepspeed_config(
            {"bf16": {"enabled": True}, "optimizer": {"params": {"betas": "big"}}},
            "zero2",
        )


def test_gradient_clipping_zero_means_disabled():
    # DeepSpeed defines gradient_clipping 0 as "disabled"; translating it to
    # clip_by_global_norm(0.0) would zero every gradient silently.
    sc = from_deepspeed_config(
        {"bf16": {"enabled": True}, "gradient_clipping": 0}, "zero2"
    )
    assert sc.grad_clip is None


def test_non_adam_optimizer_type_rejected():
    with pytest.raises(ValueError, match="SGD"):
        from_deepspeed_config(
            {"bf16": {"enabled": True},
             "optimizer": {"type": "SGD", "params": {"lr": 0.1}}},
            "zero2",
        )


def test_non_dict_sections_fail_naming_the_key():
    with pytest.raises(ValueError, match="'bf16'"):
        from_deepspeed_config({"bf16": True}, "zero2")
    with pytest.raises(ValueError, match="'optimizer'"):
        from_deepspeed_config(
            {"gradient_clipping": 1.0, "optimizer": "AdamW"}, "zero2"
        )


def test_non_warmup_scheduler_type_is_not_mapped():
    raw = {
        "bf16": {"enabled": True},
        "scheduler": {"type": "OneCycle", "params": {"warmup_num_steps": 500}},
    }
    sc = from_deepspeed_config(raw, "zero2")
    assert sc.warmup_steps == get_strategy("zero2").warmup_steps


def test_missing_fields_fall_back_to_arm_defaults():
    sc = from_deepspeed_config({"zero_optimization": {"stage": 3}}, "zero3")
    base = get_strategy("zero3")
    assert sc.learning_rate == base.learning_rate
    assert sc.warmup_steps == base.warmup_steps
    assert sc.remat == base.remat


def _args(**kw):
    ns = argparse.Namespace(
        strategy="zero2", strategy_config=None, deepspeed_config=None,
        fsdp_config=None,
    )
    for k, v in kw.items():
        setattr(ns, k, v)
    return ns


def test_resolve_strategy_translates_deepspeed_file(tmp_path, capsys):
    path = tmp_path / "my_zero2.json"
    path.write_text(json.dumps(DS_STYLE))
    sc = resolve_strategy(_args(deepspeed_config=str(path)))
    assert sc.learning_rate == 3e-4
    assert sc.warmup_steps == 11
    assert "translating DeepSpeed-format config" in capsys.readouterr().out


def test_resolve_strategy_unknown_format_falls_back(tmp_path, capsys):
    path = tmp_path / "odd.json"
    path.write_text(json.dumps({"something": "else"}))
    sc = resolve_strategy(_args(strategy_config=str(path)))
    assert sc == get_strategy("zero2")
    assert "not a recognized" in capsys.readouterr().out


@pytest.mark.parametrize("where, key, value", [
    ("deepspeed", "offload_optimizer", "cpu"),
    ("deepspeed", "offload_optimizer", "nvme"),
    ("strategy", "offload_opt_state", True),
    ("strategy", "offload_delayed_update", True),
])
def test_offload_requests_are_refused_by_name(tmp_path, where, key, value):
    """The optimizer state lives in HBM: a config that asks for a host
    offload is refused with the key to drop, not silently run on the device.
    The reference's shipped ``"device": "none"`` (and no section) still load."""
    if where == "deepspeed":
        def load(device):
            return from_deepspeed_config(
                {"zero_optimization": {"stage": 3, "offload_optimizer": {"device": device}}},
                "zero3",
            )
        assert load("none") == from_deepspeed_config(
            {"zero_optimization": {"stage": 3}}, "zero3")
    else:
        def load(v):
            path = tmp_path / "arm.json"
            path.write_text(json.dumps({"strategy": "zero3", key: v}))
            return load_strategy_config(str(path))
    with pytest.raises(ValueError, match="lives in HBM") as e:
        load(value)
    assert key in str(e.value) and "Drop" in str(e.value)
