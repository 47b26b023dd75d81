"""What the language cells' drivers make of the accepted configurations:
`arch_of`, `recipe_of` (against each configuration's own preset) and the
routing checks, as literal values. A driver that learns a further kind of
stack (no routed experts, a tied head) leaves these as they were."""

import numpy as np
import pytest

from chipbench import run as harness
from chipbench.drivers import hybrid_lm_train, lm_train

ROOT = harness.ROOT
DRIVERS = {"lm_train": lm_train, "hybrid_lm_train": hybrid_lm_train}
PINNED: dict = {}
PINNED["mistral_small4_ep16_step"] = {
    "driver": "lm_train",
    "arch": {
        "hidden_size": 4096,
        "num_attention_heads": 32,
        "q_lora_rank": 1024,
        "kv_lora_rank": 256,
        "qk_nope_head_dim": 64,
        "qk_rope_head_dim": 64,
        "v_head_dim": 128,
        "num_experts_per_tok": 4,
        "moe_intermediate_size": 2048,
        "n_shared_experts": 1,
        "rms_norm_eps": 1e-06,
        "rope_parameters": {
            "beta_fast": 32,
            "beta_slow": 1,
            "factor": 128,
            "llama_4_scaling_beta": 0.1,
            "mscale": 1,
            "mscale_all_dim": 1,
            "original_max_position_embeddings": 8192,
            "rope_theta": 10000,
            "rope_type": "yarn",
            "type": "yarn",
        },
        "n_routed_experts": 128,
    },
    "recipe": {
        "base_lr": 0.01,
        "reference_batch": 1,
        "momentum": 0.9,
        "weight_decay": 0.0,
        "schedule": "constant",
        "seq_len": 4096,
        "compute_dtype": "bfloat16",
        "first_expert": 0,
        "global_batch": 1,
    },
    "checks": [
        ("expert_load_diff", 0.041666666666666664, 0.017),
        ("dropped_assignments", 0.0, 0),
    ],
}
PINNED["nemotron3_nano_ep8_step"] = {
    "driver": "hybrid_lm_train",
    "arch": {
        "hidden_size": 2688,
        "mamba_num_heads": 64,
        "mamba_head_dim": 64,
        "n_groups": 8,
        "ssm_state_size": 128,
        "conv_kernel": 4,
        "chunk_size": 128,
        "time_step_min": 0.001,
        "time_step_max": 0.1,
        "time_step_floor": 0.0001,
        "num_attention_heads": 32,
        "num_key_value_heads": 2,
        "head_dim": 128,
        "num_experts_per_tok": 6,
        "moe_intermediate_size": 1856,
        "moe_shared_expert_intermediate_size": 3712,
        "n_shared_experts": 1,
        "routed_scaling_factor": 2.5,
        "layer_norm_epsilon": 1e-05,
        "hybrid_override_pattern": "MEMEM*EME",
        "n_routed_experts": 128,
    },
    "recipe": {
        "base_lr": 0.01,
        "reference_batch": 2,
        "momentum": 0.9,
        "weight_decay": 0.0,
        "schedule": "constant",
        "seq_len": 8192,
        "compute_dtype": "bfloat16",
        "first_expert": 0,
        "global_batch": 2,
    },
    "checks": [
        ("expert_load_diff", 0.020833333333333332, 0.005),
        ("dropped_assignments", 0.0, 0),
    ],
}
PINNED["ling3_flash_ep64_step"] = {
    "driver": "hybrid_lm_train",
    "arch": {
        "hidden_size": 2560,
        "intermediate_size": 6144,
        "num_attention_heads": 32,
        "head_dim": 128,
        "layer_group_size": 6,
        "short_conv_kernel_size": 4,
        "kda_lower_bound": -5,
        "q_lora_rank": None,
        "kv_lora_rank": 512,
        "qk_nope_head_dim": 128,
        "qk_rope_head_dim": 64,
        "v_head_dim": 128,
        "rope_theta": 6000000,
        "num_experts_per_tok": 8,
        "n_group": 8,
        "topk_group": 4,
        "moe_intermediate_size": 768,
        "moe_shared_expert_intermediate_size": 768,
        "routed_scaling_factor": 2.5,
        "rms_norm_eps": 1e-06,
        "first_k_dense_replace": 1,
        "hybrid_override_pattern": "DKKKKLK",
        "n_routed_experts": 512,
    },
    "recipe": {
        "base_lr": 0.01,
        "reference_batch": 2,
        "momentum": 0.9,
        "weight_decay": 0.0,
        "schedule": "constant",
        "seq_len": 8192,
        "compute_dtype": "bfloat16",
        "first_expert": 0,
        "global_batch": 2,
    },
    "checks": [
        ("expert_load_diff", 0.027777777777777776, 0.0095),
        ("dropped_assignments", 0.0, 0),
    ],
}
PINNED["solar_open2_ep40_step"] = {
    "driver": "hybrid_lm_train",
    "arch": {
        "hidden_size": 4096,
        "num_attention_heads": 64,
        "head_dim": 128,
        "num_key_value_heads": 8,
        "linear_attn_config": {
            "short_conv_kernel_size": 4,
            "head_dim": 128,
            "num_heads": 64,
            "num_kv_heads": None,
        },
        "moe_intermediate_size": 1280,
        "num_experts_per_tok": 8,
        "n_shared_experts": 1,
        "routed_scaling_factor": 1,
        "norm_topk_prob": True,
        "rms_norm_eps": 1e-05,
        "first_k_dense_replace": 0,
        "use_rope": False,
        "gqa_layers": [0, 4, 8, 12, 16, 20, 24, 28, 32, 36, 40, 44],
        "use_gqa_gate": True,
        "kda_use_full_proj": False,
        "kda_allow_neg_eigval": True,
        "hybrid_override_pattern": "GKKK",
        "n_routed_experts": 320,
    },
    "recipe": {
        "base_lr": 0.01,
        "reference_batch": 1,
        "momentum": 0.9,
        "weight_decay": 0.0,
        "schedule": "constant",
        "seq_len": 4096,
        "compute_dtype": "bfloat16",
        "first_expert": 0,
        "global_batch": 1,
    },
    "checks": [
        ("expert_load_diff", 0.041666666666666664, 0.012),
        ("dropped_assignments", 0.0, 0),
    ],
}


def _checks(driver, config: dict, cell: dict, arch: dict) -> list:
    """The routing checks on three steps' load tables of the cell's own
    shape, of which the program's first moves two assignments."""
    layers = (config["num_hidden_layers"] if driver is lm_train else len(
        hybrid_lm_train.reference_of(config).expert_layers(arch)))
    want = [np.ones((layers, config["n_routed_experts"]))] * 3
    got = [x.copy() for x in want]
    got[0][0, 0] += 2
    got[0][-1, -1] -= 2
    got = {"loads": got, "dropped": [0] * 3}
    checks = driver.routing_checks(got, {"loads": want}) \
        if driver is lm_train else driver.routing_checks(got, {"loads": want},
                                                         cell)
    return [(c["name"], c["value"], c["limit"]) for c in checks]


def test_every_language_cell_is_pinned():
    bench, _, _ = harness.load_cell(ROOT, "vggf_b1024_step")
    language = [w["name"] for w in bench["workloads"]
                if harness.load_cell(ROOT, w["name"])[1]["driver"]
                in DRIVERS]
    assert language == list(PINNED)


@pytest.mark.parametrize("name", sorted(PINNED))
def test_the_accepted_configuration_resolves_as_before(name):
    from distributed_vgg_f_tpu.config import get_config
    _, cell, config = harness.load_cell(ROOT, name)
    pinned = PINNED[name]
    assert cell["driver"] == pinned["driver"]
    driver = DRIVERS[cell["driver"]]
    arch = driver.arch_of(config)
    assert arch == pinned["arch"]
    assert list(arch) == list(pinned["arch"])
    assert driver.recipe_of(get_config(config["preset"]), config) \
        == pinned["recipe"]
    assert _checks(driver, config, cell, arch) == pinned["checks"]
