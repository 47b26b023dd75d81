"""What the hybrid driver makes of `jamba2_3b_v8`, as literal values, in
the form `test_lm_configs_pinned.py` pins the accepted configurations:
`arch_of`, `recipe_of` against the configuration's own preset, and no
routing check (the stack has no expert layer). The pin is entered into
that module's `PINNED` as well, so that its check that every language cell
is pinned counts this one; that module, in the benchmark's paths, is not
edited by the PR that brings the cell."""

import numpy as np
import pytest

import test_lm_configs_pinned as accepted
from chipbench import run as harness
from chipbench.drivers import hybrid_lm_train

ROOT = harness.ROOT
PINNED: dict = {}
PINNED["jamba2_3b_v8_step"] = {
    "driver": "hybrid_lm_train",
    "arch": {
        "hidden_size": 2560,
        "intermediate_size": 8192,
        "num_attention_heads": 20,
        "num_key_value_heads": 1,
        "mamba_d_state": 16,
        "mamba_d_conv": 4,
        "mamba_dt_rank": 160,
        "mamba_expand": 2,
        "mamba_conv_bias": True,
        "mamba_proj_bias": False,
        "rms_norm_eps": 1e-06,
        "attn_layer_period": 14,
        "attn_layer_offset": 7,
        "num_experts": 1,
        "tie_word_embeddings": True,
        "hybrid_override_pattern": "MMMMMMMAMMMMMM",
    },
    "recipe": {
        "base_lr": 0.01,
        "reference_batch": 1,
        "momentum": 0.9,
        "weight_decay": 0.0,
        "schedule": "constant",
        "seq_len": 4096,
        "compute_dtype": "bfloat16",
        "global_batch": 1,
    },
    "checks": [],
}
accepted.PINNED.update(PINNED)


@pytest.mark.parametrize("name", sorted(PINNED))
def test_the_configuration_resolves_as_pinned(name):
    from distributed_vgg_f_tpu.config import get_config
    _, cell, config = harness.load_cell(ROOT, name)
    pinned = PINNED[name]
    assert cell["driver"] == pinned["driver"]
    driver = accepted.DRIVERS[cell["driver"]]
    arch = driver.arch_of(config)
    assert arch == pinned["arch"]
    assert list(arch) == list(pinned["arch"])
    assert driver.recipe_of(get_config(config["preset"]), config) \
        == pinned["recipe"]
    # three steps' load tables of the reference's shape: no expert layer
    assert hybrid_lm_train.expert_layers(config) == []
    empty = [np.zeros((0, 0))] * 3
    checks = driver.routing_checks({"loads": empty, "dropped": [0] * 3},
                                   {"loads": empty}, cell)
    assert checks == pinned["checks"]


def test_the_pin_is_counted_with_the_accepted_ones():
    assert accepted.PINNED[next(iter(PINNED))] is PINNED[next(iter(PINNED))]
    accepted.test_every_language_cell_is_pinned()
