"""The language-model cell shrunk to what a CPU test holds: hidden 64, 8
experts top-2 of which this share holds experts 2-5, 2 layers, vocabulary
256, two sequences of 32, float32 compute, limits for that size."""

import copy
import os
import time

from chipbench import run as harness

ROOT = harness.ROOT
CELL = "mistral_small4_ep16_step"
LIMITS = {"loss_gap": 1e-4, "first_grad_gap": 1e-3, "change_gap": 1e-3,
          "first_grad_diff": 1e-3, "probe_grad_diff": 1e-3}


def tiny(root: str = ROOT):
    bench, cell, config = harness.load_cell(root, CELL)
    config = copy.deepcopy(config)
    config.update(
        preset="mistral_small4_tiny", hidden_size=64, num_attention_heads=4,
        q_lora_rank=32, kv_lora_rank=16, qk_nope_head_dim=8,
        qk_rope_head_dim=8, v_head_dim=16, num_experts_per_tok=2,
        moe_intermediate_size=32, num_hidden_layers=2, n_routed_experts=4,
        vocab_size=256, init=None, reference_block_rows=16,
        probe_leaves=["lm_head/kernel", "layer_0/moe/router",
                      "layer_0/moe/experts_down_proj"])
    config["published"] = {**config["published"], "n_routed_experts": 8}
    config["recipe"].update(seq_len=32, compute_dtype="float32",
                            reference_batch=2, first_expert=2)
    config["overrides"] = {"model.extra.experts_held": 4,
                           "model.extra.first_expert": 2,
                           "train.log_every": 5}
    cell = {**cell, "batch_per_chip": 2, "limits": dict(LIMITS)}
    return bench, cell, config


def context(tmp_path, *, seed=3, fault=None, seconds=0.3):
    bench, cell, config = tiny()
    return harness.Context(
        root=ROOT, bench=bench, cell=cell, config=config, seed=seed,
        seconds=seconds, trace=False, t0=time.perf_counter(),
        out_dir=os.path.join(str(tmp_path), "out"), fault=fault)
