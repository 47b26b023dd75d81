"""Ling-3.0-flash's cell shrunk to what a CPU test holds: the preset
`ling3_flash_tiny` (`DKKKKLK`, hidden 64, 4 heads of 16, latent attention
at 24-wide keys on 16-wide values, 16 experts in 4 groups top-4 of the best
2, sequences of 128) of which this share holds experts 4-7 (one group),
vocabulary 256, two sequences, float32 compute, limits for that size."""

import copy
import os
import time

from chipbench import run as harness

ROOT = harness.ROOT
CELL = "ling3_flash_ep64_step"
#: float32 on both sides, so the gaps are 1e-5, but for the router: some
#: 10,000 choices a step (a group's score, an expert's) lie 0.02 apart and
#: the two sides' inputs 1e-6, so about one choice in two steps falls the
#: other way, and one of an expert's 60 assignments moves its leaves by a
#: hundredth. The control and the faults read 0.5 and more.
LIMITS = {"loss_gap": 1e-3, "first_grad_gap": 5e-2, "change_gap": 5e-2,
          "first_grad_diff": 5e-2, "probe_grad_diff": 1e-2}


def tiny(root: str = ROOT):
    from distributed_vgg_f_tpu.config import get_config
    bench, cell, config = harness.load_cell(root, CELL)
    config = copy.deepcopy(config)
    extra = dict(get_config("ling3_flash_tiny").model.extra)
    config.update({k: extra[k] for k in config["arch_keys"]})
    config.update(
        preset="ling3_flash_tiny", num_hidden_layers=7, n_routed_experts=4,
        vocab_size=256, reference_block_rows=32,
        # the published ranges for the decays; everything else by default
        init={k: v for k, v in config["init"].items()
              if k in ("attn/A_log", "attn/dt_bias", "moe/router_bias")},
        probe_leaves=["lm_head/kernel", "layer_0/attn/A_log",
                      "layer_2/attn/dt_bias", "layer_1/moe/router",
                      "layer_4/moe/experts_down_proj",
                      "layer_5/attn/kv_b_proj/kernel"])
    config["published"] = {**config["published"], "n_routed_experts": 16}
    config["recipe"].update(seq_len=128, compute_dtype="float32",
                            reference_batch=2, first_expert=4)
    config["overrides"] = {"model.extra.experts_held": 4,
                           "model.extra.first_expert": 4,
                           "train.log_every": 5}
    cell = {**cell, "batch_per_chip": 2, "limits": dict(LIMITS),
            "load_diff_limit": 0.005}
    return bench, cell, config


def context(tmp_path, *, seed=3, fault=None, seconds=0.3):
    bench, cell, config = tiny()
    return harness.Context(
        root=ROOT, bench=bench, cell=cell, config=config, seed=seed,
        seconds=seconds, trace=False, t0=time.perf_counter(),
        out_dir=os.path.join(str(tmp_path), "out"), fault=fault)
