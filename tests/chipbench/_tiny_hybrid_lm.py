"""The hybrid language-model cell shrunk to what a CPU test holds: the
preset `nemotron3_nano_tiny` (`MEM*E`, hidden 64, chunks of 8 over
sequences of 32, 4 query heads on 2 key heads, 8 experts top-2) of which
this share holds experts 2-5, vocabulary 256, two sequences, float32
compute, limits for that size."""

import copy
import os
import time

from chipbench import run as harness

ROOT = harness.ROOT
CELL = "nemotron3_nano_ep8_step"
LIMITS = {"loss_gap": 1e-4, "first_grad_gap": 1e-3, "change_gap": 1e-3,
          "first_grad_diff": 1e-3, "probe_grad_diff": 1e-3}


def tiny(root: str = ROOT):
    from distributed_vgg_f_tpu.config import get_config
    bench, cell, config = harness.load_cell(root, CELL)
    config = copy.deepcopy(config)
    extra = dict(get_config("nemotron3_nano_tiny").model.extra)
    config.update({k: extra[k] for k in config["arch_keys"]})
    config.update(
        preset="nemotron3_nano_tiny", num_hidden_layers=5,
        n_routed_experts=4, vocab_size=256, reference_block_rows=16,
        # the published ranges for the decays; everything else by default
        init={k: v for k, v in config["init"].items()
              if k in ("mixer/A_log", "mixer/dt_bias", "mixer/D",
                       "mixer/router_bias")},
        probe_leaves=["lm_head/kernel", "layer_0/mixer/A_log",
                      "layer_1/mixer/router",
                      "layer_4/mixer/experts_down_proj",
                      "layer_3/mixer/k_proj/kernel"])
    config["published"] = {**config["published"], "n_routed_experts": 8}
    config["recipe"].update(seq_len=32, compute_dtype="float32",
                            reference_batch=2, first_expert=2)
    config["overrides"] = {"model.extra.experts_held": 4,
                           "model.extra.first_expert": 2,
                           "train.log_every": 5}
    cell = {**cell, "batch_per_chip": 2, "limits": dict(LIMITS),
            "load_diff_limit": 0.0}
    return bench, cell, config


def context(tmp_path, *, seed=3, fault=None, seconds=0.3):
    bench, cell, config = tiny()
    return harness.Context(
        root=ROOT, bench=bench, cell=cell, config=config, seed=seed,
        seconds=seconds, trace=False, t0=time.perf_counter(),
        out_dir=os.path.join(str(tmp_path), "out"), fault=fault)
