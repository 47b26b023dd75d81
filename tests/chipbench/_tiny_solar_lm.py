"""Solar-Open2's cell shrunk to what a CPU test holds: the preset
`solar_open2_tiny` (`GKKK`, hidden 64, 4 delta-rule heads of 16, 4 query
heads on 2 key/value heads of 16, 16 experts top-4, sequences of 128) of
which this share holds experts 4-7, vocabulary 256, two sequences, float32
compute, limits for that size."""

import copy
import os
import time

from chipbench import run as harness

ROOT = harness.ROOT
CELL = "solar_open2_ep40_step"
#: float32 on both sides, so the gaps are 1e-5, but for the router: a
#: near-tie of the top-4 that falls the other way moves one of an expert's
#: ~60 assignments. The control and the faults read 0.5 and more.
LIMITS = {"loss_gap": 1e-3, "first_grad_gap": 5e-2, "change_gap": 5e-2,
          "first_grad_diff": 5e-2, "probe_grad_diff": 1e-2}


def tiny(root: str = ROOT):
    from distributed_vgg_f_tpu.config import get_config
    bench, cell, config = harness.load_cell(root, CELL)
    config = copy.deepcopy(config)
    extra = dict(get_config("solar_open2_tiny").model.extra)
    config.update({k: extra[k] for k in config["arch_keys"]})
    config.update(
        preset="solar_open2_tiny", num_hidden_layers=4, n_routed_experts=4,
        vocab_size=256, reference_block_rows=32,
        # the published ranges for the decays; everything else by default
        init={k: v for k, v in config["init"].items()
              if k in ("attn/A_log", "attn/dt_bias", "moe/router_bias")},
        probe_leaves=["lm_head/kernel", "layer_1/attn/A_log",
                      "layer_2/attn/dt_bias", "layer_1/attn/f_a_proj/kernel",
                      "layer_0/attn/gate_proj/kernel",
                      "layer_2/moe/experts_down_proj"])
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
