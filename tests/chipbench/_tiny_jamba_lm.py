"""AI21-Jamba2-3B's cell shrunk to what a CPU test holds: the preset
`jamba2_3b_tiny` (`MMAM`: three Mamba-1 mixers and the attention layer, a
SwiGLU of 64 in each, hidden 32, 64 Mamba channels with a state of 16, 2
query heads on 1 key/value head of 16), vocabulary 256 tied, two
sequences of 256 (two chunks of the recurrence), float32 compute, limits
for that size."""

import copy
import os
import time

from chipbench import run as harness

ROOT = harness.ROOT
CELL = "jamba2_3b_v8_step"
#: float32 on both sides and no router: the gaps are float32 rounding
#: (1e-5 and under); each planted fault reads 0.1 and more.
LIMITS = {"loss_gap": 2e-4, "first_grad_gap": 1e-3, "change_gap": 5e-2,
          "first_grad_diff": 1e-3, "probe_grad_diff": 1e-3}


def tiny(root: str = ROOT):
    from distributed_vgg_f_tpu.config import get_config
    bench, cell, config = harness.load_cell(root, CELL)
    config = copy.deepcopy(config)
    extra = dict(get_config("jamba2_3b_tiny").model.extra)
    config.update({k: extra[k] for k in config["arch_keys"]})
    config.update(
        preset="jamba2_3b_tiny", num_hidden_layers=4, vocab_size=256,
        reference_block_rows=64,
        probe_leaves=["embed/embedding", "layer_0/mixer/A_log",
                      "layer_1/mixer/dt_proj/bias",
                      "layer_3/mixer/x_proj/kernel",
                      "layer_2/mixer/k_proj/kernel",
                      "layer_1/mlp/down_proj/kernel"])
    config["recipe"].update(seq_len=256, compute_dtype="float32",
                            reference_batch=2)
    config["overrides"] = {"train.log_every": 5}
    cell = {**cell, "batch_per_chip": 2, "limits": dict(LIMITS)}
    return bench, cell, config


def context(tmp_path, *, seed=3, fault=None, seconds=0.3):
    bench, cell, config = tiny()
    return harness.Context(
        root=ROOT, bench=bench, cell=cell, config=config, seed=seed,
        seconds=seconds, trace=False, t0=time.perf_counter(),
        out_dir=os.path.join(str(tmp_path), "out"), fault=fault)
