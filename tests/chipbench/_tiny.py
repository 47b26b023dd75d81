"""Cells of the benchmark shrunk to what a CPU test can hold: 32x32
images, 10 classes, 8 rows, limits wide enough for that size."""

import copy
import os
import time

from chipbench import run as harness

ROOT = harness.ROOT
LIMITS = {"loss_gap": 0.05, "first_grad_gap": 0.06, "first_grad_diff": 0.12,
          "change_gap": 0.06, "probe_grad_diff": 0.015}


def tiny(cell_name: str, root: str = ROOT, **cell_changes):
    bench, cell, config = harness.load_cell(root, cell_name)
    config = copy.deepcopy(config)
    config["recipe"].update(image_size=32, num_classes=10)
    config["overrides"] = {**config.get("overrides", {}),
                           "data.image_size": 32, "model.num_classes": 10,
                           "train.log_every": 5}
    if config["reference"] == "resnet50":
        # one block a stage, float32 compute: at 8 rows of 32x32, batch
        # norm over a handful of values turns bf16 rounding into noise
        config["overrides"].update({"model.extra": {
            "stage_sizes": (1, 1, 1, 1)}, "model.compute_dtype": "float32"})
    cell = {**cell, "batch_per_chip": 8, "reference_block_rows": 4,
            "limits": dict(LIMITS), **cell_changes}
    return bench, cell, config


def context(cell_name: str, tmp_path, *, seed=3, fault=None, trace=False,
            seconds=0.3, root: str = ROOT, **cell_changes):
    bench, cell, config = tiny(cell_name, root, **cell_changes)
    return harness.Context(
        root=root, bench=bench, cell=cell, config=config, seed=seed,
        seconds=seconds, trace=trace, t0=time.perf_counter(),
        out_dir=os.path.join(str(tmp_path), "out"), fault=fault)
