"""CPU train-step bench for the fused on-device augmentation stage (r13).

The acceptance claim this receipt backs: the fused augment stage
(data/augment.py — flip/jitter/mixup/RandAugment-lite INSIDE the jitted
step) costs < 2% step time. The host-pipeline half of the claim (host
rate and wire bytes unchanged) is host_pipeline_bench.py
--augment-receipt; THIS harness times the jitted train step itself,
augment-on vs augment-off, with the same min-of-N ALTERNATING-window
protocol as every r7+ receipt (both columns sample the same box drift, so
the min-of-N difference isolates the stage).

CPU is the honest qualifier: on a TPU the elementwise augment ops fuse
into memory-bound kernels XLA was already emitting, so the CPU number —
where the same ops compete for the cores running everything else — is the
UPPER bound for the stage's relative cost. The device-side cost is not
measured.

    JAX_PLATFORMS=cpu python benchmarks/augment_step_bench.py \
        --model vggf --image-size 128 --batch 16 --repeats 6 \
        --json-out benchmarks/runs/host_r13/augment_step_overhead.json
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

METRIC = "cpu_train_step_images_per_sec"


def _stats(rates):
    med = sorted(rates)[len(rates) // 2]
    return {"repeats": len(rates), "best": round(max(rates), 2),
            "median": round(med, 2),
            "spread": round((max(rates) - min(rates)) / med, 4) if med else 0}


def main() -> int:
    parser = argparse.ArgumentParser(
        description="fused-augment step-time overhead receipt (CPU)")
    parser.add_argument("--model", default="vggf",
                        choices=("vggf", "vgg16", "resnet50", "vit_s16"))
    parser.add_argument("--image-size", type=int, default=128)
    parser.add_argument("--batch", type=int, default=16)
    parser.add_argument("--num-classes", type=int, default=100)
    parser.add_argument("--steps-per-window", type=int, default=4)
    parser.add_argument("--warmup-steps", type=int, default=2)
    parser.add_argument("--repeats", type=int, default=6,
                        help="alternating window pairs (min-of-N)")
    parser.add_argument("--json-out", default=None)
    args = parser.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from distributed_vgg_f_tpu.config import (
        AugmentConfig,
        MeshConfig,
        ModelConfig,
    )
    from distributed_vgg_f_tpu.data.augment import make_device_augment
    from distributed_vgg_f_tpu.data.device_ingest import make_device_finish
    from distributed_vgg_f_tpu.models import build_model
    from distributed_vgg_f_tpu.models.ingest import (
        IMAGENET_MEAN_RGB,
        IMAGENET_STDDEV_RGB,
        ingest_descriptor,
    )
    from distributed_vgg_f_tpu.parallel.mesh import (
        MeshSpec,
        build_mesh,
        shard_host_batch,
    )
    from distributed_vgg_f_tpu.parallel.zero import plan_exchange
    from distributed_vgg_f_tpu.train.state import TrainState
    from distributed_vgg_f_tpu.train.step import build_train_step

    desc = ingest_descriptor(args.model)
    s2d = desc.space_to_depth and args.image_size % 4 == 0
    # float32 on CPU: bf16 emulation noise would swamp a 2% budget
    model = build_model(ModelConfig(name=args.model,
                                    num_classes=args.num_classes,
                                    compute_dtype="float32"))
    mesh = build_mesh(MeshSpec(("data",), (0,)))
    tx = optax.sgd(0.01, momentum=0.9)
    aug_cfg = AugmentConfig(enabled=True, hflip=True, mixup_alpha=0.2)
    augment = make_device_augment(aug_cfg, IMAGENET_MEAN_RGB,
                                  IMAGENET_STDDEV_RGB, space_to_depth=s2d)
    finish_s2d = make_device_finish(IMAGENET_MEAN_RGB, IMAGENET_STDDEV_RGB,
                                    space_to_depth=s2d)

    rng = np.random.default_rng(0)
    # the u8 wire's batch, exactly as production ships it
    pixels = rng.integers(0, 256, size=(args.batch, args.image_size,
                                        args.image_size, 3)).astype(np.uint8)
    labels = rng.integers(0, args.num_classes,
                          size=(args.batch,)).astype(np.int32)
    batch = shard_host_batch({"image": pixels, "label": labels}, mesh)
    base = jax.jit(lambda: jax.random.key(1))()

    def make(with_augment: bool):
        state = TrainState.create(
            model, tx, jax.random.key(0),
            jnp.zeros((1, args.image_size, args.image_size, 3), jnp.float32))
        # augment-on: the stage is the whole prologue (it packs and runs
        # a finish of its own); augment-off packs in the finish — each
        # column runs ITS production configuration
        step = build_train_step(
            model, mesh, 5e-4, plan_exchange(MeshConfig(), mesh, tx),
            device_finish=None if with_augment else finish_s2d,
            device_augment=augment if with_augment else None)
        return state, step

    def window(state, step):
        t0 = time.monotonic()
        for _ in range(args.steps_per_window):
            state, metrics = step(state, batch, base)
        jax.block_until_ready(metrics["loss"])
        dt = time.monotonic() - t0
        return state, args.steps_per_window * args.batch / dt

    # one persistent (state, step) per column: compile once, then windows
    # only pay the step. Alternate columns so both sample the same drift.
    cols = {False: make(False), True: make(True)}
    for k in cols:
        for _ in range(max(1, args.warmup_steps)):  # warmup/compile
            st, _ = window(*cols[k])
            cols[k] = (st, cols[k][1])
    off_rates, on_rates = [], []
    for _ in range(max(1, args.repeats)):
        st, r = window(*cols[False])
        cols[False] = (st, cols[False][1])
        off_rates.append(r)
        st, r = window(*cols[True])
        cols[True] = (st, cols[True][1])
        on_rates.append(r)

    on_best, off_best = max(on_rates), max(off_rates)
    overhead_pct = round((1.0 - on_best / off_best) * 100.0, 2)
    from distributed_vgg_f_tpu.telemetry.schema import SCHEMA_VERSION
    artifact = {
        "schema_version": SCHEMA_VERSION,
        "metric": METRIC,
        "value": round(on_best, 2),
        "unit": "images/sec",
        "model": args.model,
        "image_size": args.image_size,
        "batch": args.batch,
        "space_to_depth": s2d,
        "augment_overhead": {
            "mode": "augment_step_overhead",
            "augment_on_images_per_sec": round(on_best, 2),
            "augment_off_images_per_sec": round(off_best, 2),
            "overhead_pct": overhead_pct,
            "on": _stats(on_rates), "off": _stats(off_rates),
            "augment": aug_cfg.describe(),
            "protocol": f"min-of-{args.repeats} ALTERNATING augment-off/on "
                        f"windows x {args.steps_per_window} jitted steps of "
                        f"batch {args.batch} at {args.image_size}px "
                        f"({args.model}, f32 compute, u8-wire batch, CPU); "
                        f"'on' = flagship recipe (flips+mixup) fused into "
                        f"the step, pack deferred behind the stage",
        },
        "host_vcpus": os.cpu_count(),
    }
    print(json.dumps({k: v for k, v in artifact.items()
                      if k != "schema_version"}))
    if args.json_out:
        os.makedirs(os.path.dirname(args.json_out) or ".", exist_ok=True)
        with open(args.json_out, "w") as f:
            json.dump(artifact, f, indent=1)
    budget = 2.0
    if overhead_pct > budget:
        print(f"OVER BUDGET: fused-augment step overhead {overhead_pct}% "
              f"> {budget}% (acceptance)", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
