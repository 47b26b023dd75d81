"""CPU receipts for the bucketed, overlapped gradient exchange (r14).

Two receipts, one harness:

1. **Step-time overhead** (default): the bucketed exchange re-groups the
   gradient collectives — on CPU (where no latency-hiding scheduler can
   cash the overlap in) its cost must be ~zero, so the min-of-N
   ALTERNATING-window protocol of every r7+ receipt times the jitted
   train step bucketing-OFF vs bucketing-ON at the same sharding basis.
   CPU is the honest qualifier for the OVERHEAD half of the claim; the
   overlap WIN is device-side and is not measured.

2. **Lowered-HLO overlap evidence** (`--hlo-report`): the committed
   ASSERTION that bucketing produces an overlap-capable exchange
   (ISSUE 11 acceptance: evidence in lowered HLO, not prose). For the
   sharded bases it lowers the step both ways and checks, via
   parallel/buckets.hlo_overlap_report:
     - monolithic: exactly 1 reduce-scatter whose ancestors include the
       ENTIRE backward (the serial tail this PR deletes);
     - bucketed: >= 2 gradient collectives AND a (collective, conv/dot)
       pair with no dependency path either way — the structural license
       for XLA's latency-hiding scheduler to run them concurrently;
     - zero3 (r21, mesh.shard_params): one param all-gather PER BUCKET
       (gathers == buckets; monolithic: exactly 1) plus the committed
       GATHER witness — an (all_gather, conv/dot) pair with no path
       either way, the overlap license for the just-in-time gather.
   Exit 1 if any assertion fails.

    JAX_PLATFORMS=cpu python benchmarks/comm_overlap_bench.py \
        --sharding zero2 --bucket-mb 0.25 --repeats 6 \
        --json-out benchmarks/runs/host_r14/comm_overlap_zero2.json
    JAX_PLATFORMS=cpu python benchmarks/comm_overlap_bench.py \
        --hlo-report --json-out benchmarks/runs/host_r14/hlo_overlap.json
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
        description="bucketed gradient-exchange receipts (CPU)")
    parser.add_argument("--model", default="vggf",
                        choices=("vggf", "vgg16", "resnet50", "vit_s16"))
    parser.add_argument("--image-size", type=int, default=64)
    parser.add_argument("--batch", type=int, default=16)
    parser.add_argument("--num-classes", type=int, default=100)
    parser.add_argument("--devices", type=int, default=8,
                        help="virtual CPU mesh size (collectives need > 1)")
    parser.add_argument("--sharding", default="zero2",
                        choices=("dp", "zero1", "zero2", "zero3"))
    parser.add_argument("--bucket-mb", type=float, default=0.25,
                        help="comm_bucket_mb for the bucketed column")
    parser.add_argument("--grad-accum", type=int, default=1)
    parser.add_argument("--steps-per-window", type=int, default=4)
    parser.add_argument("--warmup-steps", type=int, default=2)
    parser.add_argument("--repeats", type=int, default=6,
                        help="alternating window pairs (min-of-N)")
    parser.add_argument("--hlo-report", action="store_true",
                        help="emit + assert the lowered-HLO overlap "
                             "evidence instead of timing windows")
    parser.add_argument("--json-out", default=None)
    args = parser.parse_args()

    # the virtual device count must be pinned before jax initializes
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            f"{flags} --xla_force_host_platform_device_count="
            f"{args.devices}").strip()

    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from distributed_vgg_f_tpu.config import MeshConfig, ModelConfig
    from distributed_vgg_f_tpu.models import build_model
    from distributed_vgg_f_tpu.parallel.buckets import hlo_overlap_report
    from distributed_vgg_f_tpu.parallel.mesh import (
        MeshSpec,
        build_mesh,
        shard_host_batch,
    )
    from distributed_vgg_f_tpu.parallel.zero import plan_exchange
    from distributed_vgg_f_tpu.train.state import TrainState
    from distributed_vgg_f_tpu.train.step import build_train_step

    n_dev = len(jax.devices())
    model = build_model(ModelConfig(name=args.model,
                                    num_classes=args.num_classes,
                                    compute_dtype="float32",
                                    dropout_rate=0.0))
    mesh = build_mesh(MeshSpec(("data",), (n_dev,)))
    tx = optax.sgd(0.01, momentum=0.9)
    zero = args.sharding in ("zero1", "zero2", "zero3")
    zero3 = args.sharding == "zero3"
    sample = jnp.zeros((1, args.image_size, args.image_size, 3), jnp.float32)

    def make(bucket_mb: float):
        plan = plan_exchange(
            MeshConfig(shard_opt_state=zero,
                       shard_gradients=args.sharding in ("zero2", "zero3"),
                       shard_params=zero3,
                       comm_bucket_mb=bucket_mb),
            mesh, tx, grad_accum_steps=args.grad_accum)
        if plan.sharded:
            shapes = jax.eval_shape(
                lambda r: TrainState.create(model, tx, r, sample),
                jax.random.key(0))
            plan = plan.bind(shapes.params, shapes.batch_stats)
            shardings = plan.state_shardings(mesh)
            state = jax.jit(
                lambda r: TrainState.create(model, tx, r, sample,
                                            exchange=plan),
                out_shardings=shardings)(jax.random.key(0))
        else:
            state = TrainState.create(model, tx, jax.random.key(0), sample)
        return state, build_train_step(model, mesh, 5e-4, plan)

    rng0 = np.random.default_rng(0)
    batch = shard_host_batch(
        {"image": rng0.standard_normal(
            (args.batch, args.image_size, args.image_size, 3)
        ).astype(np.float32),
         "label": rng0.integers(0, args.num_classes,
                                (args.batch,)).astype(np.int32)}, mesh)
    base = jax.jit(lambda: jax.random.key(1))()

    from distributed_vgg_f_tpu.telemetry.schema import SCHEMA_VERSION

    if args.hlo_report and args.grad_accum != 1:
        # the HLO parser reads TOP-LEVEL instructions only; with grad
        # accumulation the per-bucket scatters live inside the scan's
        # while body, so every assertion below would fail spuriously
        parser.error("--hlo-report requires --grad-accum 1 (accumulated "
                     "collectives lower inside the scan body, invisible "
                     "to the top-level overlap analysis)")

    if args.hlo_report:
        failures = []
        rows = []
        for bucket_mb in (0.0, args.bucket_mb):
            state, step = make(bucket_mb)
            text = step.lower(state, batch, base).as_text()
            rep = hlo_overlap_report(text)
            bucketed = bucket_mb > 0
            label = args.sharding + ("_bucketed" if bucketed else "")
            rows.append({"mode": "hlo_overlap", "sharding": label,
                         "model": args.model, "bucket_mb": bucket_mb,
                         "comm": dict(step.comm_meta), **rep})
            scatters = rep["collective_counts"].get("reduce_scatter", 0)
            if zero and not bucketed:
                # the monolithic serial tail this PR exists to break
                if scatters != 1:
                    failures.append(f"{label}: expected exactly 1 "
                                    f"reduce_scatter, saw {scatters}")
                if rep["serial_tail_collectives"] < 1:
                    failures.append(f"{label}: flat scatter should depend "
                                    "on the whole backward")
            if bucketed:
                want = step.comm_meta["buckets"]
                if zero and scatters != want:
                    failures.append(f"{label}: {scatters} reduce_scatters "
                                    f"!= {want} buckets")
                if rep["grad_collectives"] < 2:
                    failures.append(f"{label}: < 2 gradient collectives")
                if not rep["overlap_capable"]:
                    failures.append(f"{label}: no overlap witness — every "
                                    "collective depends on the full "
                                    "backward")
            if zero3:
                # r21 acceptance: one param all-gather per bucket, plus
                # the dependency-free (all_gather, conv/dot) pair — the
                # just-in-time gather's own overlap license
                want_g = step.comm_meta["gathers"]
                if rep["gathers"] != want_g:
                    failures.append(f"{label}: {rep['gathers']} all_gathers "
                                    f"!= {want_g} expected")
                if bucketed and not rep["gather_overlap_capable"]:
                    failures.append(f"{label}: no gather witness — every "
                                    "param all-gather blocks all compute")
        artifact = {"schema_version": SCHEMA_VERSION,
                    "mode": "hlo_overlap_report", "model": args.model,
                    "sharding": args.sharding, "devices": n_dev,
                    "layouts": rows, "failures": failures}
        print(json.dumps({k: v for k, v in artifact.items()
                          if k != "schema_version"}, indent=1))
        if args.json_out:
            os.makedirs(os.path.dirname(args.json_out) or ".",
                        exist_ok=True)
            with open(args.json_out, "w") as f:
                json.dump(artifact, f, indent=1)
        if failures:
            print("HLO OVERLAP ASSERTION FAILED:", *failures,
                  sep="\n  ", file=sys.stderr)
            return 1
        return 0

    def window(state, step):
        t0 = time.monotonic()
        for _ in range(args.steps_per_window):
            state, metrics = step(state, batch, base)
        jax.block_until_ready(metrics["loss"])
        dt = time.monotonic() - t0
        return state, args.steps_per_window * args.batch / dt

    cols = {0.0: make(0.0), args.bucket_mb: make(args.bucket_mb)}
    for k in cols:
        for _ in range(max(1, args.warmup_steps)):
            st, _ = window(*cols[k])
            cols[k] = (st, cols[k][1])
    off_rates, on_rates = [], []
    for _ in range(max(1, args.repeats)):
        st, r = window(*cols[0.0])
        cols[0.0] = (st, cols[0.0][1])
        off_rates.append(r)
        st, r = window(*cols[args.bucket_mb])
        cols[args.bucket_mb] = (st, cols[args.bucket_mb][1])
        on_rates.append(r)

    on_best, off_best = max(on_rates), max(off_rates)
    overhead_pct = round((1.0 - on_best / off_best) * 100.0, 2)
    comm_on = dict(cols[args.bucket_mb][1].comm_meta)
    artifact = {
        "schema_version": SCHEMA_VERSION,
        "metric": METRIC,
        "value": round(on_best, 2),
        "unit": "images/sec",
        "model": args.model,
        "image_size": args.image_size,
        "batch": args.batch,
        "devices": n_dev,
        "layouts": [
            {"mode": "comm_overlap_bench",
             "sharding": args.sharding + "_bucketed",
             "model": args.model, "comm": comm_on,
             "images_per_sec": round(on_best, 2), **_stats(on_rates)},
            {"mode": "comm_overlap_bench", "sharding": args.sharding,
             "model": args.model,
             "comm": dict(cols[0.0][1].comm_meta),
             "images_per_sec": round(off_best, 2), **_stats(off_rates)},
        ],
        "comm_overlap": {
            "mode": "comm_bucketing_overhead",
            "bucketed_images_per_sec": round(on_best, 2),
            "monolithic_images_per_sec": round(off_best, 2),
            "overhead_pct": overhead_pct,
            "buckets": comm_on["buckets"],
            "bucket_mb": args.bucket_mb,
            "on": _stats(on_rates), "off": _stats(off_rates),
            "protocol": f"min-of-{args.repeats} ALTERNATING "
                        f"monolithic/bucketed windows x "
                        f"{args.steps_per_window} jitted steps of batch "
                        f"{args.batch} at {args.image_size}px "
                        f"({args.model}, {args.sharding}, f32, "
                        f"{n_dev}-device CPU mesh); CPU pays the "
                        f"bucketing bookkeeping WITHOUT the overlap win "
                        f"— the upper bound for the stage's relative "
                        f"cost",
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
        print(f"OVER BUDGET: bucketed-exchange CPU step overhead "
              f"{overhead_pct}% > {budget}% (acceptance)", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
