"""Throughput benchmark — prints ONE JSON line with the judged metric
(BASELINE.json: images/sec/chip for VGG-F training).

Two modes:

- default (device bench): the full jitted DP train step (forward, loss+wd,
  backward, pmean all-reduce, SGD-momentum apply — one XLA computation) on a
  resident synthetic batch, isolating device step time from host input
  (SURVEY.md §4 throughput harness). Adds `mfu_est`: ANALYTIC jaxpr-counted
  matmul/conv FLOPs (utils/flops.py) per step / step time / the chip's bf16
  peak, with XLA's per-partition cost-analysis figure as the `mfu_est_xla`
  cross-check.
- `--pipeline imagenet` (end-to-end bench): the same train step driven through
  the REAL input path — fake 224-px JPEG TFRecords generated locally once,
  decoded by data/imagenet.py's tf.data pipeline, device-prefetched
  (data/prefetch.py). Reports end-to-end img/s/chip plus `device_only`,
  `host_pipeline` img/s/chip and the `infeed_stall_fraction` — SURVEY.md §7
  names the host path as where the ≥90 % scaling-efficiency target is won or
  lost, so this is the number that bounds real training.

`vs_baseline`: the reference publishes no numbers (BASELINE.json
`published: {}`, SURVEY.md §6), so the ratio is computed against
`benchmarks/baseline.json` — frozen from this framework's first measured
round per metric — and 1.0 when absent.
"""

from __future__ import annotations

import argparse
import io
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))


def _chip_for(device: dict) -> str | None:
    """The utils/mxu_model chip name for the MFU fields. The CPU has no
    peak, so a CPU run (asked for by name) carries no MFU; on a `tpu`
    platform a device_kind missing from the table is an error — a silent
    drop of the MFU fields would read as "not measured"."""
    if device["platform"] != "tpu":
        return None
    from distributed_vgg_f_tpu.utils.mxu_model import DEVICE_KIND_TO_CHIP
    try:
        return DEVICE_KIND_TO_CHIP[device["device_kind"]]
    except KeyError:
        raise RuntimeError(
            f"device_kind {device['device_kind']!r} is not in "
            "utils/mxu_model.DEVICE_KIND_TO_CHIP — add the chip and its "
            "published peaks before benchmarking on it") from None


def _emit_failure(metric: str, err: dict) -> None:
    """The failure counterpart of the contract line: same keys, value null,
    plus an ``error`` tag the driver can parse instead of a stack trace.
    Never a number from another run."""
    print(json.dumps({"metric": metric, "value": None,
                      "unit": "images/sec/chip", "vs_baseline": None,
                      **err}), flush=True)


def _make_trainer(args, data_cfg, model_extra=None):
    from distributed_vgg_f_tpu.config import (
        ExperimentConfig, ModelConfig, OptimConfig, TrainConfig,
        apply_overrides)
    from distributed_vgg_f_tpu.train.trainer import Trainer
    from distributed_vgg_f_tpu.utils.logging import MetricLogger

    cfg = ExperimentConfig(
        name=f"bench_{args.model}",
        model=ModelConfig(name=args.model, num_classes=1000,
                          compute_dtype="bfloat16",
                          extra=model_extra or {}),
        optim=OptimConfig(base_lr=0.01,
                          reference_batch_size=data_cfg.global_batch_size),
        data=data_cfg,
        train=TrainConfig(steps=args.steps, log_every=10_000, seed=0),
    )
    # --set KEY=VALUE (r13): dotted overrides through the SAME folding as
    # the trainer CLI (config.fold_override_items) — benches augment/ZeRO
    # on/off pairs (e.g. --set data.augment.enabled=true,
    # --set mesh.shard_opt_state=true) without a flag per knob.
    from distributed_vgg_f_tpu.config import fold_override_items
    try:
        overrides = fold_override_items(getattr(args, "set", None))
    except ValueError as e:
        raise SystemExit(f"--set: {e}")
    if overrides:
        cfg = apply_overrides(cfg, overrides)
    return Trainer(cfg, logger=MetricLogger(stream=io.StringIO()))


def _parsed_model_extra(args) -> dict:
    """--model-extra KEY=VALUE entries as a typed dict (config's rules)."""
    from distributed_vgg_f_tpu.config import parse_extra_value

    extra = {}
    for kv in getattr(args, "model_extra", []) or []:
        key, sep, value = kv.partition("=")
        if not sep or not key:
            raise SystemExit(f"--model-extra needs KEY=VALUE, got {kv!r}")
        extra[key] = parse_extra_value(value)
    return extra


def _emit(metric, per_chip, device, *, update_baseline=False, extra=None):
    """Print the contract JSON line — naming the device it was measured on
    — with vs_baseline from the frozen per-metric baseline file (see module
    docstring)."""
    baseline_path = os.path.join(REPO, "benchmarks", "baseline.json")
    baselines = {}
    if os.path.exists(baseline_path):
        with open(baseline_path) as f:
            data = json.load(f)
        baselines = {data["metric"]: data} if "metric" in data else data
    vs_baseline = 1.0
    if update_baseline:
        baselines[metric] = {"metric": metric, "value": per_chip,
                             "platform": device["platform"],
                             "device_kind": device["device_kind"]}
        if extra and extra.get("model_extra"):
            # a variant config must be visible in the frozen record — a
            # baseline silently redefined by a --model-extra run would make
            # every later default-config ratio a lie (code-review r3)
            baselines[metric]["model_extra"] = extra["model_extra"]
        os.makedirs(os.path.dirname(baseline_path), exist_ok=True)
        with open(baseline_path, "w") as f:
            json.dump(baselines, f)
    elif baselines.get(metric, {}).get("value"):
        vs_baseline = per_chip / baselines[metric]["value"]

    record = {
        "metric": metric,
        "value": round(per_chip, 2),
        "unit": "images/sec/chip",
        "vs_baseline": round(vs_baseline, 4),
        **device,
    }
    record.update(extra or {})
    print(json.dumps(record))


def _step_flops(trainer, state, batch, rng):
    """(analytic, xla, views) for one train step (whole mesh).

    `analytic` is the shape-exact matmul/conv FLOP total, counted before
    XLA optimization — the validated MFU basis (cost_analysis can
    double-count fused recomputation). It is derived from the SAME single
    trace that yields the roofline GEMM `views`
    (utils/mxu_model.views_from_jaxpr shares the FLOP counter's
    walk_matmul_eqns and per-op formulas, so the sum is identical to
    utils/flops.jaxpr_flops — one make_jaxpr instead of two). `xla` is the
    compiled-program cost analysis, kept as a cross-check. A count that
    cannot be made raises: MFU fields are never dropped in silence."""
    from distributed_vgg_f_tpu.utils.mxu_model import views_from_jaxpr
    views = views_from_jaxpr(trainer.train_step, state, batch, rng)
    analytic = sum(v.flops for v in views)
    if analytic <= 0:
        raise RuntimeError("analytic FLOP count of the train step is 0")
    compiled = trainer.train_step.lower(state, batch, rng).compile()
    analysis = compiled.cost_analysis()
    if isinstance(analysis, (list, tuple)):
        analysis = analysis[0]
    xla = float(analysis["flops"])
    return analytic, xla, views


def run_device_bench(args, device) -> None:
    """Device-only step throughput on a resident synthetic batch."""
    import jax

    from distributed_vgg_f_tpu.config import DataConfig
    from distributed_vgg_f_tpu.data.synthetic import SyntheticDataset

    num_chips = device["device_count"]
    batch = args.batch_size * max(1, num_chips)
    from distributed_vgg_f_tpu.config import supports_space_to_depth

    # VGG-F takes the 4x4 space-to-depth input layout (data.space_to_depth):
    # the host packs once, the device skips the stem relayout (+3.7% at batch
    # 2048 on v5e). --raw-input benches the (S, S, 3) contract instead.
    s2d = supports_space_to_depth(args.model, args.image_size) \
        and not args.raw_input
    model_extra = _parsed_model_extra(args)
    trainer = _make_trainer(args, DataConfig(
        name="synthetic", image_size=args.image_size, global_batch_size=batch,
        space_to_depth=s2d), model_extra)
    state = trainer.init_state()
    rng = trainer.base_rng()
    # the host packs only when the trainer's resolved config says so: with
    # the fused augmentation enabled (--set data.augment.enabled=true) the
    # step packs AFTER augmenting and expects unpacked batches
    # (DataConfig.host_space_to_depth — the r13 ordering contract)
    ds = SyntheticDataset(batch_size=batch, image_size=args.image_size,
                          num_classes=1000, seed=0, fixed=True,
                          image_dtype="bfloat16",
                          space_to_depth=trainer.cfg.data.host_space_to_depth)
    sharded = trainer.shard(next(ds))
    chip = _chip_for(device)
    if chip is not None:
        flops, flops_xla, gemm_views = _step_flops(trainer, state, sharded,
                                                   rng)

    # every timed window ends in a value fetch, which waits for the device
    for _ in range(args.warmup):
        state, metrics = trainer.train_step(state, sharded, rng)
    if args.warmup:
        float(jax.device_get(metrics["loss"]))

    # min-of-N on step TIME (= best-of-N on rate): each repeat is an
    # independent timed window; the best window is the least host-noise-
    # contaminated sample and median/spread quantify the noise.
    rates = []
    for _ in range(max(1, args.repeats)):
        t0 = time.monotonic()
        for _ in range(args.steps):
            state, metrics = trainer.train_step(state, sharded, rng)
        float(jax.device_get(metrics["loss"]))
        rates.append(batch * args.steps / (time.monotonic() - t0) / num_chips)

    per_chip = max(rates)
    extra = {}
    if args.repeats > 1:
        import statistics
        med = statistics.median(rates)
        extra["repeats"] = args.repeats
        extra["median"] = round(med, 2)
        extra["spread"] = round((max(rates) - min(rates)) / med, 4)
    if chip is not None:
        from distributed_vgg_f_tpu.utils.mxu_model import (
            _peak, achievable_mfu, serial_mfu)
        peak = _peak(chip)
        step_time = batch / (per_chip * num_chips)  # best window's sec/step
        extra["mfu_est"] = round(flops / num_chips / step_time / peak, 4)
        extra["mfu_basis"] = "analytic_jaxpr"
        # cost_analysis is PER-PARTITION for SPMD executables (measured:
        # mesh=8 reports ~1/8 of mesh=1) — already a per-chip figure
        extra["mfu_est_xla"] = round(flops_xla / step_time / peak, 4)
        # the measured MFU's own derived ceiling, from the same trace that
        # produced `flops` (utils/mxu_model per-op roofline): [no-overlap,
        # overlap] matmul-only bounds — the measurement should sit below
        # the upper edge; how far below is the non-matmul + bubble share
        extra["mfu_bound_roofline"] = [
            round(serial_mfu(gemm_views, chip=chip), 4),
            round(achievable_mfu(gemm_views, chip=chip), 4)]
    if model_extra:
        # variant runs must be distinguishable from default-config runs in
        # the emitted artifact (and in any baseline they freeze)
        extra["model_extra"] = model_extra
    metric = f"{args.model}_train_images_per_sec_per_chip"
    _emit(metric, per_chip, device, update_baseline=args.update_baseline,
          extra=extra)


# ---------------------------------------------------------------------------
# End-to-end pipeline bench
# ---------------------------------------------------------------------------

def _ensure_fake_imagenet(data_dir: str, *, num_files: int, per_file: int,
                          source_hw=(320, 256)) -> None:
    """Generate fake ImageNet-like JPEG TFRecords once, from a fixed seed
    (the machines this runs on have no network — SURVEY.md §0); reused
    across runs via the directory cache."""
    import numpy as np

    if any(f.startswith("train-") for f in
           (os.listdir(data_dir) if os.path.isdir(data_dir) else [])):
        return
    import tensorflow as tf
    os.makedirs(data_dir, exist_ok=True)
    # (callers encode num_files/per_file into data_dir, so a cached dir always
    # matches the requested dataset size)
    rng = np.random.default_rng(0)
    h, w = source_hw
    for i in range(num_files):
        path = os.path.join(data_dir, f"train-{i:05d}-of-{num_files:05d}")
        with tf.io.TFRecordWriter(path) as writer:
            for _ in range(per_file):
                img = rng.integers(0, 256, size=(h, w, 3)).astype(np.uint8)
                jpeg = tf.io.encode_jpeg(img, quality=90).numpy()
                ex = tf.train.Example(features=tf.train.Features(feature={
                    "image/encoded": tf.train.Feature(
                        bytes_list=tf.train.BytesList(value=[jpeg])),
                    "image/class/label": tf.train.Feature(
                        int64_list=tf.train.Int64List(
                            value=[int(rng.integers(1, 1001))])),
                }))
                writer.write(ex.SerializeToString())


def run_pipeline_bench(args, device) -> None:
    """End-to-end throughput through the real tf.data JPEG path."""
    import jax

    from distributed_vgg_f_tpu.config import DataConfig
    from distributed_vgg_f_tpu.data.prefetch import maybe_prefetch

    num_chips = device["device_count"]
    batch = args.batch_size * max(1, num_chips)
    # per-size cache subdir: rerunning with different --num-files/--per-file
    # must not silently reuse a differently-sized cached dataset
    data_dir = os.path.join(args.data_dir,
                            f"{args.num_files}x{args.per_file}")
    _ensure_fake_imagenet(data_dir, num_files=args.num_files,
                          per_file=args.per_file)
    from distributed_vgg_f_tpu.config import supports_space_to_depth

    # match the production vggf config: packed space-to-depth train batches
    # (free in the native loader; a tf.nn.space_to_depth map in tf.data)
    s2d = supports_space_to_depth(args.model, args.image_size) \
        and not args.raw_input
    data_cfg = DataConfig(name="imagenet", data_dir=data_dir,
                          image_size=args.image_size, global_batch_size=batch,
                          shuffle_buffer=min(2048, args.num_files * args.per_file),
                          image_dtype="bfloat16",
                          native_jpeg=args.host_pipeline == "native",
                          space_to_depth=s2d,
                          wire=args.wire)
    model_extra = _parsed_model_extra(args)
    trainer = _make_trainer(args, data_cfg, model_extra)
    state = trainer.init_state()
    rng = trainer.base_rng()

    host_ds = trainer.make_dataset("train")
    # report what actually ran: the native loader silently falls back to
    # tf.data when its build is unavailable
    from distributed_vgg_f_tpu.data.native_jpeg import NativeJpegTrainIterator
    actual_host_pipeline = ("native"
                            if isinstance(host_ds, NativeJpegTrainIterator)
                            else "tfdata")
    # what actually shipped: data.wire='u8' falls back to the host wire
    # when the native u8 path is refused — the artifact must say which
    # wire the measured number rode (mislabeling is worse than fallback).
    # The loader's image_dtype is the receipt; tf.data fallbacks carry no
    # attribute, so the config's resolved host dtype stands in.
    from distributed_vgg_f_tpu.data.dtypes import resolve_wire_dtype
    shipped_dtype = getattr(
        host_ds, "image_dtype",
        resolve_wire_dtype(data_cfg.wire, data_cfg.image_dtype))
    actual_wire = ("u8" if shipped_dtype == "uint8"
                   else "host_bf16" if shipped_dtype == "bfloat16"
                   else "host_f32")

    def one_rep(state, *, warmup: int):
        """One full measurement triple (e2e, device-only, host-alone) on a
        fresh prefetch worker around the shared host stream. Every host-
        sensitive metric is repeated `--repeats` times and aggregated
        min-of-N-time: a single window on a shared host cannot distinguish
        a regression from a busy neighbor."""
        ds = maybe_prefetch(host_ds, trainer.mesh, buffer_size=2)
        # warmup: compile (first rep) + fill prefetch (every rep)
        st, metrics = state, None
        for _ in range(max(1, warmup)):
            st, metrics = trainer.train_step(st, next(ds), rng)
        float(jax.device_get(metrics["loss"]))

        # NOTE: up to ~2 prefetched + ~2 tf.data-internal batches were
        # produced before t0, so the measured rate reads high by <=
        # ~4/steps — the default step count keeps that bias under ~8%;
        # raise --steps to shrink it.
        t0 = time.monotonic()
        last_batch = None
        for _ in range(args.steps):
            last_batch = next(ds)
            st, metrics = trainer.train_step(st, last_batch, rng)
        float(jax.device_get(metrics["loss"]))
        e2e_elapsed = time.monotonic() - t0

        # Stop the prefetch worker: it must not keep decoding in the
        # background (stealing host CPU, racing the host-alone loop on the
        # same iterator) while the device-only and host-only phases run.
        if hasattr(ds, "close"):
            ds.close()

        # device-only on the final resident batch — same shapes, no host
        for _ in range(2):
            st, metrics = trainer.train_step(st, last_batch, rng)
        float(jax.device_get(metrics["loss"]))
        t0 = time.monotonic()
        for _ in range(args.steps):
            st, metrics = trainer.train_step(st, last_batch, rng)
        float(jax.device_get(metrics["loss"]))
        dev_elapsed = time.monotonic() - t0

        # host pipeline alone (decode+augment+batch, no device work).
        # tf.data's internal prefetch/AUTOTUNE workers kept producing during
        # the untimed device-only phase above; drain those pre-decoded
        # batches so t0 starts against a cold buffer (residual bias from
        # mid-flight work is < 1/steps).
        for _ in range(4):
            next(host_ds)
        t0 = time.monotonic()
        for _ in range(args.steps):
            next(host_ds)
        host_elapsed = time.monotonic() - t0
        return st, (e2e_elapsed, dev_elapsed, host_elapsed)

    reps = []
    for i in range(max(1, args.repeats)):
        state, triple = one_rep(state, warmup=args.warmup if i == 0 else 2)
        reps.append(triple)

    n_img = batch * args.steps
    e2e_per_chip = n_img / min(r[0] for r in reps) / num_chips
    dev_per_chip = n_img / min(r[1] for r in reps) / num_chips
    host_per_sec = n_img / min(r[2] for r in reps)
    # stall from the SAME rep (best e2e window), not a cross-rep mix
    best = min(reps, key=lambda r: r[0])
    stall = max(0.0, 1.0 - best[1] / best[0])
    extra = {
        "device_only_images_per_sec_per_chip": round(dev_per_chip, 2),
        "host_pipeline_images_per_sec": round(host_per_sec, 2),
        "infeed_stall_fraction": round(stall, 4),
        "host_vcpus": os.cpu_count(),
        "host_pipeline": actual_host_pipeline,
        "wire": actual_wire,
    }
    if args.repeats > 1:
        import statistics
        med = statistics.median(n_img / r[0] / num_chips for r in reps)
        extra["repeats"] = args.repeats
        extra["median"] = round(med, 2)
        extra["spread"] = round((e2e_per_chip - min(
            n_img / r[0] / num_chips for r in reps)) / med, 4)
        extra["host_pipeline_median_images_per_sec"] = round(
            statistics.median(n_img / r[2] for r in reps), 2)
    if model_extra:
        extra["model_extra"] = model_extra
    metric = f"{args.model}_e2e_imagenet_images_per_sec_per_chip"
    _emit(metric, e2e_per_chip, device,
          update_baseline=args.update_baseline, extra=extra)


def main() -> None:
    from distributed_vgg_f_tpu.utils.compile_cache import enable_compile_cache
    enable_compile_cache()

    parser = argparse.ArgumentParser()
    parser.add_argument("--batch-size", type=int, default=None,
                        help="per-chip batch (default: 2048 device bench, "
                             "256 pipeline bench)")
    parser.add_argument("--image-size", type=int, default=224)
    parser.add_argument("--model", default="vggf")
    parser.add_argument("--model-extra", action="append", default=[],
                        metavar="KEY=VALUE",
                        help="model.extra entries for the benched config, "
                        "e.g. --model-extra attention_layout=flash")
    parser.add_argument("--steps", type=int, default=None)
    parser.add_argument("--warmup", type=int, default=None)
    parser.add_argument("--repeats", type=int, default=None,
                        help="independent timed windows; the reported value "
                             "is the best window (min total time) with "
                             "median/spread recorded. Default: 3 for the "
                             "host-sensitive --pipeline imagenet bench, 1 "
                             "for the device bench")
    parser.add_argument("--pipeline", choices=("none", "imagenet"),
                        default="none",
                        help="'imagenet': end-to-end bench through the real "
                             "tf.data JPEG path on locally generated fake "
                             "TFRecords")
    parser.add_argument("--data-dir", default="/tmp/dvggf_bench_imagenet",
                        help="fake-TFRecord cache dir for --pipeline imagenet")
    parser.add_argument("--host-pipeline", choices=("native", "tfdata"),
                        default="native",
                        help="host decode path for --pipeline imagenet: the "
                             "production default (native TFRecord index + "
                             "libjpeg) or the tf.data fallback")
    parser.add_argument("--wire", choices=("auto", "host_f32", "host_bf16",
                                           "u8"),
                        default="auto",
                        help="--pipeline imagenet ingest wire (data.wire): "
                             "'u8' ships raw uint8 pixels and finishes "
                             "normalize/cast/space-to-depth on device "
                             "(data/device_ingest.py); the emitted artifact "
                             "records the wire that ACTUALLY ran (u8 falls "
                             "back to the host wire when refused)")
    parser.add_argument("--num-files", type=int, default=8)
    parser.add_argument("--per-file", type=int, default=256)
    parser.add_argument("--raw-input", action="store_true",
                        help="device bench: feed (S, S, 3) images instead of "
                             "the space-to-depth packed layout VGG-F "
                             "defaults to")
    parser.add_argument("--update-baseline", action="store_true",
                        help="freeze this run's value into "
                             "benchmarks/baseline.json")
    parser.add_argument("--set", action="append", default=[],
                        metavar="KEY=VALUE",
                        help="dotted config override applied to the bench "
                             "trainer (config.apply_overrides semantics), "
                             "e.g. --set data.augment.enabled=true or "
                             "--set mesh.shard_opt_state=false")
    args = parser.parse_args()

    if args.pipeline == "imagenet":
        args.batch_size = args.batch_size or 256
        args.steps = args.steps if args.steps is not None else 48
        args.warmup = args.warmup if args.warmup is not None else 2
        args.repeats = args.repeats if args.repeats is not None else 3
        metric = f"{args.model}_e2e_imagenet_images_per_sec_per_chip"
        bench_fn = run_pipeline_bench
    else:
        # 2048/chip measured fastest on v5e: 512 → 19.6k, 1024 → 20.0k,
        # 2048 → 20.9k, 3072 → 20.9k, 4096 → 20.2k img/s/chip (idle host).
        args.batch_size = args.batch_size or 2048
        args.steps = args.steps if args.steps is not None else 30
        args.warmup = args.warmup if args.warmup is not None else 5
        args.repeats = args.repeats if args.repeats is not None else 1
        metric = f"{args.model}_train_images_per_sec_per_chip"
        bench_fn = run_device_bench

    # Config validation fails fast (~1 s), before any device work: a
    # typo'd --model-extra found after the first compile would cost a chip
    # call. Constructing the Flax module validates the model name AND the
    # extra KEYS; the jax.eval_shape pass traces the full init abstractly,
    # so invalid VALUES that only raise inside __call__ (e.g.
    # attention_layout='flashh') are caught here too.
    try:
        import jax

        from distributed_vgg_f_tpu.config import ModelConfig
        from distributed_vgg_f_tpu.models import build_model
        model = build_model(ModelConfig(name=args.model, num_classes=1000,
                                        compute_dtype="bfloat16",
                                        extra=_parsed_model_extra(args)))
        size = args.image_size

        def _abstract_init():
            import jax.numpy as jnp
            return model.init(jax.random.key(0),
                              jnp.zeros((1, size, size, 3), jnp.float32),
                              train=False)

        jax.eval_shape(_abstract_init)
    except (SystemExit, KeyError, TypeError, ValueError) as e:
        _emit_failure(metric, {"error": "bad_config",
                               "detail": f"{type(e).__name__}: {e}"[:400]})
        sys.exit(1)

    # The run is this process, on the device JAX finds — and that device
    # must be the chip, unless the CPU was asked for by name.
    from distributed_vgg_f_tpu.utils.device import (
        NoAcceleratorError, require_accelerator)
    try:
        device = require_accelerator()
    except NoAcceleratorError as e:
        _emit_failure(metric, {"error": "no_accelerator", "detail": str(e)})
        sys.exit(1)

    try:
        bench_fn(args, device)
    except KeyboardInterrupt:
        raise
    except BaseException as e:  # incl. SystemExit from deep libs
        _emit_failure(metric, {"error": "bench_failed", **device,
                               "detail": f"{type(e).__name__}: {e}"[:400]})
        sys.exit(1)


if __name__ == "__main__":
    main()
