"""Quickest proof that the system still starts on the chip.

    python chip_smoke.py             # one chip: train, flagship step, serve
    python chip_smoke.py --chips 4   # four chips: the data-parallel mesh only

One process, no child that needs the chip. Drives the trainer
(`cli.main`, the body of train.py) and the predict server
(`serve_from_trainer`, what `--mode serve` calls) at VGG-F's full width —
224x224, 1000 classes, global batch 256 — with random weights from the
config's seed, and checks what comes out by the repo's own means.

The LAST line of stdout is one JSON object,
`{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}`;
everything else worth knowing is on earlier lines. Any failed phase, or a
platform that is not `tpu`, gives `"ok": false` and a non-zero exit. It
writes only under --out and the compile cache (and native/*.so, which the
decoder builds from native/*.cc on first use).
"""

from __future__ import annotations

import argparse
import json
import logging
import os
import shutil
import sys
import threading
import time
import traceback
import urllib.request

REPO = os.path.dirname(os.path.abspath(__file__))

#: |loss_4dev - loss_1dev| <= LOSS_RTOL * |loss_1dev|, step for step. The
#: model computes in bf16 (8 bits of mantissa, ~4e-3 per rounding) and the
#: four-device step sums gradients in another order.
LOSS_RTOL = 2e-2
#: Served probabilities vs one offline forward over all the pixels as a
#: single batch: |dp| <= PROB_RTOL * p + PROB_ATOL. Another batch geometry
#: is another bf16 summation order in the logits.
PROB_RTOL = 2e-2
PROB_ATOL = 1e-6


def say(phase: str, **facts) -> None:
    print(f"[smoke:{phase}] {json.dumps(facts, default=str)}", flush=True)


def check(cond: bool, what: str) -> None:
    if not cond:
        raise AssertionError(what)


def cli_args(preset: str, ckpt_dir: str, sets=()) -> list:
    """The argv both `cli.main` and `parse_cli` take."""
    return ["--config", preset, "--set", f"train.checkpoint_dir={ckpt_dir}",
            *[arg for item in sets for arg in ("--set", item)]]


class _Warnings(logging.Handler):
    """Collects the package's WARNING+ log records: every ingest fallback
    (u8 wire refused, native decoder or grain replaced) announces itself
    there, and in the flagship phase a fallback is a failure."""

    def __init__(self):
        super().__init__(logging.WARNING)
        self.messages: list = []

    def emit(self, record):
        self.messages.append(record.getMessage())


# ------------------------------------------------------------------ device

def phase_device() -> dict:
    import jax
    import jaxlib
    from importlib import metadata

    from distributed_vgg_f_tpu.utils.compile_cache import (
        cache_entries, enable_compile_cache)
    cache_dir = enable_compile_cache()
    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    try:
        libtpu = metadata.version("libtpu")
    except metadata.PackageNotFoundError:
        libtpu = None
    say("device", **device, jax=jax.__version__, jaxlib=jaxlib.__version__,
        libtpu=libtpu, compile_cache_dir=cache_dir,
        cache_entries_before=cache_entries(cache_dir))
    return {"device": device, "cache_dir": cache_dir}


def phase_sync() -> None:
    """Does `jax.block_until_ready` wait for the device? Dispatch returns
    at once; if blocking waits, it takes the device time and a value fetch
    after it finds the result already there."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def work(x):
        y = jax.lax.fori_loop(0, 400, lambda _, a: (a @ x) * 2.0 ** -12, x)
        return y[:8, :8].astype(jnp.float32)

    x = jnp.ones((4096, 4096), jnp.bfloat16)
    jax.device_get(work(x))  # compile + warm
    t0 = time.perf_counter()
    out = work(x)
    t1 = time.perf_counter()
    jax.block_until_ready(out)
    t2 = time.perf_counter()
    jax.device_get(out)
    t3 = time.perf_counter()
    dispatch, block, fetch = t1 - t0, t2 - t1, t3 - t2
    synchronises = block > 10 * dispatch and fetch < 0.25 * block
    say("sync", dispatch_s=round(dispatch, 6), block_until_ready_s=round(
        block, 6), fetch_after_block_s=round(fetch, 6),
        block_until_ready_synchronises=synchronises)
    check(synchronises, "jax.block_until_ready did not wait for the device")


# ------------------------------------------------------------------- train

def _run_cli(preset: str, ckpt_dir: str, steps: int, sets=()) -> list:
    """`python train.py --config <preset> --set ...` in this process;
    returns the run's metrics.jsonl records after schema validation."""
    from distributed_vgg_f_tpu import cli
    from distributed_vgg_f_tpu.telemetry import schema
    cli.main(cli_args(preset, ckpt_dir, [f"train.steps={steps}",
                                         "train.log_every=1", *sets]))
    path = os.path.join(ckpt_dir, "metrics.jsonl")
    errors = schema.validate_metrics_jsonl(path)
    check(not errors, f"{path} fails the metrics schema: {errors[:3]}")
    with open(path) as f:
        return [json.loads(line) for line in f if line.strip()]


def _check_run(records: list, steps: int, platform: str) -> dict:
    """The [start] record names the platform and every loss is finite.
    Wall seconds per logged step come from the run's own records: with
    log_every=1 each window is one step and ends in a device_get of its
    metrics (a real sync), but also carries the per-step log and
    checkpoint bookkeeping — so the first is compile, the rest are an
    upper bound on a step, not a device time."""
    import math
    import statistics
    start = next(r for r in records if r["event"] == "start")
    check(start["platform"] == platform,
          f"[start] says platform={start['platform']!r}, want {platform!r}")
    train = [r for r in records if r["event"] == "train"]
    check([r["step"] for r in train] == list(range(1, steps + 1)),
          f"train records cover steps {[r['step'] for r in train]}")
    losses = [r["loss"] for r in train]
    check(all(math.isfinite(x) for x in losses), f"non-finite loss: {losses}")
    secs = [1.0 / r["steps_per_sec"] for r in train]
    return {"start": start, "losses": [round(x, 4) for x in losses],
            "first_step_s": round(secs[0], 3),
            "logged_step_wall_s": round(statistics.median(secs[1:]), 4)}


def phase_train(out: str, platform: str, steps: int = 6, sets=()) -> str:
    """Preset vggf_synthetic as it ships, a checkpoint at the end; then the
    checkpoint restored through the Trainer: its arrays must sit on the
    accelerator, and a few more steps on a resident batch, timed around
    `block_until_ready`, give the steady step seconds."""
    import jax

    from distributed_vgg_f_tpu.config import parse_cli
    from distributed_vgg_f_tpu.train.trainer import Trainer
    ckpt = os.path.join(out, "train_ckpt")
    facts = _check_run(_run_cli("vggf_synthetic", ckpt, steps, sets),
                       steps, platform)
    start = facts.pop("start")
    say("train", preset="vggf_synthetic", steps=steps,
        num_devices=start["num_devices"], device_kinds=start["device_kinds"],
        **facts)

    trainer = Trainer(parse_cli(cli_args("vggf_synthetic", ckpt, sets)))
    state = trainer.restore_or_init()
    check(int(jax.device_get(state.step)) == steps,
          f"restored step {int(jax.device_get(state.step))}, want {steps}")
    leaves = jax.tree_util.tree_leaves(state)
    platforms = sorted({d.platform for x in leaves for d in x.devices()})
    check(platforms == [platform],
          f"train state lives on {platforms}, want [{platform!r}]")

    batch = trainer.shard(next(trainer.make_dataset("train")))
    rng = trainer.base_rng()
    state, metrics = trainer.train_step(state, batch, rng)  # compile / cache
    jax.block_until_ready(metrics)
    n = 10
    t0 = time.perf_counter()
    for _ in range(n):
        state, metrics = trainer.train_step(state, batch, rng)
    jax.block_until_ready((state, metrics))
    steady = (time.perf_counter() - t0) / n
    say("train_state", arrays=len(leaves), on=platforms,
        bytes=sum(x.nbytes for x in leaves),
        image_shape=list(batch["image"].shape),
        steady_step_s=round(steady, 5), timed_steps=n,
        loss=float(jax.device_get(metrics["loss"])))
    return ckpt


# ---------------------------------------------------------------- flagship

def phase_flagship(out: str, platform: str, steps: int = 3, sets=()) -> None:
    """Preset vggf_imagenet_dp — u8 wire, on-device normalise and
    space-to-depth, fused flip+mixup, the ZeRO-2 mesh flags — over TFRecords
    generated from a seed. Receipts that nothing fell back: the decoder
    built and loaded, it decoded the images, no ingest warning was logged."""
    from bench import _ensure_fake_imagenet
    from distributed_vgg_f_tpu.data import native_jpeg
    data_dir = os.path.join(out, "imagenet_fake")
    _ensure_fake_imagenet(data_dir, num_files=2, per_file=256)

    t0 = time.perf_counter()
    check(native_jpeg.load_native_jpeg() is not None,
          "native JPEG decoder did not build/load from native/*.cc")
    check(native_jpeg.wire_u8_enabled(), "native decoder refuses the u8 wire")
    native_jpeg.decode_stats(reset=True)
    say("native", decoder_ready_s=round(time.perf_counter() - t0, 2))

    warnings = _Warnings()
    log = logging.getLogger("distributed_vgg_f_tpu")
    log.addHandler(warnings)
    try:
        records = _run_cli("vggf_imagenet_dp",
                           os.path.join(out, "flagship_ckpt"), steps,
                           [f"data.data_dir={data_dir}", *sets])
    finally:
        log.removeHandler(warnings)
    facts = _check_run(records, steps, platform)
    start = facts.pop("start")
    stats = native_jpeg.decode_stats()
    batch = records[-1].get("iterator_state", {})
    say("flagship", preset="vggf_imagenet_dp", steps=steps,
        wire=start["wire"], augment=start["augment"],
        comm=[r for r in records if r["event"] == "train"][-1].get("comm"),
        decoded_images=stats["images"], live_wire=batch.get("wire"),
        ingest_warnings=warnings.messages, **facts)
    check(start["wire"] == "u8" and start["augment"] is True,
          f"[start] wire={start['wire']!r} augment={start['augment']!r}")
    check(not warnings.messages,
          f"ingest fell back: {warnings.messages}")
    check(stats["images"] > 0, "the native decoder decoded no image")


# ------------------------------------------------------------------- serve

def _post(port: int, model: str, image) -> dict:
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/v1/predict/{model}?k=100000",
        data=image.tobytes(), method="POST")
    with urllib.request.urlopen(req, timeout=120) as r:
        check(r.status == 200, f"HTTP {r.status}")
        return json.loads(r.read())


def _probs(body: dict, num_classes: int):
    import numpy as np
    row = np.zeros((num_classes,), np.float64)
    for rec in body["top_k"]:
        row[rec["class"]] = rec["prob"]
    return row


def phase_serve(out: str, ckpt: str, sets=()) -> None:
    """`--mode serve` from the train phase's checkpoint: port 0, the default
    bucket ladder, u8 payloads over HTTP from threads of this process."""
    import io

    import jax
    import numpy as np

    from distributed_vgg_f_tpu.config import parse_cli
    from distributed_vgg_f_tpu.data.device_ingest import make_device_finish
    from distributed_vgg_f_tpu.serving.server import serve_from_trainer
    from distributed_vgg_f_tpu.train.predict import (
        build_forward, restore_predict_params, run_predict)
    from distributed_vgg_f_tpu.train.trainer import Trainer

    cfg = parse_cli(cli_args("vggf_synthetic", ckpt,
                             ["serving.enabled=true", *sets]))
    size, classes = cfg.data.image_size, cfg.model.num_classes
    name = cfg.model.name
    trainer = Trainer(cfg)
    dev = jax.devices()[0]
    mem0 = dev.memory_stats() or {}
    t0 = time.perf_counter()
    server = serve_from_trainer(trainer)
    warmup_s = time.perf_counter() - t0
    try:
        engine = server.engine(name)
        mem1 = dev.memory_stats() or {}
        with urllib.request.urlopen(
                f"http://127.0.0.1:{server.port}/v1/models", timeout=30) as r:
            row = json.loads(r.read())["models"][name]
        compile_s = row["compile_s"]
        check(sorted(int(b) for b in compile_s) == list(engine.buckets)
              and all(s > 0 for s in compile_s.values()),
              f"/v1/models compile_s {compile_s} vs buckets {engine.buckets}")
        say("serve_start", endpoint=server.endpoint, image_size=size,
            num_classes=classes, buckets=list(engine.buckets),
            warmup_s=round(warmup_s, 2), compile_s=compile_s,
            hbm_estimate_bytes=engine.hbm_estimate_bytes,
            device_bytes_in_use_before=mem0.get("bytes_in_use"),
            device_bytes_in_use_after=mem1.get("bytes_in_use"),
            device_peak_bytes_in_use=mem1.get("peak_bytes_in_use"))

        images = np.random.default_rng(cfg.train.seed).integers(
            0, 256, (3 + 16, size, size, 3)).astype(np.uint8)
        singles, burst = images[:3], images[3:]

        # batch of one, sequentially: each flushes alone through bucket 1
        served = [_post(server.port, name, img) for img in singles]
        check(all(b["bucket"] == 1 for b in served),
              f"single requests rode buckets {[b['bucket'] for b in served]}")
        # bitwise: offline predict's array path runs the same pixels through
        # its own engine's bucket-1 executable
        files = []
        for i, img in enumerate(singles):
            files.append(os.path.join(out, f"request_{i}.npy"))
            np.save(files[-1], img)
        offline = run_predict(trainer, files, top_k=classes, batch=1,
                              stream=io.StringIO())
        for rec, body in zip(offline, served):
            check([r["class"] for r in rec["top_k"]]
                  == [r["class"] for r in body["top_k"]]
                  and [r["prob"] for r in rec["top_k"]]
                  == [r["prob"] for r in body["top_k"]],
                  "served probabilities differ bitwise from offline predict "
                  "through the same bucket")

        # a burst from threads: requests queue behind a running flush and
        # leave together in a larger bucket
        bodies: list = [None] * len(burst)

        def fire(i):
            bodies[i] = _post(server.port, name, burst[i])

        threads = [threading.Thread(target=fire, args=(i,))
                   for i in range(len(burst))]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=300)
        check(all(b is not None for b in bodies), "a burst request failed")
        burst_buckets = sorted({b["bucket"] for b in bodies})
        check(burst_buckets[-1] > 1,
              f"the burst never filled a bucket above 1: {burst_buckets}")

        # tolerance: one plain jitted build_forward over ALL the pixels as
        # a single batch of 19 — no buckets, no padding, no server
        params, batch_stats = restore_predict_params(trainer)
        finish = make_device_finish(cfg.data.mean_rgb, cfg.data.stddev_rgb,
                                    image_dtype=cfg.data.image_dtype)
        reference = np.asarray(jax.jit(build_forward(
            trainer.model, params, batch_stats, finish))(images), np.float64)
        got = np.stack([_probs(b, classes) for b in served + bodies])
        check(got.shape == reference.shape and np.isfinite(got).all(),
              "served probabilities malformed")
        check(np.allclose(got.sum(axis=1), 1.0, atol=1e-3),
              "served probabilities do not sum to 1")
        err = np.abs(got - reference)
        say("serve", requests=len(got), all_http_200=True,
            bitwise_equal_to_offline_predict=len(served),
            burst_buckets=burst_buckets,
            max_abs_prob_err_vs_offline_forward=float(err.max()),
            max_rel_prob_err=float((err / reference).max()),
            prob_rtol=PROB_RTOL, prob_atol=PROB_ATOL,
            max_prob=float(reference.max()),
            latency_ms=[round(b["latency_ms"], 1) for b in served])
        check(bool((err <= PROB_RTOL * reference + PROB_ATOL).all()),
              f"served vs offline forward: max |dp| {err.max()} outside "
              f"rtol {PROB_RTOL} atol {PROB_ATOL}")
    finally:
        server.close()
        trainer.export_telemetry()


# -------------------------------------------------------------- four chips

def phase_mesh4(sets=(), steps: int = 3) -> None:
    """The path across chips: vggf_synthetic-width steps on a four-device
    mesh with the flagship's mesh flags (ZeRO-2, 4 MB buckets), against the
    same seed and global batch on a one-device mesh; then one ZeRO-3 pair.
    Dropout is off in both: its masks are folded per replica, so they would
    differ between the meshes by design."""
    import io

    import jax
    import numpy as np

    from distributed_vgg_f_tpu.config import apply_overrides, get_config
    from distributed_vgg_f_tpu.parallel.mesh import MeshSpec, build_mesh
    from distributed_vgg_f_tpu.train.trainer import Trainer
    from distributed_vgg_f_tpu.utils.logging import MetricLogger

    flagship = get_config("vggf_imagenet_dp").mesh
    base = apply_overrides(get_config("vggf_synthetic"), {
        "model.dropout_rate": 0.0,
        "mesh.shard_opt_state": flagship.shard_opt_state,
        "mesh.shard_gradients": flagship.shard_gradients,
        "mesh.comm_bucket_mb": flagship.comm_bucket_mb,
        **dict(s.split("=", 1) for s in sets)})
    devices = jax.devices()[:4]
    meshes = {4: build_mesh(MeshSpec(("data",), (4,)), devices),
              1: build_mesh(MeshSpec(("data",), (1,)), devices[:1])}

    def run(cfg, n_dev, n_steps):
        tr = Trainer(cfg, mesh=meshes[n_dev],
                     logger=MetricLogger(stream=io.StringIO()))
        ds = tr.make_dataset("train")
        state, rng = tr.init_state(), tr.base_rng()
        batch = tr.shard(next(ds))
        lowered = tr.train_step.lower(state, batch, rng)
        facts = {"tr": tr, "lowered": lowered.as_text(), "losses": []}
        for i in range(n_steps):
            state, metrics = tr.train_step(state, batch, rng)
            facts["losses"].append(float(jax.device_get(metrics["loss"])))
            if i + 1 < n_steps:
                batch = tr.shard(next(ds))
        facts["state"], facts["batch"] = state, batch
        if n_dev > 1:
            facts["compiled"] = lowered.compile().as_text()
        return facts

    def quarter_on_each(x, what):
        check(len(x.sharding.device_set) == 4,
              f"{what} sits on {len(x.sharding.device_set)} device(s)")
        check(sorted(s.device.id for s in x.addressable_shards)
              == sorted(d.id for d in devices)
              and all(s.data.shape[0] * 4 == x.shape[0]
                      for s in x.addressable_shards),
              f"{what}: each device should hold a quarter of {x.shape}")

    def compare(tag, cfg, n_steps):
        four, one = run(cfg, 4, n_steps), run(cfg, 1, n_steps)
        tr = four["tr"]
        quarter_on_each(four["batch"]["image"], "the batch")
        flat = [x for x in jax.tree_util.tree_leaves(four["state"].opt_state)
                if x.ndim == 1 and x.shape[0] == tr.exchange.total_padded]
        check(bool(flat), "no flat optimiser vector in the ZeRO state")
        for x in flat:
            quarter_on_each(x, "a flat optimiser shard")
        if tr.zero3:
            quarter_on_each(four["state"].params, "the ZeRO-3 flat params")
        asked = {k: four["lowered"].count(k)
                 for k in ("reduce_scatter", "all_gather")}
        kept = {k: four["compiled"].count(f" {k}(") + four["compiled"].count(
            f" {k}-start(") for k in ("reduce-scatter", "all-gather",
                                      "all-reduce")}
        rel = [abs(a - b) / abs(b)
               for a, b in zip(four["losses"], one["losses"])]
        say(tag, sharding=tr.train_step.comm_meta.get("sharding"),
            global_batch=cfg.data.global_batch_size,
            losses_4dev=four["losses"], losses_1dev=one["losses"],
            max_rel_loss_diff=max(rel), loss_rtol=LOSS_RTOL,
            flat_opt_shards=len(flat),
            shard_len=int(flat[0].addressable_shards[0].data.shape[0]),
            program_asks_for=asked, compiler_kept=kept)
        check(all(np.isfinite(four["losses"])), "non-finite 4-device loss")
        check(max(rel) <= LOSS_RTOL,
              f"4-device and 1-device losses differ by {max(rel)}")
        check(asked["reduce_scatter"] > 0 and asked["all_gather"] > 0,
              f"the lowered step asks for {asked}")
        check(sum(kept.values()) > 0,
              f"the compiled step holds no cross-device collective: {kept}")

    compare("mesh4_zero2", base, steps)
    compare("mesh4_zero3",
            apply_overrides(base, {"mesh.shard_params": True}), 2)


# -------------------------------------------------------------------- main

def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--chips", type=int, choices=(1, 4), default=1,
                        help="4: run only the four-device mesh phase and "
                             "its one-device comparison")
    parser.add_argument("--out", default=os.path.join(REPO, ".chip_smoke_out"),
                        help="scratch directory (wiped at start): "
                             "checkpoints, generated TFRecords, logs")
    args = parser.parse_args(argv)

    last = {"ok": False, "device": None}
    phase = "device"
    try:
        found = phase_device()
        last["device"] = found["device"]
        platform = found["device"]["platform"]
        check(platform == "tpu",
              f"platform is {platform!r}, not 'tpu' — this run meant the "
              "chip and did not get it")
        check(found["device"]["count"] >= args.chips,
              f"--chips {args.chips} but JAX sees "
              f"{found['device']['count']} device(s)")
        shutil.rmtree(args.out, ignore_errors=True)
        os.makedirs(args.out)
        phase = "sync"
        phase_sync()
        if args.chips == 4:
            phase = "mesh4"
            phase_mesh4()
        else:
            phase = "train"
            ckpt = phase_train(args.out, platform)
            phase = "flagship"
            phase_flagship(args.out, platform)
            phase = "serve"
            phase_serve(args.out, ckpt)
        from distributed_vgg_f_tpu.utils.compile_cache import cache_entries
        say("cache", compile_cache_dir=found["cache_dir"],
            cache_entries_after=cache_entries(found["cache_dir"]))
        last["ok"] = True
    except BaseException as e:  # noqa: BLE001 — incl. SystemExit from cli
        traceback.print_exc()
        say("failed", in_phase=phase, error=f"{type(e).__name__}: {e}"[:2000])
        if isinstance(e, KeyboardInterrupt):
            raise
    finally:
        sys.stderr.flush()
        print(json.dumps(last), flush=True)
    return 0 if last["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
