"""Driver for training cells: the program's `Trainer`, driven as data says.

Mode `step`: one u8 batch made from `--seed`, placed once with
`trainer.shard`, then `trainer.train_step` back to back for the window.
The loop dispatches ahead (at most `IN_FLIGHT` steps), fetches the loss
every `train.log_every` steps as the trainer's own loop does, and ends in
a fetch of the last step's loss.

Set-up builds one object, the trainer's compiled step with its state,
drives it through its first `CHECK_STEPS` steps, and hands the same object
to the window. Those steps are the warm-up (the first compiles or reads the
cache) and what `correct` compares: once the window has closed, the peak
memory has been read and the state is freed, the plain reference follows
them from the same weights and batch.
"""

from __future__ import annotations

import collections
import math
import os
import sys
import time

import numpy as np

from chipbench import compare, inputs
from chipbench.reference import step as ref_step

CHECK_STEPS = 3
IN_FLIGHT = 8
TRACE_STEPS = 10


def _say(msg: str) -> None:
    print(f"[chipbench] {msg}", file=sys.stderr, flush=True)


def recipe_of(cfg, config: dict) -> dict:
    """The configuration file's recipe, completed with the cell's batch,
    after checking that the program's preset states the same numbers."""
    recipe = dict(config["recipe"])
    stated = {
        "base_lr": cfg.optim.base_lr,
        "reference_batch": cfg.optim.reference_batch_size,
        "momentum": cfg.optim.momentum,
        "weight_decay": cfg.optim.weight_decay,
        "warmup_epochs": cfg.optim.warmup_epochs,
        "decay_epochs": list(cfg.optim.decay_epochs),
        "decay_factor": cfg.optim.decay_factor,
        "train_examples": cfg.data.num_train_examples,
        "mean_rgb": list(cfg.data.mean_rgb),
        "stddev_rgb": list(cfg.data.stddev_rgb),
        "hflip": bool(cfg.data.augment.enabled and cfg.data.augment.hflip),
        "mixup_alpha": (cfg.data.augment.mixup_alpha
                        if cfg.data.augment.enabled else 0.0),
        "dropout_rate": cfg.model.dropout_rate,
        "image_size": cfg.data.image_size,
        "num_classes": cfg.model.num_classes,
        "rng_impl": cfg.train.dropout_rng_impl,
    }
    differ = {k: (recipe.get(k), v) for k, v in stated.items()
              if recipe.get(k) != v}
    if differ:
        raise ValueError(f"configuration file and preset {cfg.name!r} "
                         f"disagree (file, preset): {differ}")
    aug = cfg.data.augment
    if aug.enabled and (aug.crop_jitter or aug.cutmix_alpha or aug.rand_ops):
        raise NotImplementedError("the plain reference knows flip and mixup")
    if cfg.optim.schedule != "step" or cfg.optim.nesterov \
            or cfg.optim.grad_clip_norm or cfg.train.grad_accum_steps != 1 \
            or cfg.train.ema_decay:
        raise NotImplementedError("the plain reference knows SGD with "
                                  "momentum on the step schedule")
    recipe["global_batch"] = cfg.data.global_batch_size
    return recipe


def build_trainer(ctx):
    import jax

    from distributed_vgg_f_tpu.config import apply_overrides, get_config
    from distributed_vgg_f_tpu.parallel.mesh import MeshSpec, build_mesh
    from distributed_vgg_f_tpu.train.trainer import Trainer
    from distributed_vgg_f_tpu.utils.compile_cache import enable_compile_cache
    from distributed_vgg_f_tpu.utils.logging import MetricLogger

    cache_dir = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    cell, config = ctx.cell, ctx.config
    chips = cell["chips"]
    overrides = {**config.get("overrides", {}), **cell.get("overrides", {}),
                 "data.global_batch_size": cell["batch_per_chip"] * chips,
                 "train.seed": ctx.seed % (2 ** 31 - 1),
                 "mesh.num_data": chips}
    cfg = apply_overrides(get_config(config["preset"]), overrides)
    mesh = build_mesh(MeshSpec((cfg.mesh.data_axis,), (chips,)),
                      jax.devices()[:chips])
    trainer = Trainer(cfg, mesh=mesh, logger=MetricLogger(stream=sys.stderr))
    return trainer, cfg, cache_dir


class CompileCounter:
    """Counts programs XLA compiled while `armed`: JAX's compile events
    less those it served from the persistent cache (each such read raises
    a compile event and a retrieval event)."""

    def __init__(self):
        import jax
        self.events, self.reads, self.armed = 0, 0, False
        jax.monitoring.register_event_duration_secs_listener(self._on)

    @property
    def count(self) -> int:
        return max(0, self.events - self.reads)

    def _on(self, event: str, duration: float, **kw) -> None:
        if not self.armed:
            return
        if event.endswith("backend_compile_duration"):
            self.events += 1
            _say(f"in the window, compiled or read: {duration:.3f} s {kw}")
        elif event.endswith("cache_retrieval_time_sec"):
            self.reads += 1


def _start_trace(trace_dir: str) -> None:
    """The profiler without its Python tracer: the device's operations and
    the driver's own `TraceAnnotation` spans are what the reduction reads."""
    import jax
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.enable_hlo_proto = False
    jax.profiler.start_trace(trace_dir, profiler_options=options)


def _momentum_tree(trainer, opt_state):
    """SGD's momentum as a tree shaped like the parameters (under ZeRO the
    program keeps it as one flat vector in its bucket layout)."""
    import jax
    is_trace = lambda s: type(s).__name__ == "TraceState"
    traces = [s.trace for s in jax.tree_util.tree_leaves(
        opt_state, is_leaf=is_trace) if is_trace(s)]
    if len(traces) != 1:
        raise ValueError(f"expected one momentum trace, found {len(traces)}")
    if not trainer.zero1:
        return traces[0]
    if trainer._bucket_layout is None:
        raise NotImplementedError("ZeRO without the bucket layout")
    return trainer._bucket_layout.from_global(traces[0])


def _momentum_reader(trainer):
    """`read(opt_state)` -> the first gradient as the optimiser got it (the
    momentum after one step from zero), copied to the host, and its
    per-leaf norms. Blocks: the next step donates the state."""
    import jax
    tree = jax.jit(lambda s: _momentum_tree(trainer, s))
    norms = jax.jit(ref_step.leaf_norms)

    def read(opt_state) -> dict:
        grad = tree(opt_state)
        return {"grad_norms": norms(grad), "first_grad": jax.device_get(grad)}
    return read


def _with_counts(tree, value):
    """Every int32 scalar of an optimiser state (its schedule counts) set
    to `value`, so that a cell may start as a job resumed at that step."""
    import jax
    import jax.numpy as jnp

    def leaf(x):
        if x.ndim == 0 and x.dtype == jnp.int32:
            return jax.device_put(np.int32(value), x.sharding)
        return x
    return jax.tree.map(leaf, tree)


def _device_facts(devices) -> dict:
    """Platform, kind, count, and the peak on the fullest chip: the peak of
    the buffers in use plus what the runtime holds reserved as scratch for
    the loaded programs (`bytes_reserved`; on the TPU a step's temporaries
    live there and `peak_bytes_in_use` alone leaves them out)."""
    stats = [d.memory_stats() or {} for d in devices]
    _say(f"memory_stats of the first device: {stats[0]}")
    held = lambda s: int(s.get("peak_bytes_in_use", 0)) + int(
        s.get("peak_bytes_reserved", s.get("bytes_reserved", 0)))
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices),
            "memory_peak_bytes": max(held(s) for s in stats)}


def run(ctx) -> dict:
    _say(f"imports {time.perf_counter() - ctx.t0:.1f} s")
    trainer, cfg, cache_dir = build_trainer(ctx)
    _say(f"trainer built at {time.perf_counter() - ctx.t0:.1f} s")
    compiles = CompileCounter()
    cell = ctx.cell
    mode = cell["mode"]
    if mode == "step":
        return _run_step(ctx, trainer, cfg, compiles)
    raise ValueError(f"driver train has no mode {mode!r}")


def _seeded_state(trainer, cell: dict, config: dict, seed: int):
    """The trainer's own state with the seed's weights in it, its step and
    schedule counts at the cell's `start_step`; and the state's shapes."""
    import jax

    start_step, init = int(cell.get("start_step", 0)), config.get("init")
    if trainer.zero3:
        raise NotImplementedError("ZeRO-3 keeps no parameter tree")
    state = trainer.init_state()
    shapes = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                          {"params": state.params,
                           "stats": state.batch_stats})
    replicated = state.step.sharding
    word = inputs.seed_word(seed)
    state = state.replace(
        params=jax.jit(lambda w: inputs.make_params(shapes["params"], w, init),
                       out_shardings=replicated)(word),
        step=jax.device_put(np.int32(start_step), replicated),
        opt_state=_with_counts(state.opt_state, start_step))
    norms = jax.jit(lambda p, w: ref_step.leaf_norms(jax.tree.map(
        jax.numpy.subtract, p, inputs.make_params(shapes["params"], w,
                                                  init))))
    return state, shapes, lambda params: norms(params, word)


def first_steps(trainer, cfg, cell: dict, config: dict, seed: int, *,
                fault=None, rng=None) -> dict:
    """State and batch from `seed`, then the first `CHECK_STEPS` steps
    through the trainer's own compiled step. Returns the live objects the
    window goes on with and what the steps gave (`got`)."""
    import jax
    import jax.numpy as jnp

    t_0 = time.perf_counter()
    state, shapes, change = _seeded_state(trainer, cell, config, seed)
    batch = trainer.shard(inputs.make_batch(
        seed, cfg.data.global_batch_size, cfg.data.image_size,
        cfg.model.num_classes))
    if rng is None:
        rng = trainer.base_rng()
    jax.block_until_ready((state, batch))
    t_1 = time.perf_counter()
    momentum = _momentum_reader(trainer)

    step_fn = real = trainer.train_step
    if fault == "state_unchanged":
        step_fn = lambda s, b, r: (s, real(jax.tree.map(jnp.copy, s), b,
                                           r)[1])
    elif fault == "half_batch":
        half = cfg.data.global_batch_size // 2
        step_fn = lambda s, b, r: real(
            s, jax.tree.map(lambda v: jnp.concatenate([v[:half], v[:half]]),
                            b), r)
    elif fault is not None:
        raise ValueError(f"no fault {fault!r} to plant in the program")

    got = {"losses": []}
    for i in range(CHECK_STEPS):
        state, metrics = step_fn(state, batch, rng)
        got["losses"].append(metrics["loss"])
        if i == 0:
            got.update(momentum(state.opt_state))
    got["change_norms"] = change(state.params)
    got = jax.device_get(got)
    _say(f"state and batch {t_1 - t_0:.1f} s, first {CHECK_STEPS} steps "
         f"{time.perf_counter() - t_1:.1f} s")
    return {"state": state, "batch": batch, "rng": rng, "metrics": metrics,
            "step_fn": step_fn, "shapes": shapes, "got": got}


def _run_step(ctx, trainer, cfg, compiles) -> dict:
    import jax

    cell = ctx.cell
    recipe = recipe_of(cfg, ctx.config)
    devices = list(trainer.mesh.devices.flat)

    # ---- set-up: state and batch from the seed, first steps, warm-up
    live = first_steps(trainer, cfg, cell, ctx.config, ctx.seed,
                       fault=ctx.fault)
    state, batch, rng, metrics = (live.pop(k) for k in
                                  ("state", "batch", "rng", "metrics"))
    step_fn, shapes, got = live["step_fn"], live["shapes"], live["got"]
    log_every = max(1, int(cfg.train.log_every))
    setup_s = time.perf_counter() - ctx.t0

    # ---- the window
    annotate = jax.profiler.TraceAnnotation
    pending: collections.deque = collections.deque()
    window_metrics: list = []
    traced = None
    trace_dir = os.path.join(ctx.out_dir, "trace")

    def one_step():
        nonlocal state, metrics
        with annotate("chipbench:dispatch"):
            state, metrics = step_fn(state, batch, rng)
        window_metrics.append(metrics)
        pending.append(metrics["loss"])
        if len(pending) > IN_FLIGHT:
            with annotate("chipbench:device_ahead"):
                jax.block_until_ready(pending.popleft())
        if len(window_metrics) % log_every == 0:
            with annotate("chipbench:logging"):
                float(jax.device_get(metrics["loss"]))

    compiles.armed = True
    t_begin = time.perf_counter()
    while True:
        elapsed = time.perf_counter() - t_begin
        if elapsed >= ctx.seconds:
            break
        if ctx.trace and traced is None and elapsed >= ctx.seconds / 2:
            jax.block_until_ready(metrics)
            _start_trace(trace_dir)
            with annotate("chipbench:traced_window"):
                for _ in range(TRACE_STEPS):
                    one_step()
                with annotate("chipbench:final_sync"):
                    jax.block_until_ready(metrics)
            jax.profiler.stop_trace()
            traced = {"steps": TRACE_STEPS}
            continue
        one_step()
    last_loss = float(jax.device_get(metrics["loss"]))
    window_s = time.perf_counter() - t_begin
    compiles.armed = False

    steps = len(window_metrics)
    device = _device_facts(devices)
    fetched = jax.device_get([(m["loss"], m.get("bad_step", 0.0))
                              for m in window_metrics])
    failed = sum(1 for loss, bad in fetched
                 if not math.isfinite(float(loss)) or float(bad) > 0)
    images = steps * cfg.data.global_batch_size
    _say(f"window: {steps} steps in {window_s:.3f} s, last loss "
         f"{last_loss:.4f}, {failed} failed, set-up {setup_s:.1f} s")

    # ---- the reference, once the program's state is freed
    del state, batch, metrics, window_metrics, pending
    t_ref = time.perf_counter()
    want = follow_reference(ctx.config, cell, cfg, recipe, shapes,
                            ctx.seed)
    gaps = compare.training_gaps(got, want, ctx.config.get("probe_leaf"))
    checks = compare.judge(gaps, cell["limits"])
    checks.append({"name": "compiles_in_window", "value": compiles.count,
                   "limit": 0, "ok": compiles.count == 0, "where": ""})
    _say(f"reference followed in {time.perf_counter() - t_ref:.1f} s")

    chips = len(devices)
    return {
        "attempted": steps, "failed": failed, "checks": checks,
        "device": device,
        "end_to_end": {
            "train_images_per_s": images / window_s / chips,
            "peak_hbm_gib": device["memory_peak_bytes"] / 2 ** 30,
            "setup_s": setup_s},
        "facts": {"trace_dir": trace_dir if traced else None,
                  "traced": traced, "chips": chips,
                  "device_kind": device["kind"], "window_s": window_s,
                  "steps": steps, "recipe": recipe,
                  "model": ctx.config["reference"],
                  "rows_per_step": cfg.data.global_batch_size,
                  "shapes": shapes},
    }


def follow_reference(config: dict, cell: dict, cfg, recipe: dict, shapes,
                     seed: int, **kw) -> dict:
    """The plain reference's first steps from the seed's weights on the
    seed's resident batch (`kw`: its precision `mode` or a planted
    `fault`)."""
    import jax
    import jax.numpy as jnp

    params = jax.jit(lambda w: inputs.make_params(
        shapes["params"], w, config.get("init")))(inputs.seed_word(seed))
    stats = jax.tree_util.tree_map_with_path(
        lambda path, s: (jnp.ones if str(getattr(path[-1], "key", ""))
                         == "var" else jnp.zeros)(s.shape, jnp.float32),
        shapes["stats"])
    batch = inputs.make_batch(seed, cfg.data.global_batch_size,
                              cfg.data.image_size, cfg.model.num_classes)
    batches = [(batch["image"], batch["label"])] * CHECK_STEPS
    key = jax.random.key(seed % (2 ** 31 - 1) + 1, impl=recipe["rng_impl"])
    return ref_step.follow(
        config["reference"], recipe, params, stats, batches, key,
        start_step=int(cell.get("start_step", 0)), steps=CHECK_STEPS,
        replicas=cell["chips"],
        block_rows=int(cell.get("reference_block_rows", 256)), **kw)
