"""Driver for language-model training cells: the program's `Trainer`,
driven as `drivers/train.py` drives it, on tokens.

Mode `step`: one batch of packed int32 tokens made from `--seed` (ids
uniform over the vocabulary rows the configuration holds), placed once with
`trainer.shard`, then `trainer.train_step` back to back for the window: at
most `IN_FLIGHT` steps dispatched ahead, the loss fetched every
`train.log_every` steps and at the end.

Set-up builds the trainer's compiled step, puts the seed's weights into a
state of the trainer's own shape (made in one jitted call: the state the
trainer would initialise is never made, 4.6 GB that the chip has no room
for twice), drives the first `CHECK_STEPS` steps and hands the same
objects to the window. After the window, with the peak read and the state
freed, the plain reference (`reference/lm_step.py`) follows the same steps
from the same weights and batch.

`correct` holds, besides `compare.judge`'s numbers against the cell's
`limits`: `expert_load_diff`, the share of the held experts' assignments
that the program's routing places differently from the reference's (top-k
is discontinuous, and a near-tie flips on bf16 rounding upstream of the
router: summed |program - reference| load over all layers of the checked
steps, over the assignments held); `dropped_assignments` = 0; no
compilation in the window.

Per-layer facts: the trace reduced by this cell's own names
(`chipbench/lm_scopes.json`) as `facts["scopes"]`, the step's needed
operations from `chipbench/lm_counts.py` as `facts["step_ops"]` (routed
experts by the assignments the reference's routing of this batch holds,
attention by its causal half, nothing recomputed), and `facts["lm"]` for
the kernels' roofline readers.
"""

from __future__ import annotations

import collections
import json
import math
import os
import time

import numpy as np

from chipbench import compare, inputs, lm_counts, scope_reduce, trace_reduce
from chipbench.drivers import train as base
from chipbench.reference import lm_step
from chipbench.reference.step import leaf_norms

CHECK_STEPS = base.CHECK_STEPS
_say = base._say

#: the share of held assignments that may sit with another expert than the
#: reference's. Read on the v5e at the cell's size (PERF.md section 6, PR 28):
#: the bf16 program 0.0083-0.0107 over 12 seeds, the fp8 control
#: 0.0246-0.031, `half_batch` 0.062-0.086
LOAD_DIFF_LIMIT = 0.017


def names() -> dict:
    """The names this cell's traces are reduced by."""
    return scope_reduce.declared(os.path.join(
        scope_reduce.ROOT, "chipbench", "lm_scopes.json"))


#: the configuration file's keys that the reference and the counts read
ARCH_KEYS = ("hidden_size", "num_attention_heads", "q_lora_rank",
             "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
             "v_head_dim", "num_experts_per_tok", "moe_intermediate_size",
             "n_shared_experts", "rms_norm_eps", "rope_parameters")


def arch_of(config: dict) -> dict:
    """The architecture as the reference and the counts take it: the
    file's published widths, and the router at its published width (the
    file's own `n_routed_experts` is the experts held)."""
    return {**{k: config[k] for k in ARCH_KEYS},
            "n_routed_experts": config["published"]["n_routed_experts"]}


def recipe_of(cfg, config: dict) -> dict:
    """The configuration file's recipe, after checking that the program's
    preset states the same numbers and every published width."""
    recipe = dict(config["recipe"])
    extra = dict(cfg.model.extra)
    stated = {
        "base_lr": cfg.optim.base_lr,
        "reference_batch": cfg.optim.reference_batch_size,
        "momentum": cfg.optim.momentum,
        "weight_decay": cfg.optim.weight_decay,
        "schedule": cfg.optim.schedule,
        "seq_len": extra["seq_len"],
        "compute_dtype": cfg.model.compute_dtype,
        "first_expert": extra.get("first_expert", 0),
    }
    differ = {k: (recipe.get(k), v) for k, v in stated.items()
              if recipe.get(k) != v}
    held = {"num_hidden_layers": extra["num_hidden_layers"],
            "n_routed_experts": extra.get("experts_held"),
            "vocab_size": cfg.model.num_classes}
    differ.update({k: (config.get(k), v) for k, v in held.items()
                   if config.get(k) != v})
    differ.update({k: (v, extra.get(k)) for k, v in arch_of(config).items()
                   if json.loads(json.dumps(extra.get(k))) != v})
    if differ:
        raise ValueError(f"configuration file and preset {cfg.name!r} "
                         f"disagree (file, preset): {differ}")
    if cfg.optim.nesterov or cfg.optim.grad_clip_norm or cfg.train.ema_decay \
            or cfg.train.grad_accum_steps != 1 or cfg.optim.warmup_epochs:
        raise NotImplementedError("the plain reference knows SGD with "
                                  "momentum at a constant rate")
    recipe["global_batch"] = cfg.data.global_batch_size
    return recipe


def make_tokens(seed: int, rows: int, seq_len: int, vocab_rows: int) -> dict:
    """`rows` sequences of `seq_len + 1` ids, uniform over the rows held."""
    rng = np.random.default_rng([seed, rows, seq_len])
    return {"tokens": rng.integers(0, vocab_rows, (rows, seq_len + 1),
                                   dtype=np.int32)}


def _seeded_state(trainer, config: dict, seed: int):
    """A state of the trainer's own shape and sharding with the seed's
    weights in it and zero momentum; the parameters' shapes; and
    `change(params)`, the per-leaf norms of their distance from the
    seed's weights."""
    import jax
    import jax.numpy as jnp

    init = config.get("init")
    shape = jax.eval_shape(trainer.init_state)
    shapes = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                          shape.params)
    word = inputs.seed_word(seed)

    def make(w):
        params = inputs.make_params(shapes, w, init)
        return shape.replace(step=jnp.zeros((), jnp.int32), params=params,
                             opt_state=trainer.tx.init(params))

    state = jax.jit(make, out_shardings=trainer._state_sharding())(word)
    change = jax.jit(lambda p, w: leaf_norms(jax.tree.map(
        jnp.subtract, p, inputs.make_params(shapes, w, init))))
    return state, shapes, lambda params: change(params, word)


def _first_grad_reader(trainer, probes):
    """`read(opt_state)` -> the first gradient as the optimiser got it
    (the momentum after one step from zero): every leaf's norm, and the
    probe leaves themselves on the host."""
    import jax

    def pick(opt_state):
        grad = base._momentum_tree(trainer, opt_state)
        flat = {inputs.leaf_name(path): leaf for path, leaf in
                jax.tree_util.tree_leaves_with_path(grad)}
        return leaf_norms(grad), {name: flat[name] for name in probes}

    pick = jax.jit(pick)

    def read(opt_state) -> dict:
        norms, kept = pick(opt_state)
        return {"grad_norms": norms, "first_grad": jax.device_get(kept)}
    return read


def first_steps(trainer, cfg, config: dict, seed: int, *, fault=None) -> dict:
    """State and batch from `seed`, then the first `CHECK_STEPS` steps
    through the trainer's own compiled step. Returns the live objects the
    window goes on with and what the steps gave (`got`)."""
    import jax
    import jax.numpy as jnp

    t_0 = time.perf_counter()
    state, shapes, change = _seeded_state(trainer, config, seed)
    batch = trainer.shard(make_tokens(
        seed, cfg.data.global_batch_size, int(cfg.model.extra["seq_len"]),
        cfg.model.num_classes))
    rng = trainer.base_rng()
    jax.block_until_ready((state, batch))
    t_1 = time.perf_counter()
    first_grad = _first_grad_reader(trainer, config["probe_leaves"])

    step_fn = real = trainer.train_step
    if fault == "state_unchanged":
        step_fn = lambda s, b, r: (s, real(jax.tree.map(jnp.copy, s), b,
                                           r)[1])
    elif fault == "half_batch":
        half = int(cfg.model.extra["seq_len"]) // 2
        step_fn = lambda s, b, r: real(s, {"tokens": jnp.concatenate(
            [b["tokens"][:, :half], b["tokens"][:, :half + 1]], 1)}, r)
    elif fault is not None:
        raise ValueError(f"no fault {fault!r} to plant in the program")

    got = {"losses": [], "loads": [], "dropped": []}
    for i in range(CHECK_STEPS):
        state, metrics = step_fn(state, batch, rng)
        got["losses"].append(metrics["loss"])
        got["loads"].append(metrics["moe_load"])
        got["dropped"].append(sum(v for k, v in metrics.items()
                                  if k.startswith("moe_dropped/")))
        if i == 0:
            got.update(first_grad(state.opt_state))
    got["change_norms"] = change(state.params)
    got = jax.device_get(got)
    _say(f"state and batch {t_1 - t_0:.1f} s, first {CHECK_STEPS} steps "
         f"{time.perf_counter() - t_1:.1f} s")
    return {"state": state, "batch": batch, "rng": rng, "metrics": metrics,
            "step_fn": step_fn, "shapes": shapes, "got": got}


def follow_reference(config: dict, cfg, recipe: dict, shapes, seed: int,
                     **kw) -> dict:
    """The plain reference's first steps from the seed's weights on the
    seed's batch (`kw`: its precision `mode` or a planted `fault`)."""
    import jax

    word, init = inputs.seed_word(seed), config.get("init")
    make = jax.jit(lambda w, group: inputs.make_params(
        {group: shapes[group]}, w, init)[group], static_argnums=1)
    tokens = make_tokens(seed, cfg.data.global_batch_size, recipe["seq_len"],
                         config["vocab_size"])["tokens"]
    return lm_step.follow(
        arch_of(config),
        (recipe["first_expert"], config["n_routed_experts"]), recipe,
        lambda group: make(word, group), list(shapes), jax.numpy.asarray(
            tokens), steps=CHECK_STEPS, probes=config["probe_leaves"],
        block_rows=int(config.get("reference_block_rows", 512)), **kw)


def routing_checks(got: dict, want: dict) -> list:
    """`expert_load_diff` and `dropped_assignments` of the checked steps."""
    ours = np.asarray(got["loads"], np.float64)
    theirs = np.asarray(want["loads"], np.float64)
    moved = float(np.abs(ours - theirs).sum() / max(theirs.sum(), 1.0))
    dropped = float(np.sum(got.get("dropped", 0)))  # a reference drops none
    return [{"name": "expert_load_diff", "value": moved,
             "limit": LOAD_DIFF_LIMIT, "ok": moved <= LOAD_DIFF_LIMIT,
             "where": ""},
            {"name": "dropped_assignments", "value": dropped, "limit": 0,
             "ok": dropped == 0, "where": ""}]


def run(ctx) -> dict:
    _say(f"imports {time.perf_counter() - ctx.t0:.1f} s")
    trainer, cfg, _ = base.build_trainer(ctx)
    _say(f"trainer built at {time.perf_counter() - ctx.t0:.1f} s")
    compiles = base.CompileCounter()
    if ctx.cell["mode"] != "step":
        raise ValueError(f"driver lm_train has no mode {ctx.cell['mode']!r}")
    return _run_step(ctx, trainer, cfg, compiles)


def _run_step(ctx, trainer, cfg, compiles) -> dict:
    import jax

    config = ctx.config
    recipe = recipe_of(cfg, config)
    devices = list(trainer.mesh.devices.flat)

    # ---- set-up: state and batch from the seed, first steps, warm-up
    live = first_steps(trainer, cfg, config, ctx.seed, fault=ctx.fault)
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
        window_metrics.append((metrics["loss"], metrics["bad_step"]))
        pending.append(metrics["loss"])
        if len(pending) > base.IN_FLIGHT:
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
            base._start_trace(trace_dir)
            with annotate("chipbench:traced_window"):
                for _ in range(base.TRACE_STEPS):
                    one_step()
                with annotate("chipbench:final_sync"):
                    jax.block_until_ready(metrics)
            jax.profiler.stop_trace()
            traced = {"steps": base.TRACE_STEPS}
            continue
        one_step()
    last_loss = float(jax.device_get(metrics["loss"]))
    window_s = time.perf_counter() - t_begin
    compiles.armed = False

    steps = len(window_metrics)
    device = base._device_facts(devices)
    failed = sum(1 for loss, bad in jax.device_get(window_metrics)
                 if not math.isfinite(float(loss)) or float(bad) > 0)
    _say(f"window: {steps} steps in {window_s:.3f} s, last loss "
         f"{last_loss:.4f}, {failed} failed, set-up {setup_s:.1f} s")

    # ---- the reference, once the program's state is freed
    del state, batch, metrics, window_metrics, pending
    t_ref = time.perf_counter()
    want = follow_reference(config, cfg, recipe, shapes, ctx.seed)
    gaps = compare.training_gaps(got, want, config["probe_leaf"])
    checks = compare.judge(gaps, ctx.cell["limits"]) \
        + routing_checks(got, want)
    checks.append({"name": "compiles_in_window", "value": compiles.count,
                   "limit": 0, "ok": compiles.count == 0, "where": ""})
    _say(f"reference followed in {time.perf_counter() - t_ref:.1f} s")

    chips = len(devices)
    rows, seq_len = cfg.data.global_batch_size, recipe["seq_len"]
    held = [float(x) for x in np.asarray(want["loads"][0]).sum(-1)]
    lm = {"arch": arch_of(config), "layers": len(held),
          "vocab_rows": config["vocab_size"],
          "experts_held": config["n_routed_experts"], "seq_len": seq_len,
          "rows": rows, "assignments_held": held}
    facts = {"trace_dir": trace_dir if traced else None, "traced": traced,
             "chips": chips, "device_kind": device["kind"],
             "window_s": window_s, "steps": steps, "lm": lm,
             "step_ops": lm_counts.step_ops(**lm)}
    if traced:
        facts["scopes"] = _scopes(trace_dir)
    return {
        "attempted": steps, "failed": failed, "checks": checks,
        "device": device,
        "end_to_end": {
            # one example is one sequence; tokens/s is this times seq_len
            "train_images_per_s": steps * rows / window_s / chips,
            "peak_hbm_gib": device["memory_peak_bytes"] / 2 ** 30,
            "setup_s": setup_s},
        "facts": facts,
    }


def _scopes(trace_dir: str):
    """The trace by this cell's names, as `scope_reduce.of` hands it to
    every reader; None (and a line on standard error) where it holds none
    of the declared phases.

    XLA:TPU rewrites a `ragged_dot` into its own grouped-product kernel
    and, for the weight-gradient product, drops the name stack on the way:
    those events arrive with the bare `tf_op` "ragged-dot-none:". Only the
    expert layer makes grouped products, so they are given back to
    `moe_experts`, backward, before the reduction: without that the
    kernel's roofline share would leave a third of its time out."""
    trace = scope_reduce.load(trace_reduce.find(trace_dir))
    for events in trace["devices"].values():
        for event in events:
            if event["category"].get("tf_op", "").startswith("ragged-dot"):
                event["category"] = {
                    **event["category"],
                    "tf_op": "transpose(jvp(lost))/moe_experts/ragged_dot:"}
    try:
        table = scope_reduce.reduce(trace, names=names())
    except ValueError as err:          # no device operation in the trace
        _say(f"scope_reduce: {err}")
        return None
    if table["phases_found"]:
        return table
    _say("scope_reduce: none of the declared phases is in the trace; "
         f"modules {table['modules']}")
    return None
