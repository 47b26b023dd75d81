"""Driver for language-model training cells whose model is named by the
configuration's file: `drivers/lm_train.py`'s window, checks and facts, with
the plain reference (`reference`: a module of `chipbench/reference/`,
followed by `reference/hybrid_lm_step.py`), the counts (`counts`: a module
of `chipbench/`), the trace's names (`scopes`: a file of `chipbench/`) and
the keys the reference reads (`arch_keys`) all taken from that file. A
further language model costs a configuration, a reference and a names file,
not a driver: also one with no routed experts (the reference's
`expert_layers` empty: no load is read and no routing check made) or with
a head tied to its embedding (`tie_word_embeddings` among `arch_keys`:
`reference/hybrid_lm_step.py`).

Mode `step`, the set-up, the window and what `correct` holds are
`lm_train`'s (its docstring): one batch of packed tokens from `--seed`,
`trainer.train_step` back to back, at most `IN_FLIGHT` steps ahead, the
loss fetched every `train.log_every` steps and at the end; the first
`CHECK_STEPS` steps against the reference's by `compare.judge` and the
cell's `limits`, `expert_load_diff` against the cell's `load_diff_limit`
and `dropped_assignments` = 0 where the model has expert layers,
`compiles_in_window` = 0.

Two things `lm_train` has no need of. The seed's weights pass through the
file's `init_from_uniform` after `inputs.make_params`: a leaf it names
(drawn N(0, 1) by the file's `init`) is carried by the normal distribution
function to a uniform draw and from there to the published initialiser's
range (`log_uniform`: log of uniform [low, high]; `inverse_softplus_
log_uniform`: the inverse softplus of exp(uniform [log low, log high]),
floored), because a state-space layer's decay drawn from a normal is no
decay a trained model has. And besides the faults planted in the program
(`state_unchanged`, `half_batch`), a fault of the reference's own
(`REFERENCE_FAULTS`) is planted in the reference, which the sound program
is then held against: the same gap, seen from the other side.
"""

from __future__ import annotations

import collections
import importlib
import json
import math
import os
import time

import numpy as np

from chipbench import compare, inputs, scope_reduce, trace_reduce
from chipbench.drivers import lm_train, train as base
from chipbench.reference import hybrid_lm_step
from chipbench.reference.step import leaf_norms

CHECK_STEPS = base.CHECK_STEPS
_say = base._say
make_tokens = lm_train.make_tokens

#: faults the reference module plants in itself (`block(..., fault=...)`)
REFERENCE_FAULTS = ("chunk_reset",)


def reference_of(config: dict):
    return importlib.import_module(f"chipbench.reference.{config['reference']}")


def counts_of(config: dict):
    return importlib.import_module(f"chipbench.{config['counts']}")


def names(config: dict) -> dict:
    """The names this configuration's traces are reduced by."""
    return scope_reduce.declared(os.path.join(
        scope_reduce.ROOT, "chipbench", config["scopes"]))


def arch_of(config: dict) -> dict:
    """The architecture as the reference and the counts take it: the
    file's published widths (the pattern as it is run), and, for a model
    with routed experts, the router at its published width (the file's own
    `n_routed_experts` is the experts held)."""
    arch = {k: config[k] for k in config["arch_keys"]}
    if "n_routed_experts" in config["published"]:
        arch["n_routed_experts"] = config["published"]["n_routed_experts"]
    return arch


def expert_layers(config: dict) -> list:
    """The layers with routed experts, as the reference reads them."""
    return reference_of(config).expert_layers(arch_of(config))


def recipe_of(cfg, config: dict) -> dict:
    """The configuration file's recipe, after checking that the program's
    preset states the same numbers, the same cut and every published
    width. A model without expert layers needs no `first_expert`."""
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
    }
    if "first_expert" in recipe or expert_layers(config):
        stated["first_expert"] = extra.get("first_expert", 0)
    differ = {k: (recipe.get(k), v) for k, v in stated.items()
              if recipe.get(k) != v}
    held = {"num_hidden_layers": len(extra["hybrid_override_pattern"])
            if "hybrid_override_pattern" in extra
            else extra["num_hidden_layers"],
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


# ---- the seed's weights ------------------------------------------------------

def _from_uniform(z, rule: dict):
    """A standard normal draw `z` as the rule's draw (module docstring)."""
    import jax
    import jax.numpy as jnp
    u = 0.5 * (1.0 + jax.lax.erf(z / math.sqrt(2.0)))
    if rule["kind"] == "log_uniform":
        return jnp.log(rule["low"] + (rule["high"] - rule["low"]) * u)
    if rule["kind"] == "inverse_softplus_log_uniform":
        x = jnp.maximum(jnp.exp(math.log(rule["low"]) + u * math.log(
            rule["high"] / rule["low"])), rule["floor"])
        return x + jnp.log(-jnp.expm1(-x))
    raise ValueError(f"no init_from_uniform kind {rule['kind']!r}")


def make_params(shapes, word, config: dict):
    """`inputs.make_params` by the file's `init`, then its
    `init_from_uniform`."""
    import jax
    rules = config.get("init_from_uniform") or {}

    def leaf(path, z):
        name = inputs.leaf_name(path)
        for tail, rule in rules.items():
            if name.endswith(tail):
                return _from_uniform(z, rule)
        return z

    return jax.tree_util.tree_map_with_path(
        leaf, inputs.make_params(shapes, word, config.get("init")))


def _seeded_state(trainer, config: dict, seed: int):
    """`lm_train._seeded_state` with this driver's `make_params`: a state
    of the trainer's own shape with the seed's weights and zero momentum;
    the parameters' shapes; `change(params)`, the per-leaf norms of their
    distance from the seed's weights."""
    import jax
    import jax.numpy as jnp

    shape = jax.eval_shape(trainer.init_state)
    shapes = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                          shape.params)
    word = inputs.seed_word(seed)

    def make(w):
        params = make_params(shapes, w, config)
        return shape.replace(step=jnp.zeros((), jnp.int32), params=params,
                             opt_state=trainer.tx.init(params))

    state = jax.jit(make, out_shardings=trainer._state_sharding())(word)
    change = jax.jit(lambda p, w: leaf_norms(jax.tree.map(
        jnp.subtract, p, make_params(shapes, w, config))))
    return state, shapes, lambda params: change(params, word)


def first_steps(trainer, cfg, config: dict, seed: int, *, fault=None) -> dict:
    """State and batch from `seed`, then the first `CHECK_STEPS` steps
    through the trainer's own compiled step (`lm_train.first_steps`, on
    this driver's weights). Returns the live objects the window goes on
    with and what the steps gave (`got`)."""
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
    first_grad = lm_train._first_grad_reader(trainer, config["probe_leaves"])

    step_fn = real = trainer.train_step
    if fault == "state_unchanged":
        step_fn = lambda s, b, r: (s, real(jax.tree.map(jnp.copy, s), b,
                                           r)[1])
    elif fault == "half_batch":
        half = int(cfg.model.extra["seq_len"]) // 2
        step_fn = lambda s, b, r: real(s, {"tokens": jnp.concatenate(
            [b["tokens"][:, :half], b["tokens"][:, :half + 1]], 1)}, r)
    elif fault is not None and fault not in REFERENCE_FAULTS:
        raise ValueError(f"no fault {fault!r} to plant")

    routed = bool(expert_layers(config))
    got = {"losses": [], "loads": [], "dropped": []}
    for i in range(CHECK_STEPS):
        state, metrics = step_fn(state, batch, rng)
        got["losses"].append(metrics["loss"])
        if routed:
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

    word = inputs.seed_word(seed)
    make = jax.jit(lambda w, group: make_params(
        {group: shapes[group]}, w, config)[group], static_argnums=1)
    tokens = make_tokens(seed, cfg.data.global_batch_size, recipe["seq_len"],
                         config["vocab_size"])["tokens"]
    return hybrid_lm_step.follow(
        reference_of(config), arch_of(config),
        (recipe.get("first_expert", 0), config.get("n_routed_experts", 0)),
        recipe,
        lambda group: make(word, group), list(shapes),
        jax.numpy.asarray(tokens), steps=CHECK_STEPS,
        probes=config["probe_leaves"],
        block_rows=int(config.get("reference_block_rows", 512)), **kw)


def routing_checks(got: dict, want: dict, cell: dict) -> list:
    """`lm_train.routing_checks`, `expert_load_diff` held to the cell's own
    `load_diff_limit` (that module's constant is its own cell's reading);
    none where the reference's load tables have no row (a model without
    expert layers), where both would read 0 by construction."""
    if not np.shape(want["loads"])[1]:
        return []
    checks = lm_train.routing_checks(got, want)
    limit = float(cell["load_diff_limit"])
    checks[0].update(limit=limit, ok=checks[0]["value"] <= limit)
    return checks


def run(ctx) -> dict:
    _say(f"imports {time.perf_counter() - ctx.t0:.1f} s")
    trainer, cfg, _ = base.build_trainer(ctx)
    _say(f"trainer built at {time.perf_counter() - ctx.t0:.1f} s")
    compiles = base.CompileCounter()
    if ctx.cell["mode"] != "step":
        raise ValueError("driver hybrid_lm_train has no mode "
                         f"{ctx.cell['mode']!r}")
    return _run_step(ctx, trainer, cfg, compiles)


def _run_step(ctx, trainer, cfg, compiles) -> dict:
    import jax

    config = ctx.config
    recipe = recipe_of(cfg, config)
    devices = list(trainer.mesh.devices.flat)
    program_fault = None if ctx.fault in REFERENCE_FAULTS else ctx.fault

    # ---- set-up: state and batch from the seed, first steps, warm-up
    live = first_steps(trainer, cfg, config, ctx.seed, fault=program_fault)
    state, batch, rng, metrics = (live.pop(k) for k in
                                  ("state", "batch", "rng", "metrics"))
    step_fn, shapes, got = live["step_fn"], live["shapes"], live["got"]
    log_every = max(1, int(cfg.train.log_every))
    setup_s = time.perf_counter() - ctx.t0

    # ---- the window (`lm_train._run_step`'s)
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
    want = follow_reference(
        config, cfg, recipe, shapes, ctx.seed,
        **({"fault": ctx.fault} if ctx.fault in REFERENCE_FAULTS else {}))
    gaps = compare.training_gaps(got, want, config["probe_leaf"])
    checks = compare.judge(gaps, ctx.cell["limits"]) \
        + routing_checks(got, want, ctx.cell)
    checks.append({"name": "compiles_in_window", "value": compiles.count,
                   "limit": 0, "ok": compiles.count == 0, "where": ""})
    _say(f"reference followed in {time.perf_counter() - t_ref:.1f} s")

    chips = len(devices)
    rows, seq_len = cfg.data.global_batch_size, recipe["seq_len"]
    held = [float(x) for x in np.asarray(want["loads"][0]).sum(-1)]
    _say(f"assignments held a layer (the reference's routing): {held}")
    lm = {"arch": arch_of(config), "layers": config["num_hidden_layers"],
          "vocab_rows": config["vocab_size"],
          "experts_held": config.get("n_routed_experts", 0),
          "seq_len": seq_len,
          "rows": rows, "assignments_held": held}
    facts = {"trace_dir": trace_dir if traced else None, "traced": traced,
             "chips": chips, "device_kind": device["kind"],
             "window_s": window_s, "steps": steps, "lm": lm,
             "lm_counts": config["counts"], "lm_names": names(config),
             "step_ops": counts_of(config).step_ops(**lm)}
    if traced:
        facts["scopes"] = _scopes(trace_dir, facts["lm_names"])
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


def _scopes(trace_dir: str, declared: dict):
    """The trace by the configuration's names (`lm_train._scopes`, which
    reads its own names file): the grouped products' weight-gradient
    events, which XLA:TPU strips of their name stack, are given back to
    `moe_experts`, backward, before the reduction; None (and a line on
    standard error) where the trace holds none of the declared phases."""
    trace = scope_reduce.load(trace_reduce.find(trace_dir))
    for events in trace["devices"].values():
        for event in events:
            if event["category"].get("tf_op", "").startswith("ragged-dot"):
                event["category"] = {
                    **event["category"],
                    "tf_op": "transpose(jvp(lost))/moe_experts/ragged_dot:"}
    try:
        table = scope_reduce.reduce(trace, names=declared)
    except ValueError as err:          # no device operation in the trace
        _say(f"scope_reduce: {err}")
        return None
    if table["phases_found"]:
        return table
    _say("scope_reduce: none of the declared phases is in the trace; "
         f"modules {table['modules']}")
    return None
