"""The jitted SPMD train/eval steps — the heart of the framework.

Reference call stack (SURVEY.md §3.1): fetch → forward → loss(+wd) → backward →
[SYNC] ring all-reduce(grads) over NCCL/MPI → SGD-momentum apply → step-LR decay.

TPU-native design: the *entire* chain from forward through optimizer apply —
including the gradient all-reduce — is ONE XLA computation, built with
`shard_map` over the device mesh so the cross-replica `lax.pmean` is explicit in
user code (mirroring the reference's visible sync point) while XLA schedules the
ICI all-reduce and overlaps it with backward compute. The Python loop only feeds
batches and reads metrics (BASELINE.json north_star).
"""

from __future__ import annotations

import functools
from typing import Callable, Mapping, Tuple

import jax
import jax.numpy as jnp
import optax
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from distributed_vgg_f_tpu.ops.losses import l2_regularization, softmax_cross_entropy
from distributed_vgg_f_tpu.ops.lrn import lrn_site_counts
from distributed_vgg_f_tpu.ops.metrics import topk_correct
from distributed_vgg_f_tpu.parallel.collectives import (
    cross_replica_mean,
    cross_replica_sum,
    fold_rng_per_replica,
)
from distributed_vgg_f_tpu.parallel.zero import Exchange
from distributed_vgg_f_tpu.train.state import TrainState

Batch = Mapping[str, jnp.ndarray]


def _apply_model(model, params, batch_stats, images, *, train: bool,
                 dropout_rng=None):
    """Run the model, handling mutable BN state uniformly for all models."""
    variables = {"params": params}
    has_bn = bool(batch_stats)
    if has_bn:
        variables["batch_stats"] = batch_stats
    rngs = {"dropout": dropout_rng} if (train and dropout_rng is not None) else None
    if train and has_bn:
        logits, new_vars = model.apply(variables, images, train=True, rngs=rngs,
                                       mutable=["batch_stats"])
        return logits, new_vars["batch_stats"]
    logits = model.apply(variables, images, train=train, rngs=rngs)
    return logits, batch_stats


def _expert_load_metrics(counts, counters, expert_layers) -> dict:
    """A language model's routing counters as step metrics. `counts` is
    (expert layers, experts held + 1): the assignments each held expert
    took in this replica's batch, then the dropped ones, a row for each
    layer of `expert_layers` (the model says which of its layers have
    experts: all of models/mistral4.py's, four of nine in
    models/nemotron_h.py's cell, six of seven in models/ling3.py's); `counters` is what the layers sowed, a
    dict for each `layer_N`. Per expert layer, under the layer's own
    index: assignments held, the largest and smallest held expert's load,
    dropped assignments, the passes the routed path made over its buffers
    and the rows those hold (and what else the expert share sowed, as
    `moe_group_share`); and the whole table as `moe_load`. What a layer
    without experts sowed, and what the attention (`attn`) of a layer with
    experts did, keeps the name it was sown under (`ssm_chunks/layer_0`,
    `kda_chunks/layer_1`)."""
    counts = counts.astype(jnp.float32)
    load, dropped = counts[:, :-1], counts[:, -1]
    metrics = {"moe_load": load}

    def sown(layer: str, prefix: str) -> None:
        for module, values in counters.get(layer, {}).items():
            own = "" if module == "attn" else prefix
            for name, (value,) in values.items():
                metrics[f"{own}{name}/{layer}"] = jnp.asarray(
                    value, jnp.float32)

    for row, i in enumerate(expert_layers):
        metrics[f"moe_held/layer_{i}"] = jnp.sum(load[row])
        metrics[f"moe_load_max/layer_{i}"] = jnp.max(load[row])
        metrics[f"moe_load_min/layer_{i}"] = jnp.min(load[row])
        metrics[f"moe_dropped/layer_{i}"] = dropped[row]
        sown(f"layer_{i}", "moe_")
    with_experts = {f"layer_{i}" for i in expert_layers}
    for layer in counters:
        if layer not in with_experts:
            sown(layer, "")
    return metrics


def build_train_step(model, mesh: Mesh, weight_decay: float,
                     exchange: Exchange, *,
                     schedule: optax.Schedule | None = None,
                     grad_clip_norm: float = 0.0,
                     ema_decay: float = 0.0,
                     skip_nonfinite: bool = False,
                     device_finish: Callable | None = None,
                     device_augment: Callable | None = None,
                     batch_kind: str = "image",
                     ) -> Callable[[TrainState, Batch, jax.Array],
                                   Tuple[TrainState, Mapping[str, jnp.ndarray]]]:
    """Returns jitted `train_step(state, batch, base_rng) -> (state, metrics)`.

    The body reads prologue → gradient (once, or the scan) → `exchange`
    reduce and update → EMA → guard. Which collectives the reduce and the
    update are, over which layout and wire, and what the state's leaves are
    sharded over, is `exchange`'s to say (parallel/zero.py `Exchange`: the
    dp | zero1 | zero2 | zero3 ladder, the bucketed exchange, the wire
    dtype, the optimizer); nothing here names a basis or a layout.

    - `state` and `base_rng` are replicated across the mesh (but for the
      leaves `exchange.state_specs` shards); `batch` is sharded on its
      leading dim over the data axis.
    - Per-replica dropout keys are derived with `fold_in(axis_index)`
      (SURVEY.md §7 hard parts).
    - `exchange.grad_accum_steps=k>1`: the per-device batch is split into k
      micro-batches folded through ONE `lax.scan` — only one micro-batch's
      activations are ever live, trading k× step latency for 1/k activation
      memory at an UNCHANGED optimizer batch (the logical global batch, LR
      schedule, and gradient sync point all stay identical; for BN-free
      models the summed micro-gradients equal the big-batch gradient
      exactly, tested). Gradients accumulate in the scan carry (O(params),
      never k×; O(params/N) where the plan reduces inside the scan);
      dropout keys fold per micro-batch; BN batch stats update
      sequentially per micro-batch (the standard accumulation semantics).
      Otherwise the cross-replica reduce happens ONCE, on the accumulated
      gradient — accumulation also divides collective bandwidth per sample.
    - `skip_nonfinite=True` (resilience layer): the step decides ON DEVICE
      whether loss and gradient norm are finite — both are cross-replica-
      reduced values, so a NaN/inf on ANY replica propagates to every
      replica and all replicas take the identical keep/skip select — and on
      a bad step keeps params/opt-state/BN/EMA bit-identical while still
      advancing the step counter (the data stream stays aligned with the
      loop index). Note the schedule split this implies: the OPTIMIZER's
      schedule position lives in the reverted opt_state, so skipped steps
      deliberately do not consume warmup/decay (a diverging phase must not
      burn the warmup); `metrics["lr"]` reads `schedule(state.step)` and
      therefore runs ahead of the applied LR by the number of skips so far
      (bounded by the guard's abort threshold for consecutive streaks).
      The verdict is reported as the `bad_step` metric (0/1) for the
      host-side NonFiniteGuard; cost is one `where` per state leaf,
      nothing cross-replica beyond what the step already reduces.
    - `device_augment` (r13, data/augment.py): the on-device
      augmentation stage, applied to the batch as it arrived inside the
      shard_map body off a constant fold of the per-replica train key
      (dropout stream untouched). It owns the device finish of its batch
      (`device_finish` is then not called: the stage permutes the wire's
      pixels first and finishes them itself). Returns possibly-mixed
      images plus the mixup/cutmix label pairing, which the loss consumes
      as lam*CE(y) + (1-lam)*CE(y[perm]). None = structurally absent (the
      augment-off kill-switch is byte-identical to a pre-r13 step). Only
      the TRAIN step takes this — eval/predict never augment.
    - `batch_kind` (models/ingest.py, the model's descriptor): "image"
      batches are {'image', 'label'} and take the prologue, the
      classification loss and top-1 above. "tokens" batches are
      {'tokens': int32[B, S + 1]}: inputs `[:, :-1]`, targets `[:, 1:]`,
      no prologue, the model's own `next_token_loss` (mean next-token
      cross-entropy, float32 logits in chunks of rows) and its routing
      counters in place of top-1. Everything after the gradient (exchange,
      optimizer, guard, donation) is shared. An image batch traces to the
      step it traced to before the kind existed.
    """
    data_axis = exchange.axis
    grad_accum_steps = exchange.grad_accum_steps
    # Static per-run exchange receipt, filled at first trace (a dp plan
    # learns the leaf shapes there). Read by the trainer's per-window
    # `comm` JSONL block and the comm/* counters below.
    comm_meta: dict = {}
    # LRN call sites of the traced step by what they lowered to (ops/lrn.py:
    # the fused kernel pair, or an XLA form), filled at first trace like
    # comm_meta; gauges lrn/fused_sites and lrn/fallback_sites below.
    lrn_sites: dict = {}

    # Named `train_step` and not `step_fn`: the jitted module's name
    # (`jit_train_step`) is what a trace's `XLA Modules` line shows, and it
    # is part of the persistent compile cache's key where the scopes below
    # are not (distributed_vgg_f_tpu/scopes.py).
    def train_step(state: TrainState, batch: Batch, base_rng: jax.Array):
        if batch_kind == "tokens":
            images, labels = batch["tokens"][:, :-1], batch["tokens"][:, 1:]
        else:
            images, labels = batch["image"], batch["label"]
        rng = jax.random.fold_in(base_rng, state.step)
        rng = fold_rng_per_replica(rng, data_axis)
        lrn_sites_before = lrn_site_counts()
        # The prologue, INSIDE the shard_map body. With on-device
        # augmentation (data/augment.py) the stage is the whole of it: flip,
        # mixup partner and pack on the batch as it arrived, then its own
        # finish, then the mix — keyed off a constant fold of the
        # per-replica train key, so every draw is reproducible from (seed,
        # step, replica) and the dropout stream below is untouched.
        # mix_labels/mix_lam carry the mixup/cutmix label pairing into the
        # loss. Without it (device_augment=None adds zero equations — the
        # kill-switch byte-identity contract) the u8-wire finish
        # (data/device_ingest.py) runs alone: normalize + cast +
        # space-to-depth, dispatching on dtype — float (host-normalized)
        # batches pass through untouched, so it is safe for every wire.
        mix_labels = mix_lam = None
        if device_augment is not None:
            from distributed_vgg_f_tpu.data.augment import AUGMENT_RNG_FOLD
            with jax.named_scope("augment"):
                images, mix_labels, mix_lam = device_augment(
                    jax.random.fold_in(rng, AUGMENT_RNG_FOLD), images, labels)
        elif device_finish is not None:
            with jax.named_scope("finish_u8"):
                images = device_finish(images)

        def make_loss_fn(images, labels, mix_labels, batch_stats,
                         dropout_rng):
            def token_loss_fn(params):
                (ce, counts), sown = model.apply(
                    {"params": params}, images, labels,
                    method="next_token_loss", mutable=["counters"])
                with jax.named_scope("loss"):
                    l2 = l2_regularization(params, weight_decay)
                    metrics = {"loss": ce, "l2_loss": l2,
                               **_expert_load_metrics(
                                   counts, sown["counters"],
                                   model.expert_layers)}
                return ce + l2, (batch_stats, metrics)

            if batch_kind == "tokens":
                return token_loss_fn

            def loss_fn(params):
                logits, new_batch_stats = _apply_model(
                    model, params, batch_stats, images, train=True,
                    dropout_rng=dropout_rng)
                with jax.named_scope("loss"):
                    if mix_labels is not None:
                        # mixup/cutmix with INTEGER labels: the mixed target
                        # is a two-point distribution, so its CE decomposes
                        # as the lam-weighted sum of the two integer-label
                        # CEs — no one-hot materialization.
                        ce = mix_lam * softmax_cross_entropy(logits, labels) \
                            + (1.0 - mix_lam) * softmax_cross_entropy(
                                logits, mix_labels)
                    else:
                        ce = softmax_cross_entropy(logits, labels)
                    l2 = l2_regularization(params, weight_decay)
                    loss = ce + l2
                    n = jnp.asarray(labels.shape[0], jnp.float32)
                    metrics = {
                        "loss": ce,
                        # top1 scores against the PRIMARY labels (the standard
                        # mixup-training convention; eval is unaugmented
                        # anyway)
                        "l2_loss": l2,
                        "top1": topk_correct(logits, labels,
                                             1).astype(jnp.float32) / n,
                    }
                return loss, (new_batch_stats, metrics)
            return loss_fn

        # the plan with the parameter shapes in it: a ZeRO plan came
        # bound (the state's layout depends on them), a dp plan binds here
        plan = exchange.bind(state.params)
        if not comm_meta:
            comm_meta.update(plan.comm_meta())
        full_params = plan.forward_params(state.params)

        if grad_accum_steps > 1:
            if batch_kind != "image":
                raise NotImplementedError(
                    "grad_accum_steps > 1 splits image batches only")
            b_local = images.shape[0]
            if b_local % grad_accum_steps:
                raise ValueError(
                    f"per-device batch {b_local} not divisible by "
                    f"grad_accum_steps={grad_accum_steps}")
            micro = b_local // grad_accum_steps
            im = images.reshape(grad_accum_steps, micro, *images.shape[1:])
            lb = labels.reshape(grad_accum_steps, micro)
            # mixup pairing crosses micro-batch boundaries (the permutation
            # ran over the whole local batch BEFORE the split), so the
            # paired labels ride the scan as a third sequence — lam is one
            # scalar per step, shared by every micro-batch.
            lb2 = (mix_labels.reshape(grad_accum_steps, micro)
                   if mix_labels is not None else None)
            g_init = plan.accum_init(state.params)

            def micro_step(carry, xs):
                g_acc, bs = carry
                if lb2 is not None:
                    im_i, lb_i, lb2_i, i = xs
                else:
                    im_i, lb_i, i = xs
                    lb2_i = None
                loss_fn = make_loss_fn(im_i, lb_i, lb2_i, bs,
                                       jax.random.fold_in(rng, i))
                (_, (bs_new, m)), g = jax.value_and_grad(
                    loss_fn, has_aux=True)(full_params)
                return (plan.accum_add(g_acc, g), bs_new), m

            micro_xs = (im, lb) + (() if lb2 is None else (lb2,)) \
                + (jnp.arange(grad_accum_steps),)
            (g_sum, new_batch_stats), metrics_stack = jax.lax.scan(
                micro_step, (g_init, state.batch_stats), micro_xs)
            grads = jax.tree.map(lambda g: g / grad_accum_steps, g_sum)
            # where the scan's carry is the 1/N shard, each micro-gradient
            # was reduced inside it and never materialized whole past it
            reduced = plan.accum_reduced
            metrics = jax.tree.map(lambda m: jnp.mean(m, axis=0),
                                   metrics_stack)
        else:
            loss_fn = make_loss_fn(images, labels, mix_labels,
                                   state.batch_stats, rng)
            (_, (new_batch_stats, metrics)), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(full_params)
            reduced = False
        with jax.named_scope("step_metrics"):
            metrics = cross_replica_mean(metrics, data_axis)

        # [SYNC] — the one cross-replica point per step, then the update
        if not reduced:
            grads = plan.reduce(grads)
        new_params, new_opt_state, metrics["grad_norm"] = plan.update(
            grads, state.opt_state, state.params, grad_clip_norm)

        if schedule is not None:
            with jax.named_scope("step_metrics"):
                metrics["lr"] = schedule(state.step)

        # Parameter EMA (train.ema_decay): stored like params, whatever the
        # exchange stores them as, so the elementwise update shards for
        # free. BN moving stats are averaged with the same decay (the TF
        # recipe's moving_average_variables). Fused into the same XLA
        # computation as the step.
        new_ema = state.ema_params
        new_ema_bs = state.ema_batch_stats
        if ema_decay > 0.0:
            avg = lambda e, p: e * ema_decay + (1.0 - ema_decay) * p
            with jax.named_scope("optimizer"):
                new_ema = jax.tree.map(avg, state.ema_params, new_params)
                new_ema_bs = jax.tree.map(avg, state.ema_batch_stats,
                                          new_batch_stats)

        if skip_nonfinite:
            # Non-finite step guard: metrics["loss"]/["l2_loss"] are the
            # cross-replica MEANS and grad_norm is psum'd — a non-finite
            # value on any replica is non-finite on every replica, so `ok`
            # is replica-consistent and the selects below cannot desync the
            # mesh. `where` never propagates NaN from the untaken branch.
            # Everything but the step counter reverts on a bad step — incl.
            # EMA, which would otherwise still drift toward the (unchanged)
            # params with one decay's worth of weight, and the optimizer's
            # internal schedule count, so skips don't consume warmup/decay
            # (see the build_train_step docstring for the metrics["lr"]
            # consequence).
            with jax.named_scope("step_metrics"):
                ok = jnp.logical_and(
                    jnp.isfinite(metrics["loss"] + metrics["l2_loss"]),
                    jnp.isfinite(metrics["grad_norm"]))
                keep = lambda new, old: jax.tree.map(
                    lambda n, o: jnp.where(ok, n, o), new, old)
                new_params = keep(new_params, state.params)
                new_opt_state = keep(new_opt_state, state.opt_state)
                new_batch_stats = keep(new_batch_stats, state.batch_stats)
                if ema_decay > 0.0:
                    new_ema = keep(new_ema, state.ema_params)
                    new_ema_bs = keep(new_ema_bs, state.ema_batch_stats)
                metrics["bad_step"] = 1.0 - ok.astype(jnp.float32)

        new_state = state.replace(step=state.step + 1, params=new_params,
                                  batch_stats=new_batch_stats,
                                  opt_state=new_opt_state,
                                  ema_params=new_ema,
                                  ema_batch_stats=new_ema_bs)
        lrn_sites.update({kind: count - lrn_sites_before[kind]
                          for kind, count in lrn_site_counts().items()})
        return new_state, metrics

    sharded = shard_map(
        train_step, mesh=mesh,
        in_specs=(exchange.state_specs, P(data_axis), P()),
        out_specs=(exchange.state_specs, P()),
        check_vma=False,
    )
    # State donation halves the step's peak state memory on accelerators:
    # callers must not touch a state after handing it to the step. NOT on
    # XLA:CPU. An earlier jaxlib corrupted the glibc heap when a donating
    # step loaded from the persistent compile cache ran after an Orbax
    # restore. Re-checked under jax 0.9.0 (PR 21): 370 targeted test runs
    # with donation on the CPU were clean, cold and warm cache, but the
    # one whole tier-1 run with it lost a worker to a segfault in a native
    # thread of an unrelated test — where heap corruption shows up. Not
    # proven either way, so the CPU, where the memory is irrelevant, stays
    # without donation.
    donate = () if jax.default_backend() == "cpu" else (0,)
    jitted = jax.jit(sharded, donate_argnums=donate)

    # Telemetry: host DISPATCH time of the jitted step ("dispatch" spans).
    # JAX dispatch is async, so this is NOT device time — but its spikes are
    # diagnostic on their own (first-call spans carry compile time; later
    # spikes mean the dispatch queue back-pressured, i.e. the host got ahead
    # of the device). The wrapper keeps `.lower` (bench.py AOT-compiles the
    # step) and is a plain passthrough when telemetry is disabled.
    from distributed_vgg_f_tpu import telemetry
    static_gauges_set = False

    @functools.wraps(jitted)
    def dispatch(state, batch, rng):
        nonlocal static_gauges_set
        rec = telemetry.get_recorder()
        if not rec.enabled:
            return jitted(state, batch, rng)
        with rec.span("train_step_dispatch", "dispatch"):
            out = jitted(state, batch, rng)
        telemetry.inc("step/dispatched")
        # comm/* receipts (ISSUE 11): per-step exchange counters,
        # single-sourced from the geometry the trace actually used
        # (comm_meta fills on first trace, so the first dispatch already
        # sees it)
        if comm_meta:
            telemetry.inc("comm/exchanges")
            telemetry.inc("comm/wire_bytes", comm_meta["wire_bytes"])
            # gather-leg receipts (r21): all_gather collectives this step
            # moved (0 dp / 1 zero1-2 re-sync / per-bucket zero3 fetch) and
            # their wire bytes — off the SAME trace-time geometry
            if comm_meta["gathers"]:
                telemetry.inc("comm/gathers", comm_meta["gathers"])
                telemetry.inc("comm/gather_wire_bytes",
                              comm_meta["gather_bytes"])
        # what the trace fixed for good (the exchange's shape, the LRN
        # sites by kind): gauges, set by the first dispatch that recorded
        if not static_gauges_set:
            static_gauges_set = True
            reg = telemetry.get_registry()
            if comm_meta:
                reg.set_gauge("comm/buckets_per_step", comm_meta["buckets"])
                reg.set_gauge("comm/bucket_mb", comm_meta["bucket_mb"])
            for kind, count in lrn_sites.items():
                reg.set_gauge(f"lrn/{kind}_sites", count)
        return out

    dispatch.lower = jitted.lower
    # the static exchange receipt (trainer JSONL `comm` block, bench rows);
    # empty until the first trace fills it
    dispatch.comm_meta = comm_meta
    dispatch.lrn_sites = lrn_sites
    return dispatch


def build_eval_step(model, mesh: Mesh, exchange: Exchange, *,
                    device_finish: Callable | None = None,
                    ) -> Callable[[TrainState, Batch], Mapping[str, jnp.ndarray]]:
    """Jitted eval step returning psum-accumulated correct counts
    (SURVEY.md §3.4): {'top1': n_correct, 'top5': n_correct5, 'count': n}.

    `exchange` is the train step's plan, so a ZeRO-sharded state is consumed
    in place (eval never touches opt state, so no gather is emitted for it)
    and zero3's resident param shard is gathered back to the tree INSIDE the
    shard_map body (`Exchange.eval_params`: always fp32)."""
    data_axis = exchange.axis

    def eval_step(state: TrainState, batch: Batch):
        images, labels = batch["image"], batch["label"]
        if device_finish is not None:
            # SAME prologue as the train step (single-normalization
            # contract): eval batches ride the host-normalize wire and pass
            # through untouched; a uint8 batch fed here is finished exactly
            # once — the host/device double-normalize hazard is
            # structurally impossible (tests/test_wire_u8.py).
            with jax.named_scope("finish_u8"):
                images = device_finish(images)
        # Exact eval (data/eval_pad.py): a "valid" mask marks padding rows in
        # the final partial batch; they contribute to neither hits nor count.
        valid = batch.get("valid")
        params = exchange.eval_params(state.params)
        logits, _ = _apply_model(model, params, state.batch_stats, images,
                                 train=False)
        with jax.named_scope("step_metrics"):
            k5 = min(5, logits.shape[-1])
            counts = {
                "top1": topk_correct(logits, labels, 1, valid),
                "top5": topk_correct(logits, labels, k5, valid),
                "count": (jnp.sum(valid.astype(jnp.int32))
                          if valid is not None
                          else jnp.asarray(labels.shape[0], jnp.int32)),
            }
            return cross_replica_sum(counts, data_axis)

    sharded = shard_map(eval_step, mesh=mesh,
                        in_specs=(exchange.state_specs, P(data_axis)),
                        out_specs=P(),
                        check_vma=False)
    return jax.jit(sharded)
