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
from typing import Any, Callable, Mapping, Tuple

import jax
import jax.numpy as jnp
import optax
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from distributed_vgg_f_tpu.ops.losses import l2_regularization, softmax_cross_entropy
from distributed_vgg_f_tpu.ops.lrn import lrn_site_counts
from distributed_vgg_f_tpu.ops.metrics import topk_correct
from distributed_vgg_f_tpu.parallel.collectives import (
    all_reduce_gradients,
    cross_replica_mean,
    cross_replica_sum,
    fold_rng_per_replica,
)
from distributed_vgg_f_tpu.parallel.zero import padded_flat_size
from distributed_vgg_f_tpu.train.state import TrainState

Batch = Mapping[str, jnp.ndarray]


def _clip_by_global_norm(tree, grad_norm, clip_norm):
    """Scale a gradient pytree so its global norm is at most `clip_norm`.
    Shared by both layouts so the replicated and ZeRO-1 paths cannot drift."""
    scale = jnp.minimum(1.0, clip_norm / (grad_norm + 1e-12))
    return jax.tree.map(lambda g: g * scale, tree)


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


def _expert_load_metrics(counts) -> dict:
    """A language model's routing counters as step metrics. `counts` is
    (layers, experts held + 1): the assignments each held expert took in
    this replica's batch, then the dropped ones (models/mistral4.py). Per
    layer: assignments held, the largest and smallest held expert's load,
    dropped assignments; and the whole table as `moe_load`."""
    counts = counts.astype(jnp.float32)
    load, dropped = counts[:, :-1], counts[:, -1]
    metrics = {"moe_load": load}
    for i in range(counts.shape[0]):
        metrics[f"moe_held/layer_{i}"] = jnp.sum(load[i])
        metrics[f"moe_load_max/layer_{i}"] = jnp.max(load[i])
        metrics[f"moe_load_min/layer_{i}"] = jnp.min(load[i])
        metrics[f"moe_dropped/layer_{i}"] = dropped[i]
    return metrics


def build_train_step(model, tx: optax.GradientTransformation, mesh: Mesh,
                     weight_decay: float,
                     schedule: optax.Schedule | None = None,
                     data_axis: str = "data",
                     zero1: bool = False,
                     state_specs=None,
                     grad_clip_norm: float = 0.0,
                     grad_accum_steps: int = 1,
                     grad_accum_shard: bool = False,
                     shard_gradients: bool = False,
                     shard_params: bool = False,
                     params_struct=None,
                     comm_bucket_mb: float = 0.0,
                     ema_decay: float = 0.0,
                     reduce_dtype: str = "float32",
                     skip_nonfinite: bool = False,
                     device_finish: Callable | None = None,
                     device_augment: Callable | None = None,
                     batch_kind: str = "image",
                     ) -> Callable[[TrainState, Batch, jax.Array],
                                   Tuple[TrainState, Mapping[str, jnp.ndarray]]]:
    """Returns jitted `train_step(state, batch, base_rng) -> (state, metrics)`.

    - `state` and `base_rng` are replicated across the mesh; `batch` is sharded on
      its leading dim over the data axis.
    - Per-replica dropout keys are derived with `fold_in(axis_index)`
      (SURVEY.md §7 hard parts).
    - Plain DP (`zero1=False`): gradients are `pmean`-all-reduced before the optax
      update, so every replica applies the identical update — synchronous
      replicated SGD, the reference's semantics (SURVEY.md §2.4).
    - `zero1=True`: optimizer-state sharding (parallel/zero.py) — gradients are
      reduce-SCATTERED (`psum_scatter`), the optimizer updates only this
      replica's 1/N flat shard against the sharded opt state, and the updated
      parameter shards are all-gathered. `state_specs` must then be the
      PartitionSpec tree from `zero.train_state_specs`.
    - `grad_accum_steps=k>1`: the per-device batch is split into k
      micro-batches folded through ONE `lax.scan` — only one micro-batch's
      activations are ever live, trading k× step latency for 1/k activation
      memory at an UNCHANGED optimizer batch (the logical global batch, LR
      schedule, and gradient sync point all stay identical; for BN-free
      models the summed micro-gradients equal the big-batch gradient
      exactly, tested). Gradients accumulate in the scan carry (O(params),
      never k×); dropout keys fold per micro-batch; BN batch stats update
      sequentially per micro-batch (the standard accumulation semantics).
      The cross-replica all-reduce still happens ONCE, on the accumulated
      gradient — accumulation also divides collective bandwidth per sample.
    - `grad_accum_shard=True` (requires BOTH of the above): the ZeRO-2-
      flavored composition — each micro-gradient is reduce-scattered
      INSIDE the scan and only this replica's 1/N flat shard accumulates
      in the carry, so the persistent accumulator is O(params/N) instead
      of O(params) (the transient per-micro-batch gradient still
      materializes, as in any backward pass). Cost: k scatter legs per
      step instead of one — k× the scatter-leg wire bytes, the explicit
      memory-for-bandwidth trade. The update it computes is the same mean
      gradient (scatter-then-sum == sum-then-scatter up to fp summation
      order; with a bf16 wire each micro-leg rounds once, k roundings
      instead of one — both compositions tested).
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
    - `shard_gradients=True` (requires `zero1`): ZeRO-2 — gradient state is
      held ONLY as this replica's 1/N flat shard. At `grad_accum_steps=1`
      the (bucketed) reduce-scatter consumes each bucket's transient
      gradients directly, so no persistent full-gradient buffer exists; at
      `grad_accum_steps>1` the scan accumulator is the 1/N shard (the
      `grad_accum_shard` composition, now implied — the accumulator drops
      from O(params) to O(params/N), utils/scaling_model.py
      `gradient_state_bytes_per_chip`). Grad-norm/clipping already ran on
      the sharded form under ZeRO-1 (psum of shard partials); ZeRO-2 keeps
      that exact expression.
    - `shard_params=True` (requires `shard_gradients` + `params_struct`):
      ZeRO-3 — `state.params` (and `state.ema_params`) are held ONLY as
      this replica's 1/N flat shard of the padded flat vector
      (bucket-major when bucketed, canonical ravel order otherwise). The
      step [SYNC] all-gathers the full param tree ONCE up front — one
      `all_gather` PER BUCKET under the bucketed exchange, each depending
      only on the step's param-shard INPUT (zero compute ancestry), so
      every gather is overlap-capable and the lowering carries gathers ==
      buckets (`hlo_overlap_report` gather witness). The gathered replica
      is a step TRANSIENT: XLA frees it after its last consumer, nothing
      downstream persists it — per-chip persistent param bytes drop to
      O(params/N) (utils/scaling_model.py `param_bytes_per_chip`). The
      gradient side is byte-for-byte the ZeRO-2 scatter; the optimizer
      updates the resident shard directly and the ZeRO-1/2 trailing
      re-sync gather DISAPPEARS (next step's just-in-time gather plays
      that role), so zero3 moves the same gather bytes per step as zero2
      — earlier in the step, and on the `mesh.reduce_dtype` wire (the
      single-sourced cast_to_wire/cast_from_wire; fp32 truth stays in the
      shard). At the default fp32 wire the gathered tree is bit-identical
      to the ZeRO-2 replicated params, so loss trajectories are EQUAL
      (tests/test_zero3.py pins the grid); a narrowed wire trades that
      strict equality for halved gather bytes — zero3 is the only basis
      where BOTH legs narrow. `grad_accum_steps>1` gathers once OUTSIDE
      the scan (the carry stays the 1/N gradient shard). Off (default):
      the ZeRO-2 step, lowered-text-identical (kill-switch pin).
    - `comm_bucket_mb>0` (parallel/buckets.py): bucketed, overlap-capable
      gradient exchange — the param tree partitions into size-targeted
      buckets in reverse-backward order and each bucket's collective
      (per-bucket pmean in plain DP, per-bucket psum_scatter under
      sharding) is emitted against ONLY that bucket's gradients, so the
      lowered HLO carries >= 2 gradient collectives with no dependency
      path to the rest of the backward — the structure XLA's
      latency-hiding scheduler overlaps (committed assertion:
      buckets.hlo_overlap_report, tests/test_comm_buckets.py,
      benchmarks/comm_overlap_bench.py). Under sharding the opt-state
      flat layout becomes bucket-major replica-interleaved
      (GradBucketLayout.to_global; checkpoint migration via
      parallel/zero.convert_opt_state + the geometry receipt in the
      checkpoint's `extra`). Unset (0) keeps the pre-r14 monolithic
      exchange and flat layout byte-for-byte — the kill-switch
      lowered-text identity is pinned.
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
    if state_specs is None:
        state_specs = P()
    if grad_accum_shard and not (zero1 and grad_accum_steps > 1):
        raise ValueError(
            "grad_accum_shard requires zero1 optimizer-state sharding AND "
            f"grad_accum_steps > 1 (got zero1={zero1}, "
            f"grad_accum_steps={grad_accum_steps}) — without both there is "
            "no sharded accumulator to build")
    if shard_gradients and not zero1:
        raise ValueError(
            "shard_gradients (ZeRO-2) requires zero1 optimizer-state "
            "sharding — there is no shard frame to hold gradients in")
    if shard_params:
        if not (zero1 and shard_gradients):
            raise ValueError(
                "shard_params (ZeRO-3) requires shard_gradients (ZeRO-2) — "
                "the sharding ladder is cumulative; params sharded without "
                "a sharded gradient frame would re-materialize O(params) "
                "gradient state every step")
        if params_struct is None:
            raise ValueError(
                "shard_params (ZeRO-3) requires params_struct — "
                "state.params is the flat shard, so the step cannot "
                "recover the tree geometry from it")
    # ZeRO-2 implies the sharded scan accumulator whenever a scan exists
    # (the explicit grad_accum_shard flag stays as the ZeRO-1 opt-in).
    grad_accum_shard = grad_accum_shard or (shard_gradients
                                            and grad_accum_steps > 1)
    # Bucketed exchange (parallel/buckets.py): geometry is decided at trace
    # time from the params tree — 0 keeps the monolithic pre-r14 paths.
    bucket_bytes = int(round(comm_bucket_mb * 1024 * 1024)) \
        if comm_bucket_mb else 0
    # Static per-run exchange receipt, filled at first trace (the layout
    # needs leaf shapes). Read by the trainer's per-window `comm` JSONL
    # block and the comm/* counters below.
    comm_meta: dict = {}
    # LRN call sites of the traced step by what they lowered to (ops/lrn.py:
    # the fused kernel pair, or an XLA form), filled at first trace like
    # comm_meta; gauges lrn/fused_sites and lrn/fallback_sites below.
    lrn_sites: dict = {}
    num_shards = mesh.shape[data_axis]
    # mesh.reduce_dtype: wire dtype for the gradient sync only (None = the
    # gradients' own fp32). Halves collective bytes at ~16 mantissa bits of
    # gradient precision; momentum/params/param-all-gather stay fp32.
    wire_dtype = (None if reduce_dtype in ("float32", None)
                  else jnp.dtype(reduce_dtype))

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
                ce, counts = model.apply({"params": params}, images, labels,
                                         method="next_token_loss")
                with jax.named_scope("loss"):
                    l2 = l2_regularization(params, weight_decay)
                    metrics = {"loss": ce, "l2_loss": l2,
                               **_expert_load_metrics(counts)}
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

        # Bucketed-exchange geometry (trace-time, pure function of leaf
        # shapes — deterministic, so the trainer's separately-built layout
        # for specs/init/checkpointing can never disagree with the step's).
        # Under ZeRO-3 state.params IS the flat shard, so the tree geometry
        # comes from params_struct instead (same leaves, same layout).
        param_geom = params_struct if shard_params else state.params
        bucket_layout = None
        if bucket_bytes > 0:
            from distributed_vgg_f_tpu.parallel.buckets import (
                build_bucket_layout)
            bucket_layout = build_bucket_layout(param_geom, num_shards,
                                                bucket_bytes)

        # ZeRO flat-shard geometry — computed ONCE so the scan carry shape,
        # the scatter padding, and the param-shard slicing below can never
        # disagree (they all derive from these three numbers).
        if zero1:
            from jax.flatten_util import ravel_pytree
            n_elem = sum(x.size for x in jax.tree.leaves(param_geom))
            if bucket_layout is not None:
                shard_size = bucket_layout.shard_size
            else:
                padded = padded_flat_size(n_elem, num_shards)
                shard_size = padded // num_shards

        if not comm_meta:
            from distributed_vgg_f_tpu.parallel.buckets import (
                exchange_wire_bytes, sharding_basis)
            n_all = sum(x.size for x in jax.tree.leaves(param_geom))
            comm_meta.update({
                # the EFFECTIVE basis: zero1/shard_gradients are already
                # post-downgrade here (single source: buckets.sharding_basis)
                "sharding": sharding_basis(zero1,
                                           zero1 and shard_gradients,
                                           shard_params),
                "bucketed": bucket_layout is not None,
                "buckets": (bucket_layout.num_buckets
                            if bucket_layout is not None
                            else (1 if zero1
                                  else len(jax.tree.leaves(param_geom)))),
                "bucket_mb": float(comm_bucket_mb or 0.0),
                "reduce_dtype": reduce_dtype or "float32",
                "grad_accum_steps": grad_accum_steps,
                # all_gather collectives per step: 0 in plain DP; the single
                # trailing (S,) re-sync gather under ZeRO-1/2; one PER
                # BUCKET under bucketed ZeRO-3 (the just-in-time fetch —
                # hlo_overlap_report's `gathers` witnesses this count)
                "gathers": (0 if not zero1
                            else (bucket_layout.num_buckets
                                  if shard_params
                                  and bucket_layout is not None else 1)),
            })
            # one shared byte accounting for bucketed AND monolithic
            # (bucketing changes the schedule, never the byte totals)
            padded_total = (bucket_layout.total_padded
                            if bucket_layout is not None
                            else (padded if zero1 else 0))
            comm_meta.update(exchange_wire_bytes(
                n_all, padded_total, zero=zero1, wire_dtype=wire_dtype,
                shard_params=shard_params))
            # scatter-leg bytes scale with the scan: k micro-scatters
            if grad_accum_shard and grad_accum_steps > 1:
                comm_meta["scatter_bytes"] *= grad_accum_steps
                comm_meta["wire_bytes"] = (comm_meta["scatter_bytes"]
                                           + comm_meta["gather_bytes"])

        @jax.named_scope("exchange")
        def scatter_mean_shard(g_tree):
            """Ravel + pad + [SYNC] reduce-scatter one gradient pytree to
            this replica's fp32 mean 1/N flat shard — PER BUCKET when the
            bucketed exchange is on (each bucket's collective consumes only
            its own gradients: the overlap-capable emission), one flat
            monolith otherwise. mesh.reduce_dtype: the scatter leg may move
            a narrower wire dtype through the single-sourced cast
            (collectives.cast_to_wire; cast back for the mean and
            everything downstream); the param all-gather below ALWAYS
            stays fp32 — replicas must re-sync exactly."""
            if bucket_layout is not None:
                return bucket_layout.scatter_mean_shards(
                    g_tree, data_axis, wire_dtype=wire_dtype)
            from distributed_vgg_f_tpu.parallel.collectives import (
                cast_from_wire, cast_to_wire)
            flat_g, _ = ravel_pytree(g_tree)
            send = cast_to_wire(jnp.pad(flat_g, (0, padded - n_elem)),
                                wire_dtype)
            return cast_from_wire(jax.lax.psum_scatter(
                send, data_axis, scatter_dimension=0,
                tiled=True), jnp.float32) / num_shards

        # ZeRO-3 just-in-time parameter gather — ONCE, up front (and OUTSIDE
        # the grad-accum scan: the scan carry stays the 1/N gradient shard;
        # re-gathering per micro-batch would move k× the gather bytes for
        # params that cannot have changed mid-step). Each bucket's
        # all_gather consumes a static slice of the step's param-shard
        # INPUT, so none has compute ancestry — the overlap license the
        # committed gather witness asserts. The gathered tree is a step
        # transient; at a fp32 wire it is bit-identical to the ZeRO-2
        # replicated params (the equality-grid pin).
        if shard_params:
            from distributed_vgg_f_tpu.parallel.collectives import (
                cast_from_wire, cast_to_wire)
            from distributed_vgg_f_tpu.parallel.zero import _unflatten_like
            with jax.named_scope("exchange"):
                if bucket_layout is not None:
                    full_params = bucket_layout.gather_param_tree(
                        state.params, data_axis, wire_dtype=wire_dtype)
                else:
                    full = cast_from_wire(jax.lax.all_gather(
                        cast_to_wire(state.params, wire_dtype), data_axis,
                        tiled=True), jnp.float32)
                    full_params = _unflatten_like(full[:n_elem],
                                                  params_struct)
        else:
            full_params = state.params

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

            if grad_accum_shard:
                # ZeRO-2-flavored carry: this replica's 1/N flat gradient
                # shard, fp32 — each micro-gradient is scattered right away
                # and only the shard persists across micro-batches.
                accumulate = lambda g_acc, g: g_acc + scatter_mean_shard(g)
                g_init = jnp.zeros((shard_size,), jnp.float32)
            else:
                accumulate = lambda g_acc, g: jax.tree.map(jnp.add, g_acc, g)
                g_init = jax.tree.map(jnp.zeros_like, state.params)

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
                return (accumulate(g_acc, g), bs_new), m

            micro_xs = (im, lb) + (() if lb2 is None else (lb2,)) \
                + (jnp.arange(grad_accum_steps),)
            (g_sum, new_batch_stats), metrics_stack = jax.lax.scan(
                micro_step, (g_init, state.batch_stats), micro_xs)
            if grad_accum_shard:
                accum_grad_shard = g_sum / grad_accum_steps
                grads = None   # never materialized whole past a micro-step
            else:
                grads = jax.tree.map(lambda g: g / grad_accum_steps, g_sum)
                accum_grad_shard = None
            metrics = jax.tree.map(lambda m: jnp.mean(m, axis=0),
                                   metrics_stack)
        else:
            loss_fn = make_loss_fn(images, labels, mix_labels,
                                   state.batch_stats, rng)
            (_, (new_batch_stats, metrics)), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(full_params)
            accum_grad_shard = None
        with jax.named_scope("step_metrics"):
            metrics = cross_replica_mean(metrics, data_axis)

        if zero1:
            if accum_grad_shard is not None:
                # grad_accum_shard: the scatter already happened per
                # micro-batch inside the scan; the mean shard is in hand.
                grad_shard = accum_grad_shard
            else:
                # reduce-scatter half of the all-reduce: each replica owns
                # the mean gradient for its contiguous 1/N flat shard.
                grad_shard = scatter_mean_shard(grads)
            with jax.named_scope("optimizer"):
                grad_norm = jnp.sqrt(jax.lax.psum(
                    jnp.sum(jnp.square(grad_shard)), data_axis))
                if grad_clip_norm > 0:
                    grad_shard = _clip_by_global_norm(grad_shard, grad_norm,
                                                      grad_clip_norm)

                if shard_params:
                    # ZeRO-3: the resident (S,) flat shard IS the optimizer's
                    # parameter frame — no slicing out of a replicated tree,
                    # and (below) no trailing re-sync gather: the NEXT step's
                    # just-in-time gather reconstitutes the tree from exactly
                    # what the ZeRO-2 step would have stored.
                    param_shard = state.params
                    unravel = None
                elif bucket_layout is not None:
                    # bucket-major flat frame (parallel/buckets.py): the
                    # param shard, the opt-state vectors, and the gathered
                    # update all live in GradBucketLayout's
                    # replica-interleaved layout
                    param_shard = bucket_layout.local_param_shard(
                        state.params, data_axis)
                else:
                    flat_params, unravel = ravel_pytree(state.params)
                    offset = jax.lax.axis_index(data_axis) * shard_size
                    param_shard = jax.lax.dynamic_slice_in_dim(
                        jnp.pad(flat_params, (0, padded - n_elem)), offset,
                        shard_size)
                updates_shard, new_opt_state = tx.update(
                    grad_shard, state.opt_state, param_shard)
                new_param_shard = optax.apply_updates(param_shard,
                                                      updates_shard)
            if shard_params:
                # ZeRO-3 persists the shard itself — params stay O(1/N).
                new_params = new_param_shard
            else:
                # [SYNC] all-gather half: replicas re-sync the updated
                # parameters.
                with jax.named_scope("exchange"):
                    if bucket_layout is not None:
                        new_params = bucket_layout.gather_params(
                            new_param_shard, data_axis)
                    else:
                        new_flat = jax.lax.all_gather(
                            new_param_shard, data_axis, tiled=True)
                        new_params = unravel(new_flat[:n_elem])
            metrics["grad_norm"] = grad_norm
        else:
            # [SYNC] — the one cross-replica point per step (reference: NCCL/MPI
            # ring all-reduce; here: XLA ICI all-reduce emitted from pmean).
            # Bucketed: one pmean per size-targeted bucket instead of one
            # per leaf — same elementwise math, ICI-friendly message sizes.
            with jax.named_scope("exchange"):
                if bucket_layout is not None:
                    grads = bucket_layout.pmean_buckets(
                        grads, data_axis, wire_dtype=wire_dtype)
                else:
                    grads = all_reduce_gradients(grads, data_axis,
                                                 reduce_dtype=wire_dtype)
            with jax.named_scope("optimizer"):
                grad_norm = optax.global_norm(grads)
                if grad_clip_norm > 0:
                    grads = _clip_by_global_norm(grads, grad_norm,
                                                 grad_clip_norm)
                updates, new_opt_state = tx.update(grads, state.opt_state,
                                                   state.params)
                new_params = optax.apply_updates(state.params, updates)
            metrics["grad_norm"] = grad_norm

        if schedule is not None:
            with jax.named_scope("step_metrics"):
                metrics["lr"] = schedule(state.step)

        # Parameter EMA (train.ema_decay): stored like params — replicated
        # tree under DP/ZeRO-1/2 (it tracks the post-all-gather params);
        # under ZeRO-3 both sides are the resident (S,) flat shard, so the
        # identical elementwise update shards for free. BN moving stats are
        # averaged with the same decay (the TF recipe's
        # moving_average_variables). Fused into the same XLA computation as
        # the step.
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
        in_specs=(state_specs, P(data_axis), P()),
        out_specs=(state_specs, P()),
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

    @functools.wraps(jitted)
    def dispatch(state, batch, rng):
        rec = telemetry.get_recorder()
        if not rec.enabled:
            return jitted(state, batch, rng)
        with rec.span("train_step_dispatch", "dispatch"):
            out = jitted(state, batch, rng)
        telemetry.inc("step/dispatched")
        # comm/* receipts (ISSUE 11): per-step exchange counters + the
        # static exchange-shape gauges, single-sourced from the geometry
        # the trace actually used (comm_meta fills on first trace, so the
        # first dispatch already sees it)
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
            reg = telemetry.get_registry()
            reg.set_gauge("comm/buckets_per_step", comm_meta["buckets"])
            reg.set_gauge("comm/bucket_mb", comm_meta["bucket_mb"])
        for kind, count in lrn_sites.items():
            telemetry.get_registry().set_gauge(f"lrn/{kind}_sites", count)
        return out

    dispatch.lower = jitted.lower
    # the static exchange receipt (trainer JSONL `comm` block, bench rows);
    # empty until the first trace fills it
    dispatch.comm_meta = comm_meta
    dispatch.lrn_sites = lrn_sites
    return dispatch


def build_eval_step(model, mesh: Mesh, data_axis: str = "data",
                    state_specs=None,
                    device_finish: Callable | None = None,
                    param_gather: Callable | None = None,
                    ) -> Callable[[TrainState, Batch], Mapping[str, jnp.ndarray]]:
    """Jitted eval step returning psum-accumulated correct counts
    (SURVEY.md §3.4): {'top1': n_correct, 'top5': n_correct5, 'count': n}.

    `state_specs` mirrors the train step's so a ZeRO-1-sharded state is consumed
    in place (eval never touches opt state, so no gather is emitted).
    `param_gather` (ZeRO-3, r21): a closure mapping the resident (S,) flat
    param shard back to the full params tree INSIDE the shard_map body (the
    trainer builds it over the same bucket layout the train step uses;
    always fp32 — eval must score the exact weights). None = params are the
    ordinary replicated tree."""
    if state_specs is None:
        state_specs = P()

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
        if param_gather is not None:
            with jax.named_scope("exchange"):
                params = param_gather(state.params)
        else:
            params = state.params
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
                        in_specs=(state_specs, P(data_axis)),
                        out_specs=P(),
                        check_vma=False)
    return jax.jit(sharded)
