"""Live elastic resize — continue training on the survivors when k of N
data shards are preempted (r19, ROADMAP item 1; the cross-replica
weight-resharding move of arXiv 2004.13336 closed into the recovery loop
that arXiv 1605.08695's restart-from-checkpoint model never closes).

The pieces were all staged by earlier rounds; this module composes them
into one in-place transition:

1. **Who died** — `PreemptConsensus.flagged_ranks` (parallel/preempt.py)
   or the rank-targeted chaos token (`preempt@rankR[+R2...]:N`,
   resilience/faults.py) names the dead data-axis positions.
2. **Shrunken mesh** — `shrink_mesh` drops the dead positions from the
   device array; survivor devices keep their order, so the new mesh is
   the old one with the reclaimed capacity cut out.
3. **Param/opt-state reshard** — `reshard_train_state` generalizes the
   checkpoint-mediated retopology path (checkpoint/retopology.py) to a
   LIVE any-geometry N→N−k conversion: params/EMA/batch_stats are
   replicated (survivors already hold full replicas — nothing to
   evacuate), and the ZeRO-1/2 flat opt-state vector is re-partitioned /
   re-bucketed through `zero.convert_opt_state` with the r14
   `GradBucketLayout` geometry receipts on both sides, placed straight
   into the new topology by jit `out_shardings`. In a real multi-host
   fleet the dead ranks' shards come from the forced preemption
   checkpoint (written before the resize is attempted); single-controller
   meshes read them from the survivor-held global view directly.
4. **Data handoff** — pure cursor handoff via the PR 15 iterator-state
   blob: the trainer captures `capture_state(next_step)`, builds a FRESH
   ingest over the new topology, and `restore_from_blob` re-derives the
   stream at the exact position (every stream is a pure function of
   (seed, position)) — zero replayed batches, routing-only ownership for
   the disaggregated service (data/service_client.py already reassigns a
   dead worker's cursors without moving data).
5. **Batch semantics** — explicit, not implicit (`ResizePlan.batch_policy`
   from `mesh.elastic.batch_policy`): `keep_global` reassigns the dead
   shards' rows to survivors (global batch and LR unchanged — the loss
   trajectory is pinned equal to a restart-from-checkpoint control on the
   same survivor count); `scale_lr` keeps the per-replica batch invariant
   (survivors keep exactly their own rows via `trim_batches`) and
   rescales the LR by N′/N (linear-scaling rule), receipted in the
   `elastic_lr_rescale` log event.

Everything that can make the transition unsound refuses loudly instead:
`plan_resize` raises the typed `ElasticDegraded` (resilience/errors.py)
and the trainer falls back to the r18 restart-from-checkpoint path with
the `elastic_degraded_restart` flight class — never `unhandled_exception`.
"""

from __future__ import annotations

import dataclasses
from typing import Iterator, Sequence

import jax
import numpy as np
from jax.sharding import Mesh

from distributed_vgg_f_tpu.resilience.errors import ElasticDegraded


@dataclasses.dataclass(frozen=True)
class ResizePlan:
    """One planned N→N−k transition, fully decided before anything moves."""

    old_size: int                 # data-axis size before the resize
    new_size: int                 # survivor count (the new data-axis size)
    dead_ranks: tuple             # data-axis positions being reclaimed
    batch_policy: str             # keep_global | scale_lr
    lr_scale: float               # 1.0 under keep_global; N'/N under scale_lr

    @property
    def topology_label(self) -> str:
        """The regression-sentinel basis label (regress.Basis.topology):
        `elastic_<N>to<M>` — a post-resize rate and a static-mesh rate are
        different machines and must never gate cross-wise."""
        return f"elastic_{self.old_size}to{self.new_size}"

    def describe(self) -> dict:
        return {"old_size": self.old_size, "new_size": self.new_size,
                "dead_ranks": list(self.dead_ranks),
                "batch_policy": self.batch_policy,
                "lr_scale": self.lr_scale,
                "topology": self.topology_label}


def plan_resize(mesh: Mesh, data_axis: str, dead_ranks: Sequence[int], *,
                elastic_cfg, global_batch: int,
                have_cursor: bool) -> ResizePlan:
    """Validate a proposed resize and freeze it into a `ResizePlan`, or
    raise `ElasticDegraded` with a machine-readable `.reason` naming why
    the fleet should restart instead. Nothing is mutated here — the plan
    is decided in full before the trainer touches any live object, so a
    refused resize leaves the r18 stop path bit-for-bit intact."""
    old_size = int(mesh.shape[data_axis])
    dead = tuple(sorted({int(r) for r in dead_ranks}))
    if not dead:
        raise ElasticDegraded(
            "unidentified_ranks",
            "preemption consensus fired but no dead rank was identified "
            "(untargeted preempt or a signal with no flagged rank) — "
            "cannot plan a survivor set")
    if any(r < 0 or r >= old_size for r in dead):
        raise ElasticDegraded(
            "rank_out_of_range",
            f"dead ranks {list(dead)} not all within the data axis "
            f"[0, {old_size})")
    if jax.process_count() > 1:
        # Honest scope: re-forming a jax.distributed world over fewer
        # processes needs a coordinator restart — the LIVE in-place resize
        # is a single-controller (one process, many devices) move; a
        # multi-controller fleet takes the checkpointed restart onto the
        # survivor slice (the checkpoint restores onto any topology,
        # checkpoint/retopology.py).
        raise ElasticDegraded(
            "multi_controller",
            f"live in-place resize is single-controller; "
            f"{jax.process_count()} processes must restart onto the "
            "survivor slice (retopology restore handles the geometry)")
    new_size = old_size - len(dead)
    if new_size < max(1, int(elastic_cfg.min_survivors)):
        raise ElasticDegraded(
            "too_few_survivors",
            f"{new_size} survivor(s) < mesh.elastic.min_survivors="
            f"{elastic_cfg.min_survivors} — restart on fresh capacity "
            "instead of limping")
    policy = elastic_cfg.batch_policy
    if policy == "keep_global":
        if global_batch % new_size != 0:
            raise ElasticDegraded(
                "indivisible_global_batch",
                f"keep_global needs data.global_batch_size={global_batch} "
                f"divisible by the survivor count {new_size}")
        lr_scale = 1.0
    else:  # scale_lr (config validated the enum)
        per_replica, rem = divmod(global_batch, old_size)
        if rem != 0:
            raise ElasticDegraded(
                "indivisible_global_batch",
                f"scale_lr needs data.global_batch_size={global_batch} "
                f"divisible by the OLD shard count {old_size} (per-replica "
                "rows must be whole)")
        lr_scale = new_size / old_size
    if not have_cursor:
        raise ElasticDegraded(
            "no_resumable_ingest",
            "elastic data handoff needs the position-exact cursor blob "
            "(data.iterator_state.enabled + a trainer-owned stream); "
            "without it a resize would replay or skip batches")
    return ResizePlan(old_size=old_size, new_size=new_size, dead_ranks=dead,
                      batch_policy=policy, lr_scale=lr_scale)


def survivor_ranks(plan: ResizePlan) -> tuple:
    dead = set(plan.dead_ranks)
    return tuple(r for r in range(plan.old_size) if r not in dead)


def shrink_mesh(mesh: Mesh, data_axis: str, plan: ResizePlan) -> Mesh:
    """The survivor mesh: the old device array with the dead data-axis
    positions removed, order preserved — every surviving device keeps its
    relative rank, so survivor-held arrays re-place without permutation."""
    axis_idx = list(mesh.axis_names).index(data_axis)
    dev_array = np.take(mesh.devices, survivor_ranks(plan), axis=axis_idx)
    return Mesh(dev_array, axis_names=tuple(mesh.axis_names))


def reshard_train_state(state, source, target, mesh: Mesh):
    """Live any-geometry reshard of a TrainState from the exchange plan
    `source` (parallel/zero.py `Exchange`, the one it was trained under)
    to `target`'s layout on the new `mesh`.

    The state is first pulled to host as its GLOBAL value (on a
    single-controller mesh every shard is addressable; `plan_resize`
    refused anything else — a multi-host fleet reads the same global view
    out of the forced preemption checkpoint via retopology restore). The
    opt state then flows through the SAME pure converter the checkpoint
    path uses (`zero.convert_opt_state`) under jit whose `out_shardings`
    place the result directly into the new topology. Params and EMA (r21):
    replicated trees re-place with one `device_put`; ZeRO-3 flat vectors
    flow through the matching `zero.convert_params` (the N→M re-interleave
    is a real permutation when bucketed, a re-pad when canonical) — also
    back to the replicated tree where the new topology holds them so (the
    zero3 → zero2/dp downgrade, e.g. a resize to one shard).
    Step/batch_stats are replicated in ALL layouts. Both the elastic path
    and a restart control therefore apply the identical conversion — which
    is what makes the chaos-grid trajectory equality a meaningful pin
    rather than a coincidence."""
    import functools

    from jax.sharding import NamedSharding, PartitionSpec as P

    from distributed_vgg_f_tpu.parallel.zero import (convert_opt_state,
                                                     convert_params)

    # the params TREE geometry is a function of the model alone; a dp
    # target that has not traced yet takes it from the source or the state
    target = target.bind(source.params_struct if source.sharded
                         else state.params)
    replicated = NamedSharding(mesh, P())
    shardings = target.state_shardings(mesh)
    host_state = jax.device_get(state)
    new_opt = jax.jit(
        functools.partial(convert_opt_state, source=source, target=target),
        out_shardings=(shardings.opt_state if target.sharded
                       else replicated))(host_state.opt_state)
    converted = {"opt_state": new_opt}
    if source.zero3 or target.zero3:
        conv_p = jax.jit(
            functools.partial(convert_params, source=source, target=target),
            out_shardings=shardings.params if target.zero3 else replicated)
        converted["params"] = conv_p(host_state.params)
        converted["ema_params"] = (
            conv_p(host_state.ema_params)
            if host_state.ema_params is not None else None)
    placed = jax.tree.map(lambda l: jax.device_put(l, replicated),
                          host_state.replace(**{k: None for k in converted}))
    return placed.replace(**converted)


def trim_batches(source: Iterator, plan: ResizePlan,
                 global_batch: int) -> Iterator:
    """The `scale_lr` host-batch adapter: each survivor keeps exactly ITS
    OWN contiguous per-replica rows; the dead ranks' rows are dropped (the
    global batch shrinks by N′/N — the LR rescale compensates). No
    mid-stream rebatching: regrouping rows would fork the SplitMix64
    shuffle basis the cursor blob names, so the stream stays a pure
    function of (seed, position) and cursor counting is unchanged."""
    per = global_batch // plan.old_size
    keep = np.concatenate([np.arange(r * per, (r + 1) * per)
                           for r in survivor_ranks(plan)])

    def gen():
        for batch in source:
            yield {k: np.asarray(v)[keep] for k, v in batch.items()}

    return gen()
