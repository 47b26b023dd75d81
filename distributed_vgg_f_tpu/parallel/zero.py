"""ZeRO-1-style optimizer-state sharding over the data-parallel axis.

Reference context: the reference keeps a full optimizer-state replica per worker
(plain synchronous DP — SURVEY.md §2.3). PAPERS.md retrieved "Automatic
Cross-Replica Sharding of Weight Update in Data-Parallel Training" against it;
SURVEY.md §2.3 marks weight-update sharding as the one stretch strategy worth
building. This module is that strategy, TPU-native:

    grads (per-replica)
      └─ flatten to one vector, pad to a multiple of N
      └─ `lax.psum_scatter`  — each replica receives its 1/N contiguous shard of
         the SUM of gradients (one reduce-scatter on ICI instead of the
         all-reduce; half the bytes moved)
      └─ optimizer update on the shard only — momentum/opt state is physically
         sharded over the data axis (1/N memory per chip)
      └─ `lax.all_gather` of the updated parameter shard — replicas re-sync

reduce-scatter + all-gather moves the same total bytes as the all-reduce they
replace (an all-reduce IS a reduce-scatter + all-gather), so step time is
unchanged while optimizer memory drops by N — the paper's observation, natively
expressed in XLA collectives.

The flat-vector layout (rather than per-leaf sharding) keeps every collective a
single large contiguous transfer — ICI-bandwidth-friendly — and makes the shard
boundary independent of parameter-tree structure.

This module owns the exchange as ONE value, `Exchange` (built by
`plan_exchange`): which basis (dp | zero1 | zero2 | zero3) a run really has
after the one-shard downgrade, over which flat layout (canonical ravel order
here, bucket-major in parallel/buckets.py), on which wire. The train step, the
trainer, `TrainState.create`, the elastic resize and the checkpoint converter
ask it; none of them names a layout.
"""

from __future__ import annotations

import dataclasses
import math
from typing import TYPE_CHECKING, Any

import jax
import jax.numpy as jnp
import optax
from jax import lax
from jax.sharding import PartitionSpec as P

from distributed_vgg_f_tpu.parallel.buckets import (
    build_bucket_layout,
    exchange_wire_bytes,
    sharding_basis,
)
from distributed_vgg_f_tpu.parallel.buckets import (
    layout_from_receipt as _bucket_layout_from_receipt,
)
from distributed_vgg_f_tpu.parallel.collectives import (
    all_reduce_gradients,
    cast_from_wire,
    cast_to_wire,
)

if TYPE_CHECKING:  # runtime import is deferred into Exchange.bind:
    # train/__init__ -> trainer -> step -> this module would cycle when the
    # package is entered via `parallel.zero` first
    from distributed_vgg_f_tpu.train.state import TrainState


def flat_param_count(params_shapes: Any) -> int:
    """Total element count of a params pytree (of arrays or ShapeDtypeStructs)."""
    return int(sum(math.prod(l.shape) for l in jax.tree.leaves(params_shapes)))


def padded_flat_size(total: int, num_shards: int) -> int:
    """Flat vector length after padding to a multiple of the shard count."""
    return total + (-total) % num_shards


def opt_state_specs(opt_state_shapes: Any, padded: int, data_axis: str) -> Any:
    """PartitionSpecs for a ZeRO-1 optimizer state: every leaf that is the
    padded flat vector (momentum trace, etc.) shards over the data axis;
    scalars (schedule counts) stay replicated."""
    def spec(leaf):
        shape = getattr(leaf, "shape", ())
        if len(shape) >= 1 and shape[0] == padded:
            return P(data_axis)
        return P()
    return jax.tree.map(spec, opt_state_shapes)


def _shapes_of(tree: Any) -> Any:
    return jax.tree.map(lambda l: jax.ShapeDtypeStruct(l.shape, l.dtype),
                        tree)


def _unflatten_like(vec, params_struct):
    """Inverse of the canonical ravel given only shapes: split `vec` into
    the params tree (tree_leaves order, C-order reshape)."""
    leaves, off = [], 0
    for l in jax.tree.leaves(params_struct):
        n = math.prod(l.shape)
        leaves.append(jnp.reshape(vec[off:off + n], l.shape).astype(l.dtype))
        off += n
    return jax.tree.unflatten(jax.tree.structure(params_struct), leaves)


def _clip_by_global_norm(tree, grad_norm, clip_norm):
    """Scale a gradient pytree so its global norm is at most `clip_norm`.
    Shared by both frames so the replicated and ZeRO paths cannot drift."""
    scale = jnp.minimum(1.0, clip_norm / (grad_norm + 1e-12))
    return jax.tree.map(lambda g: g * scale, tree)


# ---------------------------------------------------------------------------
# The canonical flat layout (the bucket-major one is buckets.GradBucketLayout)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class CanonicalFlatLayout:
    """The flat vector in ravel (tree_leaves) order, zero-padded to a
    multiple of the shard count: replica r owns the r-th contiguous 1/N
    slice. Same method names as `buckets.GradBucketLayout`, so the plan
    below calls either without asking which; this one is the reference the
    bucketed layout is tested against and the format of every checkpoint
    written without `mesh.comm_bucket_mb`. `num_shards` is None for a saved
    vector read back without a receipt (`total_padded` is then its length
    and only `from_global` is meaningful)."""

    params_struct: Any                      # tree of ShapeDtypeStruct
    num_shards: int | None
    total_padded: int

    @property
    def n_elem(self) -> int:
        return flat_param_count(self.params_struct)

    @property
    def shard_size(self) -> int:
        return self.total_padded // self.num_shards

    @property
    def num_buckets(self) -> int:
        return 1

    def describe(self) -> dict:
        return {"kind": "canonical_flat", "num_shards": self.num_shards,
                "total_padded": self.total_padded}

    def _pad(self, flat):
        return jnp.pad(flat, (0, self.total_padded - self.n_elem))

    # -------------------------------------------------------- the DP leg
    def pmean_buckets(self, grads: Any, axis_name: str,
                      wire_dtype=None) -> Any:
        """One `pmean` per leaf (the reference's ring all-reduce)."""
        return all_reduce_gradients(grads, axis_name,
                                    reduce_dtype=wire_dtype)

    # ------------------------------------------------------ the ZeRO legs
    def scatter_mean_shards(self, grads: Any, axis_name: str,
                            wire_dtype=None):
        """Ravel + pad + [SYNC] reduce-scatter one gradient pytree to this
        replica's fp32 mean 1/N flat shard, as one flat monolith.
        mesh.reduce_dtype: the scatter leg may move a narrower wire dtype
        through the single-sourced cast (collectives.cast_to_wire; cast
        back for the mean and everything downstream)."""
        from jax.flatten_util import ravel_pytree
        flat_g, _ = ravel_pytree(grads)
        send = cast_to_wire(self._pad(flat_g), wire_dtype)
        return cast_from_wire(lax.psum_scatter(
            send, axis_name, scatter_dimension=0,
            tiled=True), jnp.float32) / self.num_shards

    def local_param_shard(self, params: Any, axis_name: str):
        """This replica's contiguous (S,) slice of the padded flat params —
        the piece the sharded optimizer updates."""
        from jax.flatten_util import ravel_pytree
        flat_params, _ = ravel_pytree(params)
        offset = lax.axis_index(axis_name) * self.shard_size
        return lax.dynamic_slice_in_dim(self._pad(flat_params), offset,
                                        self.shard_size)

    def gather_params(self, param_shard, axis_name: str) -> Any:
        """[SYNC] all-gather half: replicas re-sync the updated parameters.
        ALWAYS fp32 — replicas must re-sync exactly. The split is
        `ravel_pytree`'s own inverse, from shapes alone."""
        new_flat = lax.all_gather(param_shard, axis_name, tiled=True)
        leaves, treedef = jax.tree.flatten(self.params_struct)
        chunks = lax.split(new_flat[:self.n_elem],
                           [math.prod(l.shape) for l in leaves])
        return jax.tree.unflatten(treedef, [
            c.reshape(l.shape).astype(l.dtype)
            for c, l in zip(chunks, leaves)])

    def gather_param_tree(self, param_shard, axis_name: str,
                          wire_dtype=None) -> Any:
        """ZeRO-3 [SYNC] just-in-time gather of the resident shard back to
        the params tree, one collective; the wire may narrow (the gathered
        replica is a step transient — the fp32 truth stays in the
        shard)."""
        full = cast_from_wire(lax.all_gather(
            cast_to_wire(param_shard, wire_dtype), axis_name,
            tiled=True), jnp.float32)
        return self.from_global(full)

    # ------------------------------------------------- global flat layout
    def to_global(self, params: Any):
        """Params tree -> the (T,) canonical flat vector."""
        return self._pad(jnp.concatenate(
            [jnp.ravel(l).astype(jnp.float32)
             for l in jax.tree.leaves(params)]))

    def from_global(self, vec) -> Any:
        """Inverse of `to_global`; padding elements are dropped (so the
        shard count a saved vector was padded for need not be known)."""
        return _unflatten_like(vec[:self.n_elem], self.params_struct)


def canonical_layout(params: Any, num_shards: int) -> CanonicalFlatLayout:
    struct = _shapes_of(params)
    return CanonicalFlatLayout(
        struct, int(num_shards),
        padded_flat_size(flat_param_count(struct), num_shards))


def layout_from_receipt(params_struct: Any, receipt: dict | None,
                        saved_length: int | None = None):
    """The layout a geometry receipt (`describe()`) names, rebuilt on this
    params tree and verified against every recorded field — a mismatch
    raises the typed `GeometryReceiptError`. No receipt = the canonical
    layout of a pre-receipt checkpoint, `saved_length` long."""
    if receipt is None:
        return CanonicalFlatLayout(params_struct, None, int(saved_length))
    if receipt.get("kind") != "canonical_flat":
        return _bucket_layout_from_receipt(params_struct, receipt)
    from distributed_vgg_f_tpu.resilience.errors import GeometryReceiptError
    layout = canonical_layout(params_struct, int(receipt["num_shards"]))
    if layout.total_padded != int(receipt["total_padded"]):
        raise GeometryReceiptError(
            f"canonical-layout receipt does not reproduce on this params "
            f"tree: rebuilt total_padded={layout.total_padded} != recorded "
            f"{receipt['total_padded']} — the checkpoint was written for a "
            f"different model or geometry")
    return layout


# ---------------------------------------------------------------------------
# The exchange plan
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Exchange:
    """The gradient/parameter exchange of one run, decided once
    (`plan_exchange`) and asked by everything else.

    `basis` is the EFFECTIVE rung of the cumulative ladder:
    - dp: gradients are `pmean`-all-reduced before the optax update, so
      every replica applies the identical update — synchronous replicated
      SGD, the reference's semantics (SURVEY.md §2.4).
    - zero1: optimizer-state sharding — gradients are reduce-SCATTERED
      (`psum_scatter`), the optimizer updates only this replica's 1/N flat
      shard against the sharded opt state, and the updated parameter
      shards are all-gathered.
    - zero2: gradient state is held ONLY as this replica's 1/N flat shard.
      Without a scan the (bucketed) reduce-scatter consumes each bucket's
      transient gradients directly, so no persistent full-gradient buffer
      exists; with one, the scan accumulator is the 1/N shard
      (`accum_reduced`; O(params) to O(params/N), utils/scaling_model.py
      `gradient_state_bytes_per_chip`).
    - zero3: `state.params` (and `state.ema_params`) are held ONLY as this
      replica's 1/N shard of the flat vector; see `forward_params`.

    `bucket_mb > 0` (parallel/buckets.py): bucketed, overlap-capable
    exchange — the param tree partitions into size-targeted buckets in
    reverse-backward order and each bucket's collective (per-bucket pmean
    in plain DP, per-bucket psum_scatter under sharding) is emitted
    against ONLY that bucket's gradients, so the lowered HLO carries >= 2
    gradient collectives with no dependency path to the rest of the
    backward — the structure XLA's latency-hiding scheduler overlaps
    (committed assertion: buckets.hlo_overlap_report,
    tests/test_comm_buckets.py, benchmarks/comm_overlap_bench.py). Under
    sharding the flat layout becomes bucket-major replica-interleaved
    (GradBucketLayout.to_global; checkpoints migrate through
    `convert_opt_state` and the geometry receipt in their `extra`). Unset
    (0) keeps the monolithic exchange and canonical layout byte-for-byte.

    A plan is *bound* once it knows the parameter shapes (`bind`): a ZeRO
    plan before any state exists (its state's layout depends on them), a
    dp plan at the step's first trace at the latest."""

    axis: str
    num_shards: int
    basis: str                       # dp | zero1 | zero2 | zero3, effective
    bucket_mb: float
    reduce_dtype: str
    grad_accum_steps: int
    #: the scan's accumulator is the reduced 1/N shard: each micro-gradient
    #: is reduce-scattered INSIDE the scan, so the persistent accumulator is
    #: O(params/N) (the transient per-micro-batch gradient still
    #: materializes). Cost: k scatter legs per step instead of one. The
    #: update is the same mean gradient (scatter-then-sum == sum-then-
    #: scatter up to fp summation order; with a bf16 wire each micro-leg
    #: rounds once, k roundings instead of one — both tested). Implied by
    #: zero2 with a scan; `train.grad_accum_shard` opts zero1 in.
    accum_reduced: bool
    tx: optax.GradientTransformation
    params_struct: Any = None        # params TREE shapes, once bound
    layout: Any = None               # CanonicalFlatLayout | GradBucketLayout
    state_specs: Any = P()           # TrainState of PartitionSpecs under ZeRO

    # ------------------------------------------------------------ geometry
    @property
    def sharded(self) -> bool:
        return self.basis != "dp"

    @property
    def zero2(self) -> bool:
        return self.basis in ("zero2", "zero3")

    @property
    def zero3(self) -> bool:
        return self.basis == "zero3"

    @property
    def wire_dtype(self):
        """mesh.reduce_dtype: wire dtype for the gradient sync (None = the
        gradients' own fp32). Halves collective bytes at ~16 mantissa bits
        of gradient precision; momentum/params and the ZeRO-1/2 re-sync
        gather stay fp32."""
        return (None if self.reduce_dtype in ("float32", None)
                else jnp.dtype(self.reduce_dtype))

    @property
    def total_padded(self) -> int | None:
        """The ZeRO flat length; None under replicated DP."""
        return self.layout.total_padded if self.sharded else None

    def bind(self, params: Any, batch_stats: Any = None,
             ema: bool = False) -> "Exchange":
        """The plan with its layout and state specs, from the params TREE
        (arrays or shapes; a bound plan is returned as it is). Geometry is a
        pure function of leaf shapes, so the state's layout, the scan
        carry, the scatter padding, the shard slicing and the checkpoint
        receipt all derive from this one layout. `batch_stats` and `ema`
        (ZeRO only) complete the TrainState the specs are for."""
        if self.layout is not None:
            return self
        from distributed_vgg_f_tpu.train.state import TrainState
        bucket_bytes = int(round(self.bucket_mb * 1024 * 1024))
        layout = (build_bucket_layout(params, self.num_shards, bucket_bytes)
                  or canonical_layout(params, self.num_shards))
        struct = _shapes_of(params)
        specs = P()
        if self.sharded:
            padded = layout.total_padded
            replicated = lambda tree: jax.tree.map(lambda _: P(), tree)
            param_specs = P(self.axis) if self.zero3 else replicated(struct)
            specs = TrainState(
                step=P(), params=param_specs,
                batch_stats=replicated(batch_stats or {}),
                opt_state=opt_state_specs(jax.eval_shape(
                    self.tx.init,
                    jax.ShapeDtypeStruct((padded,), jnp.float32)),
                    padded, self.axis),
                ema_params=param_specs if ema else None,
                ema_batch_stats=(replicated(batch_stats or {}) if ema
                                 else None))
        return dataclasses.replace(self, params_struct=struct, layout=layout,
                                   state_specs=specs)

    def state_shardings(self, mesh) -> Any:
        """`state_specs` as NamedShardings on `mesh` (one replicated
        sharding under plain DP): the `out_shardings` of whatever makes or
        converts a state for this plan."""
        from jax.sharding import NamedSharding
        return jax.tree.map(lambda spec: NamedSharding(mesh, spec),
                            self.state_specs,
                            is_leaf=lambda x: isinstance(x, P))

    def layout_state(self, params: Any) -> tuple:
        """`(stored params, opt_state)` for a fresh params tree: under ZeRO
        the optimizer state is initialized over the flat vector in this
        plan's layout (its vector leaves then shard over the data axis),
        and under zero3 the params themselves are stored as that vector."""
        if not self.sharded:
            return params, self.tx.init(params)
        flat = self.bind(params).layout.to_global(params)
        return (flat if self.zero3 else params), self.tx.init(flat)

    # --------------------------------------- inside the shard_map body
    def forward_params(self, params: Any) -> Any:
        """The parameters the forward pass reads. zero3: the step [SYNC]
        all-gathers the full param tree ONCE up front (and OUTSIDE the
        grad-accum scan: re-gathering per micro-batch would move k× the
        gather bytes for params that cannot have changed mid-step) — one
        `all_gather` PER BUCKET under the bucketed exchange, each
        depending only on the step's param-shard INPUT (zero compute
        ancestry), so every gather is overlap-capable and the lowering
        carries gathers == buckets (`hlo_overlap_report` gather witness).
        The gathered replica is a step TRANSIENT: XLA frees it after its
        last consumer — per-chip persistent param bytes drop to
        O(params/N) (utils/scaling_model.py `param_bytes_per_chip`). It
        rides the `mesh.reduce_dtype` wire; at the default fp32 wire the
        gathered tree is bit-identical to the ZeRO-2 replicated params, so
        loss trajectories are EQUAL (tests/test_zero3.py pins the grid); a
        narrowed wire trades that for halved gather bytes — zero3 is the
        only basis where BOTH legs narrow. Every other basis: identity."""
        if not self.zero3:
            return params
        with jax.named_scope("exchange"):
            return self.layout.gather_param_tree(
                params, self.axis, wire_dtype=self.wire_dtype)

    def eval_params(self, params: Any) -> Any:
        """Eval's view of `forward_params`: always fp32 (eval/predict must
        score the exact weights; the wire-narrowing is a train-only
        trade)."""
        if not self.zero3:
            return params
        with jax.named_scope("exchange"):
            return self.layout.gather_param_tree(params, self.axis)

    def accum_init(self, params: Any) -> Any:
        """The scan accumulator's zero: the 1/N flat shard when the scan
        reduces (`accum_reduced`), a params-shaped tree otherwise."""
        if self.accum_reduced:
            return jnp.zeros((self.layout.shard_size,), jnp.float32)
        return jax.tree.map(jnp.zeros_like, params)

    def accum_add(self, acc: Any, grads: Any) -> Any:
        if self.accum_reduced:
            return acc + self.reduce(grads)
        return jax.tree.map(jnp.add, acc, grads)

    def reduce(self, grads: Any) -> Any:
        """[SYNC] — a gradient tree to what `update` consumes. dp: the
        mean-all-reduced tree (reference: NCCL/MPI ring all-reduce; here
        XLA's ICI all-reduce from pmean — one per leaf, or one per
        size-targeted bucket: same elementwise math, ICI-friendly message
        sizes). ZeRO: the reduce-scatter half of that all-reduce — this
        replica's fp32 mean 1/N flat shard, PER BUCKET when bucketed (each
        bucket's collective consumes only its own gradients: the
        overlap-capable emission)."""
        with jax.named_scope("exchange"):
            if self.sharded:
                return self.layout.scatter_mean_shards(
                    grads, self.axis, wire_dtype=self.wire_dtype)
            return self.layout.pmean_buckets(grads, self.axis,
                                             wire_dtype=self.wire_dtype)

    def update(self, grads: Any, opt_state: Any, params: Any,
               clip_norm: float = 0.0) -> tuple:
        """`(new params, new opt_state, grad_norm)` from reduced gradients.
        Grad-norm and clipping run on the frame the gradients are in (psum
        of shard partials under ZeRO). ZeRO-1/2 update this replica's
        slice of the replicated params and [SYNC] all-gather the result.
        zero3: the resident (S,) shard IS the optimizer's parameter frame —
        no slicing out of a replicated tree and no trailing re-sync gather
        (the NEXT step's just-in-time gather reconstitutes the tree from
        exactly what the ZeRO-2 step would have stored), so zero3 moves
        the same gather bytes per step as zero2, earlier in the step."""
        resync = self.sharded and not self.zero3
        with jax.named_scope("optimizer"):
            if self.sharded:
                grad_norm = jnp.sqrt(lax.psum(
                    jnp.sum(jnp.square(grads)), self.axis))
            else:
                grad_norm = optax.global_norm(grads)
            if clip_norm > 0:
                grads = _clip_by_global_norm(grads, grad_norm, clip_norm)
            frame = (self.layout.local_param_shard(params, self.axis)
                     if resync else params)
            updates, new_opt_state = self.tx.update(grads, opt_state, frame)
            new_params = optax.apply_updates(frame, updates)
        if resync:
            with jax.named_scope("exchange"):
                new_params = self.layout.gather_params(new_params, self.axis)
        return new_params, new_opt_state, grad_norm

    # ------------------------------------------------ outside the mesh
    def params_tree(self, params: Any) -> Any:
        """Host-side inverse of the zero3 flat params: the global (T,)
        vector → the params tree; identity for every other basis. The
        offline surfaces (predict / serving restore) run outside the mesh,
        so they invert the layout here, not through the in-mesh gathers."""
        if not self.zero3:
            return params
        return self.layout.from_global(jnp.asarray(params))

    def receipts(self) -> dict:
        """The geometry receipts that ride EVERY checkpoint's `extra`.
        `opt_layout` (bucketed ZeRO only): a saved flat vector in the
        bucket-major layout is indistinguishable from the canonical one by
        shape, so restore reads this to pick the inverse permutation;
        absent = canonical (every pre-r14 checkpoint). `param_layout`
        (zero3 only): the SAVED params are the flat vector too, and its
        kind tells restore how to invert them (the bucket geometry itself
        is the opt_layout receipt, one layout for both vectors); absent =
        params are a tree (every pre-r21 checkpoint)."""
        extra = {}
        if not self.sharded:
            return extra
        described = self.layout.describe()
        if described["kind"] == "bucketed_flat":
            extra["opt_layout"] = described
        if self.zero3:
            extra["param_layout"] = {
                k: described[k]
                for k in ("kind", "num_shards", "total_padded")}
        return extra

    def comm_meta(self) -> dict:
        """The static per-run exchange receipt (the trainer's per-window
        `comm` JSONL block, the comm/* counters, bench rows)."""
        layout, sharded = self.layout, self.sharded
        bucketed = self.bucket_mb > 0
        meta = {
            "sharding": self.basis,
            "bucketed": bucketed,
            "buckets": (layout.num_buckets if bucketed or sharded
                        else len(jax.tree.leaves(self.params_struct))),
            "bucket_mb": float(self.bucket_mb or 0.0),
            "reduce_dtype": self.reduce_dtype or "float32",
            "grad_accum_steps": self.grad_accum_steps,
            # all_gather collectives per step: 0 in plain DP; the single
            # trailing (S,) re-sync gather under ZeRO-1/2; one PER BUCKET
            # under bucketed ZeRO-3 (the just-in-time fetch —
            # hlo_overlap_report's `gathers` witnesses this count)
            "gathers": (0 if not sharded
                        else (layout.num_buckets if self.zero3 else 1)),
        }
        # one byte accounting for bucketed AND monolithic (bucketing
        # changes the schedule, never the byte totals)
        meta.update(exchange_wire_bytes(
            flat_param_count(self.params_struct), layout.total_padded,
            zero=sharded, wire_dtype=self.wire_dtype,
            shard_params=self.zero3))
        # scatter-leg bytes scale with the scan: k micro-scatters
        if self.accum_reduced:
            meta["scatter_bytes"] *= self.grad_accum_steps
            meta["wire_bytes"] = meta["scatter_bytes"] + meta["gather_bytes"]
        return meta


def plan_exchange(mesh_cfg, mesh, tx, *, grad_accum_steps: int = 1,
                  grad_accum_shard: bool = False) -> Exchange:
    """THE exchange decision, from what was configured (`cfg.mesh`,
    `cfg.train.grad_accum_*`) and the mesh it runs on. The ladder is
    cumulative and downgrades HERE and nowhere else: a one-shard mesh has
    no shard to own, so zero1 drops to dp and every rung above it follows
    (as does `shard_gradients` without `shard_opt_state`: no 1/N frame to
    live in). What cannot downgrade raises."""
    if mesh_cfg.shard_params and not mesh_cfg.shard_gradients:
        raise ValueError(
            "shard_params (ZeRO-3) requires shard_gradients (ZeRO-2) — "
            "the sharding ladder is cumulative; params sharded without "
            "a sharded gradient frame would re-materialize O(params) "
            "gradient state every step")
    if grad_accum_shard and not (mesh_cfg.shard_opt_state
                                 and grad_accum_steps > 1):
        raise ValueError(
            "train.grad_accum_shard requires mesh.shard_opt_state=true "
            "AND train.grad_accum_steps > 1 — without both there is no "
            "sharded accumulator to build")
    num_shards = int(mesh.shape[mesh_cfg.data_axis])
    sharded = bool(mesh_cfg.shard_opt_state) and num_shards > 1
    basis = sharding_basis(sharded, bool(mesh_cfg.shard_gradients),
                           bool(mesh_cfg.shard_params))
    return Exchange(
        axis=mesh_cfg.data_axis, num_shards=num_shards, basis=basis,
        bucket_mb=float(mesh_cfg.comm_bucket_mb or 0.0),
        reduce_dtype=mesh_cfg.reduce_dtype,
        grad_accum_steps=int(grad_accum_steps),
        accum_reduced=(grad_accum_steps > 1 and sharded
                       and (basis != "zero1" or bool(grad_accum_shard))),
        tx=tx)



# ---------------------------------------------------------------------------
# Cross-topology layout conversion (checkpoint/retopology.py, elastic.py)
# ---------------------------------------------------------------------------

def opt_state_layout(opt_state: Any, total: int) -> tuple:
    """Detect an optax state's layout from leaf shapes alone (works on
    concrete arrays, ShapeDtypeStructs, and checkpoint ArrayMetadata):
    ('flat', padded_size) for the ZeRO-1 padded-flat-vector layout, else
    ('tree', None) for the replicated params-tree layout. A 1-D leaf at least
    `total` (the flat param count) long can only be the flat vector — no
    single parameter leaf holds the whole network."""
    for leaf in jax.tree.leaves(opt_state):
        shape = tuple(getattr(leaf, "shape", ()))
        if len(shape) == 1 and shape[0] >= total:
            return "flat", int(shape[0])
    return "tree", None


def params_layout(params: Any, total: int) -> tuple:
    """Detect a params value's layout from shapes alone: ('flat', padded)
    when it is the single ZeRO-3 padded flat vector, ('tree', None) for the
    ordinary replicated params tree. Same shape argument as
    `opt_state_layout`."""
    return opt_state_layout(params, total)


def _source_layout(source: Any, params_struct: Any, saved_length: int):
    """How to READ a saved flat vector `saved_length` long: the layout of
    the plan that held it (a live resize), or the one the checkpoint's
    geometry receipt names (None = canonical, every pre-receipt
    checkpoint) — checked against the vector's length either way."""
    layout = (source.layout if isinstance(source, Exchange)
              else layout_from_receipt(params_struct, source, saved_length))
    if layout.total_padded != saved_length:
        raise ValueError(
            f"source layout total_padded={layout.total_padded} does not "
            f"match the saved flat vector length {saved_length}")
    return layout


def convert_params(params: Any, source: Any, target: Exchange) -> Any:
    """Layout-convert a params (or EMA params) value into `target`'s:
    replicated tree ↔ ZeRO-3 canonical flat ↔ ZeRO-3 bucket-major flat.
    Pure and traceable — run under `jit` with the target shardings as
    `out_shardings`, exactly like `convert_opt_state`. `source` says how to
    read a saved flat vector: the plan that held it, or the checkpoint's
    geometry receipt (see `_source_layout`)."""
    struct = target.params_struct
    layout, padded_src = params_layout(params, flat_param_count(struct))
    tree = params
    if layout == "flat":
        tree = _source_layout(source, struct, padded_src).from_global(
            jax.tree.leaves(params)[0])
    return target.layout.to_global(tree) if target.zero3 else tree


def convert_opt_state(opt_state: Any, source: Any, target: Exchange) -> Any:
    """Layout-convert an optax state into `target`'s: replicated
    params-tree ↔ padded-flat (any shard count) ↔ bucket-major flat. Pure
    and traceable — run it under `jit` with the target shardings as
    `out_shardings` and XLA places the result directly into the target
    topology (single- or multi-host). `source`: as for `convert_params`.
    Padding regions carry zeros: a fresh pad is exactly what the momentum
    trace holds there (gradients of padding are identically zero), so
    growing/shrinking/re-bucketing the pad is lossless.

    The walk relies on one optax-chain invariant: the source and target
    states come from the same `tx`, so their structures differ ONLY where the
    params-(sub)tree of a stateful transform is replaced by the flat vector —
    leaf order is otherwise preserved. Every leaf shape is checked; a
    transform violating the invariant fails loudly, never silently."""
    struct = target.params_struct
    n_pleaves = len(jax.tree.leaves(struct))
    layout, padded_src = opt_state_layout(opt_state,
                                          flat_param_count(struct))
    read = (_source_layout(source, struct, padded_src).from_global
            if layout == "flat" else None)

    # source → canonical params-tree-grouped leaf list
    canon = []
    for leaf in jax.tree.leaves(opt_state):
        if read is not None and leaf.ndim == 1 \
                and leaf.shape[0] == padded_src:
            canon.extend(jax.tree.leaves(read(leaf)))
        else:
            canon.append(leaf)

    # canonical → target layout
    target_padded = target.total_padded
    t_struct = jax.eval_shape(
        target.tx.init,
        struct if target_padded is None
        else jax.ShapeDtypeStruct((target_padded,), jnp.float32))
    out, ci = [], 0
    for f in jax.tree.leaves(t_struct):
        if target_padded is not None and f.ndim == 1 \
                and f.shape[0] == target_padded:
            tree = jax.tree.unflatten(jax.tree.structure(struct),
                                      canon[ci:ci + n_pleaves])
            ci += n_pleaves
            out.append(target.layout.to_global(tree).astype(f.dtype))
        else:
            leaf = canon[ci]
            ci += 1
            if tuple(leaf.shape) != tuple(f.shape):
                raise ValueError(
                    f"opt-state leaf shape mismatch during layout "
                    f"conversion: {tuple(leaf.shape)} vs {tuple(f.shape)} — "
                    f"optimizer chain not convertible")
            out.append(jnp.asarray(leaf, f.dtype))
    if ci != len(canon):
        raise ValueError(
            f"opt-state leaf count mismatch: consumed {ci} of {len(canon)}")
    return jax.tree.unflatten(jax.tree.structure(t_struct), out)
