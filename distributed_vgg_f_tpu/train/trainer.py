"""Trainer: owns mesh, model, optimizer, jitted steps, and the host feed loop.

Reference equivalent: the session loop (SURVEY.md §1 trainer layer) — but here
everything from forward through optimizer apply (incl. the gradient all-reduce)
is one XLA computation; the Python loop only feeds batches, reads metrics, and
drives eval/checkpoint cadence (SURVEY.md §3.1 TPU mapping).
"""

from __future__ import annotations

import time

# the start of the `startup:import_trainer` span: the imports below are
# what it times
_IMPORT_T0_NS = time.monotonic_ns()

import os
from typing import Iterator, Mapping, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from distributed_vgg_f_tpu import telemetry
from distributed_vgg_f_tpu.checkpoint.manager import CheckpointManager
from distributed_vgg_f_tpu.config import (
    ExperimentConfig,
    supports_space_to_depth,
)
from distributed_vgg_f_tpu.data import build_dataset
from distributed_vgg_f_tpu.models import build_model
from distributed_vgg_f_tpu.parallel.distributed import (
    coordination_barrier,
    initialize_distributed,
)
from distributed_vgg_f_tpu.parallel.mesh import (
    MeshSpec,
    build_mesh,
    mesh_topology_report,
    shard_host_batch,
)
from distributed_vgg_f_tpu.resilience.errors import CheckpointIntegrityError
from distributed_vgg_f_tpu.resilience.faults import FaultPlan
from distributed_vgg_f_tpu.resilience.guard import NonFiniteGuard
from distributed_vgg_f_tpu.train.schedule import build_optimizer
from distributed_vgg_f_tpu.train.state import TrainState
from distributed_vgg_f_tpu.train.step import build_eval_step, build_train_step
from distributed_vgg_f_tpu.utils.logging import MetricLogger
from distributed_vgg_f_tpu.utils.meter import ThroughputMeter

# Set-up as the program sees it (PERF.md section 3): what this module's
# imports took (jax, flax, optax, orbax, data, models, checkpoint; less
# whatever the caller had imported already), recorded after the fact
# because the recorder is among them, and where the process started on the
# ring's clock (absent off Linux).
telemetry.record("import_trainer", "startup", _IMPORT_T0_NS,
                 time.monotonic_ns() - _IMPORT_T0_NS)
_PROCESS_START_NS = telemetry.process_start_ns()
if _PROCESS_START_NS is not None:
    telemetry.set_gauge("startup/process_start_ns", _PROCESS_START_NS)


# Monotone counter naming each alignment barrier: every process creates
# Trainers and calls fit/evaluate in the same program order, so the n-th
# barrier on one rank pairs with the n-th on every other.
_barrier_seq = {"n": 0}

# Separate tag sequence for the best-effort telemetry-sidecar barrier
# (export_telemetry): it must never share numbering with the MANDATORY
# align_N barriers — a rank that skips one telemetry barrier (local export
# failure) would otherwise shift every later align tag and deadlock the run.
_telemetry_barrier_seq = {"n": 0}


def _align_cold_start() -> None:
    """Align ranks on a coordination-service barrier (long explicit timeout)
    before a run's next FIRST collective execution. Gloo's TCP layer has a
    fixed ~30 s deadline both at rendezvous and on in-op reads; inter-rank
    skew accumulates across python phases (per-rank dataset builds,
    asymmetric compile-cache hits) and one aligned rank then times out
    waiting mid-collective for a lagging one. Re-aligning at every fit/eval
    entry collapses the accumulated skew each time — one cheap gRPC round
    per call (observed: a once-per-process barrier was NOT enough; a
    multi-phase child drifted >30 s by its third fit and died in a
    reduce-scatter read)."""
    if jax.process_count() == 1:
        return
    _barrier_seq["n"] += 1
    coordination_barrier(f"align_{_barrier_seq['n']}")


class Trainer:
    def __init__(self, cfg: ExperimentConfig, mesh=None,
                 logger: Optional[MetricLogger] = None):
        # Telemetry spine (telemetry/): configure the process-wide recorder
        # and registry from config BEFORE anything records — the wired call
        # sites (prefetch, checkpoint manager, guards) all write to the
        # defaults this flips, and the `startup` spans below are the first.
        telemetry.configure(enabled=cfg.telemetry.enabled,
                            span_capacity=cfg.telemetry.span_capacity,
                            flight_windows=cfg.telemetry.flight_windows,
                            annotate=jax.profiler.TraceAnnotation)
        with telemetry.span("trainer_init", "startup"):
            self._init(cfg, mesh, logger)

    def _init(self, cfg: ExperimentConfig, mesh,
              logger: Optional[MetricLogger]) -> None:
        """All of construction, under the `startup:trainer_init` span; the
        `startup` spans inside it are its children (scopes.STARTUP_SPANS),
        and what none of them covers is its self time."""
        self.cfg = cfg
        if cfg.data.space_to_depth and not supports_space_to_depth(
                cfg.model.name, cfg.data.image_size, cfg.data.name):
            # the packed layout is the VGG-F stem's input contract
            # (models/vggf.py Conv1SpaceToDepth); other models take (S, S, 3),
            # and only some host pipelines implement the packing
            # (config.SPACE_TO_DEPTH_DATASETS)
            raise ValueError(
                "data.space_to_depth needs the vggf model, "
                "image_size % 4 == 0, and a dataset that implements packing "
                f"(got model={cfg.model.name!r}, "
                f"image_size={cfg.data.image_size}, "
                f"dataset={cfg.data.name!r})")
        with telemetry.span("distributed_init", "startup"):
            initialize_distributed()
            self.mesh = mesh if mesh is not None else build_mesh(
                MeshSpec((cfg.mesh.data_axis,), (cfg.mesh.num_data,)))
        self.data_axis = cfg.mesh.data_axis
        with telemetry.span("build_model", "startup"):
            self.model = build_model(cfg.model)
        # what a batch of this model is (models/ingest.py): "image" or
        # "tokens". It picks the sample input, the data source's arguments
        # and the step's prologue, loss and metrics
        from distributed_vgg_f_tpu.models.ingest import ingest_descriptor
        self.batch_kind = ingest_descriptor(cfg.model.name).kind
        with telemetry.span("build_optimizer", "startup"):
            self.tx, self.schedule = build_optimizer(cfg)
        self._replicated = NamedSharding(self.mesh, P())
        with telemetry.span("plan_exchange", "startup"):
            self._plan_exchange()
        # Device-finish prologue (data/device_ingest.py, data.wire='u8'):
        # normalize/cast/space-to-depth for uint8-wire batches, fused into
        # the jitted steps. Installed UNCONDITIONALLY — it dispatches on
        # dtype, so host-normalized (float) batches pass through untouched
        # and train/eval/predict can never double-normalize. Eval batches
        # keep the unpacked (S, S, 3) host convention, so the eval finish
        # never packs.
        from distributed_vgg_f_tpu.data.device_ingest import (
            make_device_finish)
        self.device_finish = make_device_finish(
            cfg.data.mean_rgb, cfg.data.stddev_rgb,
            image_dtype=cfg.data.image_dtype,
            space_to_depth=cfg.data.space_to_depth)
        self._eval_finish = make_device_finish(
            cfg.data.mean_rgb, cfg.data.stddev_rgb,
            image_dtype=cfg.data.image_dtype, space_to_depth=False)
        from distributed_vgg_f_tpu.data.augment import make_device_augment
        # On-device augmentation (r13, data/augment.py): with the stage
        # enabled it is the train step's whole prologue, in place of
        # `device_finish` above. It packs, flips and pairs the batch as it
        # arrived (u8 on the u8 wire), then runs a finish of its own and
        # mixes; the host skips packing by the same predicate
        # (host_space_to_depth). None when disabled — structurally absent
        # from the train step (augment.enabled=false keeps the pre-r13
        # wiring byte-identical), and never handed to eval/predict at all.
        self.device_augment = make_device_augment(
            cfg.data.augment, cfg.data.mean_rgb, cfg.data.stddev_rgb,
            image_dtype=cfg.data.image_dtype,
            space_to_depth=cfg.data.space_to_depth)
        if self.batch_kind == "tokens":
            # no pixels: no finish and no augmentation to install
            self.device_finish = self.device_augment = None
        with telemetry.span("build_steps", "startup"):
            self._build_steps()
        self.logger = logger or MetricLogger()
        # Live observability endpoint (telemetry/exporter.py): one
        # process-wide HTTP server (/metrics /healthz /stallz /trace),
        # port 0 by default — the BOUND port is logged and written to the
        # run sidecar (exporter_p<rank>.jsonl) so multi-host processes
        # never collide on a fixed port. Started here (not in fit) so
        # standalone eval/predict processes are observable too.
        self.exporter = None
        # fleet identity: the role string every fleet surface keys this
        # process by — exporter sidecar, collector registry, and the
        # Chrome-trace process_name lane (Perfetto shows trainer_rank0,
        # not a bare OS pid)
        self._role = f"trainer_rank{jax.process_index()}"
        telemetry.set_process_label(self._role)
        if cfg.telemetry.enabled and cfg.telemetry.exporter:
            from distributed_vgg_f_tpu.telemetry import exporter as _exp
            try:
                self.exporter = _exp.ensure_started(
                    host=cfg.telemetry.exporter_host,
                    port=cfg.telemetry.exporter_port,
                    stalled_after_s=cfg.telemetry.exporter_stalled_after_s,
                    role=self._role)
            except OSError as e:
                # a taken fixed port (or an exhausted fd table) must cost
                # the run its observability endpoint, never the run
                if jax.process_index() == 0:
                    self.logger.log("telemetry_exporter_failed",
                                    {"error": repr(e),
                                     "port": cfg.telemetry.exporter_port})
            if self.exporter is not None:
                described = self.exporter.describe()
                if cfg.telemetry.sidecar_dir:
                    from distributed_vgg_f_tpu.parallel.distributed import (
                        write_telemetry_sidecar)
                    write_telemetry_sidecar(
                        cfg.telemetry.sidecar_dir,
                        {"event": "telemetry_exporter", **described},
                        prefix="exporter")
                if jax.process_index() == 0:
                    self.logger.log("telemetry_exporter", described)
        # Optional in-process fleet collector on rank 0 (r22,
        # telemetry/collector.py): scrapes every rank's exporter (sidecar
        # discovery) + any static endpoints into /fleetz + one aggregated
        # /metrics. Config-off by default — big fleets run the collector
        # as its own process (`python -m ...telemetry.collector`) instead.
        self.collector = None
        col = cfg.telemetry.collector
        if (cfg.telemetry.enabled and col.enabled
                and jax.process_index() == 0):
            from distributed_vgg_f_tpu.telemetry.collector import (
                FleetCollector)
            try:
                self.collector = FleetCollector(
                    sidecar_dir=col.sidecar_dir or cfg.telemetry.sidecar_dir,
                    endpoints=col.endpoints,
                    interval_s=col.interval_s,
                    stale_after_s=col.stale_after_s,
                    scrape_timeout_s=col.scrape_timeout_s,
                    fleet_log=col.fleet_log,
                    host=col.host, port=col.port)
                self.collector.start()
                self.logger.log("fleet_collector",
                                self.collector.describe())
            except OSError as e:
                # same contract as the exporter: a taken port costs the
                # fleet view, never the run
                self.collector = None
                self.logger.log("fleet_collector_failed",
                                {"error": repr(e), "port": col.port})
        self._restored_from_best = False
        # Position-exact resumable ingest (r18, data/iterator_state.py):
        # the cursor-counting rebuild surface fit() wraps the trainer-owned
        # train stream in (None when data.iterator_state.enabled=false or
        # the caller supplied the dataset), and the iterator-state blob the
        # last restore_or_init read out of the checkpoint's `extra` (None
        # for pre-r18 / receipt-absent checkpoints — those dispatch to the
        # unchanged r17 replay path).
        self._ingest = None
        self._restored_iterator_state = None
        # Live elastic resize (r19, parallel/elastic.py): cumulative
        # receipt state behind the per-window `elastic` JSONL block and
        # the elastic/ counters. topology stays "static" until a resize
        # lands (the regression sentinel's pre-r19 default basis).
        self._elastic_stats = {"resizes": 0, "downtime_ns": 0,
                               "evacuated_shards": 0,
                               "reassigned_data_shards": 0,
                               "topology": "static", "lr_scale": 1.0}
        # Closed-loop ingest autotuner (r11, data/autotune.py): created per
        # fit() once the live pipeline objects exist (the knobs bind to
        # them); None when config-off, env-killed (DVGGF_AUTOTUNE=0), or
        # the run has no verdict stream to steer by.
        self.autotuner = None
        self.checkpoints: Optional[CheckpointManager] = None
        # created lazily by fit() when tracking actually happens — eager
        # creation would litter best/ dirs into eval/predict runs (including
        # a best/best/ when checkpoint_dir itself points at a best slot)
        self.best_checkpoints: Optional[CheckpointManager] = None
        if cfg.train.checkpoint_dir:
            self.checkpoints = CheckpointManager(
                cfg.train.checkpoint_dir,
                max_to_keep=cfg.train.keep_checkpoints,
                save_interval_steps=cfg.train.checkpoint_every_steps,
                save_retries=cfg.train.checkpoint_save_retries)
        # Chaos harness (resilience/faults.py): None in production ("").
        self.faults = FaultPlan.parse(cfg.train.fault_injection)
        if self.faults is not None and jax.process_index() == 0:
            self.logger.log("fault_injection_armed",
                            {"spec": cfg.train.fault_injection})
        if cfg.train.debug_nans:
            jax.config.update("jax_debug_nans", True)

    def _build_steps(self) -> None:
        """(Re)build the jitted train/eval steps for the CURRENT mesh,
        optimizer, and sharding geometry. Called once at construction —
        and again by the elastic resize (r19, `_elastic_resize`) after
        the mesh/specs/tx are swapped for the survivor topology: the step
        closes over all of them, so a resize is a re-trace by
        construction, never a stale-closure bug."""
        cfg = self.cfg
        self.train_step = build_train_step(
            self.model, self.mesh, cfg.optim.weight_decay, self.exchange,
            schedule=self.schedule,
            grad_clip_norm=cfg.optim.grad_clip_norm,
            ema_decay=cfg.train.ema_decay,
            skip_nonfinite=cfg.train.skip_nonfinite,
            device_finish=self.device_finish,
            device_augment=self.device_augment,
            batch_kind=self.batch_kind)
        self.eval_step = build_eval_step(self.model, self.mesh,
                                         self.exchange,
                                         device_finish=self._eval_finish)

    # ------------------------------------------------------------------ state
    def _sample_input(self) -> jnp.ndarray:
        if self.batch_kind == "tokens":
            # parameter shapes do not depend on the length
            seq_len = min(128, int(self.cfg.model.extra["seq_len"]))
            return jnp.zeros((1, seq_len), jnp.int32)
        return jnp.zeros(
            (1, self.cfg.data.image_size, self.cfg.data.image_size, 3),
            jnp.float32)

    def _plan_exchange(self) -> None:
        """Decide the exchange for the CURRENT mesh and optimizer
        (parallel/zero.py `plan_exchange`: basis after the one-shard
        downgrade, layout, wire) — at construction, and again by the
        elastic resize. A ZeRO plan needs the parameter shapes now (its
        state's layout depends on them): one abstract trace of the model's
        init. A dp plan learns them at the step's first trace, so plain DP
        pays no `eval_shape` here."""
        cfg = self.cfg
        from distributed_vgg_f_tpu.parallel.zero import plan_exchange
        self.exchange = plan_exchange(
            cfg.mesh, self.mesh, self.tx,
            grad_accum_steps=cfg.train.grad_accum_steps,
            grad_accum_shard=cfg.train.grad_accum_shard)
        if self.exchange.sharded:
            shapes = jax.eval_shape(
                lambda r: TrainState.create(self.model, self.tx, r,
                                            self._sample_input()),
                jax.random.key(0))
            self.exchange = self.exchange.bind(
                shapes.params, shapes.batch_stats,
                ema=cfg.train.ema_decay > 0.0)

    # What the benchmark's drivers (chipbench/drivers/) read of the plan.
    @property
    def zero1(self) -> bool:
        return self.exchange.sharded

    @property
    def zero2(self) -> bool:
        return self.exchange.zero2

    @property
    def zero3(self) -> bool:
        return self.exchange.zero3

    @property
    def _bucket_layout(self):
        """The bucket-major layout of a ZeRO state; None under the
        canonical layout and under plain DP."""
        plan = self.exchange
        return plan.layout if plan.sharded and plan.bucket_mb > 0 else None

    def _state_sharding(self):
        return self.exchange.state_shardings(self.mesh)

    def params_tree(self, params):
        """The params TREE of a state's `params`, on the host (predict /
        serving restore run outside the mesh)."""
        return self.exchange.params_tree(params)

    def init_state(self, rng: jax.Array | None = None) -> TrainState:
        """Initialize params on-device: replicated over the mesh, except
        what the exchange shards over the data axis."""
        def init_fn(rng):
            return TrainState.create(self.model, self.tx, rng, sample,
                                     ema=self.cfg.train.ema_decay > 0.0,
                                     exchange=self.exchange)

        with telemetry.span("init_state", "startup"):
            rng = rng if rng is not None \
                else jax.random.key(self.cfg.train.seed)
            sample = self._sample_input()
            return jax.jit(init_fn,
                           out_shardings=self._state_sharding())(rng)

    def _make_best_manager(self) -> CheckpointManager:
        """The single-slot best-eval manager under <checkpoint_dir>/best.
        Retention is by eval_top1 (Orbax best_fn), so even if a crash mid-
        replacement leaves two steps in the slot, best_step() selects the
        better-SCORED one and the next save garbage-collects the loser."""
        return CheckpointManager(
            os.path.join(self.cfg.train.checkpoint_dir, "best"),
            max_to_keep=1, save_interval_steps=1, best_metric="eval_top1",
            save_retries=self.cfg.train.checkpoint_save_retries)

    def restore_or_init(self) -> TrainState:
        """Reference restart semantics (SURVEY.md §3.5): restore the latest
        checkpoint if one exists, else fresh init. The restored step counter
        reproduces the LR-schedule position inside the jitted step.
        `train.restore_from_best` restores the best-eval slot instead (by
        recorded score, not step number). Sets `self._restored_from_best` so
        fit() can gate branch-point truncation on an ACTUAL best-slot
        restore, never on the config flag alone."""
        self._restored_from_best = False
        self._restored_iterator_state = None
        # first collective of a restart can be the retopology resharding —
        # align ranks before it, not only before the step loop
        _align_cold_start()
        state = self.init_state()
        source = self.checkpoints
        if self.cfg.train.restore_from_best and self.checkpoints is not None:
            best = self._make_best_manager()
            if best.latest_step() is not None:
                source = best
            elif jax.process_index() == 0:
                self.logger.log("restore_from_best_unavailable",
                                {"fallback": "latest"})
        if source is not None and source.latest_step() is not None:
            # Topology-adaptive restore: the checkpoint may have been written
            # on a different mesh size or opt-state layout (replicated vs
            # ZeRO-1) — grow/shrink/migrate without retraining
            # (checkpoint/retopology.py; BASELINE north_star v4-8 → v4-128).
            from distributed_vgg_f_tpu.checkpoint.retopology import (
                restore_any_topology)
            # EMA presence is decided from the SAVED tree's metadata, not by
            # try/except (an exception-driven retry buried unrelated restore
            # failures under a misleading structure-mismatch — code-review
            # r3). Four deterministic cases: match either way → plain
            # restore; saved-without/run-with → seed from restored params;
            # saved-with/run-without → restore then drop.
            # Resolve the restored step ONCE and pin every read to it — a
            # concurrent save landing between two independent best_step()
            # resolutions would skew metadata vs restore (code-review r3).
            # best_step() is integrity-verified: a truncated/corrupt newest
            # step falls back to the newest INTACT one (logged below); None
            # with checkpoints on disk means NOTHING intact — refuse to
            # silently reinitialize over a damaged but real training run.
            restore_step = source.best_step()
            if restore_step is None:
                raise CheckpointIntegrityError(
                    f"checkpoints exist under the configured directory but "
                    f"none passed integrity verification "
                    f"({(source.last_integrity_fallback or {}).get('skipped')}"
                    f") — refusing to train from scratch over a damaged "
                    f"run; restore the directory from a replica/backup or "
                    f"clear it to restart deliberately")
            if source.last_integrity_fallback is not None \
                    and jax.process_index() == 0:
                self.logger.log("checkpoint_integrity_fallback",
                                source.last_integrity_fallback)
            meta = source.state_metadata(restore_step)
            saved_has_ema = bool(jax.tree_util.tree_leaves(
                meta.get("ema_params") if hasattr(meta, "get") else None))
            want_ema = state.ema_params is not None
            ema_event = None  # logged after ONE step fetch below
            restore_extra = {}
            if saved_has_ema == want_ema:
                state, restore_extra = restore_any_topology(
                    source, state, self.exchange, step=restore_step)
            elif want_ema:
                # pre-EMA checkpoint into an EMA-enabled run
                tmpl = state.replace(ema_params=None, ema_batch_stats=None)
                restored, restore_extra = restore_any_topology(
                    source, tmpl, self.exchange, step=restore_step)
                # jnp.copy: the seed must be DISTINCT buffers — sharing the
                # params' buffers trips the train step's donation ("attempt
                # to donate the same buffer twice")
                state = restored.replace(
                    ema_params=jax.tree.map(jnp.copy, restored.params),
                    ema_batch_stats=jax.tree.map(jnp.copy,
                                                 restored.batch_stats))
                ema_event = "ema_seeded_from_params"
            else:
                # EMA checkpoint into a run with ema_decay=0: restore the
                # averages into params-shaped buffers, then drop them
                tmpl = state.replace(ema_params=state.params,
                                     ema_batch_stats=state.batch_stats)
                restored, restore_extra = restore_any_topology(
                    source, tmpl, self.exchange, step=restore_step)
                state = restored.replace(ema_params=None,
                                         ema_batch_stats=None)
                ema_event = "ema_dropped_on_restore"
            # Position-exact resume receipt (r18): the iterator-state blob
            # this checkpoint carried, if any — fit()'s resume dispatch
            # keys on its presence (receipt-absent = pre-r18 checkpoint =
            # the unchanged epoch-boundary replay path).
            if self.cfg.data.iterator_state.enabled:
                self._restored_iterator_state = (restore_extra or {}).get(
                    "iterator_state")
            self._restored_from_best = source is not self.checkpoints
            if jax.process_index() == 0:
                # ONE host sync for the whole restore event; the branch log
                # and the restore log share the fetched int (the repeated
                # int(jax.device_get(state.step)) here was a redundant
                # device round-trip per log line)
                restored_step = int(jax.device_get(state.step))
                if ema_event is not None:
                    self.logger.log(ema_event, {"step": restored_step})
                self.logger.log("restore",
                                {"step": restored_step,
                                 "best": source is not self.checkpoints})
        return state

    def base_rng(self) -> jax.Array:
        # Built inside jit so the replicated output sharding also works
        # multi-process (device_put to non-addressable devices does not).
        # The dropout key uses the configured PRNG impl ("rbg" by default —
        # much cheaper random bits on TPU than threefry; see TrainConfig).
        # The seed is an argument (its low 32 bits, which is what
        # `jax.random.key` keeps of a Python int), not a constant of the
        # program: one program for every seed, so a new seed is no compile
        # and no new entry in the persistent cache.
        seed = np.uint32((self.cfg.train.seed + 1) & 0xFFFFFFFF)
        impl = self.cfg.train.dropout_rng_impl
        return jax.jit(lambda s: jax.random.key(s, impl=impl),
                       out_shardings=self._replicated)(seed)

    # ------------------------------------------------------------------ data
    def make_dataset(self, split: str = "train", data_cfg=None) -> Iterator:
        """`data_cfg` (r18) overrides the data section for THIS build only
        — the ResumableIngest rebuild factory re-enters here with a
        wire-flipped config, so the wrapped and unwrapped feed paths
        share one build_dataset call site and can never fork."""
        cfg = self.cfg
        state_dir, every = "", 0
        if split == "train" and cfg.train.checkpoint_dir:
            # Per-host iterator snapshots, written at the checkpoint cadence so
            # a snapshot exists for every resumable step (deterministic
            # ImageNet resume, SURVEY.md §5 data-iterator state).
            state_dir = f"{cfg.train.checkpoint_dir}/data_state/" \
                        f"host_{jax.process_index()}"
            every = cfg.train.checkpoint_every_steps
        return build_dataset(data_cfg if data_cfg is not None else cfg.data,
                             split, seed=cfg.train.seed,
                             num_shards=jax.process_count(),
                             shard_index=jax.process_index(),
                             state_dir=state_dir, snapshot_every=every,
                             num_classes=cfg.model.num_classes,
                             seq_len=int(cfg.model.extra.get("seq_len", 0)))

    def _make_train_ingest(self):
        """The trainer-owned train stream for fit(). With
        `data.iterator_state.enabled` (r18) it is wrapped in the
        cursor-counting ResumableIngest surface — the checkpoint blob's
        capture point and the position-exact rebuild the autotuner's wire
        knob actuates through. Kill-switched off, this returns exactly
        what make_dataset('train') returns — the r17 feed path,
        structurally identical (pinned in tests/test_iterator_state.py)."""
        cfg = self.cfg
        if not cfg.data.iterator_state.enabled:
            return self.make_dataset("train")
        from distributed_vgg_f_tpu.data.iterator_state import (
            ResumableIngest)
        return ResumableIngest(
            lambda dc: self.make_dataset("train", data_cfg=dc),
            cfg.data, seed=cfg.train.seed,
            batches_per_epoch=cfg.steps_per_epoch,
            label=cfg.data.service.label)

    def _save_extra(self, next_step: int) -> dict:
        """The host-state JSON riding every checkpoint's `extra`: the
        exchange's layout receipts (`Exchange.receipts`) plus (r18) the schema-validated iterator-state
        blob captured at the step barrier — `next_step` is the batch the
        restored run will consume first."""
        extra = {"examples_seen":
                 next_step * self.cfg.data.global_batch_size,
                 **self.exchange.receipts()}
        if self._ingest is not None:
            from distributed_vgg_f_tpu.telemetry import schema
            blob = self._ingest.capture_state(next_step)
            errors: list = []
            schema.validate_iterator_state_blob(blob, "iterator_state",
                                                errors)
            if errors:  # never let a receipt bug block a durable save
                if jax.process_index() == 0:
                    self.logger.log("iterator_state_capture_invalid",
                                    {"errors": errors[:3]})
            else:
                extra["iterator_state"] = blob
        return extra

    @staticmethod
    def _count_state_save(extra: Mapping) -> None:
        """`ingest_state/saves` counts blobs that made it into a DURABLE
        save — call only after the manager reported the save dispatched."""
        if "iterator_state" in extra:
            telemetry.inc("ingest_state/saves")

    def shard(self, batch: Mapping[str, np.ndarray]):
        return shard_host_batch(batch, self.mesh, self.data_axis)

    def _check_first_labels(self, it: Iterator) -> Iterator:
        """Pass-through that validates the FIRST host batch's labels against
        the model head (one host-side max; no per-step cost). Padding labels
        (< 0) are legal — only the upper bound can corrupt the CE gather."""
        first = True
        for batch in it:
            if first:
                first = False
                labels = np.asarray(batch["tokens" if self.batch_kind
                                          == "tokens" else "label"])
                nc = self.cfg.model.num_classes
                if labels.size and int(labels.max()) >= nc:
                    raise ValueError(
                        f"dataset yields label {int(labels.max())} but the "
                        f"model head has num_classes={nc}; out-of-range "
                        f"labels make the cross-entropy gather silently "
                        f"produce nan — align model.num_classes with the "
                        f"dataset's label space")
            yield batch

    # ---------------------------------------------------------------- elastic
    def _elastic_resize(self, next_step: int, state, ds, host_prefetch,
                        consensus):
        """Execute one live N→N−k resize (r19, parallel/elastic.py): plan
        against the flagged ranks, restore a FRESH ingest from the cursor
        blob, then swap mesh/specs/optimizer/steps for the survivor
        topology and reshard the state in place. Ordered so every
        refusable step happens BEFORE any live object is mutated — an
        `ElasticDegraded` raise leaves the r18 stop path untouched.
        Returns the rebuilt `(state, ds, host_prefetch, rng, meter)` fit()
        loop carriers."""
        import dataclasses as _dc

        from distributed_vgg_f_tpu.data.iterator_state import (
            restore_from_blob)
        from distributed_vgg_f_tpu.data.prefetch import maybe_prefetch
        from distributed_vgg_f_tpu.parallel import elastic
        from distributed_vgg_f_tpu.resilience.errors import ElasticDegraded

        cfg = self.cfg
        # WHO died: the rank-targeted chaos token when armed, else the
        # consensus gather (real multi-host SIGTERM — which plan_resize
        # then refuses as multi-controller; the checkpointed restart onto
        # the survivor slice covers that fleet shape).
        dead: tuple = ()
        if self.faults is not None and self.faults.preempt_ranks:
            dead = self.faults.preempt_ranks
        elif consensus is not None:
            dead = consensus.flagged_ranks
        plan = elastic.plan_resize(
            self.mesh, self.data_axis, dead,
            elastic_cfg=cfg.mesh.elastic,
            global_batch=cfg.data.global_batch_size,
            have_cursor=self._ingest is not None)

        # Pure cursor handoff, decided before any teardown: capture the
        # position (zero replayed batches — the blob names the exact next
        # item) and restore it into a FRESH ingest; ResumableIngest
        # refuses restore_state once started, so a new surface over the
        # new topology is the supported path (data/iterator_state.py).
        blob = self._ingest.capture_state(next_step)
        fresh = self._make_train_ingest()
        receipt = restore_from_blob(
            fresh, blob, step=next_step,
            expect={"seed": cfg.train.seed,
                    "batches_per_epoch": cfg.steps_per_epoch,
                    "ingest": cfg.data.service.label})
        if receipt is None:
            raise ElasticDegraded(
                "cursor_restore_refused",
                f"iterator-state blob did not restore into a fresh ingest "
                f"at step {next_step} — resizing without the cursor would "
                "replay or skip batches")

        # Evacuation accounting against the OLD geometry: each dead rank
        # owned one 1/N slice of every data-axis-sharded opt-state leaf.
        old = self.exchange
        evac = 0
        if old.sharded:
            evac = len(plan.dead_ranks) * sum(
                1 for s in jax.tree.leaves(
                    old.state_specs.opt_state,
                    is_leaf=lambda x: isinstance(x, P))
                if s == P(self.data_axis))

        # --- survivor topology: rebuild exactly what __init__ built, in
        # the same order (mesh → optimizer → exchange → steps), so the
        # resized trainer is indistinguishable from one constructed at
        # size N−k.
        self.mesh = elastic.shrink_mesh(self.mesh, self.data_axis, plan)
        self._replicated = NamedSharding(self.mesh, P())
        if plan.lr_scale != 1.0:
            self.tx, self.schedule = build_optimizer(
                cfg, lr_scale=plan.lr_scale)
        self._plan_exchange()
        self._build_steps()
        state = elastic.reshard_train_state(state, old, self.exchange,
                                            self.mesh)

        # --- feed over the new mesh: tear down the old chain, clear the
        # fired preempt injector (its >= predicate stays true forever), and
        # re-wrap the surviving injectors at the new start step.
        if hasattr(ds, "close"):
            ds.close()
        if host_prefetch is not None:
            host_prefetch.close()
        if self.autotuner is not None:
            # the controller's knobs bind to the torn-down pipeline
            # objects — disarm rather than steer ghosts (a later fit
            # re-arms over the live chain)
            from distributed_vgg_f_tpu.telemetry import exporter as _exp
            _exp.set_autotune_source(None)
            self.autotuner = None
            if jax.process_index() == 0:
                self.logger.log("elastic_autotune_disarmed",
                                {"step": next_step})
        self._ingest = fresh
        if self.faults is not None:
            self.faults = _dc.replace(self.faults, preempt_step=None,
                                      preempt_ranks=())
        host_batches = fresh
        if self.faults is not None and self.faults.has_data_faults:
            host_batches = self.faults.wrap_iterator(host_batches,
                                                     start_step=next_step)
        if plan.batch_policy == "scale_lr":
            host_batches = elastic.trim_batches(
                host_batches, plan, cfg.data.global_batch_size)
        host_batches = self._check_first_labels(host_batches)
        new_ds = maybe_prefetch(host_batches, self.mesh, self.data_axis,
                                buffer_size=cfg.train.prefetch_to_device,
                                batch_timeout_s=cfg.train.data_timeout_s,
                                timeout_retries=cfg.train.data_timeout_retries)

        # --- receipts
        st = self._elastic_stats
        reassigned = (len(plan.dead_ranks)
                      if plan.batch_policy == "keep_global" else 0)
        st["resizes"] += 1
        st["evacuated_shards"] += evac
        st["reassigned_data_shards"] += reassigned
        st["topology"] = plan.topology_label
        st["lr_scale"] = plan.lr_scale
        telemetry.inc("elastic/resizes")
        if evac:
            telemetry.inc("elastic/evacuated_shards", evac)
        if reassigned:
            telemetry.inc("elastic/reassigned_data_shards", reassigned)
        if jax.process_index() == 0:
            self.logger.log("elastic_resize", {
                "step": next_step, **plan.describe(),
                "evacuated_shards": evac,
                "reassigned_data_shards": reassigned,
                "cursor": receipt})
            if plan.lr_scale != 1.0:
                # the schedule receipt: what the LR rescale actually did
                self.logger.log("elastic_lr_rescale", {
                    "step": next_step, "lr_scale": plan.lr_scale,
                    "old_global_batch": cfg.data.global_batch_size,
                    "new_global_batch": int(round(
                        cfg.data.global_batch_size * plan.lr_scale))})
        return (state, new_ds, None, self.base_rng(),
                ThroughputMeter(self.mesh.devices.size))

    # ------------------------------------------------------------------ loops
    def fit(self, state: TrainState | None = None, *, num_steps: int | None = None,
            dataset: Iterator | None = None,
            eval_dataset: Iterator | None = None) -> TrainState:
        cfg = self.cfg
        branched = False
        if state is None:
            state = self.restore_or_init()
            # only an ACTUAL best-slot restore branches the chain — a fit()
            # called with an explicit state (fresh init, analysis restore)
            # must never delete checkpoints ahead of that state's step
            branched = self._restored_from_best
        rng = self.base_rng()
        total = num_steps if num_steps is not None else cfg.total_steps
        start_step = int(jax.device_get(state.step))
        if branched and self.checkpoints is not None:
            # Branch-point truncation: TRAINING from the best slot abandons
            # the chain beyond it. Stale steps ahead of the branch must go
            # NOW — replacing them lazily on collision would leave a crash
            # window where latest_step() still returns pre-branch state
            # (code-review r3). Eval/predict never call fit, so read-only
            # uses of restore_from_best keep the full chain.
            stale = [s for s in self.checkpoints.all_steps() if s > start_step]
            for s in stale:
                self.checkpoints.delete(s)
            if stale and jax.process_index() == 0:
                self.logger.log("branch_truncate", {
                    "from_step": start_step, "deleted_steps": stale})
        host_ds = dataset if dataset is not None \
            else self._make_train_ingest()
        from distributed_vgg_f_tpu.data.iterator_state import (
            ResumableIngest, restore_from_blob)
        self._ingest = host_ds if isinstance(host_ds, ResumableIngest) \
            else None
        if dataset is None and 0 < start_step < total:
            # Deterministic resume (SURVEY.md §5): restore the data iterator to
            # "next batch = start_step" so the post-resume stream is identical
            # to the uninterrupted one. Dispatch (r18): a checkpoint carrying
            # the iterator-state receipt resumes POSITION-EXACTLY through the
            # blob (validated identity + the read-ahead transplant — zero
            # replayed batches, receipted); a receipt-absent (pre-r18)
            # checkpoint takes the unchanged r17 path — O(1)
            # iterator-snapshot/seek restore when the pipeline supports it,
            # else replay the seeded iterator (cheap for numpy/native
            # iterators).
            restored = False
            if self._ingest is not None \
                    and self._restored_iterator_state is not None:
                receipt = restore_from_blob(
                    self._ingest, self._restored_iterator_state,
                    step=start_step,
                    expect={"seed": cfg.train.seed,
                            "batches_per_epoch": cfg.steps_per_epoch,
                            "ingest": cfg.data.service.label})
                restored = receipt is not None
                if restored and jax.process_index() == 0:
                    self.logger.log("iterator_state_restore", receipt)
            if not restored and getattr(host_ds, "supports_state", False):
                restored = host_ds.restore_state(start_step)
            if jax.process_index() == 0:
                self.logger.log("data_iterator_restore", {
                    "step": start_step, "restored": restored})
            if not restored and cfg.train.resume_data_fast_forward:
                for _ in range(start_step):
                    next(host_ds)
                if jax.process_index() == 0:
                    self.logger.log("data_fast_forward", {"batches": start_step})
        # First-batch label-range guard, for EVERY pipeline: an out-of-range
        # label against the model head is a CE gather past the logits and
        # surfaces as loss=nan with finite grads, nothing louder (found r3
        # via model.num_classes override + synthetic labels; the same
        # mismatch is reachable with any real dataset, code-review r3).
        # Bind the loader's error counter BEFORE wrapping — the generator
        # wrapper has no decode_errors attribute (code-review r3).
        decode_errors_src = getattr(host_ds, "decode_errors", None)
        # Closed-loop ingest autotuner (r11): gate EVERYTHING — the
        # host-prefetch wrapper stage included — on the single activation
        # predicate, so config-off / DVGGF_AUTOTUNE=0 is byte-identical to
        # controller-absent. Caller-supplied datasets are never touched
        # (their read-ahead semantics belong to the caller), and without
        # the stall attributor there is no verdict stream to steer by.
        from distributed_vgg_f_tpu.data.autotune import autotune_active
        autotune_on = (dataset is None
                       and autotune_active(cfg.data.autotune)
                       and cfg.telemetry.enabled
                       and cfg.telemetry.stall_attribution)
        raw_ds = host_ds  # the unwrapped loader the thread knob binds to
        host_prefetch = None
        if autotune_on:
            # resizable read-ahead stage between the host loader and the
            # device-prefetch worker — the controller's data.prefetch knob
            # (constructed AFTER the resume seek above: its worker starts
            # drawing immediately)
            from distributed_vgg_f_tpu.data.prefetch import (
                HostPrefetchIterator)
            host_prefetch = HostPrefetchIterator(
                host_ds, depth=max(1, cfg.data.prefetch))
            host_ds = host_prefetch
        if self.faults is not None and self.faults.has_data_faults:
            # chaos harness: NaN/stall/crash injectors wrap the host stream
            # (resilience/faults.py) — start_step keeps the 1-based fault
            # steps aligned with training steps after a resume
            host_ds = self.faults.wrap_iterator(host_ds,
                                                start_step=start_step)
        host_ds = self._check_first_labels(host_ds)
        # Device prefetch: a background thread lands sharded batches in HBM
        # ahead of compute, so step start never blocks on the H2D copy. Only a
        # trainer-owned iterator is prefetched — the thread reads ahead, which
        # would silently consume extra batches from a caller-supplied one.
        # The prefetcher doubles as the data watchdog (train.data_timeout_s):
        # a stalled/dead loader raises DataStallError instead of hanging.
        from distributed_vgg_f_tpu.data.prefetch import maybe_prefetch
        prefetch_buf = (0 if dataset is not None
                        else cfg.train.prefetch_to_device)
        if prefetch_buf == 0 and cfg.train.data_timeout_s > 0 \
                and jax.process_index() == 0:
            # the sync fallback has no thread to time-bound — a configured
            # watchdog that silently does nothing is the one state the
            # resilience layer must never be in (code-review)
            self.logger.log("data_watchdog_inactive", {
                "reason": ("caller-supplied dataset" if dataset is not None
                           else "train.prefetch_to_device=0"),
                "data_timeout_s": cfg.train.data_timeout_s,
                "hint": "the per-batch timeout needs the device-prefetch "
                        "thread; stalls will hang instead of raising "
                        "DataStallError"})
        ds = maybe_prefetch(host_ds, self.mesh, self.data_axis,
                            buffer_size=prefetch_buf,
                            batch_timeout_s=cfg.train.data_timeout_s,
                            timeout_retries=cfg.train.data_timeout_retries)

        # Arm the autotuner over the live pipeline objects. Knob factories
        # return None when a surface is absent (tf.data loader without a
        # resize ABI, sync-sharding fallback without a device ring, restart
        # path not dispatching) — the controller simply steers what exists
        # and receipts the rest as unbound. The wire knob (r18): bound
        # through the ResumableIngest rebuild surface whenever a
        # position-exact rebuild is available (native imagenet, local
        # ingest) — escalation rebuilds the live source host_f32→u8 AT the
        # captured cursor, read-ahead batches keep their old wire (the
        # device finish dispatches per batch on dtype), and the stream
        # continues byte-identically. This retires the r11 "trainer
        # deliberately leaves it unbound" receipt.
        self.autotuner = None
        from distributed_vgg_f_tpu.telemetry import exporter as _exporter
        if autotune_on:
            from distributed_vgg_f_tpu.data import autotune as _at
            at_cfg = cfg.data.autotune
            # auto (0) resolves to min(16, vCPUs), but never below the
            # configured floor — an inverted rail (min > max) would make
            # every escalation read blocked:rail with the knob ostensibly
            # healthy (the silently-never-steers state the config
            # validator rejects for explicit rails)
            max_threads = at_cfg.max_threads or max(
                at_cfg.min_threads, min(16, os.cpu_count() or 1))
            knobs = [
                _at.thread_knob(raw_ds, min_value=at_cfg.min_threads,
                                max_value=max_threads),
                _at.host_prefetch_knob(host_prefetch,
                                       min_value=at_cfg.min_prefetch,
                                       max_value=at_cfg.max_prefetch),
                _at.device_ring_knob(
                    ds, min_value=at_cfg.min_prefetch_to_device,
                    max_value=at_cfg.max_prefetch_to_device),
                _at.fanout_knob(max_value=at_cfg.max_restart_fanout),
            ]
            if self._ingest is not None:
                # escalation order: the wire is the LAST lever (it changes
                # the batch format; depths/threads are cheaper first moves)
                knobs.append(self._ingest.wire_knob())
            self.autotuner = _at.IngestAutotuner(at_cfg, knobs)
            _exporter.set_autotune_source(self.autotuner.describe)
            if jax.process_index() == 0:
                armed = self.autotuner.describe()
                armed.pop("history", None)
                self.logger.log("autotune_armed", armed)
        else:
            # a prior fit's controller must not keep serving /autotunez
            # for a run that has none
            _exporter.set_autotune_source(None)

        num_chips = self.mesh.devices.size
        meter = ThroughputMeter(num_chips)
        if jax.process_index() == 0:
            self.logger.log("start", {
                "config": cfg.name, "total_steps": total,
                # the configured ingest wire; 'u8' may still have fallen
                # back per-pipeline (data/imagenet.py logs the warning)
                "wire": cfg.data.wire,
                # disaggregated-ingest topology (r16): 'local' or
                # 'service_<N>w' — the run's ingest basis label, matching
                # the regression sentinel's Basis.ingest key
                "ingest": cfg.data.service.label,
                # fused on-device augmentation state (r13): enabled means
                # the device owns flips and the host pipelines never flip
                "augment": cfg.data.augment.enabled,
                **mesh_topology_report(self.mesh)})

        # Telemetry window state (telemetry/): the step log's stall verdict
        # and counter deltas are computed per log window. Pre-creating the
        # core counters makes "zero events" visible as 0 rather than as a
        # missing key, and the delta() call re-baselines the "trainer"
        # consumer so the first window doesn't report process-lifetime
        # totals.
        tele = cfg.telemetry
        reg = telemetry.get_registry()
        rec = telemetry.get_recorder()
        from distributed_vgg_f_tpu.telemetry.flight import get_flight
        flight = get_flight()
        window_start_ns = time.monotonic_ns()
        attributor = None
        if tele.enabled:
            for name in ("resilience/nonfinite_skips",
                         "resilience/data_stall_errors",
                         "checkpoint/saves", "step/dispatched"):
                reg.counter(name)
            reg.set_gauge("decode/errors_total", 0)
            if self.device_augment is not None:
                # augment receipts (r13): steps trained with the fused
                # stage armed (counted per log window) + the armed gauge —
                # the counter-table rows the drift guard cross-checks
                reg.counter("augment/steps")
                reg.set_gauge("augment/enabled", 1)
                # the order the stage's builder took (data/augment.py):
                # 1 = flip, partner gather and pack ran on the wire's own
                # dtype ahead of the finish; 0 = a stage such as rand_ops
                # kept the gather and the pack on floats
                reg.set_gauge(
                    "augment/permute_on_wire_dtype",
                    int(self.device_augment.permute_on_wire_dtype))
            # comm receipts (r14): pre-create so "zero exchanges" reads as
            # 0, not a missing key; the step wrapper increments per
            # dispatch and sets the static exchange-shape gauges
            reg.counter("comm/exchanges")
            reg.counter("comm/wire_bytes")
            if cfg.mesh.elastic.enabled:
                # elastic receipts (r19): pre-create so a run that never
                # resizes reads 0, not a missing key — the counter-table
                # rows the drift guard cross-checks
                for name in ("elastic/resizes", "elastic/evacuated_shards",
                             "elastic/reassigned_data_shards",
                             "elastic/downtime_ns"):
                    reg.counter(name)
            reg.delta("trainer")
            if tele.stall_attribution:
                attributor = telemetry.StallAttributor(
                    registry=reg, recorder=rec,
                    infeed_threshold=tele.infeed_threshold,
                    checkpoint_threshold=tele.checkpoint_threshold)

        profiler = None
        if cfg.train.profile:
            from distributed_vgg_f_tpu.utils.profiling import StepProfiler
            profiler = StepProfiler(
                cfg.train.profile_dir,
                start_step=start_step + cfg.train.profile_start_step,
                num_steps=cfg.train.profile_num_steps)

        eval_every = cfg.train.eval_every_steps or cfg.steps_per_epoch
        # Graceful preemption (SIGTERM = the TPU-VM/k8s grace signal): the
        # handler only sets a flag; the loop reacts at a safe point — after a
        # completed step — with a forced checkpoint and a clean stop.
        # Multi-host: a per-step asynchronous consensus collective
        # (parallel/preempt.py) stops every host at the same step within
        # ~3 steps of the signal, independent of log_every.
        preempt_flag = {"set": False}
        consensus = None
        if cfg.train.handle_preemption and jax.process_count() > 1:
            from distributed_vgg_f_tpu.parallel.preempt import (
                PreemptConsensus)
            consensus = PreemptConsensus(self.mesh, self.data_axis)
        # Best-eval tracking: single replaced slot under <checkpoint_dir>/best
        # (train.track_best_eval). A resumed run must not regress the durable
        # best with its first eval, so the threshold seeds from the slot.
        if self.best_checkpoints is None and self.checkpoints is not None \
                and cfg.train.track_best_eval and eval_dataset is not None:
            self.best_checkpoints = self._make_best_manager()
        best_top1 = float("-inf")
        if self.best_checkpoints is not None:
            best_top1 = float((self.best_checkpoints.latest_extra() or {})
                              .get("eval_top1", float("-inf")))
        old_sigterm = None
        if cfg.train.handle_preemption:
            import signal

            def _on_sigterm(signum, frame):
                preempt_flag["set"] = True

            try:
                old_sigterm = signal.signal(signal.SIGTERM, _on_sigterm)
            except ValueError:
                old_sigterm = None  # not the main thread — feature disabled
        # The native loader zero-fills corrupt/unreadable images instead of
        # raising (a single bad file must not kill a long run) — so its error
        # counter MUST be surfaced, or quality degradation is invisible.
        decode_errors = decode_errors_src
        # Non-finite step guard (resilience/guard.py): the jitted step
        # reports its all-reduced isfinite verdict as metrics["bad_step"];
        # the guard counts consecutive skips via a lagged poll (never blocks
        # dispatch) and aborts with a NonFiniteStepError diagnostic.
        guard = None
        if cfg.train.skip_nonfinite:
            guard = NonFiniteGuard(cfg.train.max_nonfinite_steps,
                                   logger=self.logger)
        _align_cold_start()
        if self.exporter is not None:
            # first heartbeat BEFORE the first step: a probe hitting
            # /healthz during compile must read "ok, step N, young age",
            # not "idle" (which a fleet health-checker treats as not-yet-
            # scheduled and reaps)
            self.exporter.heartbeat(start_step)
        # One try around the loop AND the end-of-run saves: telemetry is
        # exported on EVERY exit — clean completion (after the final forced
        # save, whose checkpoint spans/counters are often the longest
        # blocking interval of the run and must be IN the artifacts), a
        # crash mid-loop, or a crash in the final save itself: the
        # telemetry of a run that died checkpointing is the telemetry you
        # most need on disk (code-review r8 x2). A crash additionally dumps
        # the flight recorder's black box (telemetry/flight.py) BEFORE the
        # export — the last-N-windows artifact is the triage entry point.
        try:
            last_metrics = {}
            host_wait = 0.0  # time blocked waiting for the input pipeline
            ckpt_wait = 0.0  # time blocked in checkpoint machinery this window
            eval_wait = 0.0  # time inside periodic eval passes this window
            guard_seen = 0   # nonfinite skips already attributed to a window
            decode_errors_seen = 0
            window_first_step = start_step  # for the augment/steps delta
            preempted = False
            elastic_t0 = None  # monotonic_ns at consensus-fire; downtime clock
            try:
                for step in range(start_step, total):
                    if profiler is not None:
                        # device_get drains the async dispatch queue so the trace
                        # window brackets device execution, not host dispatch.
                        profiler.step(step, sync=lambda: jax.device_get(state.step))
                    # "infeed" span: consumer-side block. Overlaps the prefetch
                    # iterator's own wait span — same category, and the span
                    # occupancy union (telemetry/stall.py) dedupes overlaps, so
                    # the sync fallback path is covered without double-counting
                    # the threaded one.
                    with rec.span("next_batch", "infeed") as feed:
                        batch = next(ds)  # already sharded on-device by the prefetcher
                    host_wait += feed.dur_ns / 1e9
                    state, metrics = self.train_step(state, batch, rng)
                    if elastic_t0 is not None:
                        # the resize is OVER only when the first survivor-mesh
                        # step has EXECUTED — block on its metrics, then close
                        # the downtime receipt (consensus-fire → first step)
                        jax.block_until_ready(metrics)
                        dt_rs = int(time.monotonic_ns() - elastic_t0)
                        elastic_t0 = None
                        self._elastic_stats["downtime_ns"] += dt_rs
                        telemetry.inc("elastic/downtime_ns", dt_rs)
                        if jax.process_index() == 0:
                            self.logger.log("elastic_downtime", {
                                "step": step + 1, "downtime_ns": dt_rs})
                    if guard is not None:
                        guard.observe(step + 1, metrics["bad_step"])
                    meter.update(cfg.data.global_batch_size)
                    if (step + 1) % cfg.train.log_every == 0 or step + 1 == total:
                        # device_get syncs: throughput numbers include real device
                        # time.
                        fetched = jax.device_get(metrics)
                        # a language model's `moe_load` is a table; the
                        # log takes the scalars
                        last_metrics = {k: float(v) for k, v in
                                        fetched.items() if np.ndim(v) == 0}
                        if "moe_load" in fetched and tele.enabled:
                            # routing receipts (models/mistral4.py's
                            # expert share), over the expert layers of the
                            # step just logged; none where no layer has
                            # experts (models/jamba2.py: an empty table)
                            load = np.asarray(fetched["moe_load"])
                            if load.size:
                                reg.set_gauge("moe/assignments_held",
                                              float(load.sum()))
                                reg.set_gauge("moe/expert_load_max",
                                              float(load.max()))
                                reg.set_gauge("moe/expert_load_min",
                                              float(load.min()))
                                reg.set_gauge("moe/dropped_assignments", sum(
                                    v for k, v in last_metrics.items()
                                    if k.startswith("moe_dropped/")))
                                # 1 = the routed buffers (`routed_capacity`
                                # rows) held every layer's load in one pass
                                for gauge, metric in (
                                        ("moe/routed_passes",
                                         "moe_passes/"),
                                        ("moe/routed_capacity",
                                         "moe_capacity/")):
                                    reg.set_gauge(gauge, max(
                                        v for k, v in last_metrics.items()
                                        if k.startswith(metric)))
                                # the expert layers whose rows went back to
                                # their tokens through the Pallas kernel
                                # (ops/routed_combine_pallas.py)
                                reg.set_gauge("moe/combine_kernel_layers", sum(
                                    v for k, v in last_metrics.items()
                                    if k.startswith("moe_combine_kernel/")))
                            # the state-space layers' receipts
                            # (models/nemotron_h.py): chunks scanned in the
                            # step, the smallest decay of any layer, and
                            # the layers whose recurrence ran as the
                            # Pallas kernels (ops/ssd_pallas.py)
                            decays = [v for k, v in last_metrics.items()
                                      if k.startswith("ssm_decay_min/")]
                            if decays:
                                reg.set_gauge("ssm/decay_min", min(decays))
                                over_layers = lambda metric: sum(
                                    v for k, v in last_metrics.items()
                                    if k.startswith(metric))
                                reg.set_gauge("ssm/chunks",
                                              over_layers("ssm_chunks/"))
                                reg.set_gauge("ssm/kernel_layers",
                                              over_layers("ssm_kernel/"))
                            # the delta-rule layers' receipts and the group
                            # limit's (models/ling3.py): chunks a step, the
                            # smallest decay of any layer (exp(-5): a gate
                            # at its bound), the layers whose recurrence
                            # and whose short convolutions ran as the
                            # Pallas kernels (ops/kda_pallas.py,
                            # ops/short_conv_pallas.py), and the mean share
                            # of tokens whose kept groups include this share's
                            decays = [v for k, v in last_metrics.items()
                                      if k.startswith("kda_decay_min/")]
                            if decays:
                                reg.set_gauge("kda/decay_min", min(decays))
                                over_layers = lambda metric: sum(
                                    v for k, v in last_metrics.items()
                                    if k.startswith(metric))
                                reg.set_gauge("kda/chunks",
                                              over_layers("kda_chunks/"))
                                reg.set_gauge("kda/kernel_layers",
                                              over_layers("kda_kernel/"))
                                reg.set_gauge(
                                    "kda/conv_kernel_layers",
                                    over_layers("kda_conv_kernel/"))
                                # Kimi's own gate and beta in (0, 2)
                                # (models/solar_open2.py): the largest share
                                # of gate entries under -5, the largest beta
                                for gauge, metric in (
                                        ("kda/gate_below_bound",
                                         "kda_gate_below_bound/"),
                                        ("kda/beta_max", "kda_beta_max/")):
                                    seen = [v for k, v in last_metrics.items()
                                            if k.startswith(metric)]
                                    if seen:
                                        reg.set_gauge(gauge, max(seen))
                            shares = [v for k, v in last_metrics.items()
                                      if k.startswith("moe_group_share/")]
                            if shares:
                                reg.set_gauge("moe/group_share",
                                              sum(shares) / len(shares))
                        entry = {"step": step + 1, **last_metrics,
                                 **meter.snapshot(),
                                 # host_wait_fraction: share of wall time this
                                 # window spent blocked on the input pipeline —
                                 # ~0 when the device-prefetch hides the host
                                 # path, →1 when host-bound (SURVEY.md §7
                                 # input-pipeline watch-item).
                                 "host_wait_fraction": round(
                                     host_wait / meter.elapsed, 4)}
                        if guard is not None and guard.total:
                            # cumulative skipped (non-finite) steps this run —
                            # quality degradation must be visible in the log
                            # stream, like decode_errors below
                            entry["nonfinite_skips"] = guard.total
                        if callable(decode_errors) or jax.process_count() > 1:
                            # The counter is process-local; sum across hosts so a
                            # corrupt shard on ANY host is visible in process 0's
                            # log (one tiny allgather per log window). EVERY host
                            # participates in the collective — contributing 0 when
                            # its own pipeline has no counter (e.g. it fell back
                            # to tf.data) — or hosts would deadlock.
                            de = decode_errors() if callable(decode_errors) else 0
                            if jax.process_count() > 1:
                                from jax.experimental import multihost_utils
                                de = int(np.asarray(
                                    multihost_utils.process_allgather(
                                        np.asarray(de, np.int64))).sum())
                            if de > 0:
                                entry["data_decode_errors"] = de
                            if de > decode_errors_seen and \
                                    jax.process_index() == 0:
                                self.logger.log("decode_errors", {
                                    "step": step + 1, "total": de,
                                    "new": de - decode_errors_seen})
                            decode_errors_seen = max(decode_errors_seen, de)
                            if tele.enabled:
                                reg.set_gauge("decode/errors_total", de)
                        # Stall attribution + counter deltas: the window's wall
                        # time is attributed to infeed / checkpoint / guard /
                        # compute, and every registry counter that moved this
                        # window (decode stats via poller, prefetch, resilience,
                        # checkpoint, faults) rides the SAME record — one JSONL
                        # stream, one diagnosis per window. Computed on EVERY
                        # rank since the flight recorder (telemetry/flight.py)
                        # retains it — each rank's black box must carry its
                        # OWN windows, and a crash is exactly when rank 0's
                        # view of another host is not enough. (This walks
                        # back the r8 rank-0-only delta: one poller sweep
                        # per rank per LOG WINDOW buys per-rank crash
                        # forensics — the receipt stays inside the <2%
                        # budget, benchmarks/runs/.)
                        stall_record = None
                        window_wall = max(1e-9, meter.elapsed - eval_wait)
                        if attributor is not None:
                            guard_total = (guard.total if guard is not None
                                           else 0)
                            # eval passes inflate the window's wall time
                            # without touching any wait bucket — left in,
                            # they dilute every fraction toward 0 and
                            # stamp an eval-cratered window
                            # "compute_bound" (code-review r8)
                            stall_record = attributor.window(
                                wall_s=window_wall,
                                infeed_wait_s=host_wait,
                                checkpoint_wait_s=ckpt_wait,
                                guard_skips=guard_total - guard_seen)
                            if eval_wait > 0:
                                stall_record["eval_seconds"] = round(
                                    eval_wait, 3)
                            guard_seen = guard_total
                        # Closed-loop actuation (r11): ONE bounded observe
                        # per log window, on EVERY rank — each process
                        # tunes its own pipeline (heterogeneous host
                        # classes converge to their own knob settings).
                        # The returned record is the JSONL receipt.
                        autotune_record = None
                        if self.autotuner is not None:
                            autotune_record = self.autotuner.observe(
                                stall_record)
                        if self.device_augment is not None and tele.enabled:
                            # every step this window carried the fused
                            # augmentation — the counter rides the same
                            # per-window delta as the rest of the receipts
                            telemetry.inc("augment/steps",
                                          (step + 1) - window_first_step)
                        window_first_step = step + 1
                        window_counters = None
                        critical_path = None
                        if tele.enabled:
                            window_counters = reg.delta("trainer")
                            now_ns = time.monotonic_ns()
                            occupancy = telemetry.occupancy_from_spans(
                                rec.snapshot(), window_start_ns, now_ns)
                            flight.record_window(
                                step=step + 1, wall_s=window_wall,
                                stall=stall_record,
                                counters=window_counters,
                                spans=occupancy)
                            # Critical-path split (r22): the window's wall
                            # clock attributed {infeed, checkpoint,
                            # exchange, device} from the SAME occupancy
                            # the flight window records. Sequential clamp
                            # — each bucket takes at most what the earlier
                            # buckets left — so the four parts sum to the
                            # window EXACTLY by construction; device is
                            # the residual (unspanned host time rides it,
                            # same convention as stall's compute_bound).
                            span_wall = max(
                                0.0, (now_ns - window_start_ns) / 1e9)
                            infeed_s = min(
                                occupancy.get("infeed", 0.0), span_wall)
                            ckpt_s = min(
                                occupancy.get("checkpoint", 0.0),
                                span_wall - infeed_s)
                            exchange_s = min(
                                occupancy.get("coord", 0.0),
                                span_wall - infeed_s - ckpt_s)
                            device_s = (span_wall - infeed_s - ckpt_s
                                        - exchange_s)
                            parts = {"infeed": infeed_s,
                                     "checkpoint": ckpt_s,
                                     "exchange": exchange_s,
                                     "device": device_s}
                            critical_path = {
                                "window_s": round(span_wall, 6),
                                "infeed_s": round(infeed_s, 6),
                                "device_s": round(device_s, 6),
                                "checkpoint_s": round(ckpt_s, 6),
                                "exchange_s": round(exchange_s, 6),
                                "dominant": max(parts, key=parts.get),
                            }
                            window_start_ns = now_ns
                            if self.exporter is not None:
                                self.exporter.heartbeat(step + 1)
                        if jax.process_index() == 0:
                            if stall_record is not None:
                                entry["stall"] = stall_record
                            if window_counters is not None:
                                entry["counters"] = window_counters
                            if critical_path is not None:
                                entry["critical_path"] = critical_path
                            if autotune_record is not None:
                                entry["autotune"] = autotune_record
                            if self.device_augment is not None:
                                # schema-validated augment block
                                # (telemetry/schema.py): the per-window
                                # receipt that this run's diversity was
                                # device-side, host flips disabled
                                entry["augment"] = \
                                    cfg.data.augment.describe()
                            # schema-validated comm block (r14): the
                            # gradient-exchange shape this run actually
                            # traced — sharding basis, bucket count, wire
                            # bytes — single-sourced from the step's
                            # trace-time geometry receipt
                            comm_meta = getattr(self.train_step,
                                                "comm_meta", None)
                            if comm_meta:
                                entry["comm"] = dict(comm_meta)
                            if self._ingest is not None:
                                # schema-validated iterator_state block
                                # (r18): the window's stream position —
                                # trainer cursor, source cursor, in-flight
                                # read-ahead, rebuild count, live wire
                                entry["iterator_state"] = \
                                    self._ingest.window_receipt(step + 1)
                            if cfg.mesh.elastic.enabled:
                                # schema-validated elastic block (r19): the
                                # window's topology + resize receipts —
                                # emitted only when the kill switch is on,
                                # so a disabled run's JSONL is byte-shaped
                                # like r18's
                                est = self._elastic_stats
                                entry["elastic"] = {
                                    "topology": est["topology"],
                                    "batch_policy":
                                        cfg.mesh.elastic.batch_policy,
                                    "resizes": est["resizes"],
                                    "downtime_ns": est["downtime_ns"],
                                    "evacuated_shards":
                                        est["evacuated_shards"],
                                    "reassigned_data_shards":
                                        est["reassigned_data_shards"],
                                    "lr_scale": est["lr_scale"]}
                            self.logger.log("train", entry)
                        meter.reset()
                        host_wait = 0.0
                        ckpt_wait = 0.0
                        eval_wait = 0.0
                    if eval_dataset is not None and (step + 1) % eval_every == 0:
                        t_ev = time.monotonic()
                        result = self.evaluate(state, eval_dataset, step=step + 1)
                        eval_wait += time.monotonic() - t_ev
                        # best-eval tracking: one replaced slot under best/. The
                        # psum'd eval result is identical on every host, so all
                        # hosts take the collective save branch together.
                        if self.best_checkpoints is not None and \
                                result["eval_top1"] > best_top1:
                            best_extra = {"eval_top1": result["eval_top1"],
                                          "eval_top5": result["eval_top5"],
                                          "step": step + 1,
                                          # the layout + iterator-state
                                          # receipts ride the best slot
                                          # too: restore_from_best (and a
                                          # branch resumed from it) must
                                          # read the same geometry and
                                          # stream position as a latest
                                          # restore
                                          **self._save_extra(step + 1)}
                            best_metrics = {"eval_top1": result["eval_top1"]}
                            # replace_on_collision: a resumed run re-reaching the
                            # slot's step number must replace the stale entry —
                            # the best-metric manager stages the replacement at
                            # an unused index so the durable best is never gone
                            # mid-replacement (checkpoint/manager.py `save`).
                            t_ck = time.monotonic()
                            saved = self.best_checkpoints.save(
                                state, force=True, extra=best_extra,
                                metrics=best_metrics, replace_on_collision=True)
                            ckpt_wait += time.monotonic() - t_ck
                            if saved:
                                self._count_state_save(best_extra)
                                # only advance the threshold once the slot
                                # actually holds this model
                                best_top1 = result["eval_top1"]
                                if jax.process_index() == 0:
                                    self.logger.log("best_checkpoint", {
                                        "step": step + 1,
                                        "eval_top1": result["eval_top1"]})
                    if self.checkpoints is not None:
                        # manager applies save_interval_steps; async, non-blocking.
                        # replace_on_collision: a run branched from the best slot
                        # (restore_from_best) re-reaches step numbers the stale
                        # chain already holds — those must be overwritten or a
                        # crash mid-branch would resume from pre-branch state.
                        t_ck = time.monotonic()
                        cadence_extra = self._save_extra(step + 1)
                        if self.checkpoints.save(
                                state, extra=cadence_extra,
                                replace_on_collision=True):
                            self._count_state_save(cadence_extra)
                        ckpt_wait += time.monotonic() - t_ck
                    # Injected preemption (fault_injection "preempt@N"): raises
                    # the same local flag a real SIGTERM would, so the full stop
                    # path — consensus collective included on multi-host — is
                    # exercised without an actual signal.
                    if self.faults is not None and \
                            self.faults.preempt_now(step + 1):
                        if not preempt_flag["set"]:
                            # announce the injector in the fault/ namespace like
                            # the data injectors do (first crossing only — the
                            # >= predicate stays true every later step)
                            telemetry.inc("fault/preempt")
                        preempt_flag["set"] = True
                    # Preemption stop-consensus: single-host reacts immediately;
                    # multi-host polls the per-step async consensus collective
                    # (every host at the same loop index — a lone host acting on
                    # its local flag would strand the others in the collective
                    # save). Gated on the CONFIG flag, which is identical across
                    # hosts — gating on whether the handler installed would not
                    # be.
                    stop = False
                    if cfg.train.handle_preemption:
                        stop = (consensus.poll(preempt_flag["set"])
                                if consensus is not None else preempt_flag["set"])
                    if stop:
                        if self.checkpoints is not None:
                            # the preempt save carries the iterator-state
                            # blob like every other save — the restarted
                            # incarnation (parallel/preempt.py semantics)
                            # resumes position-exactly through the same
                            # dispatch as any other restore. It is written
                            # BEFORE an elastic resize is attempted: the
                            # durable fallback must exist whether the
                            # resize succeeds, degrades, or dies.
                            preempt_extra = self._save_extra(step + 1)
                            saved = self.checkpoints.save(
                                state, force=True, extra=preempt_extra,
                                replace_on_collision=True)
                            if saved:
                                self._count_state_save(preempt_extra)
                            self.checkpoints.wait()
                            if not saved and jax.process_index() == 0:
                                self.logger.log("checkpoint_save_dropped", {
                                    "step": step + 1, "forced": True})
                        if cfg.mesh.elastic.enabled:
                            # Live resize (r19, parallel/elastic.py): keep
                            # training on the survivors. A refused plan
                            # degrades to the r18 stop path below with the
                            # NAMED elastic_degraded_restart flight class —
                            # never unhandled_exception. The downtime clock
                            # opens HERE, after the forced save: the durable
                            # fallback is the shared prefix of BOTH recovery
                            # paths (a restart restores from this exact
                            # checkpoint), so the receipt times recovery,
                            # not the save both sides pay identically.
                            elastic_t0 = time.monotonic_ns()
                            from distributed_vgg_f_tpu.resilience.errors \
                                import ElasticDegraded
                            try:
                                (state, ds, host_prefetch, rng,
                                 meter) = self._elastic_resize(
                                     step + 1, state, ds, host_prefetch,
                                     consensus)
                            except ElasticDegraded as e:
                                from distributed_vgg_f_tpu.telemetry \
                                    import flight as _fl
                                _fl.note_crash("elastic_degraded_restart",
                                               f"{e.reason}: {e}")
                                self.dump_flight_black_box()
                                elastic_t0 = None
                                if jax.process_index() == 0:
                                    self.logger.log("elastic_degraded", {
                                        "step": step + 1,
                                        "reason": e.reason,
                                        "detail": str(e)})
                            else:
                                preempt_flag["set"] = False
                                num_chips = self.mesh.devices.size
                                continue
                        preempted = True
                        if jax.process_index() == 0:
                            self.logger.log("preempt", {
                                "step": step + 1,
                                "checkpointed": self.checkpoints is not None})
                        break
                if guard is not None:
                    # flush the lagged tail — a bad streak shorter than the poll
                    # lag at the very end of the run must still be counted (and
                    # can still abort)
                    guard.drain()
            finally:
                if old_sigterm is not None:
                    import signal
                    signal.signal(signal.SIGTERM, old_sigterm)
                if profiler is not None:
                    profiler.stop()
                if hasattr(ds, "close"):
                    ds.close()
                if host_prefetch is not None:
                    host_prefetch.close()
            if self.checkpoints is not None and not preempted:
                final_extra = self._save_extra(total)
                saved = self.checkpoints.save(
                    state, extra=final_extra,
                    force=True, replace_on_collision=True)
                if saved:
                    self._count_state_save(final_extra)
                self.checkpoints.wait()
                if not saved and jax.process_index() == 0:
                    # a dropped FORCED save means the run's end state was not
                    # persisted — must be loud, never silent (ADVICE r2 #1).
                    # state.step == total here (the loop completed un-preempted),
                    # so no device sync for the log line
                    self.logger.log("checkpoint_save_dropped", {
                        "step": total, "forced": True})
            if self.best_checkpoints is not None:
                self.best_checkpoints.wait()
            return state
        except BaseException as e:
            # the black box must land BEFORE the (fallible, barrier-bearing)
            # telemetry export, and must never mask the run exception
            self.dump_flight_black_box(exc=e)
            raise
        finally:
            if self.autotuner is not None:
                # swap the LIVE /autotunez provider for a plain-data final
                # snapshot: the run's last controller state stays readable,
                # but the bound method no longer pins the closed pipeline
                # object graph — and a later run can never be served this
                # one's state as live
                try:
                    final = self.autotuner.describe()
                    final["live"] = False
                    _exporter.set_autotune_source(lambda: final)
                except Exception:  # noqa: BLE001 — receipts never mask
                    _exporter.set_autotune_source(None)
            self.export_telemetry()

    def _flight_dump_dir(self) -> str:
        """Where the black box lands: telemetry.flight_dir explicitly, else
        the sidecar dir (the run's existing artifact home), else
        <checkpoint_dir>/flight. "" = nowhere configured."""
        tele = self.cfg.telemetry
        if tele.flight_dir:
            return tele.flight_dir
        if tele.sidecar_dir:
            return tele.sidecar_dir
        if self.cfg.train.checkpoint_dir:
            return os.path.join(self.cfg.train.checkpoint_dir, "flight")
        return ""

    def config_fingerprint(self) -> str:
        """Stable hash of the full config — the black box's "which exact
        run was this" key (two boxes from runs that differ only in a
        threshold must not look identical in triage)."""
        import dataclasses
        import hashlib
        import json
        blob = json.dumps(dataclasses.asdict(self.cfg), sort_keys=True,
                          default=str)
        return "sha256:" + hashlib.sha256(blob.encode()).hexdigest()[:16]

    def dump_flight_black_box(self, exc: BaseException | None = None) -> \
            str | None:
        """Write this process's flight-recorder black box (crash path; also
        callable for a live snapshot). Best-effort: a dump failure is
        logged, never raised — it runs while unwinding the real error."""
        tele = self.cfg.telemetry
        if not tele.enabled:
            return None
        from distributed_vgg_f_tpu.telemetry.flight import get_flight
        directory = self._flight_dump_dir()
        log_event = getattr(self.logger, "log", None)
        if not directory:
            if log_event is not None and jax.process_index() == 0:
                log_event("flight_dump_skipped", {
                    "reason": "no telemetry.flight_dir / sidecar_dir / "
                              "checkpoint_dir configured"})
            return None
        versions = {"metrics_schema": telemetry.schema.SCHEMA_VERSION,
                    "jax": jax.__version__}
        try:
            from distributed_vgg_f_tpu.data.native_jpeg import (
                JPEG_ABI_VERSION)
            versions["native_jpeg_abi"] = JPEG_ABI_VERSION
        except Exception:  # noqa: BLE001 — decoder optional by design
            pass
        try:
            path = get_flight().dump(
                directory, exc=exc, process=jax.process_index(),
                config_fingerprint=self.config_fingerprint(),
                config_name=self.cfg.name, versions=versions,
                registry=telemetry.get_registry(),
                recorder=telemetry.get_recorder())
        except Exception as e:  # noqa: BLE001 — never mask the run error
            if log_event is not None and jax.process_index() == 0:
                log_event("flight_dump_failed", {"error": repr(e)})
            return None
        if log_event is not None and jax.process_index() == 0:
            log_event("flight_black_box", {"path": path,
                                           "reason_exc": type(exc).__name__
                                           if exc else None})
        return path

    def export_telemetry(self) -> None:
        """Write the configured telemetry artifacts: the span ring buffer as
        Chrome trace-event JSON (`telemetry.trace_export`) and the
        per-process registry-snapshot sidecars + process-0 aggregate
        (`telemetry.sidecar_dir`). Called from fit()'s finally path;
        standalone eval/predict entry points (cli.py) call it explicitly.
        Best-effort by design: an export failure must never mask the run
        exception it is unwinding under."""
        tele = self.cfg.telemetry
        if not tele.enabled:
            return
        rec = telemetry.get_recorder()
        # The sidecar barrier uses its OWN tag sequence, advanced BEFORE any
        # fallible I/O: deriving it from _barrier_seq (or incrementing after
        # a possible exception) would let one rank's local export failure
        # desynchronize the mandatory align_N sequence and deadlock the
        # next fit/eval phase for the 600 s barrier timeout (code-review
        # r8). A telemetry-tag mismatch only costs a swallowed 30 s wait.
        sidecar_barrier = None
        if tele.sidecar_dir and jax.process_count() > 1:
            _telemetry_barrier_seq["n"] += 1
            sidecar_barrier = f"telemetry_{_telemetry_barrier_seq['n']}"
        try:
            if tele.trace_export:
                path = tele.trace_export
                if jax.process_count() > 1:
                    root, ext = os.path.splitext(path)
                    path = f"{root}_p{jax.process_index():05d}" \
                           f"{ext or '.json'}"
                trace = rec.export_chrome_trace(
                    path,
                    process_name=f"trainer_rank{jax.process_index()}")
                if jax.process_index() == 0:
                    self.logger.log("telemetry_trace_exported", {
                        "path": path,
                        "events": len(trace["traceEvents"]),
                        "dropped_spans": rec.dropped})
            if tele.sidecar_dir:
                from distributed_vgg_f_tpu.parallel.distributed import (
                    aggregate_telemetry_sidecars,
                    write_telemetry_sidecar,
                )
                write_telemetry_sidecar(tele.sidecar_dir, {
                    "event": "telemetry_snapshot",
                    **telemetry.get_registry().snapshot_split(),
                    "spans_recorded": rec.recorded,
                    "spans_dropped": rec.dropped})
                if sidecar_barrier is not None:
                    # Bounded-timeout barrier so a CLEAN exit aggregates
                    # every rank's sidecar (all ranks export concurrently;
                    # rank 0 racing ahead would nondeterministically drop
                    # late writers). On crash paths dead ranks time it out
                    # and the aggregate degrades to whatever is on disk —
                    # never hangs the survivors (code-review r8).
                    try:
                        coordination_barrier(sidecar_barrier,
                                             timeout_ms=30_000)
                    except Exception:  # noqa: BLE001 — best-effort
                        pass
                if jax.process_index() == 0:
                    agg = aggregate_telemetry_sidecars(
                        tele.sidecar_dir,
                        expected_processes=jax.process_count())
                    import json
                    with open(os.path.join(tele.sidecar_dir,
                                           "telemetry_aggregate.json"),
                              "w") as f:
                        json.dump(agg, f, indent=1)
        except Exception as e:  # noqa: BLE001 — never mask the run error
            log_event = getattr(self.logger, "log", None)
            if log_event is not None and jax.process_index() == 0:
                log_event("telemetry_export_failed", {"error": repr(e)})

    def evaluate(self, state: TrainState, dataset: Iterator,
                 num_batches: int | None = None,
                 use_ema: bool | None = None,
                 step: int | None = None) -> Mapping[str, float]:
        """One validation pass (SURVEY.md §3.4).

        Finite eval datasets (data/eval_pad.py FiniteEvalIterable) are scored
        EXACTLY: run to exhaustion, padding rows masked out by the eval step.
        Hosts with uneven shards stay in lockstep — a host that runs out keeps
        feeding all-invalid `padding_batch()`es while `_any_host_has_data`
        (a tiny cross-process all-gather) says another host is still scoring,
        so the psum collective inside eval_step can never strand. Infinite
        iterators fall back to a fixed `num_batches` draw (legacy/synthetic).

        `use_ema=None` (default) scores the EMA weights whenever the state
        carries them (the TF-era ImageNet recipe — the averaged weights are
        the deliverable); pass False to score the raw training weights.

        `step`: the host-side step number for the eval log line. The train
        loop already knows it as a Python int — passing it here keeps the
        log path free of a redundant device sync; standalone callers can
        omit it and pay one device_get."""
        cfg = self.cfg
        if use_ema is None:
            use_ema = state.ema_params is not None
        if use_ema:
            if state.ema_params is None:
                raise ValueError("use_ema=True but state has no ema_params "
                                 "(train.ema_decay is 0)")
            # swap BOTH trees: averaged weights against raw-trajectory BN
            # stats would mismatch the activation distribution
            state = state.replace(params=state.ema_params,
                                  batch_stats=state.ema_batch_stats)
        totals = {"top1": 0, "top5": 0, "count": 0}
        _align_cold_start()
        t0 = time.monotonic()

        def accumulate(batch):
            counts = jax.device_get(self.eval_step(state, self.shard(batch)))
            for k in totals:
                totals[k] += int(counts[k])

        with telemetry.span("eval_pass", "eval"):
            if num_batches is None and getattr(dataset, "is_finite", False):
                it = iter(dataset)
                exhausted = False
                while True:
                    batch = None
                    if not exhausted:
                        batch = next(it, None)
                        exhausted = batch is None
                    if not self._any_host_has_data(not exhausted):
                        break
                    accumulate(batch if batch is not None
                               else dataset.padding_batch())
            else:
                if num_batches is None:
                    num_batches = max(1, cfg.data.num_eval_examples
                                      // cfg.data.global_batch_size)
                it = iter(dataset)
                for _ in range(num_batches):
                    accumulate(next(it))
        n = max(1, totals["count"])
        telemetry.inc("eval/passes")
        result = {"eval_top1": totals["top1"] / n, "eval_top5": totals["top5"] / n,
                  "eval_examples": totals["count"],
                  "eval_seconds": time.monotonic() - t0}
        # The native eval iterator zero-fills corrupt images (still counted
        # valid) — surface that, or the "exact" numbers are silently skewed.
        eval_decode_errors = getattr(dataset, "decode_errors", None)
        if callable(eval_decode_errors):
            de = eval_decode_errors()
            if de > 0:
                result["eval_decode_errors"] = de
        if jax.process_index() == 0:
            if step is None:
                step = int(jax.device_get(state.step))
            self.logger.log("eval", {"step": step, **result})
        return result

    @staticmethod
    def _any_host_has_data(local_has_data: bool) -> bool:
        """True while any process still holds unscored eval examples. One tiny
        all-gather per eval batch — negligible next to the step itself, and the
        price of exactness under uneven host shards."""
        if jax.process_count() == 1:
            return local_has_data
        from jax.experimental import multihost_utils
        flags = multihost_utils.process_allgather(
            np.asarray(local_has_data, np.int32))
        return bool(np.asarray(flags).any())
