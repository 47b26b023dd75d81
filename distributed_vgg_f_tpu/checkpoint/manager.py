"""Checkpoint / resume (SURVEY.md §3.5, §5).

Reference: `tf.train.Saver`-style periodic save, restore-on-restart. Here:
Orbax — async, multi-host aware, sharded-array native. Saved unit is the full
`TrainState` (step, params, batch_stats, opt_state) plus the host data-iterator
position, so a restart resumes mid-epoch and the step-LR schedule position is
reproduced exactly (the schedule reads the restored step counter inside the
jitted step).
"""

from __future__ import annotations

import json
import os
import time
from typing import Any, Mapping, Optional

import jax
import orbax.checkpoint as ocp

from distributed_vgg_f_tpu import telemetry
from distributed_vgg_f_tpu.resilience.errors import CheckpointIntegrityError
from distributed_vgg_f_tpu.resilience.integrity import (
    list_manifest_steps,
    remove_step_manifest,
    step_size_bytes,
    verify_step_manifest,
    write_step_manifest,
)

#: The save()-path (non-blocking) manifest flush hashes a committed step
#: inline only when it is at most this large — full-file SHA-256 of a
#: multi-GB state on the TRAINING thread would stall the step loop for
#: seconds at every checkpoint cadence (code-review). Larger steps stay
#: pending and are manifested at the next wait()/restore-time blocking
#: flush instead; until then they verify as unknown-but-restorable, which
#: Orbax's commit atomicity already vouches for.
INLINE_MANIFEST_MAX_BYTES = 256 * 1024 * 1024

from typing import TYPE_CHECKING

if TYPE_CHECKING:  # annotation-only: a runtime import would cycle through
    # train/__init__ -> trainer -> this module when the package is entered
    # via `distributed_vgg_f_tpu.checkpoint` first
    from distributed_vgg_f_tpu.train.state import TrainState


class CheckpointManager:
    """Thin wrapper over `orbax.checkpoint.CheckpointManager`.

    `save(state, extra=...)` is async (returns immediately, serializes in a
    background thread); `restore(template)` blocks. `extra` carries small
    JSON-able host state (e.g. data-iterator position).
    """

    def __init__(self, directory: str, *, max_to_keep: int = 3,
                 save_interval_steps: int = 1,
                 best_metric: str | None = None,
                 save_retries: int = 2):
        """`best_metric`: retain steps by this metric (max) instead of
        recency — Orbax's native best-checkpoint GC, which keeps the
        best-SCORED step even if a stale step with a higher step number
        survives a crash (pass the metric via `save(..., metrics=...)`;
        `best_step()` then selects by score, self-healing).

        `save_retries`: transient-I/O retry budget for the save dispatch
        (exponential backoff) — a momentary filesystem blip must not kill a
        long run when the NEXT attempt would succeed.

        Integrity (resilience layer): every durable step gets a checksum
        manifest (`<dir>/integrity/<step>.json`, resilience/integrity.py);
        `best_step()`/default restores verify it and transparently fall back
        to the newest INTACT step when the preferred one is truncated or
        corrupt — the skipped steps are recorded on
        `last_integrity_fallback` for the caller to log."""
        self._save_interval = max(1, save_interval_steps)
        # steps this manager instance has durably saved: a collision with one
        # of these is a re-save of IDENTICAL state (a training session holds
        # one state per step) and must not delete-and-rewrite it
        self._saved_steps: set[int] = set()
        self._dir = os.path.abspath(directory)
        self._best_metric = best_metric
        self._save_retries = max(0, save_retries)
        # steps saved but not yet manifested (saves are async — the manifest
        # can only hash a DURABLE step, so it is flushed behind a wait)
        self._manifest_pending: set[int] = set()
        # verification verdicts are cached per content write — this manager
        # is the only writer, so a verified step stays verified
        self._verified: dict[int, bool] = {}
        #: {"chosen": step, "skipped": [(step, detail), ...]} after a
        #: best_step() resolution had to skip damaged steps; None otherwise
        self.last_integrity_fallback: Optional[dict] = None
        os.makedirs(self._dir, exist_ok=True)
        self._mngr = ocp.CheckpointManager(
            self._dir,
            item_names=("state", "extra"),
            # explicit handlers (not just names): item_metadata() must work on
            # a fresh manager that has never saved — the cross-topology restore
            # path reads the SAVED opt-state shapes before building a template
            item_handlers={"state": ocp.StandardCheckpointHandler(),
                           "extra": ocp.JsonCheckpointHandler()},
            options=ocp.CheckpointManagerOptions(
                max_to_keep=max_to_keep,
                save_interval_steps=save_interval_steps,
                enable_async_checkpointing=True,
                best_fn=(None if best_metric is None
                         else lambda m: float(m[best_metric])),
                best_mode="max",
            ),
        )

    # ------------------------------------------------------------------ save
    def save(self, state: TrainState, extra: Optional[Mapping[str, Any]] = None,
             *, force: bool = False,
             metrics: Optional[Mapping[str, Any]] = None,
             replace_on_collision: bool = False) -> bool:
        """`replace_on_collision`: Orbax never overwrites a step; a run
        branched from an earlier checkpoint (train.restore_from_best)
        re-reaches step numbers that already exist on disk holding STALE
        pre-branch state. With this flag such a collision replaces the stale
        step, synchronously (durable before returning). Two strategies:

        - plain (recency-retained) manager: delete the stale step, re-save.
          A crash inside that window loses only the stale step, never the
          rest of the chain.
        - best-metric manager: save the replacement at an UNUSED index —
          Orbax's retention GC removes the worse-scored old entry only after
          the new save is durable (checkpoint_manager._finalize), so at
          every instant at least one best checkpoint exists. `best_step()`
          selects by recorded score, not index.

        A collision with a step THIS manager instance already saved is a
        re-save of identical state (one state per step per session) — e.g.
        the end-of-run forced save landing on the step the cadence save just
        persisted — and returns True without touching the durable copy.

        Transient I/O errors (OSError family) during the save dispatch are
        retried `save_retries` times with exponential backoff before
        propagating — a blip must not kill the run when the retry would
        land."""
        step = int(jax.device_get(state.step))
        # manifest previously-committed steps when the async writer is idle
        # (non-blocking: a cadence save must never stall the train loop
        # behind the in-flight save — Orbax will serialize on it anyway if
        # this call actually dispatches)
        self._flush_manifests(block=False)
        args = {"state": ocp.args.StandardSave(state),
                "extra": ocp.args.JsonSave(dict(extra or {}))}

        def _save_at(idx: int, force_flag: bool) -> bool:
            # "checkpoint" span category: the dispatch is normally async and
            # cheap, but collision replacement / forced saves block — which
            # is exactly what the stall attributor's checkpoint_bound
            # verdict needs to see (telemetry/stall.py).
            with telemetry.span("checkpoint_save_dispatch", "checkpoint"):
                saved = self._retry_io(lambda: self._mngr.save(
                    idx, args=ocp.args.Composite(**args), force=force_flag,
                    metrics=dict(metrics) if metrics else None))
            if saved:
                telemetry.inc("checkpoint/saves")
                self._manifest_pending.add(idx)
            return saved

        def _save_replacing() -> bool:
            if step in self._saved_steps:
                return True  # already durable, identical by construction
            if self._best_metric is not None:
                staged = 1 + max(self._mngr.all_steps(), default=step)
                saved = _save_at(staged, True)
            else:
                if step in self._mngr.all_steps():
                    self.delete(step)
                saved = _save_at(step, True)
            if saved:
                self._mngr.wait_until_finished()
                self._saved_steps.add(step)
            return saved

        try:
            saved = _save_at(step, force)
        except ocp.checkpoint_manager.StepAlreadyExistsError:
            return _save_replacing() if replace_on_collision else False
        if saved:
            self._saved_steps.add(step)
            return True
        if force or not replace_on_collision:
            return saved
        # Non-forced save returned False. Orbax's should_save rejects
        # step <= latest_step BEFORE its existence check, so inside a
        # branched run's stale-overlap region a cadence save is silently
        # suppressed rather than raising StepAlreadyExistsError. Detect the
        # overlap and replace; a genuine interval skip stays skipped.
        latest = self._mngr.latest_step()
        if latest is not None and latest >= step \
                and step % self._save_interval == 0:
            return _save_replacing()
        return False

    def _retry_io(self, fn):
        """Run `fn`, retrying the OSError family with exponential backoff
        (`save_retries` attempts). Orbax control-flow exceptions
        (StepAlreadyExistsError) are not I/O faults and pass straight
        through to the collision handling above."""
        delay = 0.1
        for attempt in range(self._save_retries + 1):
            try:
                return fn()
            except OSError:
                if attempt == self._save_retries:
                    telemetry.inc("checkpoint/save_failures")
                    raise
                telemetry.inc("checkpoint/save_retries")
                time.sleep(delay)
                delay *= 2

    # ------------------------------------------------------------- integrity
    def _flush_manifests(self, block: bool = True) -> None:
        """Write checksum manifests for steps whose async save finished. A
        manifest can only hash DURABLE files, so a flush needs the async
        writer idle: `block=True` (restore/wait paths — correctness over
        latency) waits for it; `block=False` (the per-step save path) skips
        the flush while a save is still in flight rather than stall the
        train loop behind it. Process 0 writes; other hosts only drop their
        pending marks (shared filesystem, the contract Orbax itself
        relies on)."""
        in_progress = getattr(self._mngr, "is_saving_in_progress", None)
        busy = in_progress is not None and in_progress()
        if self._manifest_pending and not (busy and not block):
            self._mngr.wait_until_finished()
            on_disk = set(self._mngr.all_steps())
            deferred: set[int] = set()
            for idx in sorted(self._manifest_pending):
                if idx in on_disk and jax.process_index() == 0:
                    if not block and \
                            step_size_bytes(self._dir, idx) > \
                            INLINE_MANIFEST_MAX_BYTES:
                        # too big to hash on the training thread — defer to
                        # the next blocking flush (wait()/restore)
                        deferred.add(idx)
                        continue
                    write_step_manifest(self._dir, idx)
                self._verified.pop(idx, None)
            self._manifest_pending = deferred
        # Prune manifests orphaned by Orbax's retention GC, which deletes
        # step dirs without passing through delete(): a stale manifest left
        # for a GC'd step NUMBER would falsely flag a later re-save of that
        # number (branched runs re-reach old step numbers) as corrupt and
        # brick its restore (code-review). Cheap: one listdir + all_steps.
        if jax.process_index() == 0:
            alive = set(self._mngr.all_steps()) | self._manifest_pending
            for step in list_manifest_steps(self._dir):
                if step not in alive:
                    remove_step_manifest(self._dir, step)
                    self._verified.pop(step, None)

    def verify_step(self, step: int) -> bool:
        """True when the step's files match its checksum manifest (or no
        manifest exists to check against — legacy steps and the crash window
        before a manifest flush stay restorable on the strength of Orbax's
        commit atomicity). Verdicts are cached; this manager is the only
        writer."""
        if step not in self._verified:
            verdict, detail = verify_step_manifest(self._dir, step)
            self._verified[step] = verdict is not False
            if verdict is False:
                self._last_verify_detail = (step, detail)
        return self._verified[step]

    # --------------------------------------------------------------- restore
    def latest_step(self) -> Optional[int]:
        return self._mngr.latest_step()

    def best_step(self) -> Optional[int]:
        """The step a default restore should use: the best-scored step (when
        `best_metric` is configured), else the latest — SKIPPING any step
        that fails integrity verification, falling back through the
        remaining steps newest-first. None when no intact step remains
        (callers treat that as restore-impossible and must not silently
        reinitialize — see restore()). Skipped steps are recorded on
        `last_integrity_fallback`."""
        self._flush_manifests()
        order: list[int] = []
        if self._best_metric is not None:
            preferred = self._mngr.best_step()
            if preferred is not None:
                order.append(preferred)
        order.extend(s for s in sorted(self._mngr.all_steps(), reverse=True)
                     if s not in order)
        skipped = []
        self.last_integrity_fallback = None
        for step in order:
            if self.verify_step(step):
                if skipped:
                    self.last_integrity_fallback = {
                        "chosen": step, "skipped": skipped}
                    telemetry.inc("checkpoint/integrity_fallbacks")
                return step
            skipped.append((step, getattr(self, "_last_verify_detail",
                                          (step, "corrupt"))[1]))
        if skipped:
            self.last_integrity_fallback = {"chosen": None,
                                            "skipped": skipped}
        return None

    def restore(self, template: TrainState,
                step: Optional[int] = None) -> tuple:
        """Restore (state, extra) at `step` (default: the newest INTACT
        best/latest step — a truncated or corrupt latest falls back
        transparently, see best_step()). `template` is a concrete TrainState
        whose structure/shardings the restored arrays adopt — pass the
        freshly-initialized state so multi-host restores land replicated on
        the mesh. An EXPLICITLY requested step that fails verification
        raises CheckpointIntegrityError (the caller asked for that exact
        state; substituting another would be silent time travel), as does a
        default restore with checkpoints on disk but none intact."""
        if step is not None and not self.verify_step(step):
            raise CheckpointIntegrityError(
                f"checkpoint step {step} under {self._dir} failed integrity "
                f"verification ({getattr(self, '_last_verify_detail', '?')})"
                f" — the files are truncated or corrupt")
        step = step if step is not None else self.best_step()
        if step is None:
            if self._mngr.all_steps():
                raise CheckpointIntegrityError(
                    f"every checkpoint under {self._dir} failed integrity "
                    f"verification "
                    f"({(self.last_integrity_fallback or {}).get('skipped')})"
                    f" — refusing to restore corrupt state; restore from a "
                    f"replica/backup or clear the directory to restart from "
                    f"scratch")
            raise FileNotFoundError(f"no checkpoints under {self._dir}")
        # one measurement feeds both the span and the counter, so the two
        # views of the interval can never disagree (native_loader idiom)
        with telemetry.span("checkpoint_restore", "checkpoint") as span:
            restored = self._mngr.restore(
                step,
                args=ocp.args.Composite(
                    state=ocp.args.StandardRestore(template),
                    extra=ocp.args.JsonRestore(),
                ),
            )
        telemetry.inc("checkpoint/restores")
        telemetry.inc("checkpoint/restore_ns", span.dur_ns)
        extra = restored.get("extra") or {}
        return restored["state"], extra

    def delete(self, step: int) -> None:
        """Remove a saved step (e.g. to replace a best-slot entry whose step
        number collides after a resume — Orbax never overwrites a step)."""
        self._mngr.wait_until_finished()
        self._mngr.delete(step)
        if jax.process_index() == 0:
            remove_step_manifest(self._dir, step)
        self._manifest_pending.discard(step)
        self._verified.pop(step, None)

    def state_metadata(self, step: Optional[int] = None):
        """Structure-only view of the saved state item at `step` (default:
        best/latest): a nested dict/list tree whose leaves carry `.shape` and
        `.dtype` but no array data. Used to detect the saved opt-state layout
        for cross-topology restore (checkpoint/retopology.py)."""
        step = step if step is not None else self.best_step()
        if step is None:
            raise FileNotFoundError(f"no checkpoints under {self._dir}")
        meta = self._mngr.item_metadata(step)["state"]
        # Orbax ≥ 0.11 wraps the structure in a metadata object carrying
        # `.tree`; older releases return the nested dict directly
        return meta.tree if hasattr(meta, "tree") else meta

    def latest_extra(self) -> Optional[Mapping[str, Any]]:
        """The `extra` JSON of the latest (best-metric-selected, when
        configured) checkpoint without restoring the (large) state — e.g.
        the best-eval score a resumed run must not regress. None when no
        checkpoint exists."""
        step = self.best_step()
        if step is None:
            return None
        return self.extra_at(step)

    def extra_at(self, step: int) -> Mapping[str, Any]:
        """The `extra` JSON of one specific step (no state restore) —
        checkpoint/retopology.py reads the ZeRO-2 bucket-geometry receipt
        here BEFORE deciding how to interpret the saved flat opt state."""
        restored = self._mngr.restore(
            step, args=ocp.args.Composite(extra=ocp.args.JsonRestore()))
        return restored.get("extra") or {}

    def iterator_state_at(self, step: int) -> Optional[Mapping[str, Any]]:
        """The r18 iterator-state blob of one step's `extra` (no state
        restore), or None — receipt-absent means a pre-r18 checkpoint and
        the restore dispatch takes the epoch-boundary replay path. The
        trainer reads the blob off the restore it already performs; this
        accessor serves tools/tests/bench that inspect checkpoints
        without restoring arrays (benchmarks/resume_bench.py)."""
        blob = self.extra_at(step).get("iterator_state")
        return blob if isinstance(blob, Mapping) else None

    def wait(self) -> None:
        """Block until pending async saves are durable (and manifested)."""
        with telemetry.span("checkpoint_wait", "checkpoint") as span:
            self._mngr.wait_until_finished()
            self._flush_manifests()
        telemetry.inc("checkpoint/wait_ns", span.dur_ns)

    def close(self) -> None:
        self.wait()
        self._mngr.close()

    def all_steps(self):
        return sorted(self._mngr.all_steps())
