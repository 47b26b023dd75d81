"""Profiling / tracing subsystem (SURVEY.md §5 tracing).

Reference: at most TF-timeline prints. Here: `jax.profiler` traces — the
TPU-native tool — captured for a small window of steps mid-run (after compile
and warmup) so the trace shows steady-state device time, ICI collectives, and
host-infeed gaps. View with TensorBoard's profile plugin or Perfetto.
"""

from __future__ import annotations

import jax


class StepProfiler:
    """Captures a `jax.profiler` trace over steps [start, start+num_steps).

    Driven by the trainer loop: call `step(i)` once per step with the global
    step index; the trace starts/stops at the window edges. `stop()` is
    idempotent and must run on interrupted loops (the trainer calls it in a
    finally block) — an unterminated trace corrupts the output directory.
    """

    def __init__(self, logdir: str, *, start_step: int, num_steps: int = 5):
        self.logdir = logdir
        self.start_step = start_step
        self.end_step = start_step + num_steps
        self._active = False
        self.captured = False

    def step(self, global_step: int, sync=None) -> None:
        """`sync`: zero-arg callable that drains the device queue (e.g.
        `lambda: jax.device_get(state.step)`). JAX dispatch is async, so
        without it the trace window brackets host *dispatch* of the windowed
        steps while the device is still executing earlier ones."""
        if not self.captured and not self._active \
                and global_step >= self.start_step:
            if sync is not None:
                sync()
            jax.profiler.start_trace(self.logdir)
            self._active = True
        elif self._active and global_step >= self.end_step:
            if sync is not None:
                sync()
            self.stop()

    def stop(self) -> None:
        if self._active:
            jax.profiler.stop_trace()
            self._active = False
            self.captured = True
