"""Always-on host-side span tracing — the cheap half of the observability
spine (tf.data-paper instrumentation model, PAPERS.md).

`jax.profiler` traces (utils/profiling.py StepProfiler) are the heavyweight
tool: device timelines, ICI collectives — but they cost enough that they run
for a 5-step window per run. This module is the complement: a thread-safe
bounded ring buffer of host-side spans (monotonic-ns start + duration,
category, thread id) cheap enough to leave on for the WHOLE run — one
`monotonic_ns()` pair and a deque append per span, no allocation beyond the
5-tuple. The buffer exports as Chrome trace-event JSON (`ph: "X"` complete
events), loadable in Perfetto / chrome://tracing next to (or instead of) a
profiler window.

Categories are the stall-attribution vocabulary (telemetry/stall.py):
"infeed" (consumer blocked on the input pipeline), "infeed_source" (the
prefetch worker's own source draw / H2D), "checkpoint" (save/restore/wait),
"dispatch" (host dispatch of the jitted step), "coord" (cross-process
barriers), "eval", "host" (everything else).

A span can also ride a profiler's timeline: while the recorder is enabled
and holds an `annotate` hook, every `span(...)` opens the hook's context
manager under the name `dvggf:<category>:<name>`. The trainer installs
`jax.profiler.TraceAnnotation` there (`telemetry.configure(annotate=...)`),
so a `jax.profiler` capture shows the program's own spans on the clock of
the device's operations and an idle gap of the device can be put down to
`infeed`, `dispatch` or `checkpoint`. With no capture running the hook
costs one activity check a span.

No numpy, no jax, no TF — importing this package must stay free of heavy
deps (tests/test_telemetry.py pins that); the hook is handed in.
"""

from __future__ import annotations

import json
import os
import threading
import time
from collections import deque
from typing import Callable, ContextManager, List, Optional, Tuple

#: (name, category, start_ns, dur_ns, tid[, args]) — plain tuples, not
#: objects: recording must cost nanoseconds, not an allocation-heavy
#: dataclass. The 6th element (an args dict — trace-correlation ids for
#: cross-process stitching, telemetry/stitch.py) exists ONLY on spans that
#: passed one; the common path stays a 5-tuple, so consumers unpack with a
#: star (`name, cat, s0, dur, *rest = span`).
SpanTuple = Tuple[str, str, int, int, int]


#: Prefix of the names spans carry on a profiler's timeline.
ANNOTATION_PREFIX = "dvggf:"


class _Span:
    """Context manager handed out by `SpanRecorder.span`. After exit,
    `dur_ns` is the measured duration (for call sites that also feed a
    counter from the same measurement)."""

    __slots__ = ("_rec", "_name", "_cat", "_t0", "_annotation", "dur_ns")

    def __init__(self, rec: "SpanRecorder", name: str, category: str):
        self._rec = rec
        self._name = name
        self._cat = category

    def __enter__(self) -> "_Span":
        rec = self._rec
        self._annotation = None
        if rec.enabled and rec.annotate is not None:
            self._annotation = rec.annotate(
                f"{ANNOTATION_PREFIX}{self._cat}:{self._name}")
            self._annotation.__enter__()
        self._t0 = time.monotonic_ns()
        return self

    def __exit__(self, *exc) -> bool:
        self.dur_ns = time.monotonic_ns() - self._t0
        self._rec.record(self._name, self._cat, self._t0, self.dur_ns)
        if self._annotation is not None:
            self._annotation.__exit__(*exc)
        return False


class SpanRecorder:
    """Thread-safe bounded ring buffer of spans.

    The buffer is a `deque(maxlen=capacity)`: when full, the OLDEST span is
    evicted (and counted in `dropped`) — a long run keeps the most recent
    window, which is the one a stall diagnosis needs. `enabled=False` turns
    `record` into an attribute check + return (the kill-switch the overhead
    receipt measures against)."""

    def __init__(self, capacity: int = 8192, enabled: bool = True):
        if capacity < 1:
            raise ValueError(f"capacity must be >= 1, got {capacity}")
        self.capacity = int(capacity)
        self.enabled = bool(enabled)
        self._buf: deque = deque(maxlen=self.capacity)
        self._lock = threading.Lock()
        self._dropped = 0
        self._recorded = 0
        #: `name -> context manager` opened around every `span(...)` while
        #: enabled (module docstring); None = spans stay on this clock only.
        self.annotate: Optional[Callable[[str], ContextManager]] = None

    # ------------------------------------------------------------- recording
    def record(self, name: str, category: str, start_ns: int,
               dur_ns: int, args: Optional[dict] = None) -> None:
        """Append one completed span. Cheap enough for per-batch call sites;
        NOT meant for per-image granularity (the native decode stats cover
        that level through the registry pollers). `args` (a small JSON-able
        dict, e.g. a trace-correlation id) rides the span into the Chrome
        export; omitted, the stored tuple stays the allocation-free
        5-tuple."""
        if not self.enabled:
            return
        tid = threading.get_ident()
        with self._lock:
            if len(self._buf) == self.capacity:
                self._dropped += 1
            self._recorded += 1
            if args is None:
                self._buf.append((name, category, int(start_ns),
                                  int(dur_ns), tid))
            else:
                self._buf.append((name, category, int(start_ns),
                                  int(dur_ns), tid, args))

    def span(self, name: str, category: str = "host") -> _Span:
        """Context manager form: `with recorder.span("save", "checkpoint"):`"""
        return _Span(self, name, category)

    # --------------------------------------------------------------- reading
    def snapshot(self) -> List[SpanTuple]:
        """Copy of the current buffer contents, oldest first."""
        with self._lock:
            return list(self._buf)

    @property
    def recorded(self) -> int:
        """Total spans ever recorded (including since-evicted ones)."""
        with self._lock:
            return self._recorded

    @property
    def dropped(self) -> int:
        """Spans evicted by the ring bound — how much history the capacity
        lost. Dropped > 0 on a long run is expected, not an error."""
        with self._lock:
            return self._dropped

    def clear(self) -> None:
        with self._lock:
            self._buf.clear()
            self._dropped = 0
            self._recorded = 0

    def set_capacity(self, capacity: int) -> None:
        """Resize the ring, keeping the newest spans that fit."""
        if capacity < 1:
            raise ValueError(f"capacity must be >= 1, got {capacity}")
        with self._lock:
            self.capacity = int(capacity)
            self._buf = deque(self._buf, maxlen=self.capacity)

    # ---------------------------------------------------------------- export
    def to_chrome_trace(self, process_name: str | None = None) -> dict:
        """Chrome trace-event JSON object format: complete events (`ph: "X"`,
        timestamps/durations in MICROseconds — the format both Perfetto and
        chrome://tracing load). The monotonic-ns epoch is arbitrary but
        shared across every span in the process, so relative placement is
        exact.

        Metadata events (`ph: "M"`): `process_name` (the explicit param,
        else the module-level label from `set_process_label` — so a
        per-process sidecar reads `trainer_rank0` / `ingest_worker2` in
        Perfetto even before stitching) and one `thread_name` per live
        named thread whose ident appears in the buffer — captured at
        EXPORT time from threading.enumerate(), zero cost at record
        time."""
        pid = os.getpid()
        label = process_name or get_process_label()
        events = []
        if label:
            events.append({"name": "process_name", "ph": "M", "pid": pid,
                           "args": {"name": label}})
        spans = self.snapshot()
        tids = {s[4] for s in spans}
        for t in threading.enumerate():
            if t.ident in tids and t.name:
                events.append({"name": "thread_name", "ph": "M",
                               "pid": pid, "tid": t.ident,
                               "args": {"name": t.name}})
        for name, cat, start_ns, dur_ns, tid, *rest in spans:
            ev = {
                "name": name, "cat": cat, "ph": "X",
                "ts": start_ns / 1e3, "dur": dur_ns / 1e3,
                "pid": pid, "tid": tid,
            }
            if rest and rest[0]:
                ev["args"] = rest[0]
            events.append(ev)
        return {
            "traceEvents": events,
            "displayTimeUnit": "ms",
            "otherData": {"clock": "monotonic_ns",
                          "dropped_spans": self.dropped,
                          "recorded_spans": self.recorded},
        }

    def export_chrome_trace(self, path: str,
                            process_name: str | None = None) -> dict:
        """Write the Chrome trace JSON to `path`; returns the object written
        (so callers can log event counts without re-reading the file)."""
        trace = self.to_chrome_trace(process_name=process_name)
        os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
        with open(path, "w") as f:
            json.dump(trace, f)
        return trace


# --------------------------------------------------------------------------
# Process-wide default recorder — the one every wired call site uses, so one
# export shows the whole host picture (infeed + checkpoint + dispatch).
# --------------------------------------------------------------------------

_default = SpanRecorder()

#: Role label for THIS process's trace exports ("" = unset, exports fall
#: back to whatever explicit process_name the caller passes). Set once at
#: process startup (trainer rank, ingest worker CLI, serving entry) so
#: every export from the process — the fit-finally sidecar AND the live
#: /trace endpoint — carries the same Perfetto process label.
_process_label = ""


def set_process_label(label: str) -> None:
    global _process_label
    _process_label = str(label or "")


def get_process_label() -> str:
    return _process_label


def get_recorder() -> SpanRecorder:
    return _default


def process_start_ns() -> Optional[int]:
    """When this process started, on the ring's clock (`monotonic_ns`), to
    the kernel's clock tick (10 ms). Linux: field 22 of `/proc/self/stat`,
    in ticks since boot, against `CLOCK_BOOTTIME` now. None where either
    is missing."""
    try:
        with open("/proc/self/stat") as f:
            # the command's name (field 2) may hold spaces: count from its ")"
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        since_boot_ns = ticks * 1_000_000_000 // os.sysconf("SC_CLK_TCK")
        age_ns = time.clock_gettime_ns(time.CLOCK_BOOTTIME) - since_boot_ns
    except (OSError, AttributeError, ValueError, IndexError):
        return None
    return time.monotonic_ns() - age_ns


def span(name: str, category: str = "host") -> _Span:
    """`with spans.span("next_batch", "infeed"):` on the default recorder."""
    return _default.span(name, category)


def record(name: str, category: str, start_ns: int, dur_ns: int,
           args: Optional[dict] = None) -> None:
    _default.record(name, category, start_ns, dur_ns, args)
