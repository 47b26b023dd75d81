"""Unified telemetry spine: span tracing + counter registry + per-step
stall attribution (the observability layer the tf.data / TF-system papers
treat as core infrastructure, PAPERS.md).

Three pieces, one namespace:

- `spans` — thread-safe bounded ring buffer of host-side spans with Chrome
  trace-event export (Perfetto-loadable), cheap enough to stay on outside
  `jax.profiler` windows;
- `registry` — process-wide counters/gauges plus pull pollers that fold the
  native decoder's `decode_stats`, prefetch queue depth/wait, resilience
  events, and checkpoint timings into one `<subsystem>/<metric>` namespace;
- `stall` — classifies each logged interval as infeed_bound /
  compute_bound / checkpoint_bound / guard_stalled from the waits, span
  overlaps, and queue-depth gauges, emitted in the trainer's step log.

Plus the r10 live-observability plane over the same state (imported on
demand, not at package import):

- `exporter` — config-gated per-process HTTP server: /metrics (Prometheus
  text), /healthz (heartbeat liveness), /stallz (verdict history), /trace
  (live Chrome-trace snapshot);
- `flight` — always-on crash flight recorder: last-N-windows ring, dumped
  as a schema-validated black box on diagnosed aborts;
- `regress` — receipt-driven perf regression sentinel over the committed
  HOST_DECODE_RATE_R* trajectory (benchmarks/regression_sentinel.py CLI);
- `schema` — record validators, now carrying SCHEMA_VERSION for trainer
  JSONL records, bench artifacts, black boxes, and the trajectory file.

IMPORT CONTRACT: importing this package (or any submodule) pulls in neither
TensorFlow, nor jax, nor the native `.so`s — stdlib only. Wired call sites
(data/prefetch.py, train/trainer.py, checkpoint/manager.py, ...) import
telemetry, never the reverse; subsystems with native state hand the
registry a poller instead of being imported by it.
tests/test_telemetry.py pins this in a subprocess.
"""

from __future__ import annotations

from typing import Callable, Iterator, Optional

from distributed_vgg_f_tpu.telemetry import schema  # noqa: F401 (re-export)
from distributed_vgg_f_tpu.telemetry.registry import (
    TelemetryRegistry,
    get_registry,
    inc,
    register_poller,
    set_gauge,
)
from distributed_vgg_f_tpu.telemetry.spans import (
    SpanRecorder,
    get_process_label,
    get_recorder,
    process_start_ns,
    record,
    set_process_label,
    span,
)
from distributed_vgg_f_tpu.telemetry.stall import (
    VERDICTS,
    StallAttributor,
    classify,
    occupancy_from_spans,
)

__all__ = [
    "SpanRecorder", "TelemetryRegistry", "StallAttributor", "VERDICTS",
    "classify", "configure", "enabled", "get_process_label",
    "get_recorder", "get_registry", "inc", "instrument_iterator",
    "occupancy_from_spans", "process_start_ns", "record",
    "register_poller", "reset", "schema",
    "set_gauge", "set_process_label", "span",
]


def configure(*, enabled: Optional[bool] = None,
              span_capacity: Optional[int] = None,
              flight_windows: Optional[int] = None,
              annotate: Optional[Callable] = None) -> None:
    """Flip the process-wide default recorder+registry from config
    (TelemetryConfig → Trainer.__init__). `enabled=False` is the
    kill-switch the overhead receipt measures against: record/inc become
    attribute-check-and-return. `annotate` (the trainer hands in
    `jax.profiler.TraceAnnotation`) puts every `span(...)` on the
    profiler's timeline too, as `dvggf:<category>:<name>` (spans.py)."""
    if annotate is not None:
        get_recorder().annotate = annotate
    if enabled is not None:
        get_recorder().enabled = bool(enabled)
        get_registry().enabled = bool(enabled)
    if span_capacity is not None:
        get_recorder().set_capacity(span_capacity)
    if flight_windows is not None:
        from distributed_vgg_f_tpu.telemetry.flight import get_flight
        get_flight().set_max_windows(flight_windows)


def enabled() -> bool:
    return get_recorder().enabled


def reset() -> None:
    """Clear the default recorder AND registry (tests — the defaults are
    process-global, so suites must re-baseline between cases)."""
    get_recorder().clear()
    get_registry().reset()


def instrument_iterator(source: Iterator, name: str = "next_batch",
                        category: str = "infeed",
                        counter: str = "prefetch/batches") -> Iterator:
    """Wrap a batch iterator with the per-batch telemetry the trainer's
    FULL feed path performs, op-for-op and through the same `span(...)`
    calls: the prefetch worker's two spans + source counter + queue-depth
    gauge, the consumer's wait span + batch/wait counters + queue-depth
    gauge, and the trainer loop's own infeed span + step-dispatch
    span/counter — 5 spans, 4 counter increments, 2 gauge sets per batch
    (data/prefetch.py + trainer loop + step wrapper). This is the
    instrumented side of the bench's telemetry-on-vs-off overhead receipt
    (benchmarks/host_pipeline_bench.py): the receipt must charge the 'on'
    column AT LEAST what training pays, never a lighter stand-in."""
    rec = get_recorder()
    reg = get_registry()
    it = iter(source)
    base = counter.rsplit("/", 1)[0]
    end = object()
    while True:
        # trainer loop's own infeed span, around the consumer's wait
        # (prefetch.py __next__), around the worker's source draw + device
        # put (prefetch.py _worker; nothing is put here)
        with rec.span(name, category):
            with rec.span("prefetch_wait", category) as wait:
                with rec.span("source_next", "infeed_source"):
                    batch = next(it, end)
                if batch is end:
                    return
                with rec.span("device_put", "infeed_source"):
                    pass
                reg.inc(f"{base}/source_batches")
                reg.set_gauge(f"{base}/queue_depth", 1)
            reg.inc(counter)
            reg.inc(f"{base}/wait_ns", wait.dur_ns)
            reg.set_gauge(f"{base}/queue_depth", 0)
        # the jitted-step dispatch wrapper (train/step.py)
        with rec.span("train_step_dispatch", "dispatch"):
            pass
        reg.inc("step/dispatched")
        yield batch
