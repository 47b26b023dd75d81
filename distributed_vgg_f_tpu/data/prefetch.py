"""Device prefetch: overlap host→device batch transfer with device compute.

Reference equivalent (SURVEY.md §1 data layer / §7 hard parts): the reference's
input pipeline hides host work behind device compute with queue runners /
``tf.data`` prefetch. On TPU the analogue has two halves:

1. host-side prefetch — already done inside the dataset iterators (tf.data
   prefetch / the native C++ double-buffered loader);
2. **device-side prefetch** — this module: a bounded background thread that
   pulls the next process-local numpy batch and immediately lands it on the
   mesh (sharded over the data axis) while the current jitted step is still
   executing. The trainer then never blocks on a H2D copy at step start: JAX's
   async dispatch overlaps the copy with the previous step's device work.

The buffer is deliberately small (default 2): each slot holds a full on-device
batch in HBM, and deeper queues add memory pressure without latency benefit.

Resilience (train.data_timeout_s; resilience layer): the consumer side is
also the **data watchdog**. A loader that stalls (hung NFS/GCS read, stuck
decode worker, remote shard server gone) used to hang `next()` forever — the
step loop just stopped, indistinguishable from slow compute. With a timeout
configured, `__next__` waits `data_timeout_s`, then retries with exponential
backoff (bounded by `timeout_retries`), then raises a typed
:class:`DataStallError` carrying how long it waited and how many batches had
been delivered. Independently of the timeout, a prefetch worker thread that
dies without delivering a batch or an error is detected (thread liveness
checked while waiting) and surfaces as `DataStallError` too, instead of the
consumer blocking on a queue nothing will ever fill.
"""

from __future__ import annotations

import queue
import threading
import time
from typing import Iterator, Mapping

import numpy as np

from distributed_vgg_f_tpu import telemetry
from distributed_vgg_f_tpu.parallel.mesh import shard_host_batch
from distributed_vgg_f_tpu.resilience.errors import DataStallError


class _WaitTimeout(Exception):
    """Internal: one bounded wait elapsed (distinct from the public,
    retries-exhausted DataStallError)."""


#: what a worker's draw returns from a source that has ended (the draw is a
#: span like every other: the worker waited for it)
_EXHAUSTED = object()


class DevicePrefetchIterator:
    """Wraps a host-batch iterator; yields mesh-sharded device batches.

    A daemon thread runs ``shard_host_batch`` (device_put) ahead of the
    consumer, keeping up to ``buffer_size`` batches resident on device.
    Exceptions from the source iterator (including exhaustion) propagate to
    the consumer at the matching ``next()`` call, preserving iterator
    semantics. ``close()`` stops the thread and drops buffered batches.

    ``batch_timeout_s`` > 0 arms the watchdog: each ``next()`` waits at most
    ``batch_timeout_s``, retried ``timeout_retries`` times with the wait
    doubling per attempt (worst case ``batch_timeout_s * (2^(retries+1)-1)``
    total), then raises :class:`DataStallError`. A dead worker thread is
    detected regardless of the timeout setting.
    """

    _STOP = object()
    _POLL_S = 0.1  # liveness-check granularity while blocked on the queue

    def __init__(self, source: Iterator[Mapping[str, np.ndarray]], mesh,
                 data_axis: str = "data", buffer_size: int = 2,
                 batch_timeout_s: float = 0.0, timeout_retries: int = 2):
        if buffer_size < 1:
            raise ValueError(f"buffer_size must be >= 1, got {buffer_size}")
        # Buffer-ownership contract: a source that recycles its output
        # arrays (native_jpeg.enable_output_buffer_reuse — bench-only)
        # would have its batch overwritten while device_put may still be
        # reading (or aliasing) the host memory. Refuse loudly instead of
        # corrupting training data.
        if getattr(source, "reuses_output_buffers", False):
            raise ValueError(
                "device prefetch requires caller-owned batches, but this "
                "iterator recycles its output buffers "
                "(enable_output_buffer_reuse is for synchronous bench "
                "loops only) — construct the iterator without buffer "
                "reuse for training")
        if batch_timeout_s < 0 or timeout_retries < 0:
            raise ValueError(
                f"batch_timeout_s/timeout_retries must be >= 0, got "
                f"{batch_timeout_s}/{timeout_retries}")
        self._source = source
        self._mesh = mesh
        self._data_axis = data_axis
        self._batch_timeout = batch_timeout_s
        self._timeout_retries = timeout_retries
        self._batches_delivered = 0
        self._queue: queue.Queue = queue.Queue(maxsize=buffer_size)
        self._closed = threading.Event()
        # Telemetry (telemetry/registry.py namespace "prefetch/"): pre-create
        # the counters so a zero reads as "instrumented, nothing happened"
        # in every snapshot; the queue-depth gauge is the stall attributor's
        # corroborating signal (depth pinned at 0 <=> infeed-bound).
        reg = telemetry.get_registry()
        for name in ("prefetch/batches", "prefetch/wait_ns",
                     "prefetch/timeouts", "prefetch/dead_workers",
                     "prefetch/source_batches", "prefetch/device_put_bytes"):
            reg.counter(name)
        reg.set_gauge("prefetch/queue_depth", 0)
        # bytes_in_flight: HBM resident in queued (undelivered) batches —
        # with device_put_bytes this makes wire-format wins (u8 vs bf16 vs
        # f32, data.wire) directly visible in stall-attribution receipts.
        reg.set_gauge("prefetch/bytes_in_flight", 0)
        self._bytes_lock = threading.Lock()
        self._bytes_in_flight = 0
        self._thread = threading.Thread(target=self._worker, daemon=True,
                                        name="device-prefetch")
        self._thread.start()

    def _worker(self) -> None:
        rec = telemetry.get_recorder()
        reg = telemetry.get_registry()
        try:
            source = iter(self._source)
            while True:
                # the worker's own source wait is "infeed_source": it shows
                # WHERE the pipeline starves (host loader vs H2D) without
                # double-counting against the consumer-side "infeed" spans
                with rec.span("source_next", "infeed_source"):
                    host_batch = next(source, _EXHAUSTED)
                if host_batch is _EXHAUSTED:
                    break
                reg.inc("prefetch/source_batches")
                if self._closed.is_set():
                    return
                # wire-format receipt: bytes the host actually ships through
                # device_put for this batch (1 B/px on the u8 wire vs 2/4 on
                # host_bf16/host_f32 — the counter the bench's bytes/img
                # columns corroborate against)
                nbytes = sum(int(np.asarray(v).nbytes)
                             for v in host_batch.values())
                with rec.span("device_put", "infeed_source"):
                    device_batch = shard_host_batch(host_batch, self._mesh,
                                                    self._data_axis)
                reg.inc("prefetch/device_put_bytes", nbytes)
                # count the bytes BEFORE the queue put: the consumer may
                # dequeue (and decrement) the instant the put lands, and a
                # decrement-first interleaving would publish a negative
                # "HBM resident" gauge
                with self._bytes_lock:
                    self._bytes_in_flight += nbytes
                    reg.set_gauge("prefetch/bytes_in_flight",
                                  self._bytes_in_flight)
                if not self._put(("batch", device_batch, nbytes)):
                    # clamp: close() may have zeroed the count while this
                    # worker was blocked in _put — compensating below zero
                    # would publish a negative "HBM resident" gauge
                    with self._bytes_lock:
                        self._bytes_in_flight = max(
                            0, self._bytes_in_flight - nbytes)
                        reg.set_gauge("prefetch/bytes_in_flight",
                                      self._bytes_in_flight)
                    return
                reg.set_gauge("prefetch/queue_depth", self._queue.qsize())
            self._put(("stop", StopIteration()))
        except BaseException as exc:  # noqa: BLE001 — relayed to consumer
            self._put(("error", exc))

    def _put(self, item) -> bool:
        """Put with periodic close checks; False if closed before it landed."""
        while not self._closed.is_set():
            try:
                self._queue.put(item, timeout=0.1)
                return True
            except queue.Full:
                continue
        return False

    def __iter__(self) -> "DevicePrefetchIterator":
        return self

    def _get(self, timeout: float | None):
        """One bounded queue wait in liveness-checking slices: raises
        DataStallError the moment the worker is dead with nothing queued
        (nothing will EVER arrive — waiting longer is a hang), _WaitTimeout
        when `timeout` elapses."""
        deadline = None if timeout is None else time.monotonic() + timeout
        while True:
            try:
                return self._queue.get(timeout=self._POLL_S)
            except queue.Empty:
                if not self._thread.is_alive() and self._queue.empty():
                    telemetry.inc("prefetch/dead_workers")
                    telemetry.inc("resilience/data_stall_errors")
                    from distributed_vgg_f_tpu.telemetry import flight
                    flight.note_crash(
                        "data_stall",
                        f"prefetch worker died after "
                        f"{self._batches_delivered} batches")
                    raise DataStallError(
                        f"device-prefetch worker thread died without "
                        f"delivering a batch or an error (after "
                        f"{self._batches_delivered} batches) — the host "
                        f"loader is gone; restart the run or check the "
                        f"input pipeline") from None
                if deadline is not None and time.monotonic() >= deadline:
                    raise _WaitTimeout from None

    def __next__(self):
        if self._closed.is_set():
            raise StopIteration
        # "infeed" category = time the CONSUMER was blocked here — the
        # direct input to the stall attributor's infeed_fraction (a wait
        # that ends in the stream's end or an error is a span too)
        with telemetry.span("prefetch_wait", "infeed") as wait:
            if self._batch_timeout <= 0:
                item = self._get(None)
            else:
                timeout, waited = self._batch_timeout, 0.0
                for attempt in range(self._timeout_retries + 1):
                    try:
                        item = self._get(timeout)
                        break
                    except _WaitTimeout:
                        telemetry.inc("prefetch/timeouts")
                        waited += timeout
                        timeout *= 2  # exponential backoff between retries
                else:
                    telemetry.inc("resilience/data_stall_errors")
                    from distributed_vgg_f_tpu.telemetry import flight
                    flight.note_crash(
                        "data_stall",
                        f"watchdog timeout: no batch within {waited:.1f}s "
                        f"across {self._timeout_retries + 1} attempts "
                        f"({self._batches_delivered} batches delivered)")
                    raise DataStallError(
                        f"input pipeline stalled: no batch within "
                        f"{waited:.1f}s across {self._timeout_retries + 1} "
                        f"watchdog attempts "
                        f"(train.data_timeout_s={self._batch_timeout}, "
                        f"exponential backoff; {self._batches_delivered} "
                        f"batches delivered before the stall). The host "
                        f"loader is hung or severely underprovisioned — "
                        f"check storage/decode workers, or raise "
                        f"train.data_timeout_s if this pipeline is "
                        f"legitimately this slow.") from None
        kind, payload = item[0], item[1]
        if kind == "batch":
            self._batches_delivered += 1
            reg = telemetry.get_registry()
            reg.inc("prefetch/batches")
            reg.inc("prefetch/wait_ns", wait.dur_ns)
            reg.set_gauge("prefetch/queue_depth", self._queue.qsize())
            # clamped like the producer's rollback: a concurrent close()
            # (teardown, watchdog, __del__) may already have zeroed the
            # count, and going below zero would publish a negative gauge
            with self._bytes_lock:
                self._bytes_in_flight = max(0, self._bytes_in_flight
                                            - item[2])
                reg.set_gauge("prefetch/bytes_in_flight",
                              self._bytes_in_flight)
            return payload
        self.close()
        if kind == "stop":
            raise StopIteration
        raise payload

    @property
    def buffer_size(self) -> int:
        return self._queue.maxsize

    def set_buffer_size(self, n: int) -> int:
        """Runtime-resize the device ring (r11 — the ingest autotuner's
        `prefetch_to_device` knob). Growing takes effect at the producer's
        next put (its bounded put loop re-checks the limit every 100 ms);
        shrinking never drops queued batches — the queue simply refuses new
        puts until the consumer drains below the new bound, so HBM
        occupancy decays to the target instead of discarding work. Returns
        the now-active bound."""
        n = max(1, int(n))
        with self._queue.mutex:
            self._queue.maxsize = n
            self._queue.not_full.notify_all()
        return n

    def close(self) -> None:
        self._closed.set()
        # Drain so a blocked producer can observe the closed flag and exit.
        while True:
            try:
                self._queue.get_nowait()
            except queue.Empty:
                break
        # dropped buffered batches are no longer in flight; publish the
        # zero under the lock so it cannot stomp a concurrent update
        with self._bytes_lock:
            self._bytes_in_flight = 0
            telemetry.get_registry().set_gauge("prefetch/bytes_in_flight", 0)

    def __del__(self):  # pragma: no cover — best-effort cleanup
        try:
            self.close()
        except Exception:  # interpreter-shutdown teardown order
            pass


class HostPrefetchIterator:
    """Bounded host-side read-ahead stage: a daemon thread pulls host
    batches from `source` into a queue of numpy batches (no device work),
    decoupling decode jitter from the consumer — typically the
    device-prefetch worker, whose single-threaded pull otherwise exposes
    every source hiccup directly to `device_put` cadence.

    Built for the closed-loop ingest autotuner (data/autotune.py): `depth`
    is runtime-resizable via `set_depth` (the `data.prefetch` knob), so the
    controller can deepen the buffer when the stall attributor names the
    host pipeline. Only installed when autotuning is active — with the
    controller absent (config off or DVGGF_AUTOTUNE=0) the feed path is
    byte-identical to pre-r11 behavior, wrapper included.

    Ownership contract: queued batches are caller-owned references, so a
    source that recycles its output arrays (enable_output_buffer_reuse) is
    refused — same rule as device prefetch. Exceptions (and exhaustion)
    propagate to the consumer at the matching `next()`; `close()` stops the
    worker and drops buffered batches.
    """

    def __init__(self, source, depth: int = 2):
        if depth < 1:
            raise ValueError(f"depth must be >= 1, got {depth}")
        if getattr(source, "reuses_output_buffers", False):
            raise ValueError(
                "host prefetch requires caller-owned batches, but this "
                "iterator recycles its output buffers "
                "(enable_output_buffer_reuse is for synchronous bench "
                "loops only)")
        self._source = source
        self._queue: queue.Queue = queue.Queue(maxsize=depth)
        self._closed = threading.Event()
        reg = telemetry.get_registry()
        reg.counter("prefetch/host_batches")
        reg.set_gauge("prefetch/host_queue_depth", 0)
        self._thread = threading.Thread(target=self._worker, daemon=True,
                                        name="host-prefetch")
        self._thread.start()

    @property
    def depth(self) -> int:
        return self._queue.maxsize

    def set_depth(self, n: int) -> int:
        """Runtime-resize the read-ahead bound (same contract as
        DevicePrefetchIterator.set_buffer_size: grow engages within the
        producer's next put poll, shrink decays without dropping)."""
        n = max(1, int(n))
        with self._queue.mutex:
            self._queue.maxsize = n
            self._queue.not_full.notify_all()
        return n

    def decode_errors(self):
        """Forward the wrapped loader's corrupt-image counter (the trainer
        binds it before wrapping, but bench consumers read it here)."""
        fn = getattr(self._source, "decode_errors", None)
        return fn() if callable(fn) else 0

    def _worker(self) -> None:
        rec = telemetry.get_recorder()
        reg = telemetry.get_registry()
        try:
            source = iter(self._source)
            while not self._closed.is_set():
                with rec.span("host_prefetch_next", "infeed_source"):
                    batch = next(source, _EXHAUSTED)
                if batch is _EXHAUSTED:
                    break
                reg.inc("prefetch/host_batches")
                if not self._put(("batch", batch)):
                    return
                reg.set_gauge("prefetch/host_queue_depth",
                              self._queue.qsize())
            self._put(("stop", StopIteration()))
        except BaseException as exc:  # noqa: BLE001 — relayed to consumer
            self._put(("error", exc))

    def _put(self, item) -> bool:
        while not self._closed.is_set():
            try:
                self._queue.put(item, timeout=0.1)
                return True
            except queue.Full:
                continue
        return False

    def __iter__(self):
        return self

    def __next__(self):
        if self._closed.is_set():
            raise StopIteration
        while True:
            try:
                item = self._queue.get(timeout=0.1)
                break
            except queue.Empty:
                if self._closed.is_set():
                    # a concurrent close() drained the queue (stop marker
                    # included) — this is shutdown, not a dead worker;
                    # raising the watchdog error here would stamp every
                    # clean teardown race as a data stall
                    raise StopIteration from None
                if not self._thread.is_alive() and self._queue.empty():
                    # mirror the device-prefetch dead-worker contract: a
                    # silently dead read-ahead thread must surface as a
                    # typed stall, never an indefinite hang (the DEVICE
                    # prefetch watchdog downstream usually fires first)
                    telemetry.inc("prefetch/dead_workers")
                    raise DataStallError(
                        "host-prefetch worker thread died without "
                        "delivering a batch or an error") from None
        kind, payload = item
        if kind == "batch":
            telemetry.set_gauge("prefetch/host_queue_depth",
                                self._queue.qsize())
            return payload
        self.close()
        if kind == "stop":
            raise StopIteration
        raise payload

    def close(self) -> None:
        self._closed.set()
        # drain so a producer blocked in put() can observe the closed flag
        while True:
            try:
                self._queue.get_nowait()
            except queue.Empty:
                break
        # JOIN the worker BEFORE touching the source: closing the inner
        # loader while the worker is still inside next(source) would
        # destroy native decode state under a live call (use-after-free —
        # observed as a hung teardown in the bench's wire-rebuild hook)
        if self._thread.is_alive() \
                and threading.current_thread() is not self._thread:
            self._thread.join(timeout=10)
        while True:  # anything the worker put while we were joining
            try:
                self._queue.get_nowait()
            except queue.Empty:
                break
        telemetry.set_gauge("prefetch/host_queue_depth", 0)
        if self._thread.is_alive() \
                and threading.current_thread() is not self._thread:
            # join timed out: the worker is stuck INSIDE next(source)
            # (hung storage read). Closing the source now would be the
            # exact use-after-free the join exists to prevent — leak the
            # handles instead (the daemon thread dies with the process)
            # and leave a receipt.
            telemetry.inc("prefetch/dead_workers")
            return
        src_close = getattr(self._source, "close", None)
        if callable(src_close):
            src_close()

    def __del__(self):  # pragma: no cover — best-effort cleanup
        try:
            self.close()
        except Exception:
            pass


def maybe_prefetch(source, mesh, data_axis: str = "data", buffer_size: int = 2,
                   batch_timeout_s: float = 0.0, timeout_retries: int = 2):
    """Wrap `source` in device prefetch when buffer_size > 0, else return a
    generator that shards synchronously (the non-overlapped fallback — the
    watchdog needs the prefetch thread to time-bound, so timeouts only apply
    to the threaded path)."""
    if buffer_size > 0:
        return DevicePrefetchIterator(source, mesh, data_axis, buffer_size,
                                      batch_timeout_s=batch_timeout_s,
                                      timeout_retries=timeout_retries)

    def _sync():
        for host_batch in source:
            yield shard_host_batch(host_batch, mesh, data_axis)

    return _sync()
