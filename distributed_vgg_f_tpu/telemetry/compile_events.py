"""JAX's own compile events on the span ring and the counter registry.

JAX reports every trace, lowering and backend compile of a jitted function
to whoever listens (`jax.monitoring`), and the persistent compilation cache
reports its hits and misses the same way. `install(jax.monitoring)` (called
by `utils/compile_cache.enable_compile_cache()`, where every entry point
goes before its first jit) registers one set of listeners for the life of
the process. They write to the default recorder and registry, looked up at
every event, so `telemetry.reset()` leaves them working and
`telemetry.configure(enabled=False)` silences them.

Spans, category `compile`, named `<stage>:<fun_name>` with the stages of
`scopes.COMPILE_SPANS`:

- `trace:<fun>`: a function traced to a jaxpr. Every `jnp` call raises its
  own event, and they nest (`inner` lies inside `outer`): only events of
  `MIN_TRACE_SPAN_NS` and more become spans (the ring is bounded), every one
  is counted (`compile/trace_events`), and a total over the spans is the
  union of their intervals, never their sum.
- `lower:jit(<fun>)`: jaxpr to an MLIR module. One an executable.
- `backend:jit(<fun>)` where XLA compiled, `cache_read:jit(<fun>)` where
  the persistent cache held the executable. JAX raises the same
  backend-compile event for both; the cache's hit event fires first, on the
  compiling thread and inside the stage, so the listener knows which it was
  by the time the stage's span is reported.

JAX stamps these with `time.time()`; one offset taken at install puts them
on the ring's clock (`time.monotonic_ns`). They are recorded after the
fact, so a profiler capture does not show them (`jax.profiler` has its own
`$pjit` and compile events there).

Counters: `compile/trace_events`, `compile/programs` (lowerings, one an
executable), `compile/lower_ns`, `compile/backend_ns`,
`compile/cache_read_ns`, `compile/cache_hits`, `compile/cache_misses`. A
step that compiles in the middle of a run is then in the trainer's
per-window counter delta.

Stdlib only (the package's import contract): `jax.monitoring` is handed in.
"""

from __future__ import annotations

import threading
import time

from distributed_vgg_f_tpu.telemetry import registry, spans

#: A trace event shorter than this is counted and leaves no span.
MIN_TRACE_SPAN_NS = 1_000_000

_TRACE = "/jax/core/compile/jaxpr_trace_duration"
_LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_BACKEND = "/jax/core/compile/backend_compile_duration"
_CACHE_REQUEST = "/jax/compilation_cache/compile_requests_use_cache"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"
_CACHE_MISS = "/jax/compilation_cache/cache_misses"

#: whether the backend stage now running on this thread was a cache read
_stage = threading.local()
_install_lock = threading.Lock()
#: time.monotonic_ns() - time.time_ns() at install; None = not installed
_wall_to_ring_ns: int | None = None


def _on_event(event: str, **kw) -> None:
    if event == _CACHE_REQUEST:
        _stage.cache_read = False
    elif event == _CACHE_HIT:
        _stage.cache_read = True
        registry.inc("compile/cache_hits")
    elif event == _CACHE_MISS:
        registry.inc("compile/cache_misses")


def _on_time_span(event: str, start: float, end: float, *,
                  fun_name: str = "?", **kw) -> None:
    if event not in (_TRACE, _LOWER, _BACKEND):
        return
    start_ns = int(start * 1e9) + _wall_to_ring_ns
    dur_ns = max(0, int((end - start) * 1e9))
    if event == _TRACE:
        registry.inc("compile/trace_events")
        if dur_ns >= MIN_TRACE_SPAN_NS:
            spans.record(f"trace:{fun_name}", "compile", start_ns, dur_ns)
    elif event == _LOWER:
        registry.inc("compile/programs")
        registry.inc("compile/lower_ns", dur_ns)
        spans.record(f"lower:{fun_name}", "compile", start_ns, dur_ns)
    elif getattr(_stage, "cache_read", False):
        _stage.cache_read = False
        registry.inc("compile/cache_read_ns", dur_ns)
        spans.record(f"cache_read:{fun_name}", "compile", start_ns, dur_ns)
    else:
        registry.inc("compile/backend_ns", dur_ns)
        spans.record(f"backend:{fun_name}", "compile", start_ns, dur_ns)


def install(monitoring) -> bool:
    """Register the listeners with `monitoring` (the `jax.monitoring`
    module), once a process: a second call registers nothing and returns
    False."""
    global _wall_to_ring_ns
    with _install_lock:
        if _wall_to_ring_ns is not None:
            return False
        _wall_to_ring_ns = time.monotonic_ns() - time.time_ns()
        monitoring.register_event_listener(_on_event)
        monitoring.register_event_time_span_listener(_on_time_span)
        return True
