"""Telemetry spine (distributed_vgg_f_tpu/telemetry/): span ring buffer +
Chrome-trace export, counter registry with pollers and per-consumer deltas,
stall-attribution taxonomy, schema validators, the import-isolation
contract, and the integration seams — chaos-suite fault counters, a
synthetic slow iterator attributed infeed_bound, and a trainer smoke run
whose step records carry a verdict plus decode/prefetch/resilience counters
in one JSONL stream (ISSUE 4 acceptance)."""

import io
import json
import os
import subprocess
import sys
import threading
import time

import jax
import numpy as np
import pytest

from distributed_vgg_f_tpu import scopes, telemetry
from distributed_vgg_f_tpu.config import (
    DataConfig,
    ExperimentConfig,
    ModelConfig,
    OptimConfig,
    TelemetryConfig,
    TrainConfig,
)
from distributed_vgg_f_tpu.telemetry import schema
from distributed_vgg_f_tpu.telemetry.registry import TelemetryRegistry
from distributed_vgg_f_tpu.telemetry.spans import SpanRecorder
from distributed_vgg_f_tpu.telemetry.stall import (
    VERDICTS,
    StallAttributor,
    classify,
    occupancy_from_spans,
)
from distributed_vgg_f_tpu.utils.logging import MetricLogger


@pytest.fixture(autouse=True)
def _fresh_telemetry():
    """The default recorder/registry are process-global: re-baseline around
    every test so counter assertions see only their own activity."""
    telemetry.reset()
    telemetry.configure(enabled=True)
    yield
    telemetry.reset()
    telemetry.configure(enabled=True)


def _cfg(steps=3, tmp=None, **train_kw):
    tele = {}
    if tmp is not None:
        tele = {"trace_export": str(tmp / "trace.json"),
                "sidecar_dir": str(tmp / "sidecars")}
    return ExperimentConfig(
        name="telemetry_test",
        model=ModelConfig(name="vggf", num_classes=10, dropout_rate=0.0,
                          compute_dtype="float32"),
        optim=OptimConfig(base_lr=0.05, reference_batch_size=16),
        data=DataConfig(name="synthetic", image_size=32,
                        global_batch_size=16, num_train_examples=64),
        train=TrainConfig(steps=steps, log_every=1, seed=0, **train_kw),
        telemetry=TelemetryConfig(**tele),
    )


# ------------------------------------------------------------------- spans
def test_span_ring_bounds_and_thread_safety():
    rec = SpanRecorder(capacity=64)
    threads = [threading.Thread(
        target=lambda: [rec.record("s", "host", i, 10) for i in range(100)])
        for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    spans = rec.snapshot()
    assert len(spans) == 64                       # bounded
    assert rec.recorded == 400
    assert rec.dropped == 400 - 64                # evictions counted
    assert {s[4] for s in spans} <= {t.ident for t in threads}


def test_span_disabled_records_nothing():
    rec = SpanRecorder(enabled=False)
    with rec.span("x", "infeed"):
        pass
    rec.record("y", "host", 0, 5)
    assert rec.snapshot() == [] and rec.recorded == 0


def test_chrome_trace_export_validates(tmp_path):
    rec = SpanRecorder()
    with rec.span("load", "infeed"):
        time.sleep(0.001)
    rec.record("save", "checkpoint", time.monotonic_ns(), 5_000)
    path = str(tmp_path / "trace.json")
    trace = rec.export_chrome_trace(path, process_name="p0")
    assert schema.validate_chrome_trace(trace) == []
    assert schema.validate_trace_file(path) == []
    events = [e for e in trace["traceEvents"] if e["ph"] == "X"]
    assert {e["name"] for e in events} == {"load", "save"}
    assert all(e["dur"] >= 0 and e["ts"] > 0 for e in events)
    # µs conversion: the 1 ms sleep must be visible in the dur
    assert max(e["dur"] for e in events) >= 1_000


# ----------------------------------------------------------------- registry
def test_registry_counters_gauges_and_consumer_deltas():
    reg = TelemetryRegistry()
    reg.counter("a/zero")                 # pre-created → visible as 0
    reg.inc("a/n", 3)
    reg.set_gauge("g/depth", 2)
    snap = reg.snapshot()
    assert snap == {"a/zero": 0, "a/n": 3, "g/depth": 2}
    assert reg.delta("c1") == {"a/zero": 0, "a/n": 3, "g/depth": 2}
    reg.inc("a/n", 2)
    reg.set_gauge("g/depth", 0)
    # deltas are per-consumer: c1 sees only the new increments, a fresh
    # consumer sees the lifetime total; gauges stay absolute everywhere
    assert reg.delta("c1") == {"a/zero": 0, "a/n": 2, "g/depth": 0}
    assert reg.delta("c2")["a/n"] == 5


def test_registry_pollers_cumulative_and_errors():
    reg = TelemetryRegistry()
    state = {"images": 10}
    reg.register_poller("decode", lambda: {
        "images": state["images"], "scale_histogram": {4: 2, 8: 1}})
    assert reg.snapshot()["decode/images"] == 10
    assert reg.snapshot()["decode/scale_histogram/4"] == 2
    reg.delta("c")
    state["images"] = 25
    assert reg.delta("c")["decode/images"] == 15   # cumulative → delta'd
    reg.register_poller("bad", lambda: 1 / 0)
    snap = reg.snapshot()                          # must not raise
    assert snap["telemetry/poller_errors"] >= 1
    assert "bad" not in "".join(k.split("/")[0] for k in snap
                                if k.startswith("bad/"))


def test_registry_delta_survives_transient_poller_failure():
    """A poller that fails for one window must not reset its baseline: the
    next successful window's delta is the WINDOW's change, never the
    process-lifetime total (code-review r8)."""
    reg = TelemetryRegistry()
    state = {"images": 1000, "fail": False}

    def poll():
        if state["fail"]:
            raise RuntimeError("transient")
        return {"images": state["images"]}

    reg.register_poller("decode", poll)
    reg.delta("c")                                  # baseline at 1000
    state["fail"] = True
    assert "decode/images" not in reg.delta("c")    # failed window: absent
    state["fail"] = False
    state["images"] = 1010
    assert reg.delta("c")["decode/images"] == 10    # not 1010


def test_registry_has_poller_and_direct_gauge_read():
    """reset() drops pollers, so registration guards must key on
    has_poller (a stale module flag would sever the subsystem's counters
    for the process lifetime — code-review r8); gauge() reads one value
    without sweeping the pollers."""
    reg = TelemetryRegistry()
    assert not reg.has_poller("decode")
    reg.register_poller("decode", lambda: {"images": 1})
    assert reg.has_poller("decode")
    reg.reset()
    assert not reg.has_poller("decode")
    calls = {"n": 0}

    def poll():
        calls["n"] += 1
        return {"x": 1}

    reg.register_poller("p", poll)
    reg.set_gauge("prefetch/queue_depth", 2)
    assert reg.gauge("prefetch/queue_depth") == 2
    assert reg.gauge("missing", -1) == -1
    assert calls["n"] == 0          # no poller sweep on the direct read
    split = reg.snapshot_split()
    assert split["counters"]["p/x"] == 1
    assert split["gauges"] == {"prefetch/queue_depth": 2}


def test_registry_disabled_drops_writes():
    reg = TelemetryRegistry(enabled=False)
    reg.inc("a/n")
    reg.set_gauge("g", 1)
    assert reg.snapshot() == {}


# -------------------------------------------------------------------- stall
def test_stall_taxonomy_priorities():
    assert classify(1.0, 0.05, 0.0)["verdict"] == "compute_bound"
    assert classify(1.0, 0.5, 0.0)["verdict"] == "infeed_bound"
    assert classify(1.0, 0.1, 0.4)["verdict"] == "checkpoint_bound"
    # guard beats everything: a run skipping updates isn't training no
    # matter where its wall time goes
    assert classify(1.0, 0.9, 0.9, guard_skips=1)["verdict"] \
        == "guard_stalled"
    # checkpoint vs infeed: the LARGER blocked fraction wins, checkpoint
    # winning exact ties (it usually CAUSES the infeed gap)
    assert classify(1.0, 0.4, 0.4)["verdict"] == "checkpoint_bound"
    assert classify(1.0, 0.6, 0.3)["verdict"] == "infeed_bound"
    # candidacy is per-bucket: an infeed fraction BELOW its own (raised)
    # threshold must not veto a checkpoint fraction above its threshold
    assert classify(1.0, 0.35, 0.30,
                    infeed_threshold=0.4)["verdict"] == "checkpoint_bound"
    assert set(VERDICTS) == {"guard_stalled", "checkpoint_bound",
                             "infeed_bound", "compute_bound"}


def test_occupancy_merges_overlapping_spans():
    spans = [("a", "infeed", 0, 100, 1), ("b", "infeed", 50, 100, 2),
             ("c", "checkpoint", 300, 50, 1), ("d", "infeed", 1000, 100, 1)]
    occ = occupancy_from_spans(spans, 0, 400)
    # [0,150) union, not 200 sum; the span at 1000 is outside the window
    assert occ["infeed"] == pytest.approx(150e-9)
    assert occ["checkpoint"] == pytest.approx(50e-9)


def test_slow_iterator_attributed_infeed_bound(devices8):
    """ISSUE 4 satellite: a synthetic slow loader must come back
    infeed_bound from stall.py, driven end-to-end through the REAL
    device-prefetch spans (no hand-fed fractions)."""
    from distributed_vgg_f_tpu.data.prefetch import DevicePrefetchIterator
    from distributed_vgg_f_tpu.parallel.mesh import MeshSpec, build_mesh

    mesh = build_mesh(MeshSpec(("data",), (8,)))

    def slow_source():
        while True:
            time.sleep(0.05)  # decode 20 img/s-slow
            yield {"image": np.zeros((16, 8, 8, 3), np.float32),
                   "label": np.zeros((16,), np.int32)}

    pre = DevicePrefetchIterator(slow_source(), mesh)
    attributor = StallAttributor(registry=telemetry.get_registry(),
                                 recorder=telemetry.get_recorder())
    try:
        t0 = time.monotonic_ns()
        for _ in range(4):
            next(pre)
        t1 = time.monotonic_ns()
    finally:
        pre.close()
    verdict = attributor.window_from_spans(t0, t1)
    assert verdict["verdict"] == "infeed_bound"
    assert verdict["infeed_fraction"] > 0.5
    # the corroborating gauge: a starved consumer sees an empty queue
    assert verdict["queue_depth"] == 0


# --------------------------------------------------- chaos-suite integration
def test_fault_injectors_increment_matching_counters(devices8):
    """ISSUE 4 satellite: every train.fault_injection fault type announces
    itself in the fault/ registry namespace, and the guard's skip rides the
    resilience/ namespace — one fit exercising nan+stall+preempt, one
    exercising crash."""
    from distributed_vgg_f_tpu.resilience import InjectedFault
    from distributed_vgg_f_tpu.train.trainer import Trainer

    quiet = MetricLogger(stream=io.StringIO())
    tr = Trainer(_cfg(steps=4,
                      fault_injection="nan@1,stall@2:0.05,preempt@3"),
                 logger=quiet)
    tr.fit(tr.init_state())
    counters = telemetry.get_registry().snapshot()
    assert counters["fault/nan"] == 1
    assert counters["fault/stall"] == 1
    assert counters["fault/preempt"] == 1
    assert counters["resilience/nonfinite_skips"] == 1

    tr2 = Trainer(_cfg(steps=4, fault_injection="crash@2"), logger=quiet)
    with pytest.raises(InjectedFault):
        tr2.fit(tr2.init_state())
    assert telemetry.get_registry().snapshot()["fault/crash"] == 1


# ------------------------------------------------------------ trainer smoke
def test_trainer_smoke_one_jsonl_stream(devices8, tmp_path):
    """ISSUE 4 acceptance: a CPU smoke run produces step records carrying a
    stall-attribution verdict plus decode/prefetch/resilience counters in
    ONE JSONL stream, the stream validates against the schema, and the
    exported span file validates as Chrome trace-event JSON."""
    from distributed_vgg_f_tpu.train.trainer import Trainer

    path = str(tmp_path / "metrics.jsonl")
    with MetricLogger(jsonl_path=path, stream=io.StringIO()) as logger:
        tr = Trainer(_cfg(steps=3, tmp=tmp_path), logger=logger)
        tr.fit(tr.init_state())
    assert schema.validate_metrics_jsonl(path) == []
    records = [json.loads(l) for l in open(path)]
    train_records = [r for r in records if r["event"] == "train"]
    assert len(train_records) == 3
    for r in train_records:
        assert r["stall"]["verdict"] in VERDICTS
        counters = r["counters"]
        assert counters["prefetch/batches"] == 1          # log_every=1
        assert "resilience/nonfinite_skips" in counters
        assert "decode/errors_total" in counters
        assert "prefetch/queue_depth" in counters
        assert "window_images_per_sec" in r               # rolling meter
    # the span file: Chrome trace-event JSON with the wired categories
    trace_path = str(tmp_path / "trace.json")
    assert schema.validate_trace_file(trace_path) == []
    cats = {e.get("cat") for e in
            json.load(open(trace_path))["traceEvents"]}
    assert {"infeed", "dispatch"} <= cats
    # sidecar + aggregate written (single process: 1)
    agg = json.load(open(tmp_path / "sidecars" /
                         "telemetry_aggregate.json"))
    assert agg["processes"] == 1
    assert agg["counters"]["prefetch/batches"] >= 3
    # gauges are per-rank in the aggregate, never summed across ranks
    assert "prefetch/queue_depth" in agg["gauges_by_process"]
    assert set(agg["gauges_by_process"]["prefetch/queue_depth"]) == {"0"}


def test_telemetry_disabled_is_silent(devices8, tmp_path):
    """enabled=false is a real kill-switch: no stall/counters in the step
    records, nothing recorded into the ring."""
    from distributed_vgg_f_tpu.train.trainer import Trainer

    path = str(tmp_path / "metrics.jsonl")
    cfg = _cfg(steps=2)
    cfg = ExperimentConfig(**{**cfg.__dict__,
                              "telemetry": TelemetryConfig(enabled=False)})
    with MetricLogger(jsonl_path=path, stream=io.StringIO()) as logger:
        tr = Trainer(cfg, logger=logger)
        tr.fit(tr.init_state())
    train_records = [json.loads(l) for l in open(path)
                     if json.loads(l)["event"] == "train"]
    assert train_records and all(
        "stall" not in r and "counters" not in r for r in train_records)
    assert telemetry.get_recorder().snapshot() == []


# ------------------------------------------------------------------- schema
def test_schema_catches_drift(tmp_path):
    assert schema.validate_metrics_record({"event": "train", "loss": 1.0}) \
        == []
    assert schema.validate_metrics_record({"loss": 1.0})    # no event
    assert schema.validate_metrics_record([1, 2])           # not an object
    # bare NaN tokens — JSON-illegal, the exact drift the validator exists
    # to catch (json.loads alone would ACCEPT them)
    bad = tmp_path / "bad.jsonl"
    bad.write_text('{"event": "train", "loss": NaN}\n')
    assert schema.validate_metrics_jsonl(str(bad))
    ok = tmp_path / "ok.jsonl"
    ok.write_text('{"event": "train", "loss": null, '
                  '"loss_nonfinite": "nan"}\n')
    assert schema.validate_metrics_jsonl(str(ok)) == []
    # trace drift
    assert schema.validate_chrome_trace({"traceEvents": [
        {"name": "x", "ph": "X", "ts": "soon", "dur": 1,
         "pid": 1, "tid": 1, "cat": "host"}]})
    assert schema.validate_chrome_trace({"events": []})


def test_schema_validates_committed_bench_artifacts():
    """Record-shape drift in the committed run archives fails fast."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    checked = 0
    for name in sorted(os.listdir(repo)):
        if name.startswith("BENCH_r") and name.endswith(".json"):
            errors = schema.validate_bench_artifact_file(
                os.path.join(repo, name))
            assert errors == [], f"{name}: {errors}"
            checked += 1
    runs = os.path.join(repo, "benchmarks", "runs")
    for dirpath, _, files in os.walk(runs):
        for f in files:
            if f.endswith(".json"):
                path = os.path.join(dirpath, f)
                with open(path) as fh:
                    try:
                        obj = json.load(fh)
                    except ValueError:
                        obj = None
                if not isinstance(obj, dict):
                    continue
                if obj.get("kind") == "perf_trajectory":
                    # the r10 sentinel's trajectory file has its own shape
                    errors = schema.validate_trajectory(obj)
                    assert errors == [], f"{path}: {errors}"
                    checked += 1
                elif "metric" in obj:
                    errors = schema.validate_bench_artifact_file(path)
                    assert errors == [], f"{path}: {errors}"
                    checked += 1
    assert checked > 0


# ------------------------------------------------------------ schema_version
def test_schema_version_field_rules():
    """ISSUE 8 satellite: absent = legal (pre-versioned archives), known
    major = legal at any minor, unknown major = rejected, non-string =
    rejected — same rule on metrics records and bench artifacts."""
    ok = {"event": "train", "loss": 1.0}
    assert schema.validate_metrics_record(ok) == []
    assert schema.validate_metrics_record(
        {**ok, "schema_version": schema.SCHEMA_VERSION}) == []
    assert schema.validate_metrics_record(
        {**ok, "schema_version": "1.7"}) == []          # future minor: fine
    assert any("major" in e for e in schema.validate_metrics_record(
        {**ok, "schema_version": "2.0"}))
    assert schema.validate_metrics_record(
        {**ok, "schema_version": 1})                     # not a string
    assert schema.validate_metrics_record(
        {**ok, "schema_version": "one.oh"})
    art = {"metric": "m", "value": 1.0}
    assert schema.validate_bench_artifact(
        {**art, "schema_version": schema.SCHEMA_VERSION}) == []
    assert any("major" in e for e in schema.validate_bench_artifact(
        {**art, "schema_version": "3.0"}))


def test_metric_logger_stamps_schema_version(tmp_path):
    path = str(tmp_path / "m.jsonl")
    with MetricLogger(jsonl_path=path, stream=io.StringIO()) as logger:
        logger.log("train", {"step": 1, "loss": 0.5})
    record = json.loads(open(path).readline())
    assert record["schema_version"] == schema.SCHEMA_VERSION
    assert schema.validate_metrics_jsonl(path) == []


# ------------------------------------------------- counter-namespace guard
def _normalize_buckets(name: str) -> str:
    """Histogram bucket keys (decode/scale_histogram/8) document as one
    `<m>` placeholder row."""
    import re
    return re.sub(r"^(decode/scale_histogram)/\d+$", r"\1/<m>", name)


def test_counter_table_matches_runtime(devices8):
    """ISSUE 8 satellite — counter-namespace drift guard: the README table
    is cross-checked against (a) every counter/gauge name literal in the
    package source (the registration sites: prefetch, snapshot cache,
    resilience, checkpoint, trainer, exporter, ...) and (b) the native
    decode poller's ACTUAL runtime keys. Undocumented runtime names and
    stale documented names both fail.

    Since r15 half (a) — the static literal scan and table parse — lives
    in the unified invariant linter (`counter-namespace-drift`,
    tools/lint/rules.py); this test runs that rule and keeps the RUNTIME
    half the linter cannot see: the decode poller's dynamically-registered
    keys, reconciled against the table's `decode/` rows."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if repo not in sys.path:
        sys.path.insert(0, repo)
    from tools.lint import RepoContext, get_rule
    from tools.lint.rules import (
        package_counter_literals,
        readme_documented_counters,
    )
    ctx = RepoContext(repo)

    # (a) the static half, through the framework rule
    violations = get_rule("counter-namespace-drift").check(ctx)
    assert violations == [], "\n".join(str(v) for v in violations)

    namespaces, documented, errs = readme_documented_counters(ctx)
    assert errs == []
    assert {"decode", "prefetch", "resilience", "checkpoint", "fault",
            "exporter", "telemetry"} <= namespaces
    runtime = set(package_counter_literals(ctx))

    # (b) the native decode poller's real keys, when the decoder exists on
    # this host (it does in CI; the literal half still guards without it)
    from distributed_vgg_f_tpu.data.native_jpeg import (
        load_native_jpeg,
        register_decode_poller,
    )
    native = load_native_jpeg() is not None
    if native:
        # decode ONE image first so the scale histogram carries a bucket —
        # a fresh process's empty histogram would make the documented
        # `scale_histogram/<m>` row read as stale
        from distributed_vgg_f_tpu.data.native_jpeg import (
            decode_single_image)
        from PIL import Image
        import io as _io
        buf = _io.BytesIO()
        Image.fromarray(np.zeros((48, 48, 3), np.uint8)).save(
            buf, "JPEG", quality=90)
        decode_single_image(buf.getvalue(), 32,
                            np.zeros(3, np.float32),
                            np.ones(3, np.float32))
        register_decode_poller()
        snap = telemetry.get_registry().snapshot()
        runtime |= {k for k in snap if k.startswith("decode/")}

    runtime = {_normalize_buckets(n) for n in runtime
               if n.split("/", 1)[0] in namespaces}
    if not native:
        keep = {"decode/errors_total"}  # the trainer-side literal
        documented = {n for n in documented
                      if not n.startswith("decode/") or n in keep}
    undocumented = sorted(runtime - documented)
    stale = sorted(documented - runtime)
    assert not undocumented, (
        f"counters registered at runtime but missing from the README "
        f"table: {undocumented}")
    assert not stale, (
        f"README table documents counters nothing registers (stale "
        f"entries): {stale}")


# ------------------------------------------------- JAX's compile events
# (telemetry/compile_events.py; conftest's `enable_compile_cache` installed
# the listeners, which are global to the process and outlive every test)
TRACE = "/jax/core/compile/jaxpr_trace_duration"
LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
BACKEND = "/jax/core/compile/backend_compile_duration"
CACHE_REQUEST = "/jax/compilation_cache/compile_requests_use_cache"
CACHE_HIT = "/jax/compilation_cache/cache_hits"
CACHE_MISS = "/jax/compilation_cache/cache_misses"


def _compile_spans(name=None):
    return [s for s in telemetry.get_recorder().snapshot()
            if s[1] == "compile" and (name is None or s[0] == name)]


def _compile_counters():
    return {k: v for k, v in telemetry.get_registry().snapshot().items()
            if k.startswith("compile/")}


def _raise_stage(event, seconds, fun_name):
    """One of JAX's stage events, through JAX's own dispatcher, ending
    now. Returns the ring-clock interval it has to land in."""
    t0 = time.monotonic_ns()
    end = time.time()
    jax.monitoring.record_event_time_span(event, end - seconds, end,
                                          fun_name=fun_name)
    return t0 - int(seconds * 1e9), time.monotonic_ns()


@pytest.mark.parametrize("event,fun_name,span,counters", [
    (TRACE, "probe", "trace:probe", {"compile/trace_events": 1}),
    (LOWER, "jit(probe)", "lower:jit(probe)",
     {"compile/programs": 1, "compile/lower_ns": 5_000_000}),
    (BACKEND, "jit(probe)", "backend:jit(probe)",
     {"compile/backend_ns": 5_000_000}),
])
def test_compile_stage_event_becomes_a_span_and_counters(event, fun_name,
                                                         span, counters):
    lo, hi = _raise_stage(event, 0.005, fun_name)
    (got,) = _compile_spans()
    assert got[0] == span and got[4] == threading.get_ident()
    # on the ring's clock, to the float's rounding of `time.time()`
    assert abs(got[3] - 5_000_000) < 10_000
    assert lo - 1_000_000 <= got[2] and got[2] + got[3] <= hi + 1_000_000
    have = _compile_counters()
    assert set(have) == set(counters)
    for name, want in counters.items():
        assert abs(have[name] - want) < 10_000, (name, have)


@pytest.mark.parametrize("event,counter,span", [
    (CACHE_HIT, "compile/cache_hits", "cache_read:jit(probe)"),
    (CACHE_MISS, "compile/cache_misses", "backend:jit(probe)"),
])
def test_cache_event_is_counted_and_names_the_stage_s_span(event, counter,
                                                           span):
    """The hit fires inside the backend stage, before the stage's span is
    reported: that span is the cache's read, and the next program's (the
    cache is asked again first) is a compile again."""
    jax.monitoring.record_event(CACHE_REQUEST)
    jax.monitoring.record_event(event)
    _raise_stage(BACKEND, 0.002, "jit(probe)")
    jax.monitoring.record_event(CACHE_REQUEST)
    _raise_stage(BACKEND, 0.002, "jit(next)")
    assert [s[0] for s in _compile_spans()] == [span, "backend:jit(next)"]
    have = _compile_counters()
    assert have[counter] == 1
    read = event == CACHE_HIT
    assert ("compile/cache_read_ns" in have) == read
    assert abs(have["compile/backend_ns"]
               - (2_000_000 if read else 4_000_000)) < 10_000


def test_a_short_trace_event_is_counted_and_leaves_no_span():
    _raise_stage(TRACE, 0.0005, "sin")
    _raise_stage(TRACE, 0.0015, "outer")
    assert [s[0] for s in _compile_spans()] == ["trace:outer"]
    assert _compile_counters() == {"compile/trace_events": 2}


def test_compile_listeners_install_once_and_outlive_a_reset():
    from distributed_vgg_f_tpu.telemetry import compile_events
    from distributed_vgg_f_tpu.utils.compile_cache import (
        enable_compile_cache)
    assert compile_events.install(jax.monitoring) is False
    enable_compile_cache()          # every entry point calls it: still once
    telemetry.reset()
    _raise_stage(LOWER, 0.002, "jit(probe)")
    assert len(_compile_spans()) == 1
    assert _compile_counters()["compile/programs"] == 1


def test_compile_listeners_are_silent_when_telemetry_is_off():
    telemetry.configure(enabled=False)
    jax.monitoring.record_event(CACHE_HIT)
    for event in (TRACE, LOWER, BACKEND):
        _raise_stage(event, 0.002, "probe")
    jax.jit(lambda x: x * 3 + 1)(np.float32(2)).block_until_ready()
    telemetry.configure(enabled=True)
    assert _compile_spans() == [] and _compile_counters() == {}


@pytest.fixture
def own_compile_cache(tmp_path):
    """JAX's persistent cache in a directory of this test's own, holding
    whatever compiles, and the suite's cache back afterwards."""
    from jax.experimental.compilation_cache import compilation_cache
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    before = {k: getattr(jax.config, k) for k in keys}
    for k, v in zip(keys, (str(tmp_path / "cache"), 0.0, -1)):
        jax.config.update(k, v)
    compilation_cache.reset_cache()
    yield
    for k, v in before.items():
        jax.config.update(k, v)
    compilation_cache.reset_cache()


def test_a_real_compile_then_the_same_program_from_the_cache(
        own_compile_cache):
    """What JAX itself raises: a jitted function compiled here leaves one
    lowering and one backend span under its name; after
    `jax.clear_caches()` the same call lowers again and reads the
    executable from the persistent cache."""
    def startup_probe(x):
        return jax.numpy.tanh(x) * 3 + 1

    x = np.arange(4, dtype=np.float32)
    jax.jit(startup_probe)(x).block_until_ready()
    lower, backend, read = ("lower:jit(startup_probe)",
                            "backend:jit(startup_probe)",
                            "cache_read:jit(startup_probe)")
    assert len(_compile_spans(lower)) == 1
    assert len(_compile_spans(backend)) == 1 and not _compile_spans(read)
    first = _compile_counters()
    assert first["compile/programs"] >= 1
    assert first["compile/cache_misses"] >= 1
    assert first.get("compile/cache_hits", 0) == 0
    spans = {s[0]: s for s in _compile_spans()}
    assert spans[lower][2] + spans[lower][3] <= spans[backend][2] + 1_000

    jax.clear_caches()
    telemetry.reset()
    jax.jit(startup_probe)(x).block_until_ready()
    assert len(_compile_spans(lower)) == 1
    assert len(_compile_spans(read)) == 1 and not _compile_spans(backend)
    second = _compile_counters()
    assert second["compile/cache_hits"] >= 1
    assert second.get("compile/cache_misses", 0) == 0
    assert second["compile/cache_read_ns"] > 0
    assert second.get("compile/backend_ns", 0) == 0


# --------------------------------------- set-up on the ring: `startup` spans
_STARTUP_CHILD = """
import io, json, sys
import jax
from distributed_vgg_f_tpu import telemetry
from distributed_vgg_f_tpu.config import get_config, apply_overrides
from distributed_vgg_f_tpu.train.trainer import Trainer
from distributed_vgg_f_tpu.utils.compile_cache import enable_compile_cache
from distributed_vgg_f_tpu.utils.logging import MetricLogger
enable_compile_cache(sys.argv[1])
cfg = apply_overrides(get_config("vggf_synthetic"), {
    "data.image_size": 32, "model.num_classes": 10,
    "data.global_batch_size": 8, "mesh.num_data": 1})
trainer = Trainer(cfg, logger=MetricLogger(stream=io.StringIO()))
jax.block_until_ready(trainer.init_state())
print(json.dumps({
    "spans": telemetry.get_recorder().snapshot(),
    "start_ns": telemetry.get_registry().gauge("startup/process_start_ns"),
    "counters": telemetry.get_registry().snapshot()}))
"""


@pytest.fixture(scope="module")
def startup_ring():
    """A process of its own builds a `Trainer` at a tiny size and its
    state: the ring it leaves, from the module's import on (in this
    process the module was imported long ago and the ring reset since)."""
    from _child_bootstrap import cpu_cache_subdir
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "XLA_FLAGS": ""}
    out = subprocess.run(
        [sys.executable, "-c", _STARTUP_CHILD, cpu_cache_subdir()],
        cwd=repo, env=env, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    return json.loads(out.stdout.splitlines()[-1])


def _startup(ring, name):
    return [s for s in ring["spans"] if s[1] == "startup" and s[0] == name]


@pytest.mark.parametrize("name", scopes.STARTUP_SPANS)
def test_trainer_leaves_every_startup_span_once(startup_ring, name):
    (span,) = _startup(startup_ring, name)
    assert span[3] >= 0


def test_startup_spans_nest_as_declared(startup_ring):
    assert {s[0] for s in startup_ring["spans"] if s[1] == "startup"} \
        == set(scopes.STARTUP_SPANS)
    (imports,) = _startup(startup_ring, "import_trainer")
    (init,) = _startup(startup_ring, "trainer_init")
    (state,) = _startup(startup_ring, "init_state")
    # the process started before the import, the import ended before the
    # trainer was built, the state came after it
    assert startup_ring["start_ns"] <= imports[2]
    assert imports[2] + imports[3] <= init[2]
    assert init[2] + init[3] <= state[2]
    ends = []
    for name in scopes.TRAINER_INIT_CHILDREN:
        (child,) = _startup(startup_ring, name)
        assert child[4] == init[4]                       # its thread
        assert init[2] <= child[2]
        assert child[2] + child[3] <= init[2] + init[3]
        ends.append((child[2], child[2] + child[3]))
    # the children follow each other in the declared order
    assert ends == sorted(ends)
    assert all(a[1] <= b[0] for a, b in zip(ends, ends[1:]))
    # the state's init is a program: lowered inside `init_state`
    lowered = [s for s in startup_ring["spans"]
               if s[0] == "lower:jit(init_fn)"]
    assert len(lowered) == 1 and state[2] <= lowered[0][2] \
        and lowered[0][2] + lowered[0][3] <= state[2] + state[3]
    assert startup_ring["counters"]["compile/programs"] >= 1


# -------------------- the feed path's spans on a profiler's clock (PR 36)
class _Annotations:
    """Stand-in for `jax.profiler.TraceAnnotation`: what was opened and
    closed, in order."""

    def __init__(self):
        self.opened, self.closed = [], []
        self._lock = threading.Lock()

    def __call__(self, name):
        hook = self

        class _One:
            def __enter__(self):
                with hook._lock:
                    hook.opened.append(name)

            def __exit__(self, *exc):
                with hook._lock:
                    hook.closed.append(name)
        return _One()


def _host_batches(n, rows=8):
    return [{"image": np.full((rows, 4, 4, 3), i, np.float32),
             "label": np.full((rows,), i, np.int32)} for i in range(n)]


def _drain_device_prefetch(devices8, install):
    from distributed_vgg_f_tpu.data.prefetch import DevicePrefetchIterator
    from distributed_vgg_f_tpu.parallel.mesh import MeshSpec, build_mesh
    mesh = build_mesh(MeshSpec(("data",), (8,)), devices8)
    install()
    it = DevicePrefetchIterator(iter(_host_batches(3)), mesh)
    assert len(list(it)) == 3
    it.close()


def _drain_host_prefetch(devices8, install):
    from distributed_vgg_f_tpu.data.prefetch import HostPrefetchIterator
    install()
    it = HostPrefetchIterator(iter(_host_batches(3)))
    assert len(list(it)) == 3
    it.close()


def _one_eval_pass(devices8, install):
    from distributed_vgg_f_tpu.train.trainer import Trainer
    tr = Trainer(_cfg(), logger=MetricLogger(stream=io.StringIO()))
    batches = _host_batches(2, rows=16)
    for b in batches:
        b["image"] = np.zeros((16, 32, 32, 3), np.float32)
        b["label"] = b["label"] % 10
    state = tr.init_state()
    install()                      # the trainer had installed the profiler's
    tr.evaluate(state, iter(batches), num_batches=2, step=0)


def _native_loader_batches(devices8, install):
    from distributed_vgg_f_tpu.data.native_loader import (
        NativeBatchIterator, load_native)
    if load_native() is None:
        pytest.skip("native toolchain unavailable")
    rng = np.random.default_rng(0)
    it = NativeBatchIterator(
        rng.integers(0, 256, (64, 8, 8, 3)).astype(np.uint8),
        rng.integers(0, 10, (64,)).astype(np.int32), 16, train=False,
        seed=0, mean=(0.0, 0.0, 0.0), std=(1.0, 1.0, 1.0))
    install()
    for _ in range(3):
        next(it)
    it.close()


@pytest.mark.parametrize("drive,want", [
    # three batches; the draw and the wait that find the source at its end
    # are spans too (the worker, and the consumer, waited for them)
    (_drain_device_prefetch, {"dvggf:infeed_source:source_next": 4,
                              "dvggf:infeed_source:device_put": 3,
                              "dvggf:infeed:prefetch_wait": 4}),
    (_drain_host_prefetch, {"dvggf:infeed_source:host_prefetch_next": 4}),
    (_one_eval_pass, {"dvggf:eval:eval_pass": 1}),
    (_native_loader_batches, {"dvggf:infeed_source:native_loader_next": 3}),
], ids=["device_prefetch", "host_prefetch", "eval_pass", "native_loader"])
def test_feed_path_spans_open_on_the_profilers_clock(devices8, drive, want):
    """Each is a `with span(...)` around the work, so a `jax.profiler`
    capture shows it as `dvggf:<category>:<name>` (a span recorded after
    the fact would reach the ring only): opened and closed once a batch,
    and the ring holds the same spans."""
    hook = _Annotations()
    try:
        drive(devices8, lambda: telemetry.configure(annotate=hook))
    finally:
        telemetry.get_recorder().annotate = None
    for name, count in want.items():
        assert hook.opened.count(name) == count, (name, hook.opened)
        assert hook.closed.count(name) == count, (name, hook.closed)
        category, span = name.split(":")[1:]
        assert sum(1 for s in telemetry.get_recorder().snapshot()
                   if (s[1], s[0]) == (category, span)) == count


def test_instrument_iterator_pays_what_the_feed_path_pays():
    """The host bench's overhead receipt charges `instrument_iterator`:
    the same five `span(...)` calls a batch as prefetch worker, consumer,
    trainer loop and step wrapper make, annotations included."""
    hook = _Annotations()
    telemetry.configure(annotate=hook)
    try:
        assert list(telemetry.instrument_iterator(iter(range(3)))) \
            == [0, 1, 2]
    finally:
        telemetry.get_recorder().annotate = None
    for name in ("dvggf:infeed_source:source_next",
                 "dvggf:infeed_source:device_put",
                 "dvggf:infeed:prefetch_wait", "dvggf:infeed:next_batch",
                 "dvggf:dispatch:train_step_dispatch"):
        assert hook.opened.count(name) >= 3 and \
            hook.opened.count(name) == hook.closed.count(name), name
    counters = telemetry.get_registry().snapshot()
    assert counters["prefetch/batches"] == 3
    assert counters["prefetch/source_batches"] == 3
    assert counters["step/dispatched"] == 3 and counters["prefetch/wait_ns"] > 0


# --------------------------------------------------------- import isolation
def test_import_pulls_no_heavy_deps():
    """ISSUE 4 satellite (extended in ISSUE 8 to the live-observability
    modules): importing telemetry — including the exporter, flight
    recorder, and regression engine — must pull in neither TensorFlow, nor
    jax/numpy, nor the native .so (an import that triggers a g++ build of
    the decoder would make telemetry a correctness dependency of the thing
    it observes)."""
    code = (
        "import sys, distributed_vgg_f_tpu.telemetry\n"
        "import distributed_vgg_f_tpu.telemetry.exporter\n"
        "import distributed_vgg_f_tpu.telemetry.flight\n"
        "import distributed_vgg_f_tpu.telemetry.regress\n"
        "import distributed_vgg_f_tpu.telemetry.compile_events\n"
        "heavy = [m for m in ('tensorflow', 'jax', 'numpy')\n"
        "         if m in sys.modules]\n"
        "assert not heavy, f'telemetry imported {heavy}'\n"
        "import os\n"
        "if os.path.exists('/proc/self/maps'):\n"
        "    maps = open('/proc/self/maps').read()\n"
        "    assert 'libdvgg' not in maps, 'native .so loaded'\n"
        "print('ISOLATED')\n"
    )
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    out = subprocess.run([sys.executable, "-c", code], cwd=repo,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    assert "ISOLATED" in out.stdout
