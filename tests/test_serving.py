"""Production inference service (serving/, r17): bucketed AOT engine,
dynamic-batcher admission (max-latency partial flush, max-batch burst
flush), overload shed (typed 503, bounded queue, no collapse), clean drain,
the admission controller, /servingz, the serving sentinel basis — and the
acceptance gates: batched-server predictions bitwise-equal to offline
run_predict on the same inputs, and the kill-switch (serving off leaves
offline predict untouched, structurally)."""

import io
import json
import sys
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from distributed_vgg_f_tpu import telemetry
from distributed_vgg_f_tpu.config import (
    DataConfig,
    ExperimentConfig,
    MeshConfig,
    ModelConfig,
    OptimConfig,
    ServingConfig,
    TrainConfig,
)
from distributed_vgg_f_tpu.telemetry import exporter as exporter_mod
from distributed_vgg_f_tpu.telemetry import flight as flight_mod


@pytest.fixture(autouse=True)
def _fresh_telemetry():
    telemetry.reset()
    flight_mod.get_flight().clear()
    yield
    exporter_mod.stop_exporter()
    telemetry.reset()
    flight_mod.get_flight().clear()
    telemetry.configure(enabled=True)


# ------------------------------------------------------------------ helpers

def _tiny_engine(model_name="vggf", num_classes=5, size=32, buckets=(),
                 max_batch=4):
    import jax

    from distributed_vgg_f_tpu.data.device_ingest import make_device_finish
    from distributed_vgg_f_tpu.models.ingest import ingest_descriptor
    from distributed_vgg_f_tpu.models.registry import build_model
    from distributed_vgg_f_tpu.serving.engine import PredictEngine
    model = build_model(ModelConfig(name=model_name,
                                    num_classes=num_classes,
                                    compute_dtype="float32"))
    desc = ingest_descriptor(model_name)
    finish = make_device_finish(desc.mean_rgb, desc.stddev_rgb)
    x0 = jax.numpy.zeros((1, size, size, 3), jax.numpy.uint8)
    variables = model.init(jax.random.PRNGKey(0), finish(x0), train=False)
    return PredictEngine(
        model_name=model_name, model=model, params=variables["params"],
        batch_stats=variables.get("batch_stats", {}),
        image_size=size, num_classes=num_classes, buckets=buckets,
        max_batch=max_batch)


def _images(n, size=32, seed=0):
    return np.random.default_rng(seed).integers(
        0, 256, (n, size, size, 3)).astype(np.uint8)


def _serving_cfg(**kw):
    kw.setdefault("enabled", True)
    return ServingConfig(**kw)


def _post(port, model, image, timeout=30, k=None):
    url = f"http://127.0.0.1:{port}/v1/predict/{model}"
    if k is not None:
        url += f"?k={k}"
    req = urllib.request.Request(url, data=image.tobytes(), method="POST")
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return r.status, json.loads(r.read())


class _SlowEngine:
    """Delegating wrapper that makes every flush take `delay_s` — the
    overload/drain tests need a server that is slower than its arrivals
    without depending on box speed."""

    def __init__(self, engine, delay_s):
        self._engine = engine
        self.delay_s = delay_s

    def __getattr__(self, name):
        return getattr(self._engine, name)

    def run(self, images):
        time.sleep(self.delay_s)
        return self._engine.run(images)


class _GatedEngine:
    """Delegating wrapper whose flushes wait for `gate`: the overload test
    holds the engine until the arrivals it wants to see have arrived,
    whatever the box's speed or load."""

    def __init__(self, engine):
        self._engine = engine
        self.gate = threading.Event()

    def __getattr__(self, name):
        return getattr(self._engine, name)

    def run(self, images):
        assert self.gate.wait(60), "the test never opened the gate"
        return self._engine.run(images)


# ----------------------------------------------------------- engine/buckets

def test_resolve_buckets_ladder_and_validation():
    from distributed_vgg_f_tpu.serving.engine import resolve_buckets
    assert resolve_buckets((), 8) == (1, 2, 4, 8)
    assert resolve_buckets((), 6) == (1, 2, 4, 6)
    assert resolve_buckets((2, 4), 4) == (2, 4)
    with pytest.raises(ValueError, match="cover max_batch"):
        resolve_buckets((1, 2), 4)
    with pytest.raises(ValueError, match="ascending"):
        resolve_buckets((4, 2), 4)


def test_serving_config_validation():
    with pytest.raises(ValueError, match="cover max_batch"):
        ServingConfig(buckets=(1, 2), max_batch=8)
    with pytest.raises(ValueError, match="queue_limit"):
        ServingConfig(queue_limit=0)
    with pytest.raises(ValueError, match="rails"):
        ServingConfig(window_min_ms=50.0, window_max_ms=10.0,
                      max_latency_ms=50.0)
    with pytest.raises(ValueError, match="outside the controller rails"):
        ServingConfig(max_latency_ms=500.0)
    # the kill-switch default: serving exists on every config, OFF
    assert ExperimentConfig().serving.enabled is False


def test_engine_pad_slice_and_buckets():
    import jax
    engine = _tiny_engine(max_batch=4)
    assert engine.buckets == (1, 2, 4)
    imgs = _images(3)
    probs, bucket = engine.run(imgs)
    assert bucket == 4 and probs.shape == (3, 5)
    assert np.allclose(probs.sum(axis=1), 1.0, atol=1e-5)
    # pad rows sliced away; tolerance vs the exact-geometry jit (bitwise
    # is NOT promised across geometries — that is the whole reason the
    # offline array path shares the engine)
    exact = np.asarray(jax.jit(engine._forward)(imgs))
    assert np.allclose(probs, exact, atol=1e-5)
    # exact-size group runs its own bucket
    probs2, bucket2 = engine.run(_images(2))
    assert bucket2 == 2 and probs2.shape == (2, 5)
    with pytest.raises(ValueError, match="exceeds the top bucket"):
        engine.run(_images(5))
    with pytest.raises(ValueError, match="uint8"):
        engine.validate_payload(np.zeros((32, 32, 3), np.float32))


# ---------------------------------------------------------------- admission

def test_max_latency_flush_fires_with_partial_batch():
    from distributed_vgg_f_tpu.serving.batcher import DynamicBatcher
    engine = _tiny_engine(max_batch=8)
    batcher = DynamicBatcher(engine, max_batch=8, window_ms=120,
                             queue_limit=16)
    try:
        t0 = time.monotonic()
        pendings = [batcher.submit(img) for img in _images(3)]
        for p in pendings:
            assert p.event.wait(30)
            assert p.probs is not None and p.error is None
        elapsed = time.monotonic() - t0
        # the flush waited for the window (nobody else arrived), then ran
        # a PARTIAL batch — 3 requests, one flush, bucket 4
        assert elapsed >= 0.1
        assert {p.bucket for p in pendings} == {4}
        assert telemetry.get_registry().counter_value("serving/batches") == 1
        assert telemetry.get_registry().counter_value(
            "serving/batch_images") == 3
        assert telemetry.get_registry().counter_value(
            "serving/padded_images") == 1
    finally:
        batcher.close()


def test_max_batch_flush_fires_under_burst_before_window():
    from distributed_vgg_f_tpu.serving.batcher import DynamicBatcher
    engine = _tiny_engine(max_batch=4)
    engine.warmup()
    # window far larger than the assertion budget: only the full-batch
    # trigger can flush this fast
    batcher = DynamicBatcher(engine, max_batch=4, window_ms=10_000,
                             queue_limit=16)
    try:
        t0 = time.monotonic()
        pendings = [batcher.submit(img) for img in _images(4)]
        for p in pendings:
            assert p.event.wait(30) and p.error is None
        assert time.monotonic() - t0 < 5.0
        assert {p.bucket for p in pendings} == {4}
    finally:
        batcher.close()


def test_overload_sheds_typed_503_bounded_queue_no_collapse():
    from distributed_vgg_f_tpu.serving.server import PredictServer
    engine = _GatedEngine(_tiny_engine(max_batch=2))
    cfg = _serving_cfg(max_batch=2, buckets=(1, 2), max_latency_ms=5.0,
                       queue_limit=3, controller=False, warmup=False,
                       shed_retry_after_ms=25)
    server = PredictServer(cfg)
    server.add_engine(engine)
    port = server.start()
    try:
        statuses, sheds = [], []
        lock = threading.Lock()

        def post(i):
            try:
                status, payload = _post(port, "vggf", _images(1)[0])
            except urllib.error.HTTPError as e:
                status, payload = e.code, json.loads(e.read())
                if status == 503:
                    assert e.headers.get("Retry-After") is not None
            with lock:
                statuses.append(status)
                if status == 503:
                    sheds.append(payload)

        threads = [threading.Thread(target=post, args=(i,))
                   for i in range(14)]
        for t in threads:
            t.start()
        # The engine holds its first flush (at most max_batch=2 requests)
        # and the queue holds 3 more: of 14 arrivals at least 9 can only
        # be shed. Wait for those sheds, not for a delay that a loaded
        # host stretches until the queue drains between arrivals.
        shed_total = lambda: telemetry.get_registry().counter_value(
            "serving/shed", 0)
        deadline = time.monotonic() + 60.0
        while shed_total() < 14 - 2 - 3:
            assert time.monotonic() < deadline, \
                f"only {shed_total()} of 14 arrivals shed against a held " \
                f"engine"
            time.sleep(0.01)
        engine.gate.set()
        for t in threads:
            t.join(timeout=60)
        # overload split both ways: some admitted AND some shed
        assert statuses.count(200) >= 3
        assert len(sheds) >= 3
        for payload in sheds:
            # the TYPED shed payload — machine-actionable, not a timeout
            assert payload["error"] == "overloaded"
            assert payload["kind"] == "shed"
            assert payload["queue_limit"] == 3
            assert payload["queue_depth"] <= payload["queue_limit"]
            assert payload["retry_after_ms"] == 25
        assert telemetry.get_registry().counter_value("serving/shed") \
            == len(sheds)
        # the queue never exceeded its bound — shed, not stretched
        payload = server.servingz_payload()
        assert payload["models"]["vggf"]["admission"]["queue_peak"] <= 3
        # NO COLLAPSE: after the burst the server still answers promptly
        status, body = _post(port, "vggf", _images(1)[0], timeout=30)
        assert status == 200 and len(body["top_k"]) == 5
    finally:
        server.close()


def test_drain_answers_inflight_then_refuses():
    from distributed_vgg_f_tpu.serving.batcher import (DynamicBatcher,
                                                       OverloadShed)
    engine = _SlowEngine(_tiny_engine(max_batch=2), delay_s=0.1)
    batcher = DynamicBatcher(engine, max_batch=2, window_ms=30,
                             queue_limit=16)
    pendings = [batcher.submit(img) for img in _images(5)]
    batcher.close()  # blocks until drained
    for p in pendings:
        # every in-flight request was ANSWERED, not dropped
        assert p.event.is_set() and p.probs is not None and p.error is None
    with pytest.raises(OverloadShed) as err:
        batcher.submit(_images(1)[0])
    assert err.value.kind == "draining"


def test_expired_queue_entries_reaped_not_run():
    """Requests older than the reap horizon are answered with
    TimeoutError and NEVER run — under sustained overload the engine must
    not burn compute on requests whose clients already got 504."""
    from distributed_vgg_f_tpu.serving.batcher import DynamicBatcher
    engine = _SlowEngine(_tiny_engine(max_batch=1, buckets=(1,)),
                         delay_s=0.4)
    batcher = DynamicBatcher(engine, max_batch=1, window_ms=1,
                             queue_limit=16, reap_after_s=0.2)
    try:
        pendings = [batcher.submit(img) for img in _images(4)]
        for p in pendings:
            assert p.event.wait(30)
        # the head request ran; the ones stuck behind the slow flush
        # crossed the horizon and were expired, not executed
        assert pendings[0].error is None and pendings[0].probs is not None
        reaped = [p for p in pendings if isinstance(p.error, TimeoutError)]
        assert reaped, "no queue entry was reaped past the horizon"
        assert all(p.probs is None for p in reaped)
        assert batcher.describe()["reaped_total"] == len(reaped)
    finally:
        batcher.close()


# --------------------------------------------------------------- controller

def test_controller_widens_under_pressure_and_relaxes():
    from distributed_vgg_f_tpu.serving.batcher import DynamicBatcher
    from distributed_vgg_f_tpu.serving.controller import AdmissionController
    engine = _tiny_engine(max_batch=2)
    cfg = _serving_cfg(max_batch=2, buckets=(1, 2), max_latency_ms=10.0,
                       queue_limit=8, window_min_ms=2.0, window_max_ms=40.0,
                       controller_k_windows=2,
                       controller_cooldown_windows=0,
                       controller_relax_after_windows=2)
    batcher = DynamicBatcher(engine, max_batch=2, window_ms=10,
                             queue_limit=8)
    try:
        ctrl = AdmissionController(cfg, batcher)
        pressure = {"shed": 2, "queue_peak": 8, "latencies_ms": []}
        steady = {"shed": 0, "queue_peak": 0, "latencies_ms": []}
        assert ctrl.classify(pressure) == "queue_pressure"
        assert ctrl.classify(steady) == "steady"
        # hysteresis: one pressure window does not actuate
        rec = ctrl.observe_window(pressure)
        assert batcher.window_ms == 10 and rec["blocked"] == "hysteresis"
        # second consecutive pressure window: widen (geometric step)
        ctrl.observe_window(pressure)
        assert batcher.window_ms == 20
        # keep pressing to the rail
        for _ in range(6):
            ctrl.observe_window(pressure)
        assert batcher.window_ms == 40  # clamped at window_max_ms
        # sustained steady: relax back toward the 10ms baseline, never past
        for _ in range(12):
            ctrl.observe_window(steady)
        assert batcher.window_ms == 10
        assert telemetry.get_registry().counter_value(
            "serving/controller_actuations") >= 3
        receipt = ctrl.describe()
        assert receipt["knobs"][0]["name"] == "batch_window_ms"
        assert receipt["history"]
        # a serving crash must dump a VALID black box — the controller's
        # actuations ride the flight ring and must pass its schema
        from distributed_vgg_f_tpu.telemetry import schema
        box = flight_mod.get_flight().build_black_box(
            reason="unhandled_exception")
        assert schema.validate_flight_record(box) == []
        assert any(a["knob"] == "batch_window_ms"
                   for a in box["autotune_actuations"])
    finally:
        batcher.close()


# ------------------------------------------------- observability plane

def test_servingz_healthz_flight_and_metrics():
    from distributed_vgg_f_tpu.serving.server import PredictServer
    from distributed_vgg_f_tpu.telemetry.exporter import TelemetryExporter
    engine = _tiny_engine(max_batch=2)
    cfg = _serving_cfg(max_batch=2, buckets=(1, 2), max_latency_ms=5.0,
                       queue_limit=8, controller_interval_s=0.05,
                       warmup=False)
    exp = TelemetryExporter()
    eport = exp.start()
    # make it the process exporter so the serving heartbeat reaches it
    exporter_mod._default = exp
    server = PredictServer(cfg)
    server.add_engine(engine)
    port = server.start()
    try:
        status, _ = _post(port, "vggf", _images(1)[0])
        assert status == 200
        # two housekeeping ticks AFTER the completion: the first drains
        # the latency ring into the quantile gauges
        w0 = server._windows
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline and server._windows < w0 + 2:
            time.sleep(0.02)
        # /servingz through the exporter (provider registration)
        with urllib.request.urlopen(
                f"http://127.0.0.1:{eport}/servingz", timeout=10) as r:
            payload = json.loads(r.read())
        assert payload["enabled"] is True
        admission = payload["models"]["vggf"]["admission"]
        assert admission["queue_limit"] == 8
        assert admission["bucket_occupancy"].get("1") == 1
        assert "controller" in payload["models"]["vggf"]
        # serving heartbeat keeps /healthz a real LB health check
        with urllib.request.urlopen(
                f"http://127.0.0.1:{eport}/healthz", timeout=10) as r:
            health = json.loads(r.read())
        assert health["status"] == "ok" and health["last_step"] >= 1
        # per-window summaries ride the flight recorder's ring
        windows = flight_mod.get_flight().windows()
        assert windows and windows[-1]["stall"]["verdict"] in (
            "steady", "queue_pressure")
        # serving counters + latency gauges land on /metrics
        with urllib.request.urlopen(
                f"http://127.0.0.1:{eport}/metrics", timeout=10) as r:
            metrics = r.read().decode()
        assert "dvggf_serving_admitted 1" in metrics
        assert "dvggf_serving_latency_p99_ms" in metrics
        # GET /v1/models: the routing table over the descriptor receipt
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/v1/models", timeout=10) as r:
            table = json.loads(r.read())
        assert table["models"]["vggf"]["ingest"]["wire"] == "u8"
    finally:
        server.close()
        exp.stop()
    # close() unregisters the provider (compare-and-clear)
    assert exporter_mod.serving_payload()["enabled"] is False


def test_bad_payload_and_unknown_model_are_400():
    from distributed_vgg_f_tpu.serving.server import PredictServer
    engine = _tiny_engine(max_batch=2)
    server = PredictServer(_serving_cfg(max_batch=2, buckets=(1, 2),
                                        warmup=False))
    server.add_engine(engine)
    port = server.start()
    try:
        with pytest.raises(urllib.error.HTTPError) as err:
            _post(port, "vggf", np.zeros((8, 8, 3), np.uint8))
        assert err.value.code == 400
        assert json.loads(err.value.read())["error"] == "bad_request"
        with pytest.raises(urllib.error.HTTPError) as err:
            _post(port, "nope", _images(1)[0])
        assert err.value.code == 400
        assert "vggf" in json.loads(err.value.read())["models"]
    finally:
        server.close()


# ------------------------------------------------------ parity + kill-switch

def _trainer(tmp_path, model_name="vggf", num_classes=5, size=32):
    import distributed_vgg_f_tpu.train.trainer as trainer_mod

    from distributed_vgg_f_tpu.utils.logging import MetricLogger
    cfg = ExperimentConfig(
        name="serving_parity",
        model=ModelConfig(name=model_name, num_classes=num_classes,
                          compute_dtype="float32"),
        optim=OptimConfig(base_lr=0.01, reference_batch_size=8),
        data=DataConfig(name="synthetic", image_size=size,
                        global_batch_size=8, num_train_examples=8),
        mesh=MeshConfig(num_data=0),
        train=TrainConfig(steps=1, seed=0,
                          checkpoint_dir=str(tmp_path / "ckpt")),
    )
    tr = trainer_mod.Trainer(cfg, logger=MetricLogger(stream=io.StringIO()))
    tr.checkpoints.save(tr.init_state(), force=True)
    tr.checkpoints.wait()
    return tr


def _npy_files(tmp_path, n, size, seed=7):
    files = []
    imgs = _images(n, size=size, seed=seed)
    for i, img in enumerate(imgs):
        p = tmp_path / f"img_{i}.npy"
        np.save(p, img)
        files.append(str(p))
    return files, imgs


def _serve_parity(tr, buckets, max_batch):
    from distributed_vgg_f_tpu.serving.engine import PredictEngine
    from distributed_vgg_f_tpu.serving.server import PredictServer
    server = PredictServer(_serving_cfg(max_batch=max_batch,
                                        buckets=buckets,
                                        max_latency_ms=50.0,
                                        queue_limit=16))
    server.add_engine(PredictEngine.from_trainer(tr, buckets=buckets,
                                                 max_batch=max_batch))
    server.start()
    return server


def _assert_same_records(offline, served):
    """Bitwise: class indices identical, probabilities EXACTLY equal (both
    sides emit full precision; JSON floats round-trip exactly)."""
    assert [r["class"] for r in offline] == [r["class"] for r in served]
    assert [r["prob"] for r in offline] == [r["prob"] for r in served]


def test_server_bitwise_equals_offline_predict_vggf(tmp_path):
    from distributed_vgg_f_tpu.train.predict import run_predict
    tr = _trainer(tmp_path)
    files, imgs = _npy_files(tmp_path, 3, 32)
    # offline: the array path routes through the SAME engine machinery at
    # bucket 1 (batch=1); the server flushes sequential requests at
    # bucket 1 too — equal inputs through equal geometry
    offline = run_predict(tr, files, top_k=3, batch=1,
                          stream=io.StringIO())
    server = _serve_parity(tr, buckets=(1,), max_batch=1)
    try:
        for rec, img in zip(offline, imgs):
            status, body = _post(server.port, "vggf", img, k=3)
            assert status == 200 and body["bucket"] == 1
            _assert_same_records(rec["top_k"], body["top_k"])
    finally:
        server.close()


def test_batched_flush_bitwise_equals_offline_batch(tmp_path):
    """The grouped path: a 4-deep burst flushes as ONE bucket-4 batch and
    must equal the offline array path's bucket-4 chunk bit-for-bit.
    Submission rides the batcher directly so FIFO order is deterministic
    (HTTP thread scheduling would permute rows; cross-position equality is
    not a promise the engine makes)."""
    from distributed_vgg_f_tpu.serving.batcher import DynamicBatcher
    from distributed_vgg_f_tpu.serving.engine import PredictEngine
    from distributed_vgg_f_tpu.train.predict import run_predict
    tr = _trainer(tmp_path)
    files, imgs = _npy_files(tmp_path, 4, 32)
    offline = run_predict(tr, files, top_k=5, batch=4,
                          stream=io.StringIO())
    engine = PredictEngine.from_trainer(tr, buckets=(4,), max_batch=4)
    batcher = DynamicBatcher(engine, max_batch=4, window_ms=10_000,
                             queue_limit=8)
    try:
        pendings = [batcher.submit(img) for img in imgs]
        for p in pendings:
            assert p.event.wait(60) and p.error is None
        assert {p.bucket for p in pendings} == {4}
        for rec, p in zip(offline, pendings):
            from distributed_vgg_f_tpu.train.predict import top_k_records
            _assert_same_records(
                rec["top_k"], top_k_records(p.probs, 5,
                                            full_precision=True))
    finally:
        batcher.close()


@pytest.mark.slow
def test_server_bitwise_equals_offline_predict_zoo(tmp_path):
    """The acceptance grid: every zoo preset's model, server vs offline,
    bitwise."""
    from distributed_vgg_f_tpu.models.ingest import zoo_model_names
    from distributed_vgg_f_tpu.train.predict import run_predict
    for model_name in zoo_model_names():
        sub = tmp_path / model_name
        sub.mkdir()
        tr = _trainer(sub, model_name=model_name)
        files, imgs = _npy_files(sub, 2, 32)
        offline = run_predict(tr, files, top_k=3, batch=1,
                              stream=io.StringIO())
        server = _serve_parity(tr, buckets=(1,), max_batch=1)
        try:
            for rec, img in zip(offline, imgs):
                status, body = _post(server.port, model_name, img, k=3)
                assert status == 200
                _assert_same_records(rec["top_k"], body["top_k"])
        finally:
            server.close()


def test_zoo_routing_one_server_many_models(tmp_path):
    """One server fronts several descriptor rows: responses route by URL
    and each model's receipt carries ITS descriptor."""
    from distributed_vgg_f_tpu.serving.engine import PredictEngine
    from distributed_vgg_f_tpu.serving.server import PredictServer
    server = PredictServer(_serving_cfg(max_batch=2, buckets=(1, 2),
                                        warmup=False))
    for name, classes in (("vggf", 5), ("vit_s16", 7)):
        server.add_engine(_tiny_engine(name, num_classes=classes))
    server.start()
    try:
        s1, b1 = _post(server.port, "vggf", _images(1)[0], k=5)
        s2, b2 = _post(server.port, "vit_s16", _images(1, seed=3)[0], k=7)
        assert s1 == s2 == 200
        assert b1["model"] == "vggf" and len(b1["top_k"]) == 5
        assert b2["model"] == "vit_s16" and len(b2["top_k"]) == 7
        with urllib.request.urlopen(
                f"http://127.0.0.1:{server.port}/v1/models",
                timeout=10) as r:
            table = json.loads(r.read())["models"]
        assert table["vggf"]["ingest"]["space_to_depth"] is True
        assert table["vit_s16"]["ingest"]["space_to_depth"] is False
    finally:
        server.close()


def test_kill_switch_offline_predict_never_imports_serving(tmp_path):
    """serving off (the default) leaves run_predict structurally untouched:
    a JPEG predict run must not even import the serving package."""
    pytest.importorskip("tensorflow")
    import tensorflow as tf

    from distributed_vgg_f_tpu.train.predict import run_predict
    tr = _trainer(tmp_path)
    img = _images(1, size=48, seed=2)[0]
    jpg = tmp_path / "one.jpg"
    jpg.write_bytes(tf.io.encode_jpeg(img, quality=90).numpy())
    dropped = [m for m in list(sys.modules)
               if m.startswith("distributed_vgg_f_tpu.serving")]
    for m in dropped:
        sys.modules.pop(m)
    out = run_predict(tr, [str(jpg)], top_k=3, stream=io.StringIO())
    assert len(out) == 1
    assert not any(m.startswith("distributed_vgg_f_tpu.serving")
                   for m in sys.modules), \
        "offline JPEG predict imported the serving package — the " \
        "kill-switch is no longer structural"


def test_cli_serve_requires_explicit_enable(tmp_path):
    import train as train_cli
    with pytest.raises(SystemExit, match="serving is disabled"):
        train_cli.main([
            "--config", "vggf_cifar10_smoke", "--mode", "serve",
            "--set", f"train.checkpoint_dir={tmp_path / 'none'}",
        ])


# -------------------------------------------------------- sentinel/schema

def _serving_artifact(value=200.0, **row_overrides):
    from distributed_vgg_f_tpu.telemetry import schema
    row = {
        "layout": "openloop", "mode": "serving_bench",
        "serving_mode": "openloop_b8", "model": "vggf", "wire": "u8",
        "space_to_depth": False, "image_dtype": "float32",
        "wire_bytes_per_image": 128 * 128 * 3,
        "source": {"source_kind": "u8_payload", "source_hw": [128, 128]},
        "admitted_rps": value, "spread": 0.05, "queue_peak": 30,
        "serving": {"buckets": [1, 2, 4, 8], "max_batch": 8,
                    "window_ms": 20.0, "queue_limit": 32,
                    "controller": False},
        "stages": [
            {"offered_rps": 100.0, "duration_s": 6.0, "admitted_rps": 99.0,
             "shed_rate": 0.0, "p50_ms": 20.0, "p95_ms": 30.0,
             "p99_ms": 40.0},
            {"offered_rps": 400.0, "duration_s": 6.0,
             "admitted_rps": value, "shed_rate": 0.4, "p50_ms": 60.0,
             "p95_ms": 90.0, "p99_ms": 120.0},
        ],
    }
    row.update(row_overrides)
    return {"schema_version": schema.SCHEMA_VERSION,
            "metric": "serving_admitted_rps", "value": value,
            "layouts": [row]}


def test_serving_artifact_schema_accepts_and_rejects():
    from distributed_vgg_f_tpu.telemetry import schema
    assert schema.validate_bench_artifact(_serving_artifact()) == []
    bad = schema.validate_bench_artifact(
        _serving_artifact(serving_mode="dynamic"))
    assert any("serving_mode" in e for e in bad)
    art = _serving_artifact()
    art["layouts"][0]["stages"][0]["shed_rate"] = 1.5
    assert any("shed_rate" in e
               for e in schema.validate_bench_artifact(art))
    art = _serving_artifact()
    art["layouts"][0]["stages"][0].update(p50_ms=50.0, p99_ms=10.0)
    assert any("quantiles not ordered" in e
               for e in schema.validate_bench_artifact(art))
    art = _serving_artifact(queue_peak=99)
    assert any("queue_limit" in e
               for e in schema.validate_bench_artifact(art))


def test_serving_basis_key_and_defaults():
    from distributed_vgg_f_tpu.telemetry.regress import Basis, row_basis
    row = _serving_artifact()["layouts"][0]
    basis = row_basis(row)
    assert basis.serving == "openloop_b8" and basis.model == "vggf"
    # pre-r17 decode rows keep their committed key: serving defaults off
    assert Basis("u8", True, "noise", (320, 256), True).serving == "off"


def test_serving_receipts_are_sentinel_gated():
    """The committed open-loop receipts back SERVING_RPS_R14: the chain
    passes check_committed, the trajectory carries a serving section, and
    a new artifact on the serving basis gates against the pin (below the
    tolerance floor -> REGRESSION)."""
    import os

    from distributed_vgg_f_tpu.telemetry import regress
    from distributed_vgg_f_tpu.utils import scaling_model
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert regress.check_committed(repo) == []
    trajectory = regress.build_trajectory(repo)
    # the section grew a round per serving tier (r23); the open-loop pin's
    # round is picked by name, not by being the only one
    by_pin = {r["pin"]: r for r in trajectory["serving"]}
    assert len(by_pin) == len(trajectory["serving"])
    serving_round = by_pin["SERVING_RPS_R14"]
    assert serving_round["value"] == scaling_model.SERVING_RPS_R14 > 0
    assert any(a["pin_provenance"] for a in serving_round["artifacts"])
    # at the pin: green
    ok = _serving_artifact(value=scaling_model.SERVING_RPS_R14)
    errors, report = regress.check_artifact(ok, repo)
    assert errors == [] and report["pin"] == "SERVING_RPS_R14"
    # far below the floor: REGRESSION
    bad = _serving_artifact(value=scaling_model.SERVING_RPS_R14 * 0.5)
    errors, report = regress.check_artifact(bad, repo)
    assert any("REGRESSION" in e for e in errors)
    # measured with the admission controller steering the window: refused
    # outright (the decode chain's mid-autotune discipline)
    moving = _serving_artifact(value=scaling_model.SERVING_RPS_R14)
    moving["layouts"][0]["serving"]["controller"] = True
    errors, report = regress.check_artifact(moving, repo)
    assert any("REFUSED" in e and "controller" in e for e in errors)
