"""Disaggregated ingest (r16): framing, epoch-keyed shard ownership, the
service-off kill-switch, service ≡ local byte-identity (synthetic replay AND
native position-keyed decode), worker-death failover, all-dead local
fallback / typed stall, restore_state position-exactness, the /ingestz
endpoint, config validation, and the worker@N fault injector."""

import dataclasses
import logging
import os
import socket

import numpy as np
import pytest

from distributed_vgg_f_tpu import telemetry
from distributed_vgg_f_tpu.config import apply_overrides, get_config
from distributed_vgg_f_tpu.data import build_dataset
from distributed_vgg_f_tpu.data import ingest_service as isvc
from distributed_vgg_f_tpu.data.ingest_service import (
    IngestWorker, PositionKeyedProducer, SequentialReplayProducer,
    ServiceProtocolError, ingest_label, recv_message, send_message,
    shard_owner)
from distributed_vgg_f_tpu.data.service_client import ServiceIngestClient
from distributed_vgg_f_tpu.resilience.errors import DataStallError


def _synthetic_cfg(**over):
    cfg = get_config("vggf_synthetic")
    return apply_overrides(cfg, {
        "data.global_batch_size": 8, "data.image_size": 32, **over})


def _factory(data_cfg, seed=3):
    return lambda: build_dataset(data_cfg, "train", seed=seed,
                                 num_classes=1000)


def _replay_workers(data_cfg, n, seed=3):
    return [IngestWorker(SequentialReplayProducer(_factory(data_cfg, seed)),
                         worker_index=i, num_workers=n,
                         receipt={"seed": seed, "shard_index": 0,
                                  "num_shards": 1})
            for i in range(n)]


# ---------------------------------------------------------------- framing

def _sock_pair():
    a, b = socket.socketpair()
    return a, b


def test_frame_roundtrip_arrays():
    a, b = _sock_pair()
    try:
        arrays = {"image": np.arange(24, dtype=np.uint8).reshape(2, 3, 4),
                  "label": np.array([5, -1], np.int32),
                  "f": np.linspace(0, 1, 6, dtype=np.float32).reshape(2, 3)}
        send_message(a, {"op": "get", "cursor": 7}, arrays=arrays)
        header, got = recv_message(b)
        assert header["op"] == "get" and header["cursor"] == 7
        for k, v in arrays.items():
            assert got[k].dtype == v.dtype
            assert np.array_equal(got[k], v)
    finally:
        a.close(), b.close()


def test_frame_checksum_rejects_corruption():
    a, b = _sock_pair()
    try:
        # hand-build a frame whose blob bytes are flipped after the
        # checksum was computed: the receiver must refuse, never hand bad
        # pixels up
        import json
        import struct
        import zlib
        blob = bytes(range(16))
        hdr = json.dumps({"ok": True, "arrays": [
            {"key": "image", "dtype": "uint8", "shape": [16],
             "nbytes": 16, "adler32": zlib.adler32(blob)}]}).encode()
        bad = bytes([blob[0] ^ 0xFF]) + blob[1:]
        total = 4 + len(hdr) + len(bad)
        a.sendall(struct.pack(">Q", total) + struct.pack(">I", len(hdr))
                  + hdr + bad)
        with pytest.raises(ServiceProtocolError, match="checksum"):
            recv_message(b)
    finally:
        a.close(), b.close()


def test_frame_truncation_and_oversize_rejected():
    import struct
    a, b = _sock_pair()
    try:
        a.sendall(struct.pack(">Q", 100) + b"short")
        a.close()
        with pytest.raises(ServiceProtocolError):
            recv_message(b)
    finally:
        b.close()
    a, b = _sock_pair()
    try:
        a.sendall(struct.pack(">Q", 1 << 40))
        with pytest.raises(ServiceProtocolError, match="implausible"):
            recv_message(b)
    finally:
        a.close(), b.close()


# -------------------------------------------------------------- ownership

def test_shard_owner_deterministic_and_in_range():
    owners = [shard_owner(c, 4, seed=9, batches_per_epoch=50)
              for c in range(200)]
    assert owners == [shard_owner(c, 4, seed=9, batches_per_epoch=50)
                      for c in range(200)]
    assert set(owners) <= set(range(4))
    # within one epoch the split is static per residue class (no handoff)
    for c in range(0, 46):
        assert owners[c] == owners[c % 4]


def test_shard_owner_epoch_rebalances():
    # across epochs the permutation re-draws: some cursor's owner changes
    # (the heterogeneous-fleet rebalance), while single-worker is always 0
    changed = any(
        shard_owner(c, 4, seed=9, batches_per_epoch=8)
        != shard_owner(c + 8, 4, seed=9, batches_per_epoch=8)
        for c in range(8))
    assert changed
    assert all(shard_owner(c, 1, seed=9, batches_per_epoch=8) == 0
               for c in range(30))


def test_ingest_label():
    assert ingest_label(4) == "service_4w"
    assert ingest_label(4, enabled=False) == "local"
    cfg = _synthetic_cfg()
    assert cfg.data.service.label == "local"


# ----------------------------------------------------------- kill-switch

def test_service_off_is_local_byte_identical():
    """data.service.enabled=false ≡ local ingest: build_dataset returns
    the ordinary pipeline object (not a client) and the stream is
    byte-identical whether the service config is default or configured-
    but-disabled."""
    cfg = _synthetic_cfg()
    d_disabled = dataclasses.replace(
        cfg.data, service=dataclasses.replace(
            cfg.data.service, enabled=False,
            workers=("127.0.0.1:1",)))
    a = build_dataset(cfg.data, "train", seed=3, num_classes=1000)
    b = build_dataset(d_disabled, "train", seed=3, num_classes=1000)
    assert not isinstance(a, ServiceIngestClient)
    assert type(a) is type(b)
    for _ in range(4):
        x, y = next(a), next(b)
        assert np.array_equal(x["image"], y["image"])
        assert np.array_equal(x["label"], y["label"])


# ------------------------------------------------- service ≡ local stream

def test_service_matches_local_synthetic():
    cfg = _synthetic_cfg()
    workers = _replay_workers(cfg.data, 2)
    client = ServiceIngestClient(
        [w.endpoint for w in workers], seed=3, batches_per_epoch=16,
        expect={"seed": 3, "shard_index": 0})
    local = iter(_factory(cfg.data)())
    try:
        for b in range(10):
            got, want = next(client), next(local)
            assert np.array_equal(got["image"], want["image"]), b
            assert np.array_equal(got["label"], want["label"]), b
    finally:
        client.close()
        for w in workers:
            w.close()


def test_build_dataset_routes_to_client_and_validates_identity():
    cfg = _synthetic_cfg()
    workers = _replay_workers(cfg.data, 2)
    try:
        d_on = dataclasses.replace(
            cfg.data, service=dataclasses.replace(
                cfg.data.service, enabled=True,
                workers=tuple(w.endpoint for w in workers)))
        client = build_dataset(d_on, "train", seed=3, num_classes=1000)
        assert isinstance(client, ServiceIngestClient)
        local = build_dataset(cfg.data, "train", seed=3, num_classes=1000)
        for _ in range(4):
            got, want = next(client), next(local)
            assert np.array_equal(got["image"], want["image"])
        client.close()
        # a fleet serving a DIFFERENT stream must fail the handshake, not
        # silently train on wrong data
        with pytest.raises(ValueError, match="stream-identity"):
            build_dataset(d_on, "train", seed=4, num_classes=1000)
    finally:
        for w in workers:
            w.close()


def test_restore_state_position_exact():
    cfg = _synthetic_cfg()
    workers = _replay_workers(cfg.data, 2)
    client = ServiceIngestClient([w.endpoint for w in workers], seed=3,
                                 batches_per_epoch=16)
    try:
        assert client.supports_state
        assert client.restore_state(5)
        ref = iter(_factory(cfg.data)())
        for _ in range(5):
            next(ref)
        for _ in range(3):
            assert np.array_equal(next(client)["image"],
                                  next(ref)["image"])
        # after the first draw the seek is refused (native contract)
        assert not client.restore_state(0)
    finally:
        client.close()
        for w in workers:
            w.close()


# ---------------------------------------------------------------- chaos

def test_worker_death_fails_over_byte_identically():
    cfg = _synthetic_cfg()
    workers = _replay_workers(cfg.data, 2)
    client = ServiceIngestClient([w.endpoint for w in workers], seed=3,
                                 batches_per_epoch=16)
    local = iter(_factory(cfg.data)())
    reg = telemetry.get_registry()
    before = reg.counter_value("ingest_service/failovers", 0)
    try:
        for _ in range(3):
            assert np.array_equal(next(client)["image"],
                                  next(local)["image"])
        killed = client.kill_one_worker_for_chaos()
        assert killed in [w.endpoint for w in workers]
        for b in range(3, 10):
            assert np.array_equal(next(client)["image"],
                                  next(local)["image"]), b
        assert reg.counter_value("ingest_service/failovers", 0) > before
        assert client.describe()["workers_live"] == 1
    finally:
        client.close()
        for w in workers:
            w.close()


def test_all_workers_dead_falls_back_to_local(caplog):
    cfg = _synthetic_cfg()
    workers = _replay_workers(cfg.data, 2)
    client = ServiceIngestClient(
        [w.endpoint for w in workers], seed=3, batches_per_epoch=16,
        local_factory=_factory(cfg.data))
    local = iter(_factory(cfg.data)())
    try:
        for _ in range(2):
            assert np.array_equal(next(client)["image"],
                                  next(local)["image"])
        client.kill_one_worker_for_chaos()
        client.kill_one_worker_for_chaos()
        with caplog.at_level(logging.WARNING,
                             "distributed_vgg_f_tpu.data.service_client"):
            for b in range(2, 8):
                assert np.array_equal(next(client)["image"],
                                      next(local)["image"]), b
        assert any("falling back to LOCAL ingest" in r.message
                   for r in caplog.records)
        assert client.describe()["local_fallback_active"]
    finally:
        client.close()
        for w in workers:
            w.close()


def test_all_workers_dead_no_fallback_raises_typed_stall():
    cfg = _synthetic_cfg()
    workers = _replay_workers(cfg.data, 1)
    client = ServiceIngestClient([w.endpoint for w in workers], seed=3,
                                 batches_per_epoch=16, fetch_ahead=1)
    try:
        next(client)
        client.kill_one_worker_for_chaos()
        with pytest.raises(DataStallError, match="decode workers"):
            for _ in range(4):
                next(client)
        # the flight recorder saw a data_stall note (the chaos suite's
        # classification contract: this is a diagnosed stall, never an
        # unhandled_exception)
        from distributed_vgg_f_tpu.telemetry.flight import get_flight
        note = get_flight()._consume_note()
        assert note is not None and note["kind"] == "data_stall"
    finally:
        client.close()
        for w in workers:
            w.close()


def test_fault_plan_worker_token_and_hook():
    from distributed_vgg_f_tpu.resilience import faults
    plan = faults.FaultPlan.parse("worker@3")
    assert plan.worker_kill_step == 3 and plan.has_data_faults
    with pytest.raises(ValueError):
        faults.FaultPlan.parse("worker@2:5")  # no modifier allowed
    killed = []
    faults.set_worker_kill_hook(lambda: killed.append(1) or "w0")
    try:
        src = iter([{"image": np.zeros((2, 2)), "label": np.zeros(2)}] * 4)
        reg = telemetry.get_registry()
        before = reg.counter_value("fault/worker_kill", 0)
        out = list(plan.wrap_iterator(src))
        assert len(out) == 4 and killed == [1]
        assert reg.counter_value("fault/worker_kill", 0) == before + 1
    finally:
        faults.clear_worker_kill_hook(None)
        faults.set_worker_kill_hook(None)


def test_fault_worker_kill_through_live_client():
    """worker@N through the REAL path: the injector's hook is the client's
    chaos kill, the worker dies mid-epoch via the production shutdown op,
    and the wrapped stream continues byte-identically (failover)."""
    from distributed_vgg_f_tpu.resilience import faults
    cfg = _synthetic_cfg()
    workers = _replay_workers(cfg.data, 2)
    client = ServiceIngestClient([w.endpoint for w in workers], seed=3,
                                 batches_per_epoch=16)
    local = iter(_factory(cfg.data)())
    plan = faults.FaultPlan.parse("worker@2")
    wrapped = plan.wrap_iterator(client)
    reg = telemetry.get_registry()
    before = reg.counter_value("fault/worker_kill", 0)
    try:
        for b in range(6):
            assert np.array_equal(next(wrapped)["image"],
                                  next(local)["image"]), b
        assert reg.counter_value("fault/worker_kill", 0) == before + 1
        # The client DISCOVERS the death on a failed request, in a
        # fetch-ahead thread: the killed worker's cursors 8 and 10 were
        # scheduled by the draws above and must reach it, but nothing
        # orders that failure before this line (asserting it at once raced
        # the thread on a loaded host). Wait for the event itself.
        import time
        deadline = time.monotonic() + 30.0
        while client.describe()["workers_live"] != 1:
            assert time.monotonic() < deadline, \
                "the client never discovered the killed worker"
            time.sleep(0.01)
    finally:
        client.close()
        for w in workers:
            w.close()


class _BrokenProducer:
    """produce() raises deterministically — the worker stays up and
    replies ok:false to every get (a misconfigured worker box)."""

    def produce(self, cursor):
        raise RuntimeError("worker misconfigured")


def test_refused_requests_fail_over_not_spin():
    """A worker that REFUSES every get (up, but its producer is broken)
    must be treated like a dead one: marked dead after the first refusal
    and its cursors reassigned — retrying the owner forever would hang
    the stream (code-review r16)."""
    cfg = _synthetic_cfg()
    broken = IngestWorker(_BrokenProducer(), worker_index=0, num_workers=2)
    good = IngestWorker(SequentialReplayProducer(_factory(cfg.data)),
                        worker_index=1, num_workers=2)
    client = ServiceIngestClient([broken.endpoint, good.endpoint], seed=3,
                                 batches_per_epoch=16)
    local = iter(_factory(cfg.data)())
    try:
        for b in range(6):
            assert np.array_equal(next(client)["image"],
                                  next(local)["image"]), b
        assert client.describe()["workers_live"] == 1
    finally:
        client.close()
        broken.close()
        good.close()


# ------------------------------------------------------------- /ingestz

def test_ingestz_endpoint_serves_client_state():
    import json
    import urllib.request

    from distributed_vgg_f_tpu.telemetry.exporter import TelemetryExporter
    cfg = _synthetic_cfg()
    workers = _replay_workers(cfg.data, 2)
    client = ServiceIngestClient([w.endpoint for w in workers], seed=3,
                                 batches_per_epoch=16)
    exp = TelemetryExporter()
    port = exp.start()
    try:
        next(client)
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/ingestz", timeout=10) as r:
            payload = json.loads(r.read())
        assert payload["enabled"] is True
        assert payload["label"] == "service_2w"
        assert len(payload["workers"]) == 2
        assert payload["workers_live"] == 2
    finally:
        exp.stop()
        client.close()
        for w in workers:
            w.close()
    # after close, the provider is cleared
    from distributed_vgg_f_tpu.telemetry.exporter import ingest_payload
    assert ingest_payload()["enabled"] is False


# --------------------------------------------------------------- config

def test_service_config_validation():
    from distributed_vgg_f_tpu.config import ServiceConfig
    with pytest.raises(ValueError, match="host:port"):
        ServiceConfig(workers=("localhost",))
    with pytest.raises(ValueError, match="host:port"):
        ServiceConfig(workers=("host:abc",))
    with pytest.raises(ValueError, match="fetch_ahead"):
        ServiceConfig(fetch_ahead=-1)
    with pytest.raises(ValueError, match="timeout"):
        ServiceConfig(request_timeout_s=0)
    # enabled with no workers is rejected at client build (flag-order
    # tolerance: __post_init__ sees one override at a time)
    cfg = _synthetic_cfg(**{"data.service.enabled": True})
    with pytest.raises(ValueError, match="at least one worker"):
        build_dataset(cfg.data, "train", seed=3, num_classes=1000)
    assert ServiceConfig(enabled=True,
                         workers=("h1:1", "h2:2")).label == "service_2w"


def test_worker_stats_and_hello_receipts():
    cfg = _synthetic_cfg()
    workers = _replay_workers(cfg.data, 1)
    client = ServiceIngestClient([w.endpoint for w in workers], seed=3,
                                 batches_per_epoch=16, fetch_ahead=1)
    try:
        for _ in range(3):
            next(client)
        w = workers[0]
        assert w.hello()["seed"] == 3
        stats = w.stats()
        assert stats["batches_served"] >= 3
        assert stats["bytes_served"] > 0
    finally:
        client.close()
        for w in workers:
            w.close()


# ------------------------------------------------ native position-keyed

@pytest.fixture(scope="module")
def jpeg_train_dir(tmp_path_factory):
    native = pytest.importorskip(
        "distributed_vgg_f_tpu.data.native_jpeg")
    if native.load_native_jpeg() is None:
        pytest.skip("native jpeg loader unavailable (toolchain missing)")
    from PIL import Image
    root = tmp_path_factory.mktemp("svc_imagenet")
    rs = np.random.RandomState(0)
    for cls in ("n01", "n02"):
        d = root / "train" / cls
        d.mkdir(parents=True)
        for i in range(7):
            Image.fromarray((rs.rand(120, 130, 3) * 255).astype(np.uint8)) \
                .save(str(d / f"{i}.jpg"), "JPEG", quality=90)
    return str(root)


def _native_cfg(data_dir, **over):
    cfg = get_config("vggf_imagenet_dp")
    return apply_overrides(cfg, {
        "data.data_dir": data_dir, "data.global_batch_size": 4,
        "data.image_size": 64, "data.autotune.enabled": False,
        "data.augment.enabled": False, "train.seed": 5, **over})


def test_native_service_matches_local_stream(jpeg_train_dir):
    """The acceptance parity gate: 2 position-keyed decode workers serve
    the flagship u8-wire stream byte-identically to the local native
    iterator, across an epoch boundary (14 items, batch 4)."""
    cfg = _native_cfg(jpeg_train_dir)
    local = build_dataset(cfg.data, "train", seed=5, num_classes=1000)
    workers = [isvc.serve_from_config(cfg, worker_index=i, num_workers=2)
               for i in range(2)]
    assert all(isinstance(w._producer, PositionKeyedProducer)
               for w in workers)
    cfg_on = apply_overrides(cfg, {
        "data.service.enabled": True,
        "data.service.workers": ",".join(w.endpoint for w in workers)})
    client = build_dataset(cfg_on.data, "train", seed=5, num_classes=1000)
    try:
        assert client.describe()["label"] == "service_2w"
        for b in range(9):  # 36 items: past 2 epoch boundaries
            got, want = next(client), next(local)
            assert got["image"].dtype == np.uint8  # the u8 wire
            assert np.array_equal(got["image"], want["image"]), b
            assert np.array_equal(got["label"], want["label"]), b
    finally:
        client.close()
        local.close()
        for w in workers:
            w.close()


def test_native_worker_shared_warm_tier(jpeg_train_dir, tmp_path):
    """The shared snapshot tier: a second worker generation over the same
    store serves warm (store hits move, labels identical), inheriting the
    cache's crc/eviction contracts."""
    cfg = _native_cfg(jpeg_train_dir, **{
        "data.snapshot_cache.enabled": True,
        "data.snapshot_cache.dir": str(tmp_path / "tier")})
    reg = telemetry.get_registry()
    w_cold = isvc.serve_from_config(cfg, worker_index=0, num_workers=1)
    cold = [w_cold._producer.produce(b) for b in range(4)]
    hits0 = reg.counter_value("ingest_service/store_hits", 0)
    w_warm = isvc.serve_from_config(cfg, worker_index=0, num_workers=1)
    warm = [w_warm._producer.produce(b) for b in range(3)]  # epoch 0
    hits1 = reg.counter_value("ingest_service/store_hits", 0)
    try:
        # single-writer election: the first claimant of the generation
        # holds the writer flock, later claimants serve read-only
        # (SnapshotStore's append offsets are not multi-writer safe)
        assert w_cold._producer._store_writable
        assert not w_warm._producer._store_writable
        assert hits1 > hits0
        for a, b in zip(cold, warm):
            assert np.array_equal(a["label"], b["label"])
            assert a["image"].shape == b["image"].shape
    finally:
        w_cold.close()
        w_warm.close()


def test_native_producer_self_tuning_knob(jpeg_train_dir):
    """The per-worker PR 8 controller's knob surface: the producer's
    thread pool resizes through the same thread_knob the autotuner binds,
    and produce() keeps working across resizes."""
    from distributed_vgg_f_tpu.data import autotune as _at
    cfg = _native_cfg(jpeg_train_dir)
    w = isvc.serve_from_config(cfg, worker_index=0, num_workers=1,
                               threads=1)
    try:
        p = w._producer
        knob = _at.thread_knob(p, min_value=1, max_value=8)
        assert knob is not None
        assert p.set_num_threads(4) == 4
        batch = p.produce(0)
        assert batch["image"].shape[0] == 4
        assert p.set_num_threads(2) == 2
        assert p.num_threads() == 2
    finally:
        w.close()
