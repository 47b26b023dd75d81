"""uint8 ingest wire gates (r8): the quantization bound, the kill-switch
fallback contract, the device-finish prologue's single-normalization
invariant, and the wire's train-trajectory equivalence.

Parity structure (PR 3 style):
 - the u8 pixels differ from the float-path bilinear by at most ONE
   intensity level (the fixed-point kernels' 8-bit-fraction weights are
   the wire's only precision loss) — a tolerance gate;
 - with the wire kill-switched off, the host-normalize paths are
   BYTE-IDENTICAL to their pre-u8 (r7) behavior — an equality gate;
 - for identical u8 pixels, host normalize and device finish perform the
   same single-rounded f32 ops, so the CPU train-loss trajectories of the
   two wires are EQUAL, not merely close.
"""

import io

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_vgg_f_tpu import telemetry
from distributed_vgg_f_tpu.config import DataConfig
from distributed_vgg_f_tpu.data.device_ingest import (
    make_device_finish,
    space_to_depth_batch,
)
from distributed_vgg_f_tpu.data.native_jpeg import (
    NativeJpegTrainIterator,
    decode_single_image,
    load_native_jpeg,
    set_wire_u8,
    wire_u8_enabled,
    wire_u8_supported,
)

MEAN = (123.68, 116.78, 103.94)
STD = (58.393, 57.12, 57.375)

_native = load_native_jpeg() is not None

requires_native = pytest.mark.skipif(
    not _native, reason="native jpeg loader unavailable")
requires_wire_u8 = pytest.mark.skipif(
    not (_native and wire_u8_supported()),
    reason="uint8 wire compiled out (-DDVGGF_NO_WIRE_U8) or library "
           "unavailable")


@pytest.fixture(autouse=True)
def _restore_wire():
    """Every test leaves the process-wide u8-wire dispatch as it found it."""
    if not _native:
        yield
        return
    before = wire_u8_enabled()
    yield
    set_wire_u8(before)


def _jpeg_bytes(h=64, w=80, seed=0) -> bytes:
    from PIL import Image
    rng = np.random.default_rng(seed)
    buf = io.BytesIO()
    Image.fromarray(rng.integers(0, 256, size=(h, w, 3))
                    .astype(np.uint8)).save(buf, "JPEG", quality=90)
    return buf.getvalue()


# --------------------------------------------------------- native u8 decode
@requires_wire_u8
@pytest.mark.parametrize("eval_mode", [False, True])
def test_u8_within_one_level_of_float_bilinear(eval_mode):
    """The pinned quantization bound: identity-normalize f32 decode vs the
    fixed-point u8 decode of the SAME crop (same seed → same crop/flip)
    differ by <= 1 intensity level per channel (1/255 of full scale)."""
    data = _jpeg_bytes()
    zero, one = np.zeros(3, np.float32), np.ones(3, np.float32)
    for seed in (0, 7, 23):
        f32 = decode_single_image(data, 32, zero, one, eval_mode=eval_mode,
                                  rng_seed=seed)
        u8 = decode_single_image(data, 32, zero, one, eval_mode=eval_mode,
                                 rng_seed=seed, image_dtype="uint8")
        assert u8.dtype == np.uint8 and u8.shape == (32, 32, 3)
        err = np.abs(u8.astype(np.float32) - f32)
        assert float(err.max()) <= 1.0, (
            f"u8 wire {err.max():.3f} levels off the float bilinear "
            f"(seed {seed}) — the fixed-point kernels drifted past the "
            "pinned 1/255-per-channel bound")


@requires_wire_u8
def test_u8_never_packs_on_host():
    """space-to-depth belongs to the device finish: both creation surfaces
    refuse a u8 + pack4 combination loudly."""
    data = _jpeg_bytes()
    with pytest.raises(ValueError, match="device-finish"):
        decode_single_image(data, 32, MEAN, STD, image_dtype="uint8",
                            pack4=True)


@requires_wire_u8
def test_corrupt_image_fill_is_mean_on_u8_wire(tmp_path):
    """Decode-failure fallback parity across wires: host wires zero-fill
    POST-normalize (a mean image downstream); the u8 wire must ship the
    rounded per-channel MEAN so the device finish lands within half an
    intensity level of that zero — the same failing input may not yield
    materially different training data depending on data.wire."""
    from PIL import Image
    rng = np.random.default_rng(3)
    files = []
    for i in range(3):
        p = tmp_path / f"img_{i}.jpg"
        Image.fromarray(rng.integers(0, 256, size=(40, 44, 3))
                        .astype(np.uint8)).save(p, "JPEG", quality=90)
        files.append(str(p))
    bad = tmp_path / "corrupt.jpg"
    bad.write_bytes(b"\xff\xd8\xff\xe0not a jpeg at all")
    files.append(str(bad))

    it = NativeJpegTrainIterator(files, [0, 1, 2, 3], batch=4, image_size=32,
                                 mean=np.asarray(MEAN, np.float32),
                                 std=np.asarray(STD, np.float32),
                                 image_dtype="uint8", num_threads=1, seed=0)
    try:
        batch = next(it)  # batch == dataset, so the corrupt item is in it
        # one error in every decoded epoch: this batch's, and one more for
        # each batch the ring decoded ahead of the consumer (an exact == 1
        # raced the workers)
        assert 1 <= it.decode_errors() <= 1 + it.decode_ahead_batches
    finally:
        it.close()
    expected = np.broadcast_to(
        np.round(np.asarray(MEAN)).astype(np.uint8), (32, 32, 3))
    filled = [i for i in range(4)
              if np.array_equal(batch["image"][i], expected)]
    assert len(filled) == 1, "exactly the corrupt item is mean-filled"
    # and the device finish reads it as ~the host wires' zero-fill
    finish = make_device_finish(MEAN, STD)
    finished = np.asarray(finish(jnp.asarray(batch["image"][filled[0]][None])))
    assert np.abs(finished).max() <= 0.5 / min(STD) + 1e-6


@requires_native
def test_kill_switch_off_is_byte_identical_to_r7_path():
    """DVGGF_WIRE_U8 off: u8 loader creation refuses (the Python layer
    falls back ABOVE the ABI) and the host-normalize wires produce
    byte-identical output whether the u8 wire is armed or not — the
    r7-parity half of the kill-switch contract."""
    data = _jpeg_bytes()
    outs = {}
    for enabled in (True, False):
        if set_wire_u8(enabled) is None:
            pytest.skip("native library unavailable")
        for dtype in ("float32", "bfloat16"):
            out = decode_single_image(data, 32, MEAN, STD, image_dtype=dtype,
                                      eval_mode=True)
            key = (dtype,)
            if key in outs:
                np.testing.assert_array_equal(
                    outs[key].view(np.uint8), out.view(np.uint8),
                    err_msg=f"{dtype} host wire drifted with the u8 "
                            "kill-switch — the wire must be purely additive")
            outs[key] = out
    # and with the wire off, the u8 kind is refused, not silently degraded
    set_wire_u8(False)
    if wire_u8_supported():
        with pytest.raises(RuntimeError, match="refused"):
            decode_single_image(data, 32, MEAN, STD, image_dtype="uint8")


@requires_wire_u8
def test_train_iterator_ships_uint8(tmp_path):
    """The u8-armed train iterator yields raw uint8 HWC batches (no
    normalize, no pack) and refuses a host space_to_depth request."""
    from PIL import Image
    rng = np.random.default_rng(0)
    files = []
    for i in range(4):
        p = tmp_path / f"img_{i}.jpg"
        Image.fromarray(rng.integers(0, 256, size=(48, 52, 3))
                        .astype(np.uint8)).save(p, "JPEG", quality=90)
        files.append(str(p))
    it = NativeJpegTrainIterator(files, [0, 1, 2, 3], batch=4, image_size=32,
                                 mean=np.asarray(MEAN, np.float32),
                                 std=np.asarray(STD, np.float32),
                                 image_dtype="uint8", num_threads=1, seed=0)
    try:
        batch = next(it)
        assert batch["image"].dtype == np.uint8
        assert batch["image"].shape == (4, 32, 32, 3)
        assert it.image_dtype == "uint8"
    finally:
        it.close()
    with pytest.raises(ValueError, match="space-to-depth|space_to_depth"):
        NativeJpegTrainIterator(files, [0, 1, 2, 3], batch=4, image_size=32,
                                mean=np.asarray(MEAN, np.float32),
                                std=np.asarray(STD, np.float32),
                                image_dtype="uint8", num_threads=1, seed=0,
                                space_to_depth=True)


@requires_native
def test_ingest_layer_falls_back_when_wire_refused(tmp_path, caplog):
    """data.wire='u8' with the wire kill-switched: the imagenet builder
    must construct the HOST-normalize iterator (pre-r8 behavior) and log
    the fallback — never fail, never silently ship a different format."""
    import logging

    from distributed_vgg_f_tpu.data.imagenet import _wire_u8_active
    cfg = DataConfig(name="imagenet", data_dir=str(tmp_path), wire="u8")
    set_wire_u8(False)
    with caplog.at_level(logging.WARNING,
                         logger="distributed_vgg_f_tpu.data.imagenet"):
        assert _wire_u8_active(cfg, is_train=True) is False
    assert any("falling back" in r.message for r in caplog.records)
    if wire_u8_supported():
        set_wire_u8(True)
        assert _wire_u8_active(cfg, is_train=True) is True
    # eval streams always ride the host wire, no warning involved
    assert _wire_u8_active(cfg, is_train=False) is False


def test_non_native_backend_warns_wire_unshipped(caplog):
    """data.wire='u8' on a backend that cannot ship it (tf.data, grain)
    logs the fallback — the 'never a silent format change' half of the
    contract for the paths that never reach the native loader."""
    import logging

    from distributed_vgg_f_tpu.data.imagenet import _warn_wire_u8_unshipped
    cfg = DataConfig(name="imagenet", wire="u8")
    with caplog.at_level(logging.WARNING,
                         logger="distributed_vgg_f_tpu.data.imagenet"):
        _warn_wire_u8_unshipped(cfg, True, "tf.data")
    assert any("only the native train loader" in r.message
               for r in caplog.records)
    caplog.clear()
    with caplog.at_level(logging.WARNING,
                         logger="distributed_vgg_f_tpu.data.imagenet"):
        # eval streams and host wires are by-design silent
        _warn_wire_u8_unshipped(cfg, False, "tf.data")
        _warn_wire_u8_unshipped(DataConfig(name="imagenet"), True, "grain")
    assert not caplog.records


# ------------------------------------------------------------ device finish
def test_finish_passthrough_on_float_batches():
    """Host-normalized batches (every pre-r8 wire) pass through UNTOUCHED —
    the structural half of the single-normalization contract."""
    finish = make_device_finish(MEAN, STD)
    for dtype in (jnp.float32, jnp.bfloat16):
        x = jnp.asarray(np.random.default_rng(0).normal(size=(2, 8, 8, 3)),
                        dtype)
        np.testing.assert_array_equal(np.asarray(finish(x)), np.asarray(x))


def test_finish_normalizes_uint8_exactly_once():
    """A sentinel uint8 batch is normalized once; re-feeding the finish its
    own output is a no-op (dtype dispatch makes double-normalize
    structurally impossible)."""
    finish = make_device_finish(MEAN, STD)
    x = jnp.full((2, 8, 8, 3), 100, jnp.uint8)
    once = finish(x)
    assert once.dtype == jnp.float32
    expect = (100.0 - np.asarray(MEAN, np.float32)) \
        * (np.float32(1.0) / np.asarray(STD, np.float32))
    np.testing.assert_allclose(np.asarray(once)[0, 0, 0], expect, rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(finish(once)), np.asarray(once))


def test_finish_matches_host_normalize_bitwise():
    """For identical u8 pixels the device finish and the host wire compute
    the SAME single-rounded f32 ops ((v - mean) * (1/std)) — bit-identical
    output, the basis of the loss-trajectory equivalence gate."""
    rng = np.random.default_rng(3)
    pixels = rng.integers(0, 256, size=(4, 16, 16, 3)).astype(np.uint8)
    finish = make_device_finish(MEAN, STD)
    device = np.asarray(jax.jit(finish)(jnp.asarray(pixels)))
    mean = np.asarray(MEAN, np.float32)
    inv = np.float32(1.0) / np.asarray(STD, np.float32)
    host = (pixels.astype(np.float32) - mean) * inv
    np.testing.assert_array_equal(device, host)


def test_finish_space_to_depth_matches_reference():
    """The device-side 4x4 packing emits tf.nn.space_to_depth's (dy, dx, c)
    channel order — the host packer's and the VGG-F stem's contract."""
    rng = np.random.default_rng(5)
    pixels = rng.integers(0, 256, size=(2, 8, 8, 3)).astype(np.uint8)
    finish = make_device_finish((0.0, 0.0, 0.0), (1.0, 1.0, 1.0),
                                space_to_depth=True)
    packed = np.asarray(finish(jnp.asarray(pixels)))
    assert packed.shape == (2, 2, 2, 48)
    x = pixels.astype(np.float32)
    for b in (0, 1):
        for by in (0, 1):
            for bx in (0, 1):
                for dy in range(4):
                    for dx in range(4):
                        for c in range(3):
                            assert packed[b, by, bx, (dy * 4 + dx) * 3 + c] \
                                == x[b, by * 4 + dy, bx * 4 + dx, c]
    # eval-shaped (non-%4 or packed-already) inputs pass through unpacked
    odd = jnp.asarray(rng.integers(0, 256, size=(1, 6, 6, 3)), jnp.uint8)
    assert finish(odd).shape == (1, 6, 6, 3)


def test_space_to_depth_batch_bfloat16_preserved():
    x = jnp.ones((1, 8, 8, 3), jnp.bfloat16)
    assert space_to_depth_batch(x).dtype == jnp.bfloat16


def test_vggf_refuses_raw_uint8():
    """Raw wire pixels must never silently reach the model: a uint8 batch
    convolved as 0..255 floats would train, badly, with no error."""
    from distributed_vgg_f_tpu.models.vggf import VGGF
    model = VGGF(num_classes=4, compute_dtype=jnp.float32)
    with pytest.raises(TypeError, match="device-finish"):
        model.init(jax.random.key(0),
                   jnp.zeros((1, 32, 32, 3), jnp.uint8))


# ----------------------------------------------- step-level single-normalize
class _MiniNet:
    """Tiny flax model standing in for VGG-F in step-level gates (one conv
    + head keeps the jit cheap inside the tier-1 budget)."""

    def __new__(cls):
        import flax.linen as nn

        class Net(nn.Module):
            @nn.compact
            def __call__(self, x, *, train=False, rngs=None):
                x = nn.Conv(8, (3, 3), strides=(2, 2), dtype=jnp.float32)(x)
                x = nn.relu(x)
                x = x.reshape((x.shape[0], -1))
                return nn.Dense(10, dtype=jnp.float32)(x)

        return Net()


def _mesh8(devices8):
    from distributed_vgg_f_tpu.parallel.mesh import MeshSpec, build_mesh
    return build_mesh(MeshSpec(("data",), (8,)), devices=devices8)


def test_eval_step_u8_matches_host_wire(devices8):
    """The satellite's sentinel gate: the SAME pixels fed as (a) a uint8
    wire batch and (b) a host-normalized f32 batch produce identical eval
    counts through the eval step's device-finish prologue — and the float
    batch is provably not re-normalized (it matches the no-finish step)."""
    from distributed_vgg_f_tpu.parallel.mesh import shard_host_batch
    from distributed_vgg_f_tpu.train.step import build_eval_step
    mesh = _mesh8(devices8)
    model = _MiniNet()
    rng = np.random.default_rng(11)
    pixels = rng.integers(0, 256, size=(16, 16, 16, 3)).astype(np.uint8)
    labels = rng.integers(0, 10, size=(16,)).astype(np.int32)
    mean = np.asarray(MEAN, np.float32)
    inv = np.float32(1.0) / np.asarray(STD, np.float32)
    host_norm = (pixels.astype(np.float32) - mean) * inv

    from distributed_vgg_f_tpu.train.state import TrainState
    import optax
    state = TrainState.create(model, optax.sgd(0.1), jax.random.key(0),
                              jnp.zeros((1, 16, 16, 3), jnp.float32))

    finish = make_device_finish(MEAN, STD)
    from distributed_vgg_f_tpu.config import MeshConfig
    from distributed_vgg_f_tpu.parallel.zero import plan_exchange
    dp = plan_exchange(MeshConfig(), mesh, None)
    with_finish = build_eval_step(model, mesh, dp, device_finish=finish)
    without = build_eval_step(model, mesh, dp)

    def counts(step, images):
        batch = shard_host_batch({"image": images, "label": labels}, mesh)
        return {k: int(v) for k, v in
                jax.device_get(step(state, batch)).items()}

    via_u8 = counts(with_finish, pixels)
    via_host = counts(with_finish, host_norm)
    no_finish = counts(without, host_norm)
    assert via_u8 == via_host, (
        "u8 wire and host wire disagree through the eval prologue")
    assert via_host == no_finish, (
        "the finish re-normalized an already-normalized batch — the "
        "double-normalize hazard the dtype dispatch must prevent")


def test_train_loss_trajectory_equivalent_across_wires(devices8):
    """CPU loss-trajectory equivalence smoke (the acceptance gate): N steps
    on the SAME u8 pixel stream, once through the u8 wire + device finish,
    once host-normalized — equal loss trajectories (both wires perform the
    same single-rounded f32 normalize, see
    test_finish_matches_host_normalize_bitwise)."""
    import optax

    from distributed_vgg_f_tpu.config import MeshConfig
    from distributed_vgg_f_tpu.parallel.mesh import shard_host_batch
    from distributed_vgg_f_tpu.parallel.zero import plan_exchange
    from distributed_vgg_f_tpu.train.state import TrainState
    from distributed_vgg_f_tpu.train.step import build_train_step
    mesh = _mesh8(devices8)
    model = _MiniNet()
    rng = np.random.default_rng(17)
    batches = [rng.integers(0, 256, size=(16, 16, 16, 3)).astype(np.uint8)
               for _ in range(3)]
    labels = [rng.integers(0, 10, size=(16,)).astype(np.int32)
              for _ in range(3)]
    mean = np.asarray(MEAN, np.float32)
    inv = np.float32(1.0) / np.asarray(STD, np.float32)

    def run(as_u8: bool):
        tx = optax.sgd(0.05)
        state = TrainState.create(model, tx, jax.random.key(0),
                                  jnp.zeros((1, 16, 16, 3), jnp.float32))
        step = build_train_step(
            model, mesh, 1e-4, plan_exchange(MeshConfig(), mesh, tx),
            device_finish=make_device_finish(MEAN, STD))
        base = jax.jit(lambda: jax.random.key(1))()
        losses = []
        for px, lb in zip(batches, labels):
            images = px if as_u8 else (px.astype(np.float32) - mean) * inv
            batch = shard_host_batch({"image": images, "label": lb}, mesh)
            state, metrics = step(state, batch, base)
            losses.append(float(jax.device_get(metrics["loss"])))
        return losses

    np.testing.assert_array_equal(run(True), run(False))


# ----------------------------------------------------- prefetch + telemetry
@pytest.fixture()
def _fresh_telemetry():
    telemetry.reset()
    telemetry.configure(enabled=True)
    yield
    telemetry.reset()
    telemetry.configure(enabled=True)


def test_prefetch_refuses_u8_ring_armed_source(devices8):
    """The r7 buffer-ownership contract holds for uint8-armed sources: a
    ring-recycling iterator is refused regardless of wire format."""
    from distributed_vgg_f_tpu.data.prefetch import DevicePrefetchIterator
    mesh = _mesh8(devices8)

    class _U8RingSource:
        reuses_output_buffers = True

        def __iter__(self):
            return self

        def __next__(self):
            return {"image": np.zeros((8, 4, 4, 3), np.uint8),
                    "label": np.zeros((8,), np.int32)}

    with pytest.raises(ValueError, match="reuse"):
        DevicePrefetchIterator(_U8RingSource(), mesh, buffer_size=2)


def test_prefetch_device_put_bytes_counter(devices8, _fresh_telemetry):
    """prefetch/device_put_bytes counts what the wire actually ships —
    1 B/px for a u8 batch, 4 for f32 — and bytes_in_flight drains to 0
    once every queued batch is consumed."""
    from distributed_vgg_f_tpu.data.prefetch import DevicePrefetchIterator
    mesh = _mesh8(devices8)

    def source(dtype, n=3):
        for _ in range(n):
            yield {"image": np.zeros((8, 4, 4, 3), dtype),
                   "label": np.zeros((8,), np.int32)}

    for dtype, px_bytes in ((np.uint8, 1), (np.float32, 4)):
        telemetry.reset()
        per_batch = 8 * 4 * 4 * 3 * px_bytes + 8 * 4  # images + i32 labels
        pre = DevicePrefetchIterator(source(dtype), mesh, buffer_size=2)
        try:
            for _ in range(3):
                next(pre)
            with pytest.raises(StopIteration):
                next(pre)
        finally:
            pre.close()
        snap = telemetry.get_registry().snapshot_split()
        counters = snap.get("counters", snap)
        assert counters["prefetch/device_put_bytes"] == 3 * per_batch, dtype
        gauges = snap.get("gauges", {})
        assert gauges.get("prefetch/bytes_in_flight", 0) == 0


# ------------------------------------------------------------------- schema
def test_schema_validates_wire_fields():
    from distributed_vgg_f_tpu.telemetry.schema import validate_bench_artifact
    good = {"metric": "m", "value": 1000.0, "layouts": [
        {"wire": "u8", "wire_bytes_per_image": 150528,
         "profile": {"jpeg_us_per_image": 700.0,
                     "resample_us_per_image": 110.0}}]}
    assert validate_bench_artifact(good) == []
    bad = {"metric": "m", "value": 1000.0, "layouts": [
        {"wire": "u9", "wire_bytes_per_image": -3,
         "profile": {"jpeg_us_per_image": -1.0}}]}
    errors = validate_bench_artifact(bad)
    assert any("'wire'" in e for e in errors)
    assert any("wire_bytes_per_image" in e for e in errors)
    assert any("jpeg_us_per_image" in e for e in errors)


def test_config_validates_wire():
    with pytest.raises(ValueError, match="data.wire"):
        DataConfig(wire="uint8")
    with pytest.raises(ValueError, match="image_dtype"):
        DataConfig(image_dtype="uint8")
    for wire in ("auto", "host_f32", "host_bf16", "u8"):
        DataConfig(wire=wire)


def test_wire_bytes_per_pixel():
    from distributed_vgg_f_tpu.data.dtypes import wire_bytes_per_pixel
    assert wire_bytes_per_pixel("u8", "float32") == 3
    assert wire_bytes_per_pixel("host_bf16", "float32") == 6
    assert wire_bytes_per_pixel("host_f32", "bfloat16") == 12
    assert wire_bytes_per_pixel("auto", "bfloat16") == 6
