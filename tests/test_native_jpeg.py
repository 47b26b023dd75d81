"""Native libjpeg training loader (native/jpeg_loader.cc via
data/native_jpeg.py): determinism regardless of thread count, O(1) exact seek
resume, bf16 output, corrupt-image fallback, and imagefolder integration."""

import os

import numpy as np
import pytest

pytest.importorskip("tensorflow")

from distributed_vgg_f_tpu.data.native_jpeg import (  # noqa: E402
    NativeJpegTrainIterator,
    load_native_jpeg,
)

if load_native_jpeg() is None:  # pragma: no cover — g++/libjpeg exist here
    pytest.skip("native jpeg loader unavailable", allow_module_level=True)

MEAN = np.array([123.68, 116.78, 103.94], np.float32)
STD = np.array([58.393, 57.12, 57.375], np.float32)


@pytest.fixture(scope="module")
def jpeg_files(tmp_path_factory):
    import tensorflow as tf
    root = tmp_path_factory.mktemp("jpegs")
    rng = np.random.default_rng(0)
    files, labels = [], []
    for i in range(24):
        p = str(root / f"img_{i:03d}.jpg")
        img = rng.integers(0, 256, size=(96, 128, 3)).astype(np.uint8)
        with open(p, "wb") as f:
            f.write(tf.io.encode_jpeg(img, quality=90).numpy())
        files.append(p)
        labels.append(i % 10)
    return files, labels


def _make(files, labels, **kw):
    kw.setdefault("seed", 7)
    kw.setdefault("mean", MEAN)
    kw.setdefault("std", STD)
    return NativeJpegTrainIterator(files, labels, 8, 64, **kw)


def test_shapes_normalization_and_no_errors(jpeg_files):
    it = _make(*jpeg_files)
    b = next(it)
    assert b["image"].shape == (8, 64, 64, 3)
    assert b["image"].dtype == np.float32
    assert b["label"].shape == (8,) and b["label"].dtype == np.int32
    assert abs(float(b["image"].mean())) < 2.0
    assert float(np.asarray(b["image"], np.float32).std()) > 0.2
    assert it.decode_errors() == 0
    it.close()


def test_deterministic_regardless_of_thread_count(jpeg_files):
    files, labels = jpeg_files
    a = _make(files, labels, num_threads=1)
    b = _make(files, labels, num_threads=4)
    for _ in range(8):  # crosses an epoch boundary (24 imgs / batch 8)
        ba, bb = next(a), next(b)
        np.testing.assert_array_equal(ba["image"], bb["image"])
        np.testing.assert_array_equal(ba["label"], bb["label"])
    a.close()
    b.close()


def test_seek_resume_bit_identical(jpeg_files):
    files, labels = jpeg_files
    ref = _make(files, labels, num_threads=2)
    batches = [next(ref) for _ in range(9)]
    resumed = _make(files, labels, num_threads=3)
    assert resumed.supports_state
    assert resumed.restore_state(5)
    for i in range(5, 9):
        b = next(resumed)
        np.testing.assert_array_equal(b["image"], batches[i]["image"])
        np.testing.assert_array_equal(b["label"], batches[i]["label"])
    # seeking after the stream started must refuse (position already consumed)
    assert resumed.restore_state(2) is False
    ref.close()
    resumed.close()


def test_bf16_output(jpeg_files):
    import ml_dtypes
    it = _make(*jpeg_files, image_dtype="bfloat16")
    assert next(it)["image"].dtype == np.dtype(ml_dtypes.bfloat16)
    it.close()


def test_corrupt_image_zero_fills_and_counts(jpeg_files, tmp_path):
    files, labels = jpeg_files
    bad = str(tmp_path / "corrupt.jpg")
    with open(bad, "wb") as f:
        f.write(b"\xff\xd8\xffnot a real jpeg at all")
    it = NativeJpegTrainIterator([bad] * 4, [1, 2, 3, 4], 4, 32,
                                 seed=0, mean=MEAN, std=STD)
    b = next(it)
    assert (np.asarray(b["image"], np.float32) == 0).all()
    # Every item is corrupt, so the counter reads the items decoded so far:
    # the batch handed out, plus whatever the workers decoded ahead of the
    # consumer, which the iterator bounds (`decode_ahead_batches` more
    # batches once one is consumed). An exact == 4 raced the ring, and a
    # bound of 3 batches forgot the consumed one's freed slot.
    errs = it.decode_errors()
    assert 4 <= errs <= 4 * (1 + it.decode_ahead_batches), errs
    it.close()


def test_imagefolder_native_toggle(tmp_path):
    import tensorflow as tf

    from distributed_vgg_f_tpu.config import DataConfig
    from distributed_vgg_f_tpu.data import build_dataset

    rng = np.random.default_rng(1)
    for cls in ("n01", "n02"):
        d = tmp_path / "train" / cls
        d.mkdir(parents=True)
        for i in range(3):
            img = rng.integers(0, 256, size=(48, 56, 3)).astype(np.uint8)
            with open(d / f"{cls}_{i}.JPEG", "wb") as f:
                f.write(tf.io.encode_jpeg(img).numpy())

    cfg = DataConfig(name="imagenet", data_dir=str(tmp_path), image_size=32,
                     global_batch_size=4, shuffle_buffer=8)
    ds = build_dataset(cfg, "train", seed=0)
    assert isinstance(ds, NativeJpegTrainIterator)
    b = next(ds)
    assert b["image"].shape == (4, 32, 32, 3)
    assert set(b["label"].tolist()) <= {0, 1}
    ds.close()

    import dataclasses
    cfg_tf = dataclasses.replace(cfg, native_jpeg=False)
    ds_tf = build_dataset(cfg_tf, "train", seed=0)
    assert not isinstance(ds_tf, NativeJpegTrainIterator)
    b = next(ds_tf)
    assert b["image"].shape == (4, 32, 32, 3)

# ---------------------------------------------------------------------------
# r7 scale-selection logic (ISSUE 3): the pure-Python mirror
# (expected_scale_denom) must agree with the native ABI's reported choice
# across source sizes and crop modes, the chooser must only pick libjpeg-
# turbo's SIMD IDCT scales, and it must never upscale.
# ---------------------------------------------------------------------------

SOURCE_SIZES = (224, 256, 320, 448, 512, 1024)


def _eval_crop_side(w, h, out_size):
    """Mirror of the native eval center-crop geometry (jpeg_loader.cc):
    side = min(W, H) * out / 256, clamped to the image."""
    side = max(1, round(min(w, h) * out_size / 256.0))
    return min(side, min(w, h))


def test_scale_chooser_mirror_matches_native_abi():
    """dvgg_jpeg_choose_scale == expected_scale_denom across the announced
    source-size grid x train/eval crop modes. Train crops are represented
    by their extremes and a sweep of interior sizes (the chooser only sees
    the crop geometry, not the RNG that produced it)."""
    from distributed_vgg_f_tpu.data.native_jpeg import (
        choose_scale, expected_scale_denom)

    for src in SOURCE_SIZES:
        for out_size in (224, 96):
            # eval mode: the deterministic center crop
            side = _eval_crop_side(src, src, out_size)
            assert choose_scale(side, side, out_size) == \
                expected_scale_denom(side, side, out_size), (src, out_size)
            # train mode: area in [0.08, 1.0] -> linear crop in
            # [~0.28, 1.0] x src, aspect in [3/4, 4/3]; sweep the span
            for frac_num in range(28, 101, 6):
                cw = max(1, src * frac_num // 100)
                for ch in (cw, max(1, cw * 3 // 4), min(src, cw * 4 // 3)):
                    assert choose_scale(cw, ch, out_size) == \
                        expected_scale_denom(cw, ch, out_size), \
                        (src, out_size, cw, ch)


def test_scale_chooser_invariants():
    """Never-upscale: the chosen scale's output still covers out_size in
    both dims, or it is 8/8 (the crop itself is smaller than the target —
    the resample upscales true full-resolution pixels, never scale-decoded
    ones). And only power-of-two scales (libjpeg-turbo's SIMD IDCT sizes)
    are ever chosen — 5/8..7/8 run a slower plain-C IDCT and measured
    net-slower than full decode."""
    from distributed_vgg_f_tpu.data.native_jpeg import (
        SCALE_CANDIDATES, choose_scale)

    for src in SOURCE_SIZES:
        for out_size in (224, 96):
            for cw in range(out_size // 3, src + 1,
                            max(1, src // 17)):
                ch = min(src, max(1, cw * 4 // 3))
                m = choose_scale(cw, ch, out_size)
                assert m in SCALE_CANDIDATES, (cw, ch, out_size, m)
                covered = (cw * m) // 8 >= out_size and \
                          (ch * m) // 8 >= out_size
                assert covered or m == 8, (cw, ch, out_size, m)
                # minimality within the candidate set: no smaller
                # power-of-two scale would also have covered
                for smaller in [c for c in SCALE_CANDIDATES if c < m]:
                    assert not ((cw * smaller) // 8 >= out_size
                                and (ch * smaller) // 8 >= out_size), \
                        (cw, ch, out_size, m, smaller)


def test_chooser_matches_decoded_scale_histogram():
    """The chooser's prediction must match what the decoder actually DID:
    decode a 512px eval image (center crop 448 -> 4/8 scaled decode when
    the scaled path is on) and read the choice back from the decode-stats
    receipt, not from the chooser."""
    import io

    from PIL import Image

    from distributed_vgg_f_tpu.data.native_jpeg import (
        decode_single_image, decode_stats, expected_scale_denom, scaled_kind,
        set_scaled)

    if scaled_kind() != "scaled":
        pytest.skip("scaled decode disabled (kill-switch or -DDVGGF_"
                    "NO_SCALED build) — no scaled choice to observe")
    rng = np.random.default_rng(5)
    buf = io.BytesIO()
    Image.fromarray(rng.integers(0, 256, size=(512, 512, 3))
                    .astype(np.uint8)).save(buf, "JPEG", quality=90)
    side = _eval_crop_side(512, 512, 224)
    expect_m = expected_scale_denom(side, side, 224)
    assert expect_m == 4  # 448-crop to 224: exactly the half-scale decode
    before = set_scaled(True)
    try:
        decode_stats(reset=True)
        img = decode_single_image(buf.getvalue(), 224, MEAN, STD,
                                  eval_mode=True)
        assert img is not None
        stats = decode_stats()
        assert stats["scale_histogram"] == {expect_m: 1}, stats
        assert stats["images"] == 1
    finally:
        set_scaled(before == "scaled")
