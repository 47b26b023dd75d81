"""ImageNet-1k input pipeline — the reference's JPEG decode/crop/flip path
(BASELINE.json north_star: "ImageNet JPEG decode/crop/flip pipeline moves to
tf.data on the TPU VM host feeding device infeed"; SURVEY.md §2.1 #5).

Two on-disk layouts are supported, auto-detected from `data_dir`:

1. TFRecords in the standard `train-*-of-*` / `validation-*-of-*` layout
   (each record: encoded JPEG + integer label) — sharded per host by file.
2. Raw JPEG directory-per-class (`train/<wnid>/*.JPEG`) — sharded per host by
   a strided split of the (deterministically shuffled) file list; labels are
   the sorted class-directory index.

Both feed the same preprocessing:

  train: decode(+crop window straight from JPEG bytes) → random-resized-crop
         to `image_size` → random h-flip → mean/std normalize; shuffle, batch
  eval:  decode → resize short side 256 → center crop → normalize; repeated so
         uneven host shards cannot strand the eval collective

TensorFlow is imported lazily so the rest of the framework has no TF dependency.
"""

from __future__ import annotations

import os
from typing import Iterator

from distributed_vgg_f_tpu.config import DataConfig
from distributed_vgg_f_tpu.data.iter_snapshots import SnapshotResumableIterator

IMAGE_FEATURES = {
    "image/encoded": "jpeg bytes",
    "image/class/label": "int64 label (1-based in classic ImageNet TFRecords)",
}


class DataLayoutError(Exception):
    """The dataset itself is broken/misdescribed (e.g. labels below
    label_offset). Deliberately NOT a ValueError: backend fallback chains
    catch ValueError as "this backend is unavailable, try the next one", but
    a broken dataset must fail the run loudly on EVERY backend — falling
    back would silently train on corrupt labels."""


def _preprocess_fns(tf, cfg: DataConfig, seed: int = 0):
    """(train_fn, eval_fn). train_fn is (index, (encoded, label)) -> (image,
    label) with STATELESS augmentations keyed on (seed, stream index): the
    train stream is a pure function of (seed, position), which is what makes
    mid-stream iterator restore bit-identical (deterministic resume) — TF's
    stateful random ops would re-draw differently after a restart."""
    mean = tf.constant(cfg.mean_rgb, tf.float32)
    std = tf.constant(cfg.stddev_rgb, tf.float32)
    size = cfg.image_size
    # Flip ownership (r13): with the fused on-device augmentation stage
    # enabled and owning flips (data/augment.py, AugmentConfig.owns_hflip),
    # the host pipeline must never flip — exactly one side of the
    # host/device boundary holds the flag, so double-flip is structurally
    # impossible.
    host_flips = not cfg.augment.owns_hflip

    def train_preprocess(index, encoded_label):
        encoded, label = encoded_label
        aug_seed = tf.stack([tf.cast(seed, tf.int64), index])
        # random-resized crop straight from JPEG bytes: decode only the crop
        # window (decode_and_crop_jpeg) — large host-CPU saving on 1-vCPU hosts
        shape = tf.io.extract_jpeg_shape(encoded)
        bbox = tf.constant([0.0, 0.0, 1.0, 1.0], shape=[1, 1, 4])
        begin, crop_size, _ = tf.image.stateless_sample_distorted_bounding_box(
            shape, bbox, seed=aug_seed, area_range=(0.08, 1.0),
            aspect_ratio_range=(3 / 4, 4 / 3), max_attempts=10,
            use_image_if_no_bounding_boxes=True)
        offset_y, offset_x, _ = tf.unstack(begin)
        target_h, target_w, _ = tf.unstack(crop_size)
        img = tf.image.decode_and_crop_jpeg(
            encoded, tf.stack([offset_y, offset_x, target_h, target_w]),
            channels=3)
        img = tf.image.resize(img, (size, size), method="bilinear")
        if host_flips:
            img = tf.image.stateless_random_flip_left_right(
                img, seed=aug_seed + 1)
        img = (tf.cast(img, tf.float32) - mean) / std
        return img, label

    def eval_preprocess(encoded, label):
        img = tf.io.decode_jpeg(encoded, channels=3)
        shape = tf.shape(img)
        h, w = shape[0], shape[1]
        scale = 256.0 / tf.cast(tf.minimum(h, w), tf.float32)
        nh = tf.cast(tf.round(tf.cast(h, tf.float32) * scale), tf.int32)
        nw = tf.cast(tf.round(tf.cast(w, tf.float32) * scale), tf.int32)
        img = tf.image.resize(img, (nh, nw), method="bilinear")
        top = (nh - size) // 2
        left = (nw - size) // 2
        img = tf.image.crop_to_bounding_box(img, top, left, size, size)
        img = (tf.cast(img, tf.float32) - mean) / std
        return img, label

    return train_preprocess, eval_preprocess


class CheckpointableTfIterator(SnapshotResumableIterator):
    """Infinite train iterator over a tf.data pipeline with O(1) mid-stream
    restore (SURVEY.md §5: data-iterator state in the checkpoint).

    SYMBOLIC tf.data checkpoints (seeds + offsets, not buffer contents) are
    written to a rotating set of files under `snapshot_dir`; the snapshot
    cadence/rotation/restore protocol lives in data/iter_snapshots.py,
    shared with the grain backend. `restore_state(D)` replaces the
    O(decoded images) replay that deterministic ImageNet resume previously
    required.
    """

    def __init__(self, tf, ds, *, snapshot_dir: str = "",
                 snapshot_every: int = 0, keep: int = 4):
        super().__init__(snapshot_dir=snapshot_dir,
                         snapshot_every=snapshot_every, keep=keep)
        self._tf = tf
        self._it = iter(ds)
        self._ckpt = tf.train.Checkpoint(iterator=self._it)

    def __next__(self):
        img, label = next(self._it)
        self._after_draw()
        return {"image": img.numpy(), "label": label.numpy()}

    def _path(self, draws: int) -> str:
        return os.path.join(self._dir, f"iter_{draws:012d}")

    def _write_snapshot(self, draws: int) -> None:
        # Write under a tmp prefix, then rename: a SIGKILL mid-write must not
        # leave a final-named half-snapshot that a restart would trust. The
        # .index file is renamed LAST so its presence implies a complete set.
        tmp = os.path.join(self._dir, f"tmp_{draws:012d}")
        final = self._path(draws)
        self._ckpt.write(tmp)
        parts = [f for f in os.listdir(self._dir)
                 if f.startswith(f"tmp_{draws:012d}.")]
        for f in sorted(parts, key=lambda f: f.endswith(".index")):
            os.replace(os.path.join(self._dir, f),
                       final + f[len(f"tmp_{draws:012d}"):])

    def _snapshot_exists(self, draws: int) -> bool:
        return os.path.exists(self._path(draws) + ".index")

    def _read_snapshot(self, draws: int) -> None:
        self._ckpt.read(self._path(draws)).expect_partial()

    def _remove_snapshot(self, draws: int) -> None:
        for f in os.listdir(self._dir):
            if f.startswith(f"iter_{draws:012d}"):
                os.remove(os.path.join(self._dir, f))

    def _list_stamps(self) -> list[int]:
        return [int(f[len("iter_"):-len(".index")])
                for f in os.listdir(self._dir)
                if f.startswith("iter_") and f.endswith(".index")]


def _finalize(tf, ds, cfg: DataConfig, is_train: bool, local_batch: int,
              seed: int, state_dir: str = "",
              snapshot_every: int = 0) -> Iterator:
    """Shared pipeline tail: preprocess → batch → dtype → prefetch.

    Train: infinite shuffled iterator, deterministic per seed (seeded shuffle,
    stateless index-keyed augmentation), checkpointable via
    CheckpointableTfIterator. Eval: a FINITE re-iterable pass over this host's
    shard — the final partial batch is pad-and-masked (data/eval_pad.py) so
    every example is scored exactly once; hosts with uneven shards are kept in
    lockstep by Trainer.evaluate feeding all-invalid padding batches, not by
    `.repeat()` re-scoring."""
    _warn_wire_u8_unshipped(cfg, is_train, "tf.data")
    train_fn, eval_fn = _preprocess_fns(tf, cfg, seed)
    out_dtype = tf.dtypes.as_dtype(cfg.image_dtype)
    if is_train:
        ds = ds.shuffle(cfg.shuffle_buffer, seed=seed + 1)
        ds = ds.repeat()
        # enumerate AFTER repeat: the stream index keys the stateless
        # augmentations, so crops/flips differ across epochs yet are a pure
        # function of (seed, position) — bit-identical under resume.
        ds = ds.enumerate()
        ds = ds.map(train_fn, num_parallel_calls=tf.data.AUTOTUNE)
        ds = ds.batch(local_batch, drop_remainder=True)
        if cfg.host_space_to_depth:
            # tf.nn.space_to_depth's channel order (dy, dx, c) matches the
            # VGG-F stem's packed-input contract (models/vggf.py). With
            # device augmentation enabled the host never packs — the train
            # step's augmentation stage relayouts
            # (DataConfig.host_space_to_depth is the single source).
            ds = ds.map(lambda img, label:
                        (tf.nn.space_to_depth(img, 4), label),
                        num_parallel_calls=tf.data.AUTOTUNE)
        if cfg.image_dtype != "float32":
            ds = ds.map(lambda img, label: (tf.cast(img, out_dtype), label),
                        num_parallel_calls=tf.data.AUTOTUNE)
        ds = ds.prefetch(cfg.prefetch)
        # Symbolic checkpoints: iterator state = seeds + offsets, not the
        # shuffle buffer's contents, so snapshot files stay tiny.
        opts = tf.data.Options()
        opts.experimental_symbolic_checkpoint = True
        ds = ds.with_options(opts)
        return CheckpointableTfIterator(tf, ds, snapshot_dir=state_dir,
                                        snapshot_every=snapshot_every)

    from distributed_vgg_f_tpu.data.eval_pad import FiniteEvalIterable

    ds = ds.map(eval_fn, num_parallel_calls=tf.data.AUTOTUNE)
    ds = ds.batch(local_batch, drop_remainder=False)
    if cfg.image_dtype != "float32":
        ds = ds.map(lambda img, label: (tf.cast(img, out_dtype), label),
                    num_parallel_calls=tf.data.AUTOTUNE)
    ds = ds.prefetch(cfg.prefetch)

    def epoch():
        for img, label in ds.as_numpy_iterator():
            yield {"image": img, "label": label}

    import numpy as np
    np_dtype = (np.dtype("float32") if cfg.image_dtype == "float32"
                else out_dtype.as_numpy_dtype)
    return FiniteEvalIterable(epoch, local_batch,
                              (cfg.image_size, cfg.image_size, 3), np_dtype)


def _resolve_wire(cfg: DataConfig) -> DataConfig:
    """Fold `cfg.wire` host-dtype overrides into `image_dtype` so every
    downstream path (tf.data, grain, native) ships the requested
    host-normalize dtype without knowing about wires."""
    import dataclasses

    from distributed_vgg_f_tpu.data.dtypes import resolve_wire_dtype
    dtype = resolve_wire_dtype(cfg.wire, cfg.image_dtype)
    if dtype != cfg.image_dtype:
        cfg = dataclasses.replace(cfg, image_dtype=dtype)
    return cfg


def _wire_u8_active(cfg: DataConfig, is_train: bool) -> bool:
    """True iff this pipeline should ship the uint8 wire: requested
    (data.wire='u8'), a TRAIN stream (eval keeps the host path for parity),
    and the native library actually accepts the u8 kind right now (library
    loaded, compiled in, not kill-switched). A refused request falls back
    to the host-normalize wire with a logged warning — byte-identical to
    the pre-u8 behavior, never a silent format change."""
    if cfg.wire != "u8" or not is_train:
        return False
    from distributed_vgg_f_tpu.data.native_jpeg import wire_u8_enabled
    if wire_u8_enabled():
        return True
    import logging
    logging.getLogger(__name__).warning(
        "data.wire='u8' requested but the native uint8 wire is unavailable "
        "(library missing, -DDVGGF_NO_WIRE_U8 build, or DVGGF_WIRE_U8=0) — "
        "falling back to the host-normalize %s wire", cfg.image_dtype)
    return False


def _warn_wire_u8_unshipped(cfg: DataConfig, is_train: bool,
                            backend: str) -> None:
    """The uint8 wire is a native-TRAIN-loader capability; every other
    backend ships host-normalized batches. The start record labels the run
    with the REQUESTED wire, so the fallback must be in the log — a silent
    format change would misattribute the run's throughput/H2D numbers."""
    if cfg.wire == "u8" and is_train:
        import logging
        logging.getLogger(__name__).warning(
            "data.wire='u8' requested but the %s backend ships "
            "host-normalized %s batches — only the native train loader "
            "ships the uint8 wire", backend, cfg.image_dtype)


def build_imagenet(cfg: DataConfig, split: str, local_batch: int, *,
                   seed: int = 0, num_shards: int = 1, shard_index: int = 0,
                   label_offset: int | None = None, state_dir: str = "",
                   snapshot_every: int = 0) -> Iterator:
    import tensorflow as tf

    cfg = _resolve_wire(cfg)

    tf.config.set_visible_devices([], "GPU")
    tf.config.set_visible_devices([], "TPU")

    is_train = split == "train"
    pattern = os.path.join(
        cfg.data_dir, "train-*" if is_train else "validation-*")
    files = tf.io.gfile.glob(pattern)
    if not files:
        # Fall back to the raw-JPEG directory-per-class layout
        # (train/<wnid>/*.JPEG), the other common ImageNet distribution.
        return _build_imagenet_imagefolder(
            tf, cfg, split, local_batch, seed=seed, num_shards=num_shards,
            shard_index=shard_index, state_dir=state_dir,
            snapshot_every=snapshot_every)
    files.sort()
    if label_offset is None:
        # classic ImageNet TFRecords store labels 1..1000
        label_offset = 1
    host_files = files[shard_index::num_shards] if num_shards > 1 else files

    if cfg.backend == "grain":
        try:
            return _build_tfrecord_grain(
                cfg, host_files, split, local_batch, seed, label_offset,
                state_dir=state_dir, snapshot_every=snapshot_every)
        except (RuntimeError, OSError, ValueError, ImportError) as e:
            import logging
            logging.getLogger(__name__).warning(
                "grain backend unavailable (%s); falling back to auto", e)

    if _use_native(cfg, is_train):
        # Native path: index the shards once (JPEG byte ranges + labels,
        # native/tfrecord_index.cc), then decode straight out of the TFRecord
        # files with the ranged libjpeg loader — no TF in the hot loop.
        try:
            return _build_tfrecord_native(cfg, host_files, is_train,
                                          local_batch, seed, label_offset)
        except (RuntimeError, OSError, ValueError) as e:
            # observable fallback — see the imagefolder branch's rationale
            import logging
            logging.getLogger(__name__).warning(
                "native tfrecord loader unavailable (%s); using tf.data", e)

    def parse(serialized):
        feats = tf.io.parse_single_example(serialized, {
            "image/encoded": tf.io.FixedLenFeature([], tf.string),
            "image/class/label": tf.io.FixedLenFeature([], tf.int64),
        })
        label = tf.cast(feats["image/class/label"], tf.int32) - label_offset
        return feats["image/encoded"], label

    ds = tf.data.Dataset.from_tensor_slices(files)
    if num_shards > 1:
        ds = ds.shard(num_shards, shard_index)
    if is_train:
        ds = ds.shuffle(len(files), seed=seed)
    # deterministic=True even for train: the stream must be a pure function of
    # the seed for bit-identical deterministic resume (and symbolic iterator
    # checkpoints require a deterministic pipeline). The file-level shuffle
    # above still decorrelates the read order.
    ds = ds.interleave(
        tf.data.TFRecordDataset,
        cycle_length=min(16, max(1, len(files))),
        num_parallel_calls=tf.data.AUTOTUNE,
        deterministic=True)
    ds = ds.map(parse, num_parallel_calls=tf.data.AUTOTUNE)
    return _finalize(tf, ds, cfg, is_train, local_batch, seed,
                     state_dir=state_dir, snapshot_every=snapshot_every)


def _use_native(cfg: DataConfig, is_train: bool) -> bool:
    """Backend selection for the native loader ("grain" is tried before this
    and falls back into the auto rules)."""
    if cfg.backend == "native":
        return True
    if cfg.backend == "tfdata":
        return False
    return cfg.native_jpeg and (is_train or cfg.native_jpeg_eval)


def _tfrecord_items(cfg: DataConfig, files: list[str], label_offset: int):
    """(path_idx, offsets, lengths, labels) for TFRecord shards via the
    native indexer, with labels shifted into the 0-based space."""
    import numpy as np

    from distributed_vgg_f_tpu.data.native_tfrecord import index_tfrecords

    cache_dir = os.path.join(
        os.path.expanduser("~"), ".cache", "distributed_vgg_f_tpu")
    path_idx, offsets, lengths, labels64 = index_tfrecords(
        files, cache_dir=cache_dir)
    if len(labels64) == 0:
        raise ValueError("no records with image/encoded found")
    labels = (labels64 - label_offset).astype(np.int32)
    if (labels < 0).any():
        bad = int((labels < 0).sum())
        raise DataLayoutError(
            f"{bad} records have label < label_offset ({label_offset}) — "
            "records missing image/class/label, or wrong label_offset")
    return path_idx, offsets, lengths, labels


def _build_tfrecord_grain(cfg: DataConfig, files: list[str], split: str,
                          local_batch: int, seed: int, label_offset: int, *,
                          state_dir: str = "",
                          snapshot_every: int = 0) -> Iterator:
    from distributed_vgg_f_tpu.data.grain_imagenet import build_grain_imagenet

    _warn_wire_u8_unshipped(cfg, split == "train", "grain")
    path_idx, offsets, lengths, labels = _tfrecord_items(cfg, files,
                                                         label_offset)
    # files are already sharded per host (file-striding, like every other
    # path) — grain's own sharding stays disabled
    return build_grain_imagenet(
        cfg, split, local_batch, seed=seed, num_shards=1, shard_index=0,
        files=files, path_idx=path_idx, offsets=offsets, lengths=lengths,
        labels=labels, state_dir=state_dir, snapshot_every=snapshot_every)


def _build_tfrecord_native(cfg: DataConfig, files: list[str], is_train: bool,
                           local_batch: int, seed: int,
                           label_offset: int) -> Iterator:
    """TFRecord layout on the native loader: tfrecord_index.cc byte ranges →
    jpeg_loader.cc ranged decode. Train is the infinite deterministic stream
    (O(1) seek resume); eval is the exact finite center-crop pass."""
    import numpy as np

    from distributed_vgg_f_tpu.data.native_jpeg import (
        NativeJpegEvalIterator, NativeJpegTrainIterator)

    path_idx, offsets, lengths, labels = _tfrecord_items(cfg, files,
                                                         label_offset)
    u8 = _wire_u8_active(cfg, is_train)
    common = dict(
        batch=local_batch, image_size=cfg.image_size,
        mean=np.asarray(cfg.mean_rgb, np.float32),
        std=np.asarray(cfg.stddev_rgb, np.float32),
        image_dtype="uint8" if u8 else cfg.image_dtype,
        num_threads=cfg.native_threads or None,
        ranges=(path_idx, offsets, lengths))
    if is_train:
        # u8 wire: the host never packs — normalize/cast/space-to-depth
        # ride the device-finish prologue (data/device_ingest.py).
        # hflip=False (ABI v9) when the fused on-device augmentation owns
        # the flip (r13): the native decoder then never flips, same crops.
        it = NativeJpegTrainIterator(
            files, labels, seed=seed,
            space_to_depth=cfg.host_space_to_depth and not u8,
            hflip=not cfg.augment.owns_hflip, **common)
        # decoded-crop snapshot cache (r9): warm epochs skip libjpeg
        from distributed_vgg_f_tpu.data.snapshot_cache import (
            wrap_train_iterator)
        return wrap_train_iterator(it, cfg, seed=seed, files=files,
                                   labels=labels,
                                   ranges=(path_idx, offsets, lengths))
    return NativeJpegEvalIterator(files, labels, **common)


def _class_index(cfg: DataConfig) -> list[str] | None:
    """Sorted wnid list from the train split's class directories — the label
    space every layout maps into (label = sorted-wnid index)."""
    d = os.path.join(cfg.data_dir, "train")
    if os.path.isdir(d):
        classes = sorted(x for x in os.listdir(d)
                         if os.path.isdir(os.path.join(d, x)))
        if classes:
            return classes
    return None


_LABEL_MAP_NAMES = ("val_labels.txt", "validation_labels.txt",
                    "ILSVRC2012_validation_ground_truth.txt")


def _flat_val_listing(cfg: DataConfig, split_dir: str):
    """(files, labels) for the common real-ImageNet FLAT validation layout:
    `val/ILSVRC2012_val_*.JPEG` directly in the split dir plus a label mapping
    file. Accepted mapping formats (auto-detected per line):

    - two columns ``<filename> <wnid>``: wnid resolved to the sorted-wnid index
      of the train split's class directories (or of the wnids in the file when
      no train split is present);
    - two columns ``<filename> <int>``: the integer IS the class index in this
      framework's sorted-wnid label space (0-based);
    - one column ``<int>`` per line (ILSVRC2012 ground-truth style): line i
      labels the i-th file in sorted filename order. NOTE: the devkit's
      1-based ints are in the devkit's own class order, NOT sorted-wnid order —
      only use this format if your ints are already 0-based sorted-wnid
      indices; prefer the unambiguous ``filename wnid`` form.
    """
    # the label mapping may itself live inside the split dir — never count it
    # (or any .txt sidecar) as a validation image
    skip = set(_LABEL_MAP_NAMES)
    if cfg.val_labels_file:
        skip.add(os.path.basename(cfg.val_labels_file))
    entries = sorted(f for f in os.listdir(split_dir)
                     if os.path.isfile(os.path.join(split_dir, f))
                     and not f.startswith(".")
                     and not f.endswith(".txt") and f not in skip)
    if not entries:
        raise FileNotFoundError(f"no validation images under {split_dir!r}")
    candidates = ([cfg.val_labels_file] if cfg.val_labels_file else [
        os.path.join(d, n)
        for d in (split_dir, cfg.data_dir)
        for n in _LABEL_MAP_NAMES])
    map_path = next((p for p in candidates if p and os.path.isfile(p)), None)
    if map_path is None:
        raise FileNotFoundError(
            f"flat validation layout at {split_dir!r} needs a label mapping "
            "file (val_labels.txt with '<filename> <wnid>' lines, or set "
            "data.val_labels_file); none found")
    with open(map_path) as f:
        lines = [ln.split() for ln in f.read().splitlines() if ln.strip()]
    if all(len(ln) == 1 for ln in lines):
        # ordered ground-truth ints, one per sorted filename
        if len(lines) != len(entries):
            raise ValueError(
                f"{map_path!r} has {len(lines)} labels for {len(entries)} "
                f"validation files")
        by_name = {name: ln[0] for name, ln in zip(entries, lines)}
    else:
        by_name = {ln[0]: ln[1] for ln in lines}
    missing = [e for e in entries if e not in by_name]
    if missing:
        raise ValueError(
            f"{map_path!r} is missing labels for {len(missing)} files "
            f"(first: {missing[0]!r})")
    values = [by_name[e] for e in entries]
    if all(v.lstrip("-").isdigit() for v in values):
        labels = [int(v) for v in values]
    else:
        classes = _class_index(cfg) or sorted(set(values))
        index = {wnid: i for i, wnid in enumerate(classes)}
        unknown = next((v for v in values if v not in index), None)
        if unknown is not None:
            raise ValueError(
                f"wnid {unknown!r} from {map_path!r} not among the "
                f"{len(index)} train class directories")
        labels = [index[v] for v in values]
    return [os.path.join(split_dir, e) for e in entries], labels


def _imagefolder_listing(cfg: DataConfig, split: str, *, seed: int,
                         num_shards: int, shard_index: int):
    """(files, labels) numpy arrays for the imagefolder layout, after the
    deterministic global shuffle and strided per-host split. The SINGLE
    listing implementation — `_build_imagenet_imagefolder` and the
    disaggregated-ingest worker (`native_train_items`) both call it, so
    the decode-worker fleet can never drift from the trainer's item set."""
    import numpy as np

    is_train = split == "train"
    split_dir = None
    for name in (("train",) if is_train else ("validation", "val")):
        d = os.path.join(cfg.data_dir, name)
        if os.path.isdir(d):
            split_dir = d
            break
    if split_dir is None:
        raise FileNotFoundError(
            f"no ImageNet data under {cfg.data_dir!r}: neither TFRecords "
            "(train-*-of-*) nor a train/validation/val directory found")
    classes = sorted(d for d in os.listdir(split_dir)
                     if os.path.isdir(os.path.join(split_dir, d)))
    if classes:
        files, labels = [], []
        for idx, cls in enumerate(classes):
            for fname in sorted(os.listdir(os.path.join(split_dir, cls))):
                files.append(os.path.join(split_dir, cls, fname))
                labels.append(idx)
    elif not is_train:
        # Flat real-ImageNet validation layout: val/*.JPEG + label mapping.
        files, labels = _flat_val_listing(cfg, split_dir)
    else:
        raise FileNotFoundError(f"no class directories under {split_dir!r}")
    # deterministic global shuffle, then strided per-host split so every host
    # sees a class-balanced 1/num_shards slice; slice the index array BEFORE
    # materializing paths so each host only holds its own shard (the global
    # padded-unicode path array would be ~0.5GB at ImageNet scale). Example
    # order within the shard is then _finalize's shuffle_buffer.
    order = np.random.default_rng(seed).permutation(len(files))
    if num_shards > 1:
        order = order[shard_index::num_shards]
    return (np.asarray([files[i] for i in order]),
            np.asarray(labels, np.int32)[order])


def native_train_items(cfg: DataConfig, *, seed: int = 0,
                       num_shards: int = 1, shard_index: int = 0):
    """(files, labels, ranges | None): the exact TRAIN item set the native
    builders construct their iterator over — TFRecord byte ranges when the
    `train-*` shards exist (classic 1-based labels, the build_imagenet
    default), the imagefolder listing otherwise. This is what makes the
    disaggregated-ingest worker's position-keyed reconstruction
    (data/ingest_service.py) byte-identical to the trainer's local stream:
    both sides index the SAME items in the SAME order."""
    pattern = os.path.join(cfg.data_dir, "train-*")
    if "://" in (cfg.data_dir or ""):
        import tensorflow as tf  # remote filesystems (gs://, ...) only
        files = tf.io.gfile.glob(pattern)
    else:
        # local paths glob without TF — decode workers start in ~a second
        import glob as _glob
        files = _glob.glob(pattern)
    if files:
        files.sort()
        host_files = files[shard_index::num_shards] if num_shards > 1 \
            else files
        path_idx, offsets, lengths, labels = _tfrecord_items(
            cfg, host_files, 1)
        return (host_files, [int(l) for l in labels],
                (path_idx, offsets, lengths))
    files, labels = _imagefolder_listing(
        cfg, "train", seed=seed, num_shards=num_shards,
        shard_index=shard_index)
    return [str(f) for f in files], [int(l) for l in labels], None


def _build_imagenet_imagefolder(tf, cfg: DataConfig, split: str,
                                local_batch: int, *, seed: int,
                                num_shards: int, shard_index: int,
                                state_dir: str = "",
                                snapshot_every: int = 0) -> Iterator:
    import numpy as np

    is_train = split == "train"
    files, labels = _imagefolder_listing(
        cfg, split, seed=seed, num_shards=num_shards,
        shard_index=shard_index)

    if cfg.backend == "grain":
        try:
            from distributed_vgg_f_tpu.data.grain_imagenet import (
                build_grain_imagenet)
            from distributed_vgg_f_tpu.data.native_jpeg import (
                _whole_file_ranges)
            _warn_wire_u8_unshipped(cfg, is_train, "grain")
            path_idx, offsets, lengths = _whole_file_ranges(len(files))
            return build_grain_imagenet(
                cfg, split, local_batch, seed=seed, num_shards=1,
                shard_index=0, files=[str(f) for f in files],
                path_idx=path_idx, offsets=offsets, lengths=lengths,
                labels=labels, state_dir=state_dir,
                snapshot_every=snapshot_every)
        except (RuntimeError, OSError, ValueError, ImportError) as e:
            import logging
            logging.getLogger(__name__).warning(
                "grain backend unavailable (%s); falling back to auto", e)

    if _use_native(cfg, is_train):
        # Native libjpeg path (native/jpeg_loader.cc): DCT-scaled partial
        # decode in C++ worker threads — measured ~1.3–1.6x tf.data per host
        # core (benchmarks/host_pipeline_bench.py; frozen per-core baseline
        # in benchmarks/baseline.json). Train is deterministic per seed with
        # O(1) exact seek
        # (restore_state), so it also satisfies the deterministic-resume
        # protocol without snapshot files; eval is the exact finite
        # center-crop pass. Falls back to tf.data below if the build fails.
        try:
            from distributed_vgg_f_tpu.data.native_jpeg import (
                NativeJpegEvalIterator, NativeJpegTrainIterator)
            u8 = _wire_u8_active(cfg, is_train)
            common = dict(
                batch=local_batch, image_size=cfg.image_size,
                mean=np.asarray(cfg.mean_rgb, np.float32),
                std=np.asarray(cfg.stddev_rgb, np.float32),
                image_dtype="uint8" if u8 else cfg.image_dtype,
                num_threads=cfg.native_threads or None)
            fl = [str(f) for f in files]
            lb = [int(l) for l in labels]
            if is_train:
                # u8 wire: space-to-depth moves to the device finish;
                # hflip=False when device-side augmentation owns flips (r13)
                it = NativeJpegTrainIterator(
                    fl, lb, seed=seed,
                    space_to_depth=cfg.host_space_to_depth and not u8,
                    hflip=not cfg.augment.owns_hflip, **common)
                # decoded-crop snapshot cache (r9): warm epochs skip libjpeg
                from distributed_vgg_f_tpu.data.snapshot_cache import (
                    wrap_train_iterator)
                return wrap_train_iterator(it, cfg, seed=seed, files=fl,
                                           labels=lb)
            return NativeJpegEvalIterator(fl, lb, **common)
        except (RuntimeError, OSError, ValueError) as e:
            # the switch must be observable: the tf.data stream draws
            # different (same-distribution) augmentations and resumes via
            # snapshots instead of seek — a silent swap would be confusing,
            # and in multi-host runs a single host falling back deserves a
            # visible signal.
            import logging
            logging.getLogger(__name__).warning(
                "native jpeg loader unavailable (%s); using tf.data", e)
    ds = tf.data.Dataset.from_tensor_slices((files, labels))
    ds = ds.map(lambda path, label: (tf.io.read_file(path), label),
                num_parallel_calls=tf.data.AUTOTUNE)
    return _finalize(tf, ds, cfg, is_train, local_batch, seed,
                     state_dir=state_dir, snapshot_every=snapshot_every)
