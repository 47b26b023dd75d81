"""Device-finish prologue for the uint8 ingest wire (r8).

The host input path historically finished every batch on the CPU —
``(pixel - mean) / std`` in f32, optional bf16 round, optional 4x4
space-to-depth — and shipped 2-4 bytes/pixel into ``device_put``. The u8
wire (native/jpeg_loader.cc out_kind=2, ``data.wire='u8'``) ships the raw
resampled uint8 pixels instead (1 byte/pixel, a 4x wire/ring reduction vs
f32) and performs that elementwise finishing math HERE, on the
accelerator: ``make_device_finish`` returns a pure function the jitted
train/eval steps apply to the batch's images INSIDE the ``shard_map`` body
(train/step.py) — the tf.data-paper move (PAPERS.md arxiv 2101.12127) of
pushing elementwise work off the host. It is not free on the device: at
batch 1024 the float32 prologue was a quarter of the VGG-F step (PERF.md
§5, PR 26), which is why everything that only moves pixels runs on the
1-byte batch BEFORE this arithmetic (data/augment.py's ordering contract).

Single-normalization contract: the finish dispatches on DTYPE — uint8
batches are normalized exactly once; float batches (the host-normalize
wires ``host_f32``/``host_bf16``, every non-native backend, and all eval
parity paths) pass through UNTOUCHED. Feeding the finish its own output is
therefore a no-op, which is what makes it safe to install unconditionally
in train, eval, and predict (the double-normalize hazard is structurally
impossible; tests/test_wire_u8.py pins it with a sentinel batch).

Numerics: the host path computes ``(v - mean) * (1/std)`` in f32 (with a
reciprocal multiply — jpeg_loader.cc inv_std); the finish performs the
SAME single-rounded f32 ops, so for identical u8 pixels the two wires
produce bit-identical normalized values (the CPU loss-trajectory
equivalence gate). The u8 pixels themselves differ from the float-path
bilinear by at most one intensity level (the fixed-point kernels' pinned
quantization bound).
"""

from __future__ import annotations

from typing import Callable, Sequence

import jax.numpy as jnp


def space_to_depth_batch(x: jnp.ndarray, block: int = 4) -> jnp.ndarray:
    """(B, H, W, C) -> (B, H/b, W/b, b*b*C) in tf.nn.space_to_depth's
    (dy, dx, c) channel order — the same layout the native host packer and
    the VGG-F stem contract use (models/vggf.py Conv1SpaceToDepth)."""
    b, h, w, c = x.shape
    x = x.reshape(b, h // block, block, w // block, block, c)
    return x.transpose(0, 1, 3, 2, 4, 5).reshape(
        b, h // block, w // block, block * block * c)


def make_device_finish(mean_rgb: Sequence[float], stddev_rgb: Sequence[float],
                       *, image_dtype: str = "float32",
                       space_to_depth: bool = False) -> Callable:
    """Build the jit-safe finish fn: uint8 batches get normalize → cast →
    (optional) space-to-depth; anything else passes through untouched.

    `image_dtype` is the dtype the equivalent HOST wire would have shipped
    ('float32' | 'bfloat16') — the model's own compute-dtype cast happens
    downstream either way. `space_to_depth` packs 4x4 blocks when the
    batch arrives unpacked with a %4 spatial size (the u8 wire never packs
    on the host); eval/predict callers leave it False, matching the
    host-path convention that eval batches stay (S, S, 3).

    Ordering under the fused augmentation stage (data/augment.py): with
    `data.augment.enabled` the train step does not call a finish of its
    own. The augment stage owns one (built without `space_to_depth`) and
    calls it AFTER its pixel permutations — flip, mixup partner, 4x4 pack —
    which run on the batch as it arrived, so the finish sees an already
    packed (B, S/4, S/4, 48) uint8 batch there and tiles its per-channel
    constants over the packed (dy, dx, c) order: the same float32 ops per
    element as on (B, S, S, 3). The host skips packing by the same
    predicate (DataConfig.host_space_to_depth), so the pack happens exactly
    once in every configuration.
    """
    mean = jnp.asarray(mean_rgb, jnp.float32)
    # reciprocal-multiply, NOT divide: mirrors the native kernels'
    # `inv_std` so host-normalize and device-finish are the same
    # single-rounded f32 ops for identical u8 inputs
    inv_std = (jnp.float32(1.0)
               / jnp.asarray(stddev_rgb, jnp.float32))
    out_dtype = jnp.bfloat16 if image_dtype == "bfloat16" else jnp.float32

    def finish(images: jnp.ndarray) -> jnp.ndarray:
        if images.dtype != jnp.uint8:
            return images  # host-normalized already — never touch twice
        # a packed batch carries block*block pixels of 3 channels in its
        # last axis, c fastest: the constants repeat once a pixel
        reps = images.shape[-1] // mean.shape[0]
        x = (images.astype(jnp.float32) - jnp.tile(mean, reps)) \
            * jnp.tile(inv_std, reps)
        if out_dtype != jnp.float32:
            x = x.astype(out_dtype)
        if space_to_depth and x.ndim == 4 and x.shape[-1] == 3 \
                and x.shape[1] % 4 == 0 and x.shape[2] % 4 == 0:
            x = space_to_depth_batch(x)
        return x

    return finish
