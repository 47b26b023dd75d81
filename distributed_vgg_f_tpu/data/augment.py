"""On-device augmentation stage (r13), the train step's whole prologue.

The host ships raw u8 pixels (data/device_ingest.py, r8) and every flip,
translation (crop) jitter, mixup/cutmix pairing and RandAugment-lite op is
drawn and applied on the device, as a PURE function of (train PRNG, batch)
INSIDE the `shard_map` step body (train/step.py), so:

- the host wire stays raw u8 (bytes/image unchanged, receipted) and every
  host-side flip is deleted — the large-distributed-CNN study's
  host-offload argument (arXiv 1711.00705) applied to augmentation;
- every augmentation decision is reproducible from (seed, step, replica):
  the step folds the train PRNG as `fold_in(fold_in(base_rng, step),
  axis_index)` and this stage folds ONE more constant off that key, so the
  dropout stream is untouched and a checkpoint-resumed step re-draws the
  exact augmentations (mixup pairings included) the uninterrupted run
  would have — pinned by test;
- eval/predict are structurally untouched: only `build_train_step` takes a
  `device_augment`; the eval step's jaxpr is bit-identical augment-on vs
  off (sentinel test).

Ordering contract: permute → finish → arithmetic. Everything that only
MOVES pixels runs first, on the batch in the dtype it arrived in (1 byte a
pixel on the u8 wire): the 4x4 space-to-depth pack (for a model whose
ingest descriptor packs), the flip (of the packed batch: W/4 and the dx of
the channels reversed), the mixup/cutmix partner `x[perm]`. Then the
stage's own device finish normalizes the batch and its partner once
(uint8 only; a host wire's floats pass through), and the arithmetic
(mixup's `x*lam + partner*(1-lam)`, cutmix's select) runs in float32 on
the layout the stem consumes. A permutation of pixels commutes with
per-pixel arithmetic, so each output element sees the same float32 ops in
the same order as normalize → flip → mix → pack, the order this stage had
until PR 27, gives it: the results are equal bit for bit
(tests/test_augment.py holds the stage to that, the old order written
out). Two optional stages address pixels by (y, x) and hold the pack back:
the crop jitter (flip → jitter → pack → partner, still on the wire dtype)
and `rand_ops`, arithmetic with a per-image mean over H and W (flip →
jitter → finish → rand_ops → pack → partner, the last two on floats).
`augment.permute_on_wire_dtype` says whether a built stage took its
partner and packed ahead of the finish; the trainer reports it as a gauge.
Normalize-first on float32 cost a quarter of the VGG-F step at batch 1024
(PERF.md §5, PR 26). With augmentation enabled the host never packs either
(`DataConfig.host_space_to_depth`): this stage performs the relayout for
BOTH wires.

Wire parity: the u8 and host wires produce bit-identical normalized values
for identical pixels (the r8 contract), the permutations are dtype-blind,
and identical inputs through identical jitted ops give identical outputs —
so the per-model CPU loss-trajectory equality gates (u8 ≡ host) hold with
augmentation on.

Flip ownership: `AugmentConfig.owns_hflip` is the single predicate. When
this stage owns the flip, the native decoder (ABI v9 per-loader switch),
tf.data, grain, cifar10, and the snapshot cache's warm-path redraw are ALL
disabled by it — exactly one side of the host/device boundary ever holds
the flip flag, so double-flip is structurally impossible (grid-pinned in
tests/test_augment.py).
"""

from __future__ import annotations

from typing import Callable, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from distributed_vgg_f_tpu.data.device_ingest import (
    make_device_finish,
    space_to_depth_batch,
)

#: fold_in constant deriving the augment key off the step's per-replica
#: train key — distinct from dropout (which uses the key directly) and from
#: the grad-accum micro-batch folds (small non-negative ints).
AUGMENT_RNG_FOLD = 0xA06

#: RandAugment-lite op table (op 0 = identity). Elementwise but for the
#: contrast pivot, a per-image mean over H and W.
RAND_OPS = ("identity", "brightness", "contrast", "posterize")

#: Maximum brightness shift at magnitude 1.0, in 0..255 intensity levels.
_BRIGHTNESS_MAX_LEVELS = 64.0
#: Maximum contrast factor deviation at magnitude 1.0 (factor in 1 ± this).
_CONTRAST_MAX_DELTA = 0.8
#: Maximum posterize coarsening at magnitude 1.0: quantization step 2^k,
#: k in [0, 3] — keeps >= 5 effective bits, the RandAugment-paper range.
_POSTERIZE_MAX_SHIFT = 3.0


def _hflip(key: jax.Array, x: jnp.ndarray) -> jnp.ndarray:
    """Per-image 50% horizontal flip: reverse W and select per image. On a
    4x4-packed (B, H/4, W/4, 48) batch W is the W/4 axis times the dx of
    the (dy, dx, c) channels, and both are reversed."""
    bits = jax.random.bernoulli(key, 0.5, (x.shape[0],))
    flipped = x[:, :, ::-1, :]
    if x.shape[-1] != 3:
        b, h, w, _ = x.shape
        flipped = flipped.reshape(b, h, w, 4, 4, 3)[:, :, :, :, ::-1, :] \
            .reshape(x.shape)
    return jnp.where(bits[:, None, None, None], flipped, x)


def _rows(x: jnp.ndarray, perm: jnp.ndarray) -> jnp.ndarray:
    """`x[perm]`, the mixup partner. The TPU keeps an image batch with the
    batch axis innermost (in the lanes) and the rest as (H, C, W); taking
    rows through a view in that order costs it one transposing copy each
    way around its row gather, where the plain form of a packed batch
    became a 1024-trip loop on rows padded 48 to 128 (PERF.md §6, PR 27)."""
    b, h, w, c = x.shape
    rows = x.transpose(0, 1, 3, 2).reshape(b, h, c * w)[perm]
    return rows.reshape(b, h, c, w).transpose(0, 1, 3, 2)


def _crop_jitter(key: jax.Array, x: jnp.ndarray, max_px: int) -> jnp.ndarray:
    """Per-image translation by (dy, dx) ∈ [-max_px, max_px]^2 with edge
    replication (clipped gather indices) — the cheap device-side stand-in
    for re-sampling the crop window, which only the host decoder could do."""
    b, h, w, _ = x.shape
    ky, kx = jax.random.split(key)
    dy = jax.random.randint(ky, (b,), -max_px, max_px + 1)
    dx = jax.random.randint(kx, (b,), -max_px, max_px + 1)
    rows = jnp.clip(jnp.arange(h)[None, :] + dy[:, None], 0, h - 1)
    x = jnp.take_along_axis(x, rows[:, :, None, None], axis=1)
    cols = jnp.clip(jnp.arange(w)[None, :] + dx[:, None], 0, w - 1)
    return jnp.take_along_axis(x, cols[:, None, :, None], axis=2)


def _rand_ops(key: jax.Array, x: jnp.ndarray, mean: jnp.ndarray,
              inv_std: jnp.ndarray, n_ops: int,
              magnitude: float) -> jnp.ndarray:
    """RandAugment-lite: `n_ops` independent draws per image from RAND_OPS,
    each at a per-image random strength up to `magnitude`. All elementwise
    (every candidate is computed and the per-image draw selects — 3 extra
    elementwise passes beat a data-dependent branch inside shard_map).
    Works on the 0..255 pixel scale — de-normalize, op, clip, re-normalize
    with the SAME single-rounded constants the finish used."""
    std = 1.0 / inv_std
    for i in range(n_ops):
        k_op, k_mag, key = jax.random.split(jax.random.fold_in(key, i), 3)
        b = x.shape[0]
        op = jax.random.randint(k_op, (b,), 0, len(RAND_OPS))
        u = jax.random.uniform(k_mag, (b,), minval=-1.0, maxval=1.0)
        sel = lambda k: (op == k)[:, None, None, None]
        p = x * std + mean  # back to the 0..255 pixel scale
        # brightness: additive shift, up to ±64 levels at magnitude 1
        bright = p + (u * magnitude * _BRIGHTNESS_MAX_LEVELS)[
            :, None, None, None]
        # contrast: scale around the per-image per-channel mean
        pivot = jnp.mean(p, axis=(1, 2), keepdims=True)
        factor = (1.0 + u * magnitude * _CONTRAST_MAX_DELTA)[
            :, None, None, None]
        contrast = (p - pivot) * factor + pivot
        # posterize: quantize to a 2^k-level grid, k in [0, 3] (|u| — the
        # op has no meaningful sign)
        step = jnp.exp2(jnp.round(
            jnp.abs(u) * magnitude * _POSTERIZE_MAX_SHIFT))[
            :, None, None, None]
        poster = jnp.floor(p / step) * step
        p = jnp.where(sel(1), bright,
                      jnp.where(sel(2), contrast,
                                jnp.where(sel(3), poster, p)))
        p = jnp.clip(p, 0.0, 255.0)
        x = (p - mean) * inv_std
    return x


def _blend(keys, x: jnp.ndarray, partner: jnp.ndarray,
           size: Tuple[int, int], mixup_alpha: float, cutmix_alpha: float,
           layout: Callable) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Mixup (arXiv 1710.09412) / cutmix (arXiv 1905.04899) of a float batch
    with its partner `x[perm]` over the LOCAL shard: one Beta-drawn lam per
    step (the standard batchwise formulation). `size` is the unpacked
    (H, W) the cutmix box is drawn in and `layout` brings a (1, H, W, 3)
    array to the layout of `x` (the pack, or nothing). Returns (x, lam);
    the loss mixes as lam*CE(y) + (1-lam)*CE(y[perm])."""
    k_lam, k_box, k_choice = keys
    h, w = size

    def do_mixup(lam0):
        lam = lam0.astype(x.dtype)
        return lam0, x * lam + partner * (1.0 - lam)

    def do_cutmix(lam0):
        # box with area fraction (1 - lam0), centered uniformly; lam is
        # re-derived from the CLIPPED box so the label mix matches the
        # pixels actually pasted
        ratio = jnp.sqrt(1.0 - lam0)
        bh = jnp.round(ratio * h).astype(jnp.int32)
        bw = jnp.round(ratio * w).astype(jnp.int32)
        cy = jax.random.randint(k_box, (), 0, h)
        cx = jax.random.randint(jax.random.fold_in(k_box, 1), (), 0, w)
        y0 = jnp.clip(cy - bh // 2, 0, h)
        y1 = jnp.clip(cy + (bh + 1) // 2, 0, h)
        x0 = jnp.clip(cx - bw // 2, 0, w)
        x1 = jnp.clip(cx + (bw + 1) // 2, 0, w)
        in_rows = (jnp.arange(h) >= y0) & (jnp.arange(h) < y1)
        in_cols = (jnp.arange(w) >= x0) & (jnp.arange(w) < x1)
        mask = layout(jnp.broadcast_to(
            (in_rows[:, None] & in_cols[None, :])[None, :, :, None],
            (1, h, w, 3)))
        lam = 1.0 - ((y1 - y0) * (x1 - x0)).astype(jnp.float32) / (h * w)
        return lam, jnp.where(mask, partner, x)

    if mixup_alpha > 0 and cutmix_alpha > 0:
        lam_mix = jax.random.beta(k_lam, mixup_alpha, mixup_alpha)
        lam_cut = jax.random.beta(jax.random.fold_in(k_lam, 1),
                                  cutmix_alpha, cutmix_alpha)
        use_cut = jax.random.bernoulli(k_choice, 0.5)
        lam, x = jax.lax.cond(use_cut, do_cutmix, do_mixup,
                              jnp.where(use_cut, lam_cut, lam_mix))
    elif cutmix_alpha > 0:
        lam, x = do_cutmix(
            jax.random.beta(k_lam, cutmix_alpha, cutmix_alpha))
    else:
        lam, x = do_mixup(jax.random.beta(k_lam, mixup_alpha, mixup_alpha))
    return x, lam.astype(jnp.float32)


def make_device_augment(aug_cfg, mean_rgb: Sequence[float],
                        stddev_rgb: Sequence[float], *,
                        image_dtype: str = "float32",
                        space_to_depth: bool = False) -> Optional[Callable]:
    """Build the train step's prologue, or None when `aug_cfg.enabled` is
    false — the kill-switch contract is STRUCTURAL absence: a disabled
    stage contributes zero jaxpr equations and the step calls its plain
    device finish instead (pinned by test).

    The returned `augment(rng, images, labels) -> (images, mix_labels,
    mix_lam)` takes the batch AS IT ARRIVED, unpacked (B, S, S, 3): uint8
    from the u8 wire, which it finishes itself (`image_dtype` as for
    `make_device_finish`), or a host wire's normalized floats. The order of
    its stages is the module docstring's contract; `mix_labels`/`mix_lam`
    are None unless mixup/cutmix is configured, and the step's loss then
    mixes integer-label CE terms. With `space_to_depth` the output is the
    4x4-packed (B, S/4, S/4, 48) the stem consumes. The attribute
    `permute_on_wire_dtype` of the returned function says whether the
    partner gather and the pack run ahead of the finish."""
    if aug_cfg is None or not aug_cfg.enabled:
        return None
    mean = jnp.asarray(mean_rgb, jnp.float32)
    inv_std = jnp.float32(1.0) / jnp.asarray(stddev_rgb, jnp.float32)
    finish = make_device_finish(mean_rgb, stddev_rgb, image_dtype=image_dtype)
    hflip = bool(aug_cfg.hflip)
    jitter = int(aug_cfg.crop_jitter)
    mixup_alpha = float(aug_cfg.mixup_alpha)
    cutmix_alpha = float(aug_cfg.cutmix_alpha)
    mixing = mixup_alpha > 0 or cutmix_alpha > 0
    rand_ops = int(aug_cfg.rand_ops)
    magnitude = float(aug_cfg.rand_magnitude)
    # `_rand_ops` averages float rows over H and W, so the gather and the
    # pack follow it, on floats; the jitter addresses pixels by (y, x), so
    # the pack follows it too. With neither (the shipped recipe) the batch
    # is packed first and flipped packed: reversing W/4 = 56 rows of a
    # packed u8 batch costs the chip 1.4 ms at batch 1024, reversing
    # W = 224 2.6 ms (PERF.md §6, PR 27).
    permute_first = rand_ops == 0
    pack_first = permute_first and jitter == 0

    def augment(rng: jax.Array, images: jnp.ndarray, labels: jnp.ndarray):
        if images.ndim != 4 or images.shape[-1] != 3:
            raise ValueError(
                f"device augmentation expects the unpacked (B, S, S, 3) "
                f"batch as it arrived, got {images.shape} — when "
                f"data.augment.enabled the host must not pack "
                f"(DataConfig.host_space_to_depth): this stage packs")
        rows, h, w, _ = images.shape
        layout = space_to_depth_batch \
            if space_to_depth and h % 4 == 0 and w % 4 == 0 else (lambda a: a)
        k_flip, k_jit, k_rand, k_mix = jax.random.split(rng, 4)
        k_perm, *k_blend = jax.random.split(k_mix, 4)

        # what the finish makes of this wire: the stage's output dtype
        out_dtype = jax.eval_shape(finish, images).dtype

        def finished(x):
            """uint8 normalized by the finish; then float32 either way."""
            with jax.named_scope("finish_u8"):
                return finish(x).astype(jnp.float32)

        # each stage under its own scope (distributed_vgg_f_tpu/scopes.py);
        # the step wraps the whole call in `augment`
        x = layout(images) if pack_first else images
        if hflip:
            with jax.named_scope("flip"):
                x = _hflip(k_flip, x)
        if jitter > 0:
            with jax.named_scope("crop_jitter"):
                x = _crop_jitter(k_jit, x, jitter)
        if not permute_first:
            x = finished(x)
            with jax.named_scope("rand_ops"):
                x = _rand_ops(k_rand, x, mean, inv_std, rand_ops, magnitude)
        if not pack_first:
            x = layout(x)
        # The barriers hold XLA to the order written. Without them the TPU
        # compiler hoists the finish's uint8-to-float32 convert above the
        # relayouts and then moves four bytes a pixel through the pack and
        # around the gather, twice (18.7 ms at batch 1024 against 5.9:
        # PERF.md §6, PR 27). They add no operation and change no value.
        partner = mix_labels = mix_lam = None
        if mixing:
            x = jax.lax.optimization_barrier(x)
            with jax.named_scope("mix"):
                perm = jax.random.permutation(k_perm, rows)
                partner = _rows(x, perm)
        x, partner = jax.lax.optimization_barrier((x, partner))
        x = finished(x)
        if mixing:
            partner = finished(partner)
            with jax.named_scope("mix"):
                x, mix_lam = _blend(k_blend, x, partner, (h, w),
                                    mixup_alpha, cutmix_alpha, layout)
                mix_labels = labels[perm]
        return x.astype(out_dtype), mix_labels, mix_lam

    augment.permute_on_wire_dtype = permute_first
    return augment
