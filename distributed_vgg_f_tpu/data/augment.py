"""Fused on-device augmentation stage (r13) — diversity at zero host cost.

The device-finish prologue (data/device_ingest.py, r8) proved elementwise
finishing is free inside the jitted step: XLA fuses normalize/cast/relayout
into the step's first kernels and the host ships raw u8 pixels. This module
extends that prologue into a full augmentation stage — horizontal flip,
translation (crop) jitter, mixup/cutmix, and a RandAugment-lite elementwise
subset — implemented as a PURE function of (train PRNG, batch) and applied
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

Ordering contract: finish (normalize/cast, NO pack) → augment (geometric →
photometric → mix) → space-to-depth pack. Packing moves AFTER the
geometric augments — flipping a 4x4-packed (S/4, S/4, 48) block layout
would have to permute channels per block — so when augmentation is
enabled the host never packs either (`DataConfig.host_space_to_depth`) and
this stage performs the relayout for BOTH wires, exactly as the u8 finish
always did.

Wire parity: the stage runs on the post-finish float batch. The u8 and
host wires produce bit-identical normalized values for identical pixels
(the r8 contract), and identical inputs through identical jitted ops give
identical outputs — so the per-model CPU loss-trajectory equality gates
(u8 ≡ host) hold with augmentation on, unchanged.

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

from distributed_vgg_f_tpu.data.device_ingest import space_to_depth_batch

#: fold_in constant deriving the augment key off the step's per-replica
#: train key — distinct from dropout (which uses the key directly) and from
#: the grad-accum micro-batch folds (small non-negative ints).
AUGMENT_RNG_FOLD = 0xA06

#: RandAugment-lite op table (op 0 = identity). Elementwise only — the
#: whole point is ops XLA fuses into the step for free.
RAND_OPS = ("identity", "brightness", "contrast", "posterize")

#: Maximum brightness shift at magnitude 1.0, in 0..255 intensity levels.
_BRIGHTNESS_MAX_LEVELS = 64.0
#: Maximum contrast factor deviation at magnitude 1.0 (factor in 1 ± this).
_CONTRAST_MAX_DELTA = 0.8
#: Maximum posterize coarsening at magnitude 1.0: quantization step 2^k,
#: k in [0, 3] — keeps >= 5 effective bits, the RandAugment-paper range.
_POSTERIZE_MAX_SHIFT = 3.0


def _hflip(key: jax.Array, x: jnp.ndarray) -> jnp.ndarray:
    """Per-image 50% horizontal flip: reverse W and select per image."""
    bits = jax.random.bernoulli(key, 0.5, (x.shape[0],))
    return jnp.where(bits[:, None, None, None], x[:, :, ::-1, :], x)


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


def _mix(key: jax.Array, x: jnp.ndarray, labels: jnp.ndarray,
         mixup_alpha: float, cutmix_alpha: float
         ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Mixup (arXiv 1710.09412) / cutmix (arXiv 1905.04899) over the LOCAL
    shard: one Beta-drawn lam and one permutation per step (the standard
    batchwise formulation). Returns (x, labels[perm], lam) — integer labels
    stay integer; the loss mixes as lam*CE(y) + (1-lam)*CE(y[perm])."""
    b, h, w, _ = x.shape
    k_perm, k_lam, k_box, k_choice = jax.random.split(key, 4)
    perm = jax.random.permutation(k_perm, b)

    def do_mixup(args):
        x, lam0 = args
        lam = lam0.astype(x.dtype)
        return lam0, x * lam + x[perm] * (1.0 - lam)

    def do_cutmix(args):
        x, lam0 = args
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
        mask = (in_rows[:, None] & in_cols[None, :])[None, :, :, None]
        lam = 1.0 - ((y1 - y0) * (x1 - x0)).astype(jnp.float32) / (h * w)
        return lam, jnp.where(mask, x[perm], x)

    if mixup_alpha > 0 and cutmix_alpha > 0:
        lam_mix = jax.random.beta(k_lam, mixup_alpha, mixup_alpha)
        lam_cut = jax.random.beta(jax.random.fold_in(k_lam, 1),
                                  cutmix_alpha, cutmix_alpha)
        use_cut = jax.random.bernoulli(k_choice, 0.5)
        lam, x = jax.lax.cond(use_cut, do_cutmix, do_mixup,
                              (x, jnp.where(use_cut, lam_cut, lam_mix)))
    elif cutmix_alpha > 0:
        lam0 = jax.random.beta(k_lam, cutmix_alpha, cutmix_alpha)
        lam, x = do_cutmix((x, lam0))
    else:
        lam0 = jax.random.beta(k_lam, mixup_alpha, mixup_alpha)
        lam, x = do_mixup((x, lam0))
    return x, labels[perm], lam.astype(jnp.float32)


def make_device_augment(aug_cfg, mean_rgb: Sequence[float],
                        stddev_rgb: Sequence[float], *,
                        space_to_depth: bool = False) -> Optional[Callable]:
    """Build the fused augmentation stage for the train step, or None when
    `aug_cfg.enabled` is false — the kill-switch contract is STRUCTURAL
    absence: a disabled stage contributes zero jaxpr equations, so the
    augment-off step is byte-identical to a pre-r13 build (pinned by test).

    The returned `augment(rng, images, labels) -> (images, mix_labels,
    mix_lam)` expects the POST-finish batch: float dtype, UNPACKED
    (B, S, S, 3). `mix_labels`/`mix_lam` are None unless mixup/cutmix is
    configured; the step's loss then mixes integer-label CE terms. When
    `space_to_depth` is set the stage performs the 4x4 relayout AFTER
    augmenting (the finish and the host both skip packing under
    augmentation — see the module docstring's ordering contract)."""
    if aug_cfg is None or not aug_cfg.enabled:
        return None
    mean = jnp.asarray(mean_rgb, jnp.float32)
    inv_std = jnp.float32(1.0) / jnp.asarray(stddev_rgb, jnp.float32)
    hflip = bool(aug_cfg.hflip)
    jitter = int(aug_cfg.crop_jitter)
    mixup_alpha = float(aug_cfg.mixup_alpha)
    cutmix_alpha = float(aug_cfg.cutmix_alpha)
    rand_ops = int(aug_cfg.rand_ops)
    magnitude = float(aug_cfg.rand_magnitude)
    pack = bool(space_to_depth)

    def augment(rng: jax.Array, images: jnp.ndarray, labels: jnp.ndarray):
        if images.ndim != 4 or images.shape[-1] != 3:
            raise ValueError(
                f"device augmentation expects the unpacked (B, S, S, 3) "
                f"post-finish batch, got {images.shape} — when "
                f"data.augment.enabled the host must not pack "
                f"(DataConfig.host_space_to_depth) and the finish defers "
                f"space-to-depth to this stage")
        if images.dtype == jnp.uint8:
            raise TypeError(
                "device augmentation runs AFTER the device finish — a raw "
                "uint8 batch here means the finish was not installed")
        in_dtype = images.dtype
        x = images.astype(jnp.float32)
        k_flip, k_jit, k_rand, k_mix = jax.random.split(rng, 4)
        # each stage under its own scope (distributed_vgg_f_tpu/scopes.py);
        # the step wraps the whole call in `augment`
        if hflip:
            with jax.named_scope("flip"):
                x = _hflip(k_flip, x)
        if jitter > 0:
            with jax.named_scope("crop_jitter"):
                x = _crop_jitter(k_jit, x, jitter)
        if rand_ops > 0:
            with jax.named_scope("rand_ops"):
                x = _rand_ops(k_rand, x, mean, inv_std, rand_ops, magnitude)
        mix_labels = mix_lam = None
        if mixup_alpha > 0 or cutmix_alpha > 0:
            with jax.named_scope("mix"):
                x, mix_labels, mix_lam = _mix(k_mix, x, labels,
                                              mixup_alpha, cutmix_alpha)
        x = x.astype(in_dtype)
        if pack and x.shape[1] % 4 == 0 and x.shape[2] % 4 == 0:
            x = space_to_depth_batch(x)
        return x, mix_labels, mix_lam

    return augment
