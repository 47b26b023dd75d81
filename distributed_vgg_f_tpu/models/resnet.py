"""ResNet-50 with cross-replica sync-BatchNorm — BASELINE.json config #4.

He et al. 2015, v1.5 variant (stride-2 on the 3x3 conv of downsampling
bottlenecks — the variant used by standard ImageNet throughput benchmarks).

Sync-BN (SURVEY.md §2.3 cross-replica statistics): `nn.BatchNorm` is given the
mesh's data axis as `axis_name`, so during training the batch mean/var are
`pmean`-reduced across all replicas inside the jitted step — global-batch
statistics over ICI, the TPU-native equivalent of NCCL sync-BN. Running averages
then update identically on every replica, keeping state replicated. Set
`bn_axis_name=None` for per-replica (local) BN.
"""

from __future__ import annotations

import functools
from typing import Any, Optional, Sequence

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax


class StemConv(nn.Module):
    """ResNet's 7x7/2 stem conv, optionally computed via 2x2 space-to-depth.

    `stem="space_to_depth"` (the targeted experiment from the r3 trace,
    VERDICT r3 #5): C_in=3 underfills the MXU's 128-deep contraction the
    same way VGG-F's stem did (models/vggf.py Conv1SpaceToDepth). Reshape
    the input HxWx3 → (H/2)x(W/2)x12 (2x2 pixel blocks into channels) and
    convolve with the kernel zero-padded 7x7 → 8x8 (one leading tap) and
    rearranged to 4x4x12xF at stride 1, block padding (2, 1): output i
    reads pixel taps 2i−4..2i+3 = blocks i−2..i+1, where the −4 tap is the
    zero row — bit-identical to the 7x7/2 pad-3 conv, with a 4x deeper
    contraction. The logical parameter stays (7, 7, 3, F) — checkpoints are
    layout-unchanged. Falls back to the plain conv when H/W aren't even.
    """

    features: int = 64
    compute_dtype: Any = jnp.bfloat16
    stem: str = "conv7"

    @nn.compact
    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        if self.stem not in ("conv7", "space_to_depth"):
            raise ValueError(f"unknown resnet stem {self.stem!r}; "
                             f"expected 'conv7' or 'space_to_depth'")
        kernel = self.param("kernel", nn.initializers.lecun_normal(),
                            (7, 7, 3, self.features), jnp.float32)
        h, w = x.shape[1], x.shape[2]
        if (self.stem == "space_to_depth" and h % 2 == 0 and w % 2 == 0
                and min(h, w) >= 8):
            b = x.shape[0]
            xs = x.reshape(b, h // 2, 2, w // 2, 2, 3)
            xs = xs.transpose(0, 1, 3, 2, 4, 5).reshape(b, h // 2, w // 2, 12)
            k = jnp.pad(kernel, ((1, 0), (1, 0), (0, 0), (0, 0)))  # 8x8 taps
            k = k.reshape(4, 2, 4, 2, 3, self.features)
            k = k.transpose(0, 2, 1, 3, 4, 5).reshape(4, 4, 12, self.features)
            return lax.conv_general_dilated(
                xs, k.astype(self.compute_dtype), window_strides=(1, 1),
                padding=[(2, 1), (2, 1)],
                dimension_numbers=("NHWC", "HWIO", "NHWC"))
        return lax.conv_general_dilated(
            x, kernel.astype(self.compute_dtype), window_strides=(2, 2),
            padding=[(3, 3), (3, 3)],
            dimension_numbers=("NHWC", "HWIO", "NHWC"))


class BottleneckBlock(nn.Module):
    features: int          # width of the 1x1/3x3 convs; output is 4x this
    strides: int = 1
    compute_dtype: Any = jnp.bfloat16
    bn_axis_name: Optional[str] = "data"

    @nn.compact
    def __call__(self, x: jnp.ndarray, *, train: bool) -> jnp.ndarray:
        conv = functools.partial(nn.Conv, use_bias=False,
                                 dtype=self.compute_dtype,
                                 param_dtype=jnp.float32)
        bn = functools.partial(nn.BatchNorm, use_running_average=not train,
                               momentum=0.9, epsilon=1e-5,
                               dtype=self.compute_dtype,
                               param_dtype=jnp.float32,
                               axis_name=self.bn_axis_name if train else None)
        residual = x
        y = nn.relu(bn(name="bn1")(conv(self.features, (1, 1), name="conv1")(x)))
        y = nn.relu(bn(name="bn2")(conv(self.features, (3, 3),
                                        strides=(self.strides, self.strides),
                                        name="conv2")(y)))
        # zero-init the last BN scale: identity-at-init residual branch,
        # standard large-batch ResNet practice (Goyal et al.).
        y = bn(name="bn3", scale_init=nn.initializers.zeros)(
            conv(4 * self.features, (1, 1), name="conv3")(y))
        if residual.shape != y.shape:
            residual = bn(name="bn_proj")(
                conv(4 * self.features, (1, 1),
                     strides=(self.strides, self.strides),
                     name="conv_proj")(residual))
        return nn.relu(y + residual)


class ResNet(nn.Module):
    stage_sizes: Sequence[int] = (3, 4, 6, 3)   # ResNet-50
    num_classes: int = 1000
    compute_dtype: Any = jnp.bfloat16
    bn_axis_name: Optional[str] = "data"
    stem: str = "conv7"      # or "space_to_depth" (StemConv docstring)

    @nn.compact
    def __call__(self, x: jnp.ndarray, *, train: bool = False) -> jnp.ndarray:
        from distributed_vgg_f_tpu.models.ingest import reject_raw_uint8
        reject_raw_uint8(x, "ResNet")  # u8-wire zoo contract
        with jax.named_scope("cast_in"):
            x = x.astype(self.compute_dtype)
        x = StemConv(64, self.compute_dtype, stem=self.stem,
                     name="conv_init")(x)
        x = nn.BatchNorm(use_running_average=not train, momentum=0.9,
                         epsilon=1e-5, dtype=self.compute_dtype,
                         param_dtype=jnp.float32,
                         axis_name=self.bn_axis_name if train else None,
                         name="bn_init")(x)
        x = nn.relu(x)
        with jax.named_scope("pool_init"):
            x = nn.max_pool(x, (3, 3), strides=(2, 2),
                            padding=[(1, 1), (1, 1)])
        for stage, num_blocks in enumerate(self.stage_sizes):
            for block in range(num_blocks):
                x = BottleneckBlock(
                    features=64 * 2 ** stage,
                    strides=2 if stage > 0 and block == 0 else 1,
                    compute_dtype=self.compute_dtype,
                    bn_axis_name=self.bn_axis_name,
                    name=f"stage{stage + 1}_block{block + 1}")(x, train=train)
        with jax.named_scope("gap"):
            x = jnp.mean(x, axis=(1, 2))
        x = nn.Dense(self.num_classes, dtype=self.compute_dtype,
                     param_dtype=jnp.float32, name="head")(x)
        return x.astype(jnp.float32)


def ResNet50(**kwargs) -> ResNet:
    kwargs.setdefault("stage_sizes", (3, 4, 6, 3))
    return ResNet(**kwargs)
