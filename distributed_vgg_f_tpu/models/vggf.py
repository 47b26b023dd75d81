"""VGG-F (CNN-F) — the reference's flagship model.

Architecture per SURVEY.md §3.3 (BASELINE.json north_star: "conv→ReLU→LRN→max-pool
stack + 3 FC heads"; exact dims from Chatfield et al., *Return of the Devil in the
Details*, BMVC 2014, Table 1 CNN-F row — the reference mount was empty, see
SURVEY.md §0):

    conv1 64@11x11/4 (VALID) → ReLU → LRN → maxpool 3x3/2
    conv2 256@5x5/1 (SAME)   → ReLU → LRN → maxpool 3x3/2
    conv3 256@3x3/1 (SAME)   → ReLU
    conv4 256@3x3/1 (SAME)   → ReLU
    conv5 256@3x3/1 (SAME)   → ReLU → maxpool 3x3/2
    flatten → fc6 4096 → ReLU → dropout
            → fc7 4096 → ReLU → dropout → fc8 num_classes

≈61M parameters at 1000 classes / 224×224 input.

TPU notes: convs/matmuls run in `compute_dtype` (bfloat16 by default) on the MXU
with float32 params; LRN computes its normalizer in float32 (ops/lrn.py). All
shapes static, NHWC layout (XLA:TPU's preferred image layout).
"""

from __future__ import annotations

from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax

from distributed_vgg_f_tpu.ops.lrn import lrn as local_response_norm
from distributed_vgg_f_tpu.ops.pooling import maxpool_3x3s2_ceil


class Conv1SpaceToDepth(nn.Module):
    """VGG-F's 11x11/4 stem conv, computed via 4x4 space-to-depth.

    C_in=3 packs the MXU's 128-wide contraction lanes terribly (~12% MXU
    utilization measured for the plain conv at batch 1024 on v5e). The classic
    TPU fix (MLPerf ResNet stem trick): reshape the input 224x224x3 →
    56x56x48 (4x4 pixel blocks into channels) and convolve with the kernel
    rearranged to 3x3x48x64 at stride 1 — bit-identical output (the zero-padded
    12th tap multiplies pixels the 11-tap kernel never saw *within each 4-pixel
    phase*, i.e. nothing), with a 16x deeper contraction. Falls back to the
    plain conv when H/W aren't multiples of 4 (or are too small), so arbitrary
    input sizes keep working. The logical parameter stays (11,11,3,64) —
    checkpoints and torch-parity are layout-unchanged."""

    features: int = 64
    compute_dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        kernel = self.param("kernel", nn.initializers.lecun_normal(),
                            (11, 11, 3, self.features), jnp.float32)
        bias = self.param("bias", nn.initializers.zeros, (self.features,),
                          jnp.float32)
        h, w = x.shape[1], x.shape[2]
        packed = x.shape[-1] == 48  # input already space-to-depth packed
        if packed or (h % 4 == 0 and w % 4 == 0 and h >= 12 and w >= 12):
            if packed:
                # the host pipeline (data.space_to_depth) already emitted
                # (H/4, W/4, 48) blocks — skip the on-device relayout
                xs = x
            else:
                b = x.shape[0]
                xs = x.reshape(b, h // 4, 4, w // 4, 4, 3)
                xs = xs.transpose(0, 1, 3, 2, 4, 5).reshape(
                    b, h // 4, w // 4, 48)
            k = jnp.pad(kernel, ((0, 1), (0, 1), (0, 0), (0, 0)))  # 12x12 taps
            k = k.reshape(3, 4, 3, 4, 3, self.features)
            k = k.transpose(0, 2, 1, 3, 4, 5).reshape(3, 3, 48, self.features)
            y = lax.conv_general_dilated(
                xs, k.astype(self.compute_dtype), window_strides=(1, 1),
                padding="VALID", dimension_numbers=("NHWC", "HWIO", "NHWC"))
        else:
            y = lax.conv_general_dilated(
                x, kernel.astype(self.compute_dtype), window_strides=(4, 4),
                padding="VALID", dimension_numbers=("NHWC", "HWIO", "NHWC"))
        return y + bias.astype(self.compute_dtype)


# 3x3/2 ceil-mode (Caffe-semantics) max pool with a hand-written backward —
# see ops/pooling.py for the why (select_and_scatter was ~7% of the step).
_maxpool_3x3s2 = maxpool_3x3s2_ceil


class VGGF(nn.Module):
    num_classes: int = 1000
    dropout_rate: float = 0.5
    compute_dtype: Any = jnp.bfloat16
    # LRN hyperparameters (AlexNet-paper / TF convention; SURVEY.md §7 hard parts).
    lrn_depth_radius: int = 2
    lrn_bias: float = 2.0
    lrn_alpha: float = 1e-4
    lrn_beta: float = 0.75
    # Layer widths. The defaults ARE CNN-F (param shapes unchanged for every
    # existing checkpoint); the serving-only `vggf_student` zoo preset halves
    # all three (models/registry.py) — the distillation target of
    # train/distill.py and the `student` serving tier.
    stem_features: int = 64
    conv_features: int = 256
    fc_features: int = 4096

    @nn.compact
    def __call__(self, x: jnp.ndarray, *, train: bool = False) -> jnp.ndarray:
        conv = lambda feat, kernel, stride, pad, name: nn.Conv(
            feat, kernel, strides=stride, padding=pad, name=name,
            dtype=self.compute_dtype, param_dtype=jnp.float32)
        dense = lambda feat, name: nn.Dense(
            feat, name=name, dtype=self.compute_dtype, param_dtype=jnp.float32)
        # relu then LRN, in one call: where `lrn` takes its fused kernel pair
        # the relu and its mask are made inside it (ops/lrn.py)
        relu_lrn = lambda v: local_response_norm(
            v, self.lrn_depth_radius, self.lrn_bias, self.lrn_alpha,
            self.lrn_beta, relu_input=True)

        from distributed_vgg_f_tpu.models.ingest import reject_raw_uint8
        reject_raw_uint8(x, "VGGF")  # u8-wire contract (r8; zoo-wide r13)
        # LRN and the pools are plain function calls, not modules: a scope
        # (distributed_vgg_f_tpu/scopes.py) names their device time.
        with jax.named_scope("cast_in"):
            x = x.astype(self.compute_dtype)
        x = Conv1SpaceToDepth(self.stem_features, self.compute_dtype,
                              name="conv1")(x)
        with jax.named_scope("lrn1"):
            x = relu_lrn(x)
        with jax.named_scope("pool1"):
            x = _maxpool_3x3s2(x)
        x = conv(self.conv_features, (5, 5), (1, 1), "SAME", "conv2")(x)
        with jax.named_scope("lrn2"):
            x = relu_lrn(x)
        with jax.named_scope("pool2"):
            x = _maxpool_3x3s2(x)
        x = nn.relu(conv(self.conv_features, (3, 3), (1, 1), "SAME", "conv3")(x))
        x = nn.relu(conv(self.conv_features, (3, 3), (1, 1), "SAME", "conv4")(x))
        x = nn.relu(conv(self.conv_features, (3, 3), (1, 1), "SAME", "conv5")(x))
        with jax.named_scope("pool5"):
            x = _maxpool_3x3s2(x)

        x = x.reshape((x.shape[0], -1))
        x = nn.relu(dense(self.fc_features, "fc6")(x))
        x = nn.Dropout(self.dropout_rate, deterministic=not train)(x)
        x = nn.relu(dense(self.fc_features, "fc7")(x))
        x = nn.Dropout(self.dropout_rate, deterministic=not train)(x)
        x = dense(self.num_classes, "fc8")(x)
        return x.astype(jnp.float32)
