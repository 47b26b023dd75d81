"""VGG-16 — BASELINE.json config #3: "deeper conv stack, same DP path".

Simonyan & Zisserman 2014 configuration D: 13 conv3x3 layers in five blocks
(64,64 / 128,128 / 256x3 / 512x3 / 512x3), 2x2/2 max-pool after each block,
fc 4096-4096-N. ~138M params at 1000 classes. No LRN (the VGG paper dropped it).

Same TPU conventions as VGG-F: NHWC, bf16 compute on the MXU, fp32 params/logits.
"""

from __future__ import annotations

from typing import Any, Sequence

import flax.linen as nn
import jax
import jax.numpy as jnp


class VGG16(nn.Module):
    num_classes: int = 1000
    dropout_rate: float = 0.5
    compute_dtype: Any = jnp.bfloat16
    block_sizes: Sequence[int] = (2, 2, 3, 3, 3)
    block_features: Sequence[int] = (64, 128, 256, 512, 512)

    @nn.compact
    def __call__(self, x: jnp.ndarray, *, train: bool = False) -> jnp.ndarray:
        from distributed_vgg_f_tpu.models.ingest import reject_raw_uint8
        reject_raw_uint8(x, "VGG16")  # u8-wire zoo contract
        with jax.named_scope("cast_in"):
            x = x.astype(self.compute_dtype)
        for b, (reps, feat) in enumerate(zip(self.block_sizes,
                                             self.block_features), start=1):
            for i in range(1, reps + 1):
                x = nn.Conv(feat, (3, 3), padding="SAME",
                            dtype=self.compute_dtype, param_dtype=jnp.float32,
                            name=f"conv{b}_{i}")(x)
                x = nn.relu(x)
            with jax.named_scope(f"pool{b}"):
                x = nn.max_pool(x, (2, 2), strides=(2, 2))
        x = x.reshape((x.shape[0], -1))
        x = nn.relu(nn.Dense(4096, dtype=self.compute_dtype,
                             param_dtype=jnp.float32, name="fc6")(x))
        x = nn.Dropout(self.dropout_rate, deterministic=not train)(x)
        x = nn.relu(nn.Dense(4096, dtype=self.compute_dtype,
                             param_dtype=jnp.float32, name="fc7")(x))
        x = nn.Dropout(self.dropout_rate, deterministic=not train)(x)
        x = nn.Dense(self.num_classes, dtype=self.compute_dtype,
                     param_dtype=jnp.float32, name="fc8")(x)
        return x.astype(jnp.float32)
