"""Time ViT-S/16 train-step variants of the attention sublayer on the chip.

The r3 TPU trace attributed ~15.5% of the ViT step to
`data formatting` HLOs (attention layout transposes) and ~10% to
rng-bit-generator + per-block uniforms (attention-weight dropout masks over
(B,H,197,197) ×12 blocks). This harness measures each lever independently,
plus the round-2 flax `nn.MultiHeadDotProductAttention` build as the
regression reference, all in ONE process (a chip belongs to one process at
a time, so the variants run serially in it).

Usage:
    python benchmarks/vit_attention_variants.py [--batch-size 256] [--steps 20]

Prints one JSON line per variant: {"variant": ..., "images_per_sec_per_chip": ...}
"""

from __future__ import annotations

import argparse
import io
import json
import os
import sys
import time
from typing import Any

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _register_mha_reference() -> None:
    """Round-2 ViT build: per-block flax MHA (three separate projection GEMMs,
    dropout_rate applied to attention weights) — the 1,866 img/s/chip r2
    baseline, kept here as the regression reference."""
    import flax.linen as nn
    import jax.numpy as jnp

    from distributed_vgg_f_tpu.config import ModelConfig
    from distributed_vgg_f_tpu.models.registry import _dtype, register
    from distributed_vgg_f_tpu.models.vit import MlpBlock, ViT

    class MhaEncoderBlock(nn.Module):
        num_heads: int
        mlp_dim: int
        dropout_rate: float
        compute_dtype: Any
        attention_dropout_rate: float = 0.0
        attention_layout: str = "unused"

        @nn.compact
        def __call__(self, x, *, train: bool):
            y = nn.LayerNorm(dtype=jnp.float32, name="ln1")(x)
            y = nn.MultiHeadDotProductAttention(
                num_heads=self.num_heads, dtype=self.compute_dtype,
                param_dtype=jnp.float32,
                dropout_rate=self.attention_dropout_rate,
                deterministic=not train, name="attn")(y, y)
            x = x + nn.Dropout(self.dropout_rate, deterministic=not train)(y)
            y = nn.LayerNorm(dtype=jnp.float32, name="ln2")(x)
            y = MlpBlock(self.mlp_dim, self.dropout_rate, self.compute_dtype,
                         name="mlp")(y, train=train)
            return x + y

    class MhaViT(ViT):
        @nn.compact
        def __call__(self, x, *, train: bool = False):
            import jax.numpy as jnp
            B = x.shape[0]
            x = x.astype(self.compute_dtype)
            x = nn.Conv(self.hidden_dim,
                        (self.patch_size, self.patch_size),
                        strides=(self.patch_size, self.patch_size),
                        padding="VALID", dtype=self.compute_dtype,
                        param_dtype=jnp.float32, name="patch_embed")(x)
            x = x.reshape(B, -1, self.hidden_dim)
            cls_tok = self.param("cls", nn.initializers.zeros,
                                 (1, 1, self.hidden_dim), jnp.float32)
            x = jnp.concatenate(
                [jnp.broadcast_to(cls_tok.astype(self.compute_dtype),
                                  (B, 1, self.hidden_dim)), x], axis=1)
            pos = self.param("pos_embed",
                             nn.initializers.normal(stddev=0.02),
                             (1, x.shape[1], self.hidden_dim), jnp.float32)
            x = x + pos.astype(self.compute_dtype)
            x = nn.Dropout(self.dropout_rate, deterministic=not train)(x)
            for i in range(self.depth):
                x = MhaEncoderBlock(
                    self.num_heads, self.mlp_dim, self.dropout_rate,
                    self.compute_dtype,
                    attention_dropout_rate=self.attention_dropout_rate,
                    name=f"block{i}")(x, train=train)
            x = nn.LayerNorm(dtype=jnp.float32, name="ln_final")(x)
            x = x[:, 0]
            x = nn.Dense(self.num_classes, dtype=self.compute_dtype,
                         param_dtype=jnp.float32, name="head")(x)
            return x.astype(jnp.float32)

    @register("vit_s16_mha_ref")
    def _build(cfg: ModelConfig):
        return MhaViT(num_classes=cfg.num_classes,
                      dropout_rate=cfg.dropout_rate,
                      compute_dtype=_dtype(cfg), **cfg.extra)


def time_variant(name: str, model_name: str, extra: dict, args) -> dict:
    import jax

    from distributed_vgg_f_tpu.config import (
        DataConfig, ExperimentConfig, ModelConfig, OptimConfig, TrainConfig)
    from distributed_vgg_f_tpu.data.synthetic import SyntheticDataset
    from distributed_vgg_f_tpu.train.trainer import Trainer
    from distributed_vgg_f_tpu.utils.logging import MetricLogger

    num_chips = jax.device_count()
    batch = args.batch_size * max(1, num_chips)
    cfg = ExperimentConfig(
        name=f"vit_variant_{name}",
        model=ModelConfig(name=model_name, num_classes=1000,
                          dropout_rate=0.1, compute_dtype="bfloat16",
                          extra=extra),
        optim=OptimConfig(base_lr=0.01, reference_batch_size=batch),
        data=DataConfig(name="synthetic", image_size=224,
                        global_batch_size=batch),
        train=TrainConfig(steps=args.steps, log_every=10_000, seed=0),
    )
    trainer = Trainer(cfg, logger=MetricLogger(stream=io.StringIO()))
    state = trainer.init_state()
    rng = trainer.base_rng()
    ds = SyntheticDataset(batch_size=batch, image_size=224, num_classes=1000,
                          seed=0, fixed=True, image_dtype="bfloat16")
    sharded = trainer.shard(next(ds))

    for _ in range(args.warmup):
        state, metrics = trainer.train_step(state, sharded, rng)
    if args.warmup:
        float(jax.device_get(metrics["loss"]))

    t0 = time.monotonic()
    for _ in range(args.steps):
        state, metrics = trainer.train_step(state, sharded, rng)
    float(jax.device_get(metrics["loss"]))
    elapsed = time.monotonic() - t0
    return {
        "variant": name,
        "images_per_sec_per_chip": round(batch * args.steps / elapsed / num_chips, 1),
        "step_ms": round(elapsed / args.steps * 1e3, 2),
        "batch": batch,
    }


def main() -> None:
    parser = argparse.ArgumentParser()
    parser.add_argument("--batch-size", type=int, default=256)
    parser.add_argument("--steps", type=int, default=20)
    parser.add_argument("--warmup", type=int, default=5)
    args = parser.parse_args()

    _register_mha_reference()

    variants = [
        # (name, model, extra)
        ("mha_attndrop0.1_r2ref", "vit_s16_mha_ref",
         {"attention_dropout_rate": 0.1}),
        ("mha_attndrop0.0", "vit_s16_mha_ref", {}),
        ("fused_token_major_attndrop0.1_r3asmeasured", "vit_s16",
         {"attention_layout": "token_major", "attention_dropout_rate": 0.1}),
        ("fused_token_major_attndrop0.0", "vit_s16",
         {"attention_layout": "token_major"}),
        ("fused_head_major_attndrop0.1", "vit_s16",
         {"attention_layout": "head_major", "attention_dropout_rate": 0.1}),
        ("fused_head_major_attndrop0.0_proposed", "vit_s16",
         {"attention_layout": "head_major"}),
        ("fused_flash_pallas", "vit_s16",
         {"attention_layout": "flash"}),
    ]
    for name, model_name, extra in variants:
        row = time_variant(name, model_name, extra, args)
        print(json.dumps(row), flush=True)


if __name__ == "__main__":
    main()
