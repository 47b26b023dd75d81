"""ViT-S/16 — BASELINE.json config #5: "patch-embed + attention under the same
DP all-reduce".

Dosovitskiy et al. 2020 / Touvron DeiT-S dimensions: patch 16, width 384,
depth 12, heads 6, MLP 1536, cls token, learned position embeddings.

SURVEY.md §5 (long-context): sequence length is 197 tokens under plain DP — no
sequence sharding required or built; attention runs per-replica on the MXU
(bf16 matmuls), with fp32 softmax for stability.
"""

from __future__ import annotations

import math
import os
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp


#: attention_layout="auto" switches to the Pallas flash kernel from this
#: many tokens. Evidence-backed edges only (r4 v5e microbench, fwd+bwd,
#: non-causal): XLA's fused einsum wins every measured point up to 4096
#: (31.9 vs 58.9 ms) and FAILS TO COMPILE at 8192 (4 GiB probs), where
#: flash runs 214.9 ms — so the switch sits at 8192 until a measured
#: 4k-8k crossover (the r5 long-context rows) justifies lowering it.
#: Env-overridable for other chip generations, same pattern as
#: ops.flash_attention.CAUSAL_SKIP_AUTO_THRESHOLD.
try:
    ATTENTION_AUTO_FLASH_THRESHOLD = int(
        os.environ.get("DVGGF_ATTENTION_AUTO_FLASH_THRESHOLD", 8192))
except ValueError as _e:
    raise ValueError(
        "DVGGF_ATTENTION_AUTO_FLASH_THRESHOLD must be an integer token "
        "count, got "
        f"{os.environ['DVGGF_ATTENTION_AUTO_FLASH_THRESHOLD']!r}") from _e


class FusedSelfAttention(nn.Module):
    """Self-attention with ONE fused QKV projection.

    Why not `nn.MultiHeadDotProductAttention`: it issues three separate
    (D, D) projection GEMMs per block; fusing them into a single (D, 3·H·hd)
    GEMM keeps the MXU on one large matmul and removes two kernel-launch /
    fusion boundaries per block — a ViT-S/16 step is 12 blocks deep, so the
    savings compound (VERDICT r2 #2 ViT candidate; TPU measurement tracked
    in PARITY.md). Numerics match flax's module exactly given repacked
    params (tests/test_model_zoo.py::test_fused_attention_matches_flax_mha);
    softmax runs in fp32 (bf16 logits lose ~2 decimal digits across 197
    tokens' worth of exp/sum).

    `dropout_rate` here is ATTENTION-WEIGHT dropout (the (B,H,T,T) probs
    tensor). The r3 TPU trace showed generating those masks cost ~10% of the
    ViT step (rng-bit-generator + per-block uniforms), so the model default
    is 0.0 — matching the canonical recipes for these dimensions (DeiT-S and
    the official ViT ImageNet configs both set attention dropout 0.0 while
    keeping 0.1 elsewhere). Set `model.extra.attention_dropout_rate` to
    re-enable.

    `layout` selects where the head axis lives between the projections:
      - "head_major": one explicit (B,T,3,H,hd)→(3,B,H,T,hd) transpose right
        after the QKV GEMM; q/k/v are then free major-axis slices already in
        the (b,h,t,d) layout both attention einsums want, so XLA inserts no
        further operand transposes.
      - "token_major": split+squeeze on the packed middle axis (three strided
        copies) and token-major einsums whose operands XLA must transpose —
        measured 15.5% of the step in `data formatting` HLOs (r3 trace).
      - "flash": the Pallas blockwise kernel (ops/flash_attention.py) — pads
        197 → 256 tokens with kv_len masking; (T, T) probs never reach HBM.
        Incompatible with attention-weight dropout (probs don't exist).
      - "auto": the measured regime rule as code — head_major below
        ATTENTION_AUTO_FLASH_THRESHOLD tokens (XLA's fused einsum wins the
        whole measured range 512–4096: r4 microbench, 31.9 vs 58.9 ms at
        4k), flash from the threshold up (XLA cannot even compile the 4 GiB
        probs at 8192; flash runs it at 214.9 ms — the kernel is the only
        path). Resolved per call from the actual T.
    All layouts share identical param shapes (checkpoint-compatible).
    """

    num_heads: int
    dropout_rate: float
    compute_dtype: Any
    layout: str = "head_major"

    def __post_init__(self):
        # Eager rejection (ADVICE r5): "flash" can never apply attention-
        # weight dropout, and "auto" ROUTES to flash once T crosses the
        # threshold — deferring that to call time made the failure
        # length-dependent (a config validated fine at T=197 and blew up the
        # first long-context batch). Reject at construction, naming the
        # configured layout.
        if self.layout in ("flash", "auto") and self.dropout_rate > 0.0:
            raise ValueError(
                f"attention layout {self.layout!r} uses the flash kernel "
                f"(for 'auto': once T >= ATTENTION_AUTO_FLASH_THRESHOLD), "
                f"which never materializes the attention weights — "
                f"incompatible with attention-weight dropout_rate="
                f"{self.dropout_rate}; pick an einsum layout "
                f"('head_major'/'token_major') or set the attention "
                f"dropout to 0")
        super().__post_init__()

    @nn.compact
    def __call__(self, x, *, train: bool):
        B, T, D = x.shape
        H = self.num_heads
        hd = D // H
        layout = self.layout
        if layout == "auto":
            layout = ("flash" if T >= ATTENTION_AUTO_FLASH_THRESHOLD
                      else "head_major")
        qkv = nn.DenseGeneral((3, H, hd), axis=-1, dtype=self.compute_dtype,
                              param_dtype=jnp.float32, name="qkv")(x)
        # weak python float: a numpy scalar is a STRONG type and would
        # promote q (and the QK^T GEMM) to fp32 under bf16 compute
        scale = 1.0 / math.sqrt(hd)
        if layout == "flash":
            # Pallas blockwise kernel (ops/flash_attention.py): probs never
            # materialize, so attention-weight dropout cannot apply —
            # flash/auto + dropout_rate > 0 is rejected in __post_init__.
            from distributed_vgg_f_tpu.ops.flash_attention import (
                flash_self_attention)
            q, k, v = (jnp.squeeze(t_, 2) for t_ in jnp.split(qkv, 3, axis=2))
            # pad-to-block (197 → 256 with kv_len masking) happens INSIDE
            # flash_self_attention since the r5 pad_to_block work — the
            # hand-rolled copy of that padding that used to live here was
            # the same mechanism at the wrong altitude (simplify r5)
            ctx = flash_self_attention(q, k, v)
            return nn.DenseGeneral(D, axis=(-2, -1), dtype=self.compute_dtype,
                                   param_dtype=jnp.float32, name="out")(ctx)
        if layout == "head_major":
            qkv = jnp.transpose(qkv, (2, 0, 3, 1, 4))  # (3, B, H, T, hd)
            q, k, v = qkv[0] * scale, qkv[1], qkv[2]
            scores = jnp.einsum("bhqd,bhkd->bhqk", q, k)
        elif layout == "token_major":
            q, k, v = (jnp.squeeze(t, 2) for t in jnp.split(qkv, 3, axis=2))
            q = q * scale
            scores = jnp.einsum("bqhd,bkhd->bhqk", q, k)
        else:
            raise ValueError(f"unknown attention layout {layout!r}")
        probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1)
        probs = probs.astype(self.compute_dtype)
        if train and self.dropout_rate > 0.0:
            probs = nn.Dropout(self.dropout_rate, deterministic=False)(probs)
        if layout == "head_major":
            ctx = jnp.einsum("bhqk,bhkd->bhqd", probs, v)
            # contract (H, hd) out of (B, H, T, hd) → (B, T, D); same
            # (H, hd, D) kernel as the token-major path
            return nn.DenseGeneral(D, axis=(1, 3), dtype=self.compute_dtype,
                                   param_dtype=jnp.float32, name="out")(ctx)
        ctx = jnp.einsum("bhqk,bkhd->bqhd", probs, v)
        return nn.DenseGeneral(D, axis=(-2, -1), dtype=self.compute_dtype,
                               param_dtype=jnp.float32, name="out")(ctx)


class MlpBlock(nn.Module):
    mlp_dim: int
    dropout_rate: float
    compute_dtype: Any

    @nn.compact
    def __call__(self, x, *, train: bool):
        d = x.shape[-1]
        x = nn.Dense(self.mlp_dim, dtype=self.compute_dtype,
                     param_dtype=jnp.float32, name="fc1")(x)
        x = nn.gelu(x)
        x = nn.Dropout(self.dropout_rate, deterministic=not train)(x)
        x = nn.Dense(d, dtype=self.compute_dtype, param_dtype=jnp.float32,
                     name="fc2")(x)
        x = nn.Dropout(self.dropout_rate, deterministic=not train)(x)
        return x


class EncoderBlock(nn.Module):
    num_heads: int
    mlp_dim: int
    dropout_rate: float
    compute_dtype: Any
    attention_dropout_rate: float = 0.0
    attention_layout: str = "head_major"

    @nn.compact
    def __call__(self, x, *, train: bool):
        y = nn.LayerNorm(dtype=jnp.float32, name="ln1")(x)
        y = FusedSelfAttention(
            num_heads=self.num_heads,
            dropout_rate=self.attention_dropout_rate,
            compute_dtype=self.compute_dtype,
            layout=self.attention_layout, name="attn")(y, train=train)
        x = x + nn.Dropout(self.dropout_rate, deterministic=not train)(y)
        y = nn.LayerNorm(dtype=jnp.float32, name="ln2")(x)
        y = MlpBlock(self.mlp_dim, self.dropout_rate, self.compute_dtype,
                     name="mlp")(y, train=train)
        return x + y


class ViT(nn.Module):
    num_classes: int = 1000
    patch_size: int = 16
    hidden_dim: int = 384
    depth: int = 12
    num_heads: int = 6
    mlp_dim: int = 1536
    dropout_rate: float = 0.1
    # attention-WEIGHT dropout; 0.0 per the canonical DeiT-S / official ViT
    # recipes AND the r3 trace (mask RNG alone was ~10% of the TPU step)
    attention_dropout_rate: float = 0.0
    attention_layout: str = "head_major"
    compute_dtype: Any = jnp.bfloat16

    def __post_init__(self):
        # Same eager rejection as FusedSelfAttention, but at MODEL build
        # time (registry.build_model) — the inner module is only constructed
        # on the first trace, which is still later than a config error
        # should surface (ADVICE r5).
        if self.attention_layout in ("flash", "auto") \
                and self.attention_dropout_rate > 0.0:
            raise ValueError(
                f"attention_layout {self.attention_layout!r} uses the flash "
                f"kernel (for 'auto': once T crosses the flash threshold), "
                f"which never materializes the attention weights — "
                f"incompatible with attention_dropout_rate="
                f"{self.attention_dropout_rate}; pick an einsum layout "
                f"('head_major'/'token_major') or set "
                f"model.extra.attention_dropout_rate=0")
        super().__post_init__()

    @classmethod
    def s16(cls, **kwargs) -> "ViT":
        return cls(**kwargs)

    @nn.compact
    def __call__(self, x: jnp.ndarray, *, train: bool = False) -> jnp.ndarray:
        from distributed_vgg_f_tpu.models.ingest import reject_raw_uint8
        reject_raw_uint8(x, "ViT")  # u8-wire zoo contract
        B = x.shape[0]
        with jax.named_scope("cast_in"):
            x = x.astype(self.compute_dtype)
        # patch embedding as a strided conv → (B, H/p, W/p, D), then flatten
        x = nn.Conv(self.hidden_dim,
                    (self.patch_size, self.patch_size),
                    strides=(self.patch_size, self.patch_size),
                    padding="VALID", dtype=self.compute_dtype,
                    param_dtype=jnp.float32, name="patch_embed")(x)
        x = x.reshape(B, -1, self.hidden_dim)

        cls_tok = self.param("cls", nn.initializers.zeros,
                             (1, 1, self.hidden_dim), jnp.float32)
        with jax.named_scope("embed_tokens"):
            x = jnp.concatenate(
                [jnp.broadcast_to(cls_tok.astype(self.compute_dtype),
                                  (B, 1, self.hidden_dim)), x], axis=1)
            pos = self.param("pos_embed",
                             nn.initializers.normal(stddev=0.02),
                             (1, x.shape[1], self.hidden_dim), jnp.float32)
            x = x + pos.astype(self.compute_dtype)
        x = nn.Dropout(self.dropout_rate, deterministic=not train)(x)

        for i in range(self.depth):
            x = EncoderBlock(self.num_heads, self.mlp_dim, self.dropout_rate,
                             self.compute_dtype,
                             attention_dropout_rate=self.attention_dropout_rate,
                             attention_layout=self.attention_layout,
                             name=f"block{i}")(x, train=train)
        x = nn.LayerNorm(dtype=jnp.float32, name="ln_final")(x)
        x = x[:, 0]
        x = nn.Dense(self.num_classes, dtype=self.compute_dtype,
                     param_dtype=jnp.float32, name="head")(x)
        return x.astype(jnp.float32)
