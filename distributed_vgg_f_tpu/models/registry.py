"""Model registry — keeps the trainer model-agnostic (SURVEY.md §7: configs are
config swaps, not forks). `build_model(cfg.model)` returns a Flax module whose
`__call__(images, train=...)` yields logits.

The registry is also the public surface of the per-model INGEST contract
(r13): `ingest_descriptor(name)` declares what each stem consumes from the
u8 ingest wire — packed vs plain layout, stem dtype, normalize constants —
replacing the VGGF-only preset wiring. The table itself lives in
models/ingest.py (a light module: presets and benches read descriptors
without importing flax)."""

from __future__ import annotations

from typing import Callable, Dict

import flax.linen as nn
import jax.numpy as jnp

from distributed_vgg_f_tpu.config import ModelConfig
from distributed_vgg_f_tpu.models.ingest import (  # noqa: F401 — re-export
    INGEST_DESCRIPTORS,
    IngestDescriptor,
    ingest_descriptor,
)

_REGISTRY: Dict[str, Callable[[ModelConfig], nn.Module]] = {}


def register(name: str):
    def deco(fn):
        _REGISTRY[name] = fn
        return fn
    return deco


def available_models():
    return sorted(_REGISTRY)


def build_model(cfg: ModelConfig) -> nn.Module:
    try:
        builder = _REGISTRY[cfg.name]
    except KeyError:
        raise KeyError(f"unknown model {cfg.name!r}; available: {available_models()}")
    return builder(cfg)


def _dtype(cfg: ModelConfig):
    return jnp.dtype(cfg.compute_dtype)


@register("vggf")
def _build_vggf(cfg: ModelConfig) -> nn.Module:
    from distributed_vgg_f_tpu.models.vggf import VGGF
    return VGGF(num_classes=cfg.num_classes, dropout_rate=cfg.dropout_rate,
                compute_dtype=_dtype(cfg), **cfg.extra)


@register("vggf_student")
def _build_vggf_student(cfg: ModelConfig) -> nn.Module:
    # Half-width CNN-F (stem 32, convs 128, FC 2048) — the distillation
    # target train/distill.py trains against data/teacher.py logits, served
    # as the `student` tier (serving/tiers.py). Serving-only: no training
    # preset derives from it (models/ingest.py serving_only flag).
    from distributed_vgg_f_tpu.models.vggf import VGGF
    return VGGF(num_classes=cfg.num_classes, dropout_rate=cfg.dropout_rate,
                compute_dtype=_dtype(cfg), stem_features=32,
                conv_features=128, fc_features=2048, **cfg.extra)


@register("vgg16")
def _build_vgg16(cfg: ModelConfig) -> nn.Module:
    from distributed_vgg_f_tpu.models.vgg16 import VGG16
    return VGG16(num_classes=cfg.num_classes, dropout_rate=cfg.dropout_rate,
                 compute_dtype=_dtype(cfg), **cfg.extra)


@register("resnet50")
def _build_resnet50(cfg: ModelConfig) -> nn.Module:
    from distributed_vgg_f_tpu.models.resnet import ResNet50
    return ResNet50(num_classes=cfg.num_classes, compute_dtype=_dtype(cfg),
                    **cfg.extra)


@register("vit_s16")
def _build_vit_s16(cfg: ModelConfig) -> nn.Module:
    from distributed_vgg_f_tpu.models.vit import ViT
    return ViT.s16(num_classes=cfg.num_classes, dropout_rate=cfg.dropout_rate,
                   compute_dtype=_dtype(cfg), **cfg.extra)


@register("mistral4")
def _build_mistral4(cfg: ModelConfig) -> nn.Module:
    # `num_classes` is the vocabulary rows held; `extra` the published
    # widths and the share (models/mistral4.py `build`)
    from distributed_vgg_f_tpu.models import mistral4
    return mistral4.build(cfg.num_classes, _dtype(cfg), cfg.extra)


@register("nemotron_h")
def _build_nemotron_h(cfg: ModelConfig) -> nn.Module:
    # as `mistral4`: vocabulary rows held, published widths and the share
    from distributed_vgg_f_tpu.models import nemotron_h
    return nemotron_h.build(cfg.num_classes, _dtype(cfg), cfg.extra)


@register("ling3")
def _build_ling3(cfg: ModelConfig) -> nn.Module:
    # as `mistral4`: vocabulary rows held, published widths, the share and
    # the cut in depth
    from distributed_vgg_f_tpu.models import ling3
    return ling3.build(cfg.num_classes, _dtype(cfg), cfg.extra)
