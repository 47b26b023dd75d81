"""Per-model ingest descriptors — ONE u8-wire + device-ingest contract for
the whole model zoo (r13).

Through r12 only the VGG-F stem was first-class on the uint8 ingest wire:
the flagship preset wired `wire='u8'` + `space_to_depth=True` by hand and
the derived zoo presets hand-overrode the packing back off. The descriptor
table below replaces that VGGF-only wiring with a per-model declaration of
what each stem actually consumes:

- `space_to_depth` — whether the stem takes the 4x4-packed (S/4, S/4, 48)
  input layout (models/vggf.py Conv1SpaceToDepth's contract). Models whose
  stems take plain (S, S, 3) declare False and the device-finish prologue
  simply skips the relayout. (ResNet-50's optional 2x2 stem trick,
  models/resnet.py StemConv, is an ON-DEVICE relayout behind
  `model.extra.stem` — it consumes (S, S, 3) from the wire either way, so
  its descriptor stays False.)
- `stem_dtype` — the compute dtype the stem casts wire pixels into (the
  models' `compute_dtype` default); recorded so benches and receipts can
  label per-model rows without instantiating flax modules.
- `mean_rgb` / `stddev_rgb` — the normalize constants the device finish
  folds into the jitted step for this model (the zoo shares the ImageNet
  constants; a future model with different constants declares them HERE,
  not in a preset override).
- `wire` — the ingest wire the model's preset ships by default. Every zoo
  stem consumes the u8 contract: raw uint8 pixels over the wire,
  normalize/cast/(pack) fused into the step (data/device_ingest.py).
- `accepts_uint8` — always False for the zoo: raw 0..255 pixels must NEVER
  reach a stem (every model raises TypeError; the device finish is the
  only legal consumer of wire pixels).

This module is deliberately LIGHT (no flax/jax/numpy imports): config.py
presets resolve descriptors at preset-build time and the bench labels rows
from them, neither of which should pull the model libraries in. The public
import surface is models/registry.py, which re-exports everything here
next to `build_model`.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Tuple

#: The ImageNet normalize constants every zoo model shares (the values
#: DataConfig defaults to; single-sourced here so descriptor and config
#: can never drift apart — config's defaults are pinned equal by test).
IMAGENET_MEAN_RGB: Tuple[float, float, float] = (123.68, 116.78, 103.94)
IMAGENET_STDDEV_RGB: Tuple[float, float, float] = (58.393, 57.12, 57.375)


@dataclasses.dataclass(frozen=True)
class IngestDescriptor:
    """What one model's stem consumes from the ingest wire."""
    model: str
    #: stem consumes the 4x4-packed (S/4, S/4, 48) layout (VGG-F only)
    space_to_depth: bool = False
    #: compute dtype the stem casts pixels into (the model default)
    stem_dtype: str = "bfloat16"
    #: per-model normalize constants the device finish applies
    mean_rgb: Tuple[float, float, float] = IMAGENET_MEAN_RGB
    stddev_rgb: Tuple[float, float, float] = IMAGENET_STDDEV_RGB
    #: the ingest wire the model's preset ships (u8 for the whole zoo)
    wire: str = "u8"
    #: raw wire pixels may reach the stem directly (never, for the zoo —
    #: every stem raises TypeError on uint8; the device finish is the only
    #: legal consumer)
    accepts_uint8: bool = False
    #: serving-only preset (r23): the model exists for the serving tier
    #: ladder (a distilled student), not as a training preset — excluded
    #: from `zoo_model_names()` so the training/parity grids and the
    #: per-model presets never pick it up, but first-class for the serving
    #: router (serving/tiers.py builds the `student` tier from it)
    serving_only: bool = False
    #: what a batch of this model is: "image" ({'image', 'label'}, the u8
    #: or host-float wire above) or "tokens" ({'tokens': int32[B, S + 1]},
    #: inputs [:, :-1] and next-token targets [:, 1:]). The trainer picks
    #: the data source, the sample input and the step's prologue, loss and
    #: metrics by it; an image batch compiles to the step it always did
    kind: str = "image"

    def describe(self) -> dict:
        """JSON-ready receipt for bench rows and the trainer start record."""
        return {"model": self.model, "wire": self.wire,
                "space_to_depth": self.space_to_depth,
                "stem_dtype": self.stem_dtype}


#: The zoo contract table — one row per registered model. A model missing
#: here gets the conservative default (unpacked, u8 wire, ImageNet
#: constants) via `ingest_descriptor`.
INGEST_DESCRIPTORS: Dict[str, IngestDescriptor] = {
    "vggf": IngestDescriptor("vggf", space_to_depth=True),
    "vgg16": IngestDescriptor("vgg16"),
    "resnet50": IngestDescriptor("resnet50"),
    "vit_s16": IngestDescriptor("vit_s16"),
    # the half-width distillation target (train/distill.py) behind the
    # `student` serving tier — same stem contract as the flagship it
    # stands in for, but never a training preset
    "vggf_student": IngestDescriptor("vggf_student", space_to_depth=True,
                                     serving_only=True),
    # the decoder-only language models (models/mistral4.py,
    # models/nemotron_h.py, models/ling3.py): packed int32 tokens, no pixel
    # wire;
    # `zoo_model_names` (the image grids, the serving router) leaves them
    # out by their kind
    "mistral4": IngestDescriptor("mistral4", kind="tokens", wire="tokens"),
    "nemotron_h": IngestDescriptor("nemotron_h", kind="tokens",
                                   wire="tokens"),
    "ling3": IngestDescriptor("ling3", kind="tokens", wire="tokens"),
}


def reject_raw_uint8(x, model_name: str) -> None:
    """The zoo-wide `accepts_uint8=False` contract, enforced once: raw
    wire pixels must be finished (normalize/cast, data/device_ingest.py)
    BEFORE any stem — silently casting 0..255 integers to the compute
    dtype would train on an input distribution ~50x off the normalized
    one, with no error. The trainer/eval/predict steps all install the
    finish; a uint8 reaching a model means some caller bypassed it.
    Dtype-name comparison keeps this module jax-free (the import-weight
    contract in the module docstring); trace-time shapes carry a real
    dtype either way."""
    if str(getattr(x, "dtype", "")) == "uint8":
        raise TypeError(
            f"{model_name} received a raw uint8 batch — apply the "
            "device-finish prologue (data/device_ingest.py "
            "make_device_finish) before the model; the train/eval/predict "
            "steps install it automatically")


def zoo_model_names(*, include_serving_only: bool = False) -> Tuple[str, ...]:
    """The registered image zoo, in table order — the serving router's model
    vocabulary (serving/server.py fronts one engine per descriptor row)
    and the per-model test grids iterate THIS, never a hand-kept list.
    Serving-only rows (the distilled student) are excluded by default so
    training grids and presets never see them; the serving surfaces opt
    in with `include_serving_only=True`."""
    return tuple(name for name, d in INGEST_DESCRIPTORS.items()
                 if d.kind == "image"
                 and (include_serving_only or not d.serving_only))


def ingest_descriptor(model_name: str) -> IngestDescriptor:
    """The model's ingest contract; unknown models get the conservative
    unpacked default (so out-of-zoo experiments keep working) — packing is
    strictly opt-in via the table because a wrongly-packed batch fails
    shapes loudly while an unpacked one merely loses the stem trick."""
    desc = INGEST_DESCRIPTORS.get(model_name)
    if desc is None:
        return IngestDescriptor(model_name)
    return desc
