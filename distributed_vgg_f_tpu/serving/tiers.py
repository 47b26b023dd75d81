"""Latency tiers for the predict server (r23): bf16 / int8 / student
engine variants behind one router.

Each tier is a full `PredictEngine` — its own AOT bucket ladder over its
own forward — so the PR 14 parity contract holds PER TIER: a tier's
server response is bitwise-equal to that tier's own offline `engine.run`,
because both are the same executables. The ladder:

- **fp32** — the base engine, unchanged (the r17 surface).
- **bf16** — the same architecture with `compute_dtype` flipped to
  bfloat16: params cast ONCE at build (not per request), the
  device-finish prologue emits bf16 activations, logits come back fp32
  (every zoo model casts its output, train/predict softmaxes in f32).
- **int8** — post-training quantization of the FC-heavy heads (fc6/fc7/
  fc8 are ~90 % of CNN-F's parameters, arXiv 2004.13336's exact
  workload): per-OUT-channel symmetric weight scales, per-tensor
  activation scales from a deterministic calibration pass over the u8
  wire. The per-tensor activation scale forces a structural fact this
  tier exploits for latency: any channel whose calibrated range falls
  below half the activation LSB (`scale/2`) rounds to ZERO under int8
  quantization, so its row of the next weight matrix contributes nothing
  — the engine elides those channels from the compacted GEMMs instead of
  multiplying zeros. On calibration-range inputs the compacted network
  computes exactly what dense int8 emulation computes (pinned in
  tests/test_serving_tiers.py); off-range inputs are where the tier's
  committed accuracy-delta receipt earns its keep. The conv trunk stays
  in the model's serving compute dtype (bf16 on the TPU presets) — heads
  are where the quantizable parameter mass lives.
- **student** — the half-width `vggf_student` (train/distill.py) serving
  the flagship's route: same wire, same descriptor contract, ~4x fewer
  head parameters.

Quantized execution note: weights are STORED int8 + f32 scales (that is
the receipt and the device-residency win); the host executes the heads as
dequantized-constant GEMMs (XLA folds `wq * scale` once at compile), and
activations are still rounded/clamped onto the int8 grid so the numerics
are int8-faithful. XLA:CPU has no fast int8 GEMM kernel (measured ~6x
SLOWER than f32 at batch 8 on this host — benchmarks/runs/host_r23
protocol notes); the MXU int8 path is not measured.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Sequence

import numpy as np

from distributed_vgg_f_tpu.config import SERVING_TIERS, ServingTiersConfig
from distributed_vgg_f_tpu.serving.engine import PredictEngine

#: Router vocabulary, descending fidelity (mirrors config.SERVING_TIERS;
#: telemetry/schema.py keeps its own literal by the leaf-module contract).
TIERS = SERVING_TIERS

#: The FC head stack the int8 tier quantizes (CNN-F naming, models/vggf.py
#: — the int8 builder refuses architectures without it).
_HEAD_LAYERS = ("fc6", "fc7", "fc8")


# --------------------------------------------------------------------- bf16
def _cast_tree(tree, dtype):
    import jax
    import jax.numpy as jnp
    return jax.tree_util.tree_map(
        lambda a: a.astype(dtype) if np.dtype(a.dtype) == np.float32 else a,
        tree) if tree is not None else None


def build_bf16_engine(base: PredictEngine) -> PredictEngine:
    """The bf16 tier: clone the model at compute_dtype=bfloat16, cast the
    params once, finish the wire into bf16 — logits stay fp32 (the zoo
    models cast their outputs; the shared predict forward softmaxes f32)."""
    import jax.numpy as jnp
    model = base._model.clone(compute_dtype=jnp.bfloat16)
    return PredictEngine(
        model_name=base.model_name, model=model,
        params=_cast_tree(base._params, jnp.bfloat16),
        batch_stats=base._batch_stats,
        image_size=base.image_size, num_classes=base.num_classes,
        buckets=base.buckets, max_batch=base.buckets[-1],
        image_dtype="bfloat16", mean_rgb=base._mean, stddev_rgb=base._std,
        tier="bf16")


# --------------------------------------------------------------------- int8
@dataclasses.dataclass(frozen=True)
class CalibrationResult:
    """The committed activation-range pass: one per-tensor scale per head
    input plus the kept-channel index sets the sub-LSB elision derives
    from them. `receipt()` is the JSON the bench commits next to the
    latency rows so a re-run can reproduce the exact quantization."""
    scales: Dict[str, float]          # head layer -> activation LSB a
    keep: Dict[str, np.ndarray]       # head layer -> kept input channels
    widths: Dict[str, int]            # head layer -> dense input width
    batches: int
    batch_size: int
    seed: int

    def receipt(self) -> dict:
        return {"scales": {k: float(v) for k, v in self.scales.items()},
                "kept": {k: int(len(v)) for k, v in self.keep.items()},
                "widths": {k: int(v) for k, v in self.widths.items()},
                "batches": self.batches, "batch_size": self.batch_size,
                "seed": self.seed}


def calibration_images(image_size: int, *, batches: int, batch_size: int,
                       seed: int) -> np.ndarray:
    """Deterministic u8-wire calibration stream. Drawn from the teacher
    task's procedural textures (data/teacher.py `_raw_images`) — the
    distribution the teacher-task weights actually serve — at a seeded
    index range disjoint from both train and eval splits."""
    from distributed_vgg_f_tpu.data.teacher import _raw_images
    n = batches * batch_size
    idx = np.arange(n) + (int(seed) << 16) + (1 << 24)
    raw = _raw_images(idx, image_size, base_seed=11)
    return np.clip(np.rint(raw), 0, 255).astype(np.uint8)


def _split_params(params):
    """(trunk_params, head_params) — refuses non-CNN-F head stacks."""
    p = {k: v for k, v in dict(params).items()}
    missing = [k for k in _HEAD_LAYERS if k not in p]
    if missing:
        raise ValueError(
            f"int8 tier needs the CNN-F head stack {list(_HEAD_LAYERS)}; "
            f"params are missing {missing} — only the vggf family serves "
            "this tier")
    heads = {k: p.pop(k) for k in _HEAD_LAYERS}
    return p, heads


def _make_trunk(model, trunk_variables, finish):
    """The conv trunk as a standalone function: run the model capturing
    conv5's output, then apply the SAME relu/pool/flatten the model does
    (ops imported, not duplicated). XLA dead-code-eliminates the unused
    head computation when this is jitted, so the trunk costs trunk."""
    import flax.linen as nn
    import jax.numpy as jnp
    from distributed_vgg_f_tpu.ops.pooling import maxpool_3x3s2_ceil

    def trunk(images):
        _, inter = model.apply(
            trunk_variables, finish(images), train=False,
            capture_intermediates=lambda mdl, _: mdl.name == "conv5")
        c5 = inter["intermediates"]["conv5"]["__call__"][0]
        h = maxpool_3x3s2_ceil(nn.relu(c5))
        return h.reshape((h.shape[0], -1)).astype(jnp.float32)

    return trunk


def quantize_dense(kernel: np.ndarray):
    """Per-OUT-channel symmetric int8 weight quantization:
    `scale_j = max_i |W_ij| / 127`, `Wq = clip(round(W / scale), ±127)`.
    Returns (int8 kernel, f32 per-column scales)."""
    w = np.asarray(kernel, np.float32)
    scale = np.max(np.abs(w), axis=0) / 127.0
    scale = np.where(scale > 0, scale, 1.0).astype(np.float32)
    wq = np.clip(np.rint(w / scale), -127, 127).astype(np.int8)
    return wq, scale


def calibrate(base: PredictEngine, images: np.ndarray, *,
              batch_size: int, seed: int) -> CalibrationResult:
    """The activation-range pass over the u8 wire: run the fp32 forward on
    the calibration stream capturing each head layer's INPUT, record the
    per-tensor max (→ the activation LSB a = max/127) and per-channel
    maxima (→ which channels stay below a/2 and therefore always quantize
    to zero — the elision set's complement)."""
    import flax.linen as nn
    import jax
    import jax.numpy as jnp
    from distributed_vgg_f_tpu.data.device_ingest import make_device_finish

    model, params = base._model, base._params
    finish = make_device_finish(base._mean, base._std)
    variables = {"params": params}
    if base._batch_stats:
        variables["batch_stats"] = base._batch_stats

    def head_inputs(imgs):
        _, inter = model.apply(
            variables, finish(imgs), train=False,
            capture_intermediates=lambda mdl, _: mdl.name in
            ("conv5",) + _HEAD_LAYERS)
        from distributed_vgg_f_tpu.ops.pooling import maxpool_3x3s2_ceil
        c5 = inter["intermediates"]["conv5"]["__call__"][0]
        x6 = maxpool_3x3s2_ceil(nn.relu(c5))
        x6 = x6.reshape((x6.shape[0], -1)).astype(jnp.float32)
        x7 = nn.relu(inter["intermediates"]["fc6"]["__call__"][0]) \
            .astype(jnp.float32)
        x8 = nn.relu(inter["intermediates"]["fc7"]["__call__"][0]) \
            .astype(jnp.float32)
        return x6, x7, x8

    fn = jax.jit(head_inputs)
    per_channel = {k: None for k in _HEAD_LAYERS}
    n = int(images.shape[0])
    batches = 0
    for i in range(0, n, batch_size):
        chunk = images[i:i + batch_size]
        if chunk.shape[0] != batch_size:
            break  # AOT discipline: one shape, one executable
        batches += 1
        for layer, x in zip(_HEAD_LAYERS, fn(chunk)):
            m = np.max(np.abs(np.asarray(x)), axis=0)
            per_channel[layer] = m if per_channel[layer] is None \
                else np.maximum(per_channel[layer], m)
    if batches == 0:
        raise ValueError(
            f"calibration stream of {n} images yields no full batch of "
            f"{batch_size}")
    scales, keep, widths = {}, {}, {}
    for layer, m in per_channel.items():
        a = float(np.max(m)) / 127.0
        if a <= 0:
            raise ValueError(
                f"calibration saw an all-zero input to {layer} — the "
                "weights are untrained garbage or the stream is empty")
        scales[layer] = a
        # channels whose calibrated range stays below half an LSB round
        # to 0 under clip(round(x / a)) — eliding them is int8-exact on
        # calibration-range data
        keep[layer] = np.flatnonzero(m >= a / 2).astype(np.int32)
        widths[layer] = int(m.size)
    return CalibrationResult(scales=scales, keep=keep, widths=widths,
                             batches=batches, batch_size=int(batch_size),
                             seed=int(seed))


def _quantized_heads(params, calib: CalibrationResult):
    """Quantize + compact the head stack. Returns (folded f32 constants
    for execution, int8 residency bytes for the HBM estimate)."""
    _, heads = _split_params(params)
    k6, k7, k8 = (calib.keep[layer] for layer in _HEAD_LAYERS)
    a6, a7, a8 = (calib.scales[layer] for layer in _HEAD_LAYERS)
    folded, int8_bytes = {}, 0
    for layer, a, rows, cols in (("fc6", a6, k6, k7), ("fc7", a7, k7, k8),
                                 ("fc8", a8, k8, None)):
        w = np.asarray(heads[layer]["kernel"], np.float32)
        b = np.asarray(heads[layer]["bias"], np.float32)
        wq, s = quantize_dense(w)
        wq = wq[rows]
        if cols is not None:
            wq, s, b = wq[:, cols], s[cols], b[cols]
        # executed form: dequantized-constant GEMM (XLA folds this once);
        # stored form: the int8 matrix + f32 scales the receipt counts
        folded[layer] = {"w": wq.astype(np.float32) * (a * s), "b": b}
        int8_bytes += wq.size + s.size * 4 + b.size * 4
    return folded, int8_bytes


def dense_int8_reference(params, calib: CalibrationResult):
    """Dense (no-elision) int8 emulation with the same scales — the
    equivalence oracle for the compacted engine (tests pin compacted ≡
    dense on calibration-range inputs)."""
    import jax.numpy as jnp
    _, heads = _split_params(params)

    def q(x, a):
        return jnp.clip(jnp.round(x / a), -127, 127)

    mats = {}
    for layer in _HEAD_LAYERS:
        wq, s = quantize_dense(np.asarray(heads[layer]["kernel"]))
        a = calib.scales[layer]
        mats[layer] = (jnp.asarray(wq.astype(np.float32) * (a * s)),
                       jnp.asarray(np.asarray(heads[layer]["bias"],
                                              np.float32)))

    def heads_fn(x):
        import jax.nn
        w, b = mats["fc6"]
        x = jax.nn.relu(q(x, calib.scales["fc6"]) @ w + b)
        w, b = mats["fc7"]
        x = jax.nn.relu(q(x, calib.scales["fc7"]) @ w + b)
        w, b = mats["fc8"]
        return q(x, calib.scales["fc8"]) @ w + b

    import jax
    return heads_fn


def build_int8_engine(base: PredictEngine,
                      calib: Optional[CalibrationResult] = None, *,
                      tiers_cfg: Optional[ServingTiersConfig] = None
                      ) -> PredictEngine:
    """The int8 tier over a base engine: calibrate (unless handed a
    committed `CalibrationResult`), quantize + compact the heads, build
    the tier forward (trunk → activation-quantized compacted GEMMs → f32
    softmax) and wrap it in a fresh AOT bucket ladder."""
    import jax
    import jax.numpy as jnp
    from distributed_vgg_f_tpu.data.device_ingest import make_device_finish

    cfg = tiers_cfg if tiers_cfg is not None else ServingTiersConfig()
    if calib is None:
        images = calibration_images(
            base.image_size, batches=cfg.calibration_batches,
            batch_size=cfg.calibration_batch_size,
            seed=cfg.calibration_seed)
        calib = calibrate(base, images,
                          batch_size=cfg.calibration_batch_size,
                          seed=cfg.calibration_seed)
    trunk_params, _ = _split_params(base._params)
    folded, int8_bytes = _quantized_heads(base._params, calib)
    finish = make_device_finish(base._mean, base._std)
    variables = {"params": base._params}
    if base._batch_stats:
        variables["batch_stats"] = base._batch_stats
    trunk = _make_trunk(base._model, variables, finish)
    k6 = jnp.asarray(calib.keep["fc6"])
    a6, a7, a8 = (calib.scales[layer] for layer in _HEAD_LAYERS)
    w6, b6 = jnp.asarray(folded["fc6"]["w"]), jnp.asarray(folded["fc6"]["b"])
    w7, b7 = jnp.asarray(folded["fc7"]["w"]), jnp.asarray(folded["fc7"]["b"])
    w8, b8 = jnp.asarray(folded["fc8"]["w"]), jnp.asarray(folded["fc8"]["b"])

    def forward(images):
        x = trunk(images)
        q = jnp.clip(jnp.round(x / a6), -127, 127)
        x = jax.nn.relu(jnp.take(q, k6, axis=1) @ w6 + b6)
        q = jnp.clip(jnp.round(x / a7), -127, 127)
        x = jax.nn.relu(q @ w7 + b7)
        q = jnp.clip(jnp.round(x / a8), -127, 127)
        logits = q @ w8 + b8
        return jax.nn.softmax(logits.astype(jnp.float32), axis=-1)

    eng = PredictEngine(
        model_name=base.model_name, model=base._model, params=trunk_params,
        batch_stats=base._batch_stats, image_size=base.image_size,
        num_classes=base.num_classes, buckets=base.buckets,
        max_batch=base.buckets[-1], image_dtype=base._image_dtype,
        mean_rgb=base._mean, stddev_rgb=base._std, tier="int8",
        forward=forward, extra_param_bytes=int8_bytes)
    eng.calibration = calib
    return eng


# ------------------------------------------------------------------ student
def build_student_engine(base: PredictEngine, *, student_model,
                         student_params, student_batch_stats=None
                         ) -> PredictEngine:
    """The student tier: the distilled half-width architecture serving the
    flagship's route — its own forward, its own ladder, the flagship's
    wire contract (same descriptor family, same class count)."""
    return PredictEngine(
        model_name=base.model_name, model=student_model,
        params=student_params, batch_stats=student_batch_stats,
        image_size=base.image_size, num_classes=base.num_classes,
        buckets=base.buckets, max_batch=base.buckets[-1],
        image_dtype=base._image_dtype, mean_rgb=base._mean,
        stddev_rgb=base._std, tier="student", served_by="vggf_student")


def build_tier_engines(base: PredictEngine, cfg: ServingTiersConfig, *,
                       tiers: Sequence[str] = ("bf16", "int8"),
                       calib: Optional[CalibrationResult] = None,
                       student_model=None, student_params=None,
                       student_batch_stats=None) -> Dict[str, PredictEngine]:
    """Build the requested tier ladder over one base engine. The student
    tier is included iff its distilled weights are supplied (it cannot be
    derived from the flagship's checkpoint)."""
    out: Dict[str, PredictEngine] = {}
    for tier in tiers:
        if tier == "fp32":
            continue
        if tier == "bf16":
            out[tier] = build_bf16_engine(base)
        elif tier == "int8":
            out[tier] = build_int8_engine(base, calib, tiers_cfg=cfg)
        elif tier == "student":
            continue  # handled below: needs its own weights
        else:
            raise ValueError(f"unknown tier {tier!r}; ladder is {TIERS}")
    if student_model is not None and student_params is not None:
        out["student"] = build_student_engine(
            base, student_model=student_model, student_params=student_params,
            student_batch_stats=student_batch_stats)
    return out
