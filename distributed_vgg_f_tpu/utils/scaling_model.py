"""Analytic multi-chip scaling model — the ≥90 % v4-8 → v4-128 north star
(BASELINE.json `north_star`).

A pod slice is not reachable from the builder's machines (one host of at
most four v5e chips), so the scaling-efficiency target cannot be *measured*
there. What CAN be committed is the physics: synchronous data-parallel SGD has
exactly one cross-replica dependency per step — the gradient all-reduce
(train/step.py [SYNC]) — so predicted efficiency is a function of

  - the measured single-chip step time (benchmarks/runs/tpu_r*/),
  - the per-step collective bytes (param bytes and layout — replicated
    all-reduce vs ZeRO-1 reduce-scatter + all-gather),
  - the chip's ICI injection bandwidth and the slice's hop latency,
  - how much of the collective XLA hides under backward compute, and
  - the host input pipeline, which binds before ICI does for the fast
    models (SURVEY.md §7 names the host path as where the target is won
    or lost).

Every input is an explicit field with its provenance in `ASSUMPTIONS`;
`predict()` is pure arithmetic (unit-tested in tests/test_scaling_model.py),
and `benchmarks/scaling_model.py` renders the committed table.

Collective cost model (bandwidth-optimal ring all-reduce; the scaling-book
recipe): a gradient of G bytes costs 2·G·(N−1)/N wire bytes per chip.
ZeRO-1 moves the SAME wire bytes (reduce-scatter G·(N−1)/N + all-gather
G·(N−1)/N) — its win is opt-state memory and update FLOPs, not bandwidth.
On a v4 3-D torus the reduction runs per-dimension, so the latency term uses
torus hops (3·(∛N−1) per traversal direction), not a flat ring's N−1; with
µs-class hops it is negligible at these message sizes either way.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Mapping, Sequence

# ---------------------------------------------------------------------------
# Inputs, each with provenance. Values are overridable per-call; these are the
# committed defaults the README table is generated from.
# ---------------------------------------------------------------------------

#: The r5-measured native-loader decode rate (img/s/core): the LOWER of the
#: two committed quiet-host best-of-3 contract lines after the r5 bilinear
#: hoists in native/jpeg_loader.cc (734.31 spread 0.014 / 728.05 spread
#: 0.039 — benchmarks/runs/host_r5/host_pipeline_run{1,2}.json). Historical
#: since r6 (kept as a sensitivity row; float32 unpacked output, 1-vCPU
#: host). The frozen r4 baseline 556.34 lives in benchmarks/baseline.json
#: so vs_baseline keeps recording the win.
HOST_DECODE_RATE_R5 = 728.05

#: The r6-measured native-loader decode rate (img/s/core) after the SIMD
#: resample path (native/jpeg_loader.cc "resample kernels": runtime-
#: dispatched AVX2+FMA vertical/horizontal lerp + normalize, bf16 rounded
#: in-lane, memcpy space-to-depth repack). Measured in the FLAGSHIP INGEST
#: configuration — bfloat16 output + space-to-depth, the exact layout the
#: judged 22,028 img/s/chip device rate consumes (bench.py) — because the
#: provisioning quotient divides that device rate; r5's constant was the
#: float32-unpacked rate, a different (then-faster, now-slower) basis.
#: Quiet-host min-of-6 windows, two committed runs, LOWER contract value
#: kept (1064.76 spread 0.049 / 1031.36 spread 0.109); same-box same-config
#: scalar before-rate 862.17/854.68 → the kernels are a 1.21–1.24×
#: end-to-end win with the resample phase cut ~410→~160 µs/img and the
#: residual 80 % of the budget pinned as libjpeg entropy+IDCT (the
#: committed profile split in each artifact). Host: 2-vCPU AVX2/AVX512 box,
#: benchmarks/runs/host_r6/decode_{scalar,simd}_bf16s2d_run{1,2}.json; the
#: r5 1-vCPU box is gone, so cross-round ratios must go through the
#: same-box scalar column, not HOST_DECODE_RATE_R5. Historical since r7
#: (kept as a sensitivity row).
HOST_DECODE_RATE_R6 = 1031.36

#: The r7-measured native-loader decode rate (img/s/core) after the DCT-
#: scaled + partial decode rework in native/jpeg_loader.cc (ABI v5:
#: power-of-two scale chooser over libjpeg-turbo's SIMD IDCT sizes,
#: dlsym-probed jpeg_crop_scanline/jpeg_skip_scanlines partial decode with
#: a fancy-upsampling context margin, per-thread reused decode context +
#: grow-only buffer pool). Same flagship ingest basis as r6 (bfloat16 +
#: space-to-depth, tfrecord, 320x256 noise sources — the continuity
#: protocol): LOWER of the final alternating drift-controlled pair
#: (1027.79 / 991.15, runs 3/4 of benchmarks/runs/host_r7/
#: decode_r7_bf16s2d_320noise_run{1..4}.json). The movement from
#: HOST_DECODE_RATE_R6=1031.36 is BOX DRIFT, not a decode regression:
#: same-session worktree runs of the r6 code on the same sources measure
#: 989.3–1047.1 (decode_r6code_* columns) — this virtualized box now sits
#: ~3-4 % below its r6-era windows, and r7 ≡ r6 code within noise on this
#: config. The r7 wins live elsewhere, receipted in host_r7/README.md:
#: +12.1 % same-box on the f32-unpacked contract config (buffer pool +
#: output ring; 907.3 → 1017.0), +17-26 % over full decode at ≥448px
#: sources (scaled+partial machinery, now kill-switchable and exact), and
#: the committed entropy-floor analysis showing why no decode-side change
#: moves the ≥448px rate past ~1150 img/s/core on this host class. The
#: SINGLE source for the provisioning default below, the predict()
#: host-ceiling default, the sensitivity rows in benchmarks/
#: scaling_model.py, and the tests — an r8 re-measure is a one-line
#: change here.
HOST_DECODE_RATE_R7 = 991.15

#: The r8-measured native-loader decode rate (img/s/core) on the uint8
#: ingest wire (native/jpeg_loader.cc ABI v6: fixed-point integer resample
#: kernels emitting raw uint8 HWC — normalize/cast/space-to-depth move to
#: the device-finish prologue, data/device_ingest.py). The provisioning
#: basis FOLLOWS the production ingest contract: the flagship now ships
#: data.wire='u8' (1 B/px through device_put, 0.5x the bf16 wire, with
#: the finishing math fused into the jitted step), so the constant is the
#: LOWER of the committed u8 flagship-replacement pair (1114.19 / 1200.29
#: — benchmarks/runs/host_r9/decode_r8_u8_s2d_320noise_run{1,2}.json;
#: s2d requested, deferred to device — host work identical to the plain
#: u8 rows, which measured 1180.9-1226.4 in the same session). Same-
#: session controls (host_r9/README.md): r7-code worktree f32 columns sat
#: at 1069.9-1089.9 (this box currently runs ~5-8 % ABOVE its r7-era
#: windows — cross-round ratios must go through the same-session columns,
#: not HOST_DECODE_RATE_R7), r8 host wires are parity-within-noise with
#: r7 code, and the u8 win is +10.4 % lower-vs-lower / +12.5 % best-vs-
#: best over the same-session f32 control, with the resample phase cut
#: ~130-140 → ~81-89 µs/img. Kill-switches: DVGGF_WIRE_U8=0 env /
#: dvgg_jpeg_set_wire_u8 runtime / -DDVGGF_NO_WIRE_U8 compile-out, all
#: falling back to the byte-identical r7 host path. The SINGLE source for
#: the provisioning default below, the predict() host-ceiling default,
#: and the tests — an r9 re-measure is a one-line change here.
HOST_DECODE_RATE_R8 = 1114.19

#: The r9-measured native-loader decode rate (img/s/core) with the
#: restart-marker excerpt entropy decode engaged (native/jpeg_loader.cc
#: ABI v7: the decoder scans RSTn segment boundaries with a pure memchr
#: byte walk, splices a synthetic JPEG from only the segments covering
#: the sampled crop band, and entropy-parses nothing outside it — the
#: sequential path must Huffman-parse every row above the crop; parity
#: suite pins the excerpt byte-identical). Same continuity basis as r8
#: (u8 wire + deferred s2d, tfrecord, 320x256 noise sources, min-of-6
#: alternating windows) with one NEW dataset assumption the constant
#: inherits from the production ingest contract: the dataset carries
#: interval-1 restart markers, injected ONCE offline by the lossless
#: coefficient-domain transcode (benchmarks/reencode_restart.py, ~1-3 %
#: size cost — pixels identical). LOWER of the committed restart-on trio
#: (1228.96 / 1336.17 / 1268.34 — benchmarks/runs/host_r10/
#: decode_r10_on_320noise_rst1_run{1..3}.json). Same-session controls
#: (host_r10/README.md): the restart-OFF columns on the same marker
#: sources measured 1032.0-1050.7 — this box has drifted ~6 % BELOW its
#: r9-session windows, so the committed-vs-committed +10.3 % over
#: HOST_DECODE_RATE_R8 UNDERSTATES the feature; drift-controlled the
#: excerpt decode is +19.1 % lower-vs-lower on this basis, +10.1 % at
#: 448 px textured and +35.9 % at 768 px (the win rises with resolution
#: because the Huffman share does). A marker-absent dataset decodes
#: sequentially (receipted in restart_stats) and reads as the off
#: column, i.e. the r8 rate modulo drift. Kill-switches:
#: DVGGF_DECODE_RESTART=0 env / dvgg_jpeg_set_restart runtime /
#: -DDVGGF_NO_RESTART compile-out, all byte-identical fallbacks. The
#: SINGLE source for the provisioning default below, the predict()
#: host-ceiling default, and the tests — an r10 re-measure is a one-line
#: change here. (The r9 snapshot cache — warm epochs 2.69x cold,
#: host_r10 — is opt-in and deliberately NOT a provisioning basis: warm
#: epochs re-serve epoch-1 crop geometry, a training-distribution trade
#: the spec must not silently assume.)
HOST_DECODE_RATE_R9 = 1228.96

#: r13 (bench round r13, feature round r10) — the fused-on-device-
#: augmentation + one-ingest-contract round's pins. All four are
#: measured on the SAME protocol as HOST_DECODE_RATE_R9 (u8 wire,
#: tfrecord, 320x256 noise, interval-1 restart markers, min-of-6
#: alternating windows, LOWER of the committed run pair —
#: benchmarks/runs/host_r13/) and gate their OWN (model, augment) basis
#: in the regression sentinel, independent of the VGG-F flips-on-host
#: line. Absolute levels sit ~9-15 % below HOST_DECODE_RATE_R9 because
#: this box drifted between sessions (window spreads 4-16 % in the
#: committed artifacts; host_r13/README.md carries the same-session
#: evidence) — the within-session claims are what these rows pin:
#:
#: AUG (vggf, augment-on): host flips DELETED (ABI v9 per-loader
#: switch; the fused stage in data/augment.py owns them on device).
#: The same-session alternating receipt (decode_r13_augment_on_run1
#: `augment_overhead`) measured augment-ON 1209.06 vs OFF 1181.18
#: img/s/core (-2.36 % "overhead" = noise-floor; ON does strictly less
#: host work) at IDENTICAL wire bytes/image (150528) — augmentation
#: diversity at zero host cost, the r13 acceptance claim. The fused
#: stage's STEP cost is the separate augment_step_overhead.json receipt
#: (+0.27 % min-of-6, <2 % budget).
HOST_DECODE_RATE_R10_AUG = 1057.42
#: Zoo rows (vgg16 / resnet50 / vit_s16 ingest descriptors: u8 wire,
#: NO space-to-depth — models/ingest.py): host decode work is identical
#: to the flagship's on the u8 wire by construction (packing was already
#: deferred to the device), so these pin the SAME pipeline under each
#: model's label; their value is that a zoo preset's ingest regression
#: now fails its own gate instead of hiding behind the VGG-F line.
HOST_ZOO_RATE_R10_VGG16 = 1055.52
HOST_ZOO_RATE_R10_RESNET50 = 1076.98
HOST_ZOO_RATE_R10_VIT_S16 = 1041.85

#: r14 (feature round r17) — the serving chain's first pin, its OWN metric
#: (`serving_admitted_rps`, telemetry/regress.SERVING_PINS): peak admitted
#: requests/sec of the dynamic-batching predict server among open-loop
#: RPS-ramp stages whose admitted p99 stayed within the SLO budget —
#: benchmarks/serving_bench.py on CPU (vggf head, 128 px u8 payloads,
#: bucket ladder 1..8, LOWER of the committed run pair,
#: benchmarks/runs/host_r16/serving_openloop_run{1,2}.json). A CPU number
#: on a shared box: it pins the admission machinery's throughput floor
#: (batching + HTTP + shed path), not device inference — device serving
#: RPS is not measured.
SERVING_RPS_R14 = 278.05

#: r18 (feature round r23) — the latency-TIER ladder's pins, one per
#: (vggf, tier) basis: same open-loop protocol as SERVING_RPS_R14
#: (Poisson ramp, admitted-RPS-within-SLO contract, LOWER of the
#: committed run pair, benchmarks/runs/host_r23/serving_r18_tier_*) but
#: on TRAINED weights at the teacher task's native 32 px geometry —
#: where CNN-F's FC heads dominate the forward (fc6_in=256), the compute
#: profile the tier designs target. NOT comparable to the 128 px
#: fresh-init R14 line (different basis, drift-noted in SERVING_PINS).
#: The frontier claim the receipts gate: int8 (calibrated sub-LSB
#: channel elision over per-out-channel-quantized heads) and student
#: (half-width distilled vggf_student) admit STRICTLY more RPS than
#: fp32 within the same SLO, at top-1 deltas within the configured
#: bounds (row `accuracy` blocks); bf16 is emulated on XLA:CPU and pins
#: its CPU baseline only — its latency claim needs an MXU and is not
#: measured.
SERVING_RPS_R18_FP32 = 165.97
SERVING_RPS_R18_BF16 = 172.85
SERVING_RPS_R18_INT8 = 210.09
SERVING_RPS_R18_STUDENT = 300.94

ASSUMPTIONS: Mapping[str, str] = {
    "v4_peak_bf16_flops": "275e12 — TPU v4 public spec (ISCA'23 paper class)",
    "v5e_peak_bf16_flops": "197e12 — TPU v5e public spec",
    "ici_links_v4": "6 links/chip (3-D torus), ~45 GB/s usable per link per "
                    "direction — 50 GB/s-class links derated ~10 % for "
                    "protocol overhead",
    "ici_collective_utilization": "0.8 — fraction of aggregate injection "
                                  "bandwidth a multi-ring torus all-reduce "
                                  "sustains (XLA uses all torus dimensions)",
    "hop_latency_s": "1e-6 — per-ICI-hop latency, µs class",
    "overlap_fraction": "0.75 — fraction of backward compute XLA's latency-"
                        "hiding scheduler can run under the all-reduce "
                        "(layerwise grads are ready before backward ends); "
                        "0.0 row = no-overlap worst case",
    "backward_fraction_of_step": "2/3 — fwd:bwd FLOP ratio 1:2 for these "
                                 "nets; the optimizer tail is ~free",
    "v4_step_time_scaling": "t_v4 = t_v5e × 197/275 — assumes the measured "
                            "v5e MFU carries to v4 (both MXU-bound on the "
                            "same fusions); HBM ratio (1228/819 GB/s) is "
                            "MORE favorable, so this is the conservative "
                            "axis",
    "grad_dtype_bytes": "4 — grads/params are fp32 in train/step.py "
                        "(compute is bf16; the reduction is full precision)",
    "v4_chips_per_host": "4 — one v4 host serves a 2×2×1 tray",
    "v4_host_cores": "240 — v4 VM host vCPUs (n2d class)",
    "host_decode_rate_per_core": f"{HOST_DECODE_RATE_R9} img/s/core "
                                 "(HOST_DECODE_RATE_R9) — measured r9 "
                                 "with the restart-marker excerpt "
                                 "entropy decode (native/jpeg_loader.cc "
                                 "ABI v7) on the u8 ingest wire: LOWER "
                                 "of the committed restart-on continuity "
                                 "trio (1228.96/1336.17/1268.34 — "
                                 "benchmarks/runs/host_r10/decode_r10_"
                                 "on_320noise_rst1_run{1..3}.json), "
                                 "+19.1 % lower-vs-lower over the same-"
                                 "session restart-off columns (1032.0-"
                                 "1050.7; the box drifted ~6 % BELOW its "
                                 "r9-session windows, so the +10.3 % "
                                 "over the committed r8 value "
                                 "understates). ASSUMES the dataset "
                                 "carries interval-1 restart markers "
                                 "(one-time lossless transcode, "
                                 "benchmarks/reencode_restart.py); a "
                                 "marker-absent dataset reads as the r8 "
                                 "rate 1114.19 modulo drift. The r8 rate "
                                 "(u8 wire, marker-free), r7 991.15, r6 "
                                 "1031.36, r5 728.05 and the frozen r4 "
                                 "baseline 556.34 stay as sensitivity "
                                 "rows / vs_baseline anchor",
    "step_times": "measured v5e device benches, benchmarks/runs/tpu_r3/ "
                  "(vggf 22,028 img/s/chip @2048; vgg16 1,372.8 @128; "
                  "resnet50 2,543.4 @256; vit_s16 1,910.1 @256)",
}


@dataclasses.dataclass(frozen=True)
class ChipSpec:
    name: str
    peak_bf16_flops: float
    ici_links: int
    ici_link_bytes_per_s: float      # usable, per direction
    chips_per_host: int
    host_cores: int

    @property
    def injection_bytes_per_s(self) -> float:
        return self.ici_links * self.ici_link_bytes_per_s


V4 = ChipSpec("TPU v4", 275e12, 6, 45e9, 4, 240)
V5E = ChipSpec("TPU v5e", 197e12, 4, 45e9, 8, 224)


@dataclasses.dataclass(frozen=True)
class ModelPoint:
    """A measured single-chip operating point (v5e, device-only bench)."""
    name: str
    param_count: int                 # exact, jax.eval_shape over model.init
    per_chip_batch: int
    v5e_images_per_sec_per_chip: float

    @property
    def v5e_step_time_s(self) -> float:
        return self.per_chip_batch / self.v5e_images_per_sec_per_chip

    def step_time_on(self, chip: ChipSpec) -> float:
        """Compute-bound rescale by peak-FLOPs ratio (ASSUMPTIONS)."""
        return self.v5e_step_time_s * (V5E.peak_bf16_flops
                                       / chip.peak_bf16_flops)


# Exact param counts: jax.eval_shape over model.init (models/*.py), 2026-07.
MEASURED: Sequence[ModelPoint] = (
    ModelPoint("vggf", 60_834_536, 2048, 22_028.4),
    ModelPoint("vgg16", 138_357_544, 128, 1_372.79),
    ModelPoint("resnet50", 25_557_032, 256, 2_543.39),
    ModelPoint("vit_s16", 22_050_664, 256, 1_910.06),
)


# ---------------------------------------------------------------------------
# The model
# ---------------------------------------------------------------------------

def allreduce_bytes_per_chip(grad_bytes: float, n_chips: int,
                             *, zero1: bool = False,
                             param_bytes: float | None = None) -> float:
    """Wire bytes each chip moves for one gradient sync.

    Replicated DP: ring all-reduce = reduce-scatter + all-gather fused,
    2·G·(N−1)/N — BOTH internal phases move the gradient's wire dtype.
    ZeRO-1 (train/step.py zero1=True): explicit psum_scatter of gradients
    (G·(N−1)/N) then all-gather of updated PARAMS (P·(N−1)/N) — the gather
    leg moves parameters, which stay fp32 regardless of mesh.reduce_dtype
    (replicas must re-sync exactly; config.py). With fp32 grads the two
    layouts move identical bytes; with a narrower gradient wire dtype
    ZeRO-1 saves only the scatter leg (code-review r4). `param_bytes`
    defaults to `grad_bytes` (the fp32 case)."""
    if n_chips <= 1:
        return 0.0
    frac = (n_chips - 1) / n_chips
    if zero1:
        return (grad_bytes + (param_bytes if param_bytes is not None
                              else grad_bytes)) * frac
    return 2.0 * grad_bytes * frac


def exchange_bytes_per_chip(grad_bytes: float, n_chips: int, *,
                            sharding: str = "dp",
                            param_bytes: float | None = None) -> float:
    """Wire bytes per chip per step for one gradient exchange, by sharding
    basis (r14/r21 — the (dp | zero1 | zero2 | zero3) key of train/step.py
    comm_meta). ZeRO-2 moves EXACTLY ZeRO-1's bytes: the reduce-scatter
    leg and the param all-gather leg are unchanged — its win is
    gradient-state MEMORY (`gradient_state_bytes_per_chip`), not
    bandwidth. ZeRO-3 (r21, mesh.shard_params) also moves the same bytes
    at the fp32 wire: the trailing param re-sync all-gather simply becomes
    the just-in-time pre-forward gather (same P·(N−1)/N) — but its gather
    leg follows `mesh.reduce_dtype` where ZeRO-1/2's stays fp32 by the
    replica-sync contract, so a narrowed wire is expressed by passing the
    narrowed `param_bytes` under zero3 only. Bucketing changes the message
    SCHEDULE (`bucketed_exposed_comm_s`), not the byte total (each element
    still crosses the wire once per leg)."""
    if sharding not in ("dp", "zero1", "zero2", "zero3"):
        raise ValueError(f"sharding {sharding!r} not one of "
                         "('dp', 'zero1', 'zero2', 'zero3')")
    return allreduce_bytes_per_chip(grad_bytes, n_chips,
                                    zero1=sharding != "dp",
                                    param_bytes=param_bytes)


def param_bytes_per_chip(param_count: int, n_chips: int, *,
                         sharding: str = "dp",
                         ema: bool = False) -> float:
    """Per-chip bytes of PERSISTENT parameter state, by sharding basis —
    the ZeRO-3 memory claim (arXiv 2004.13336 §parameter sharding;
    train/state.py): dp/zero1/zero2 replicate the full fp32 tree on every
    chip (O(params)); zero3 (r21, mesh.shard_params) persists only the 1/N
    padded flat shard (O(params/N) — the padding is < N elements per
    bucket, noise at these sizes). The just-in-time gathered full tree is
    TRANSIENT (alive only inside the step, like the AD activations), so it
    does not count as persistent state. `ema=True` doubles the figure (the
    EMA trace rides the same layout as the params in every basis)."""
    if sharding not in ("dp", "zero1", "zero2", "zero3"):
        raise ValueError(f"sharding {sharding!r} not one of "
                         "('dp', 'zero1', 'zero2', 'zero3')")
    b = 4.0 * param_count
    per_chip = b / max(1, n_chips) if sharding == "zero3" else b
    return per_chip * (2.0 if ema else 1.0)


def gradient_state_bytes_per_chip(param_count: int, n_chips: int, *,
                                  sharding: str = "dp",
                                  grad_accum_steps: int = 1,
                                  bucket_bytes: int = 0,
                                  momentum: bool = True) -> Mapping[str, float]:
    """Per-chip bytes of persistent GRADIENT-adjacent state, by sharding
    basis — the ZeRO-2 memory claim, O(params/N) where DP/ZeRO-1 hold
    O(params) (arXiv 2004.13336 §gradient sharding; train/step.py):

      - `opt_state`: the momentum trace — sharded 1/N under ZeRO-1 and
        ZeRO-2, replicated under DP (the PR-10 ZeRO-1 win, unchanged).
      - `grad_accumulator`: the scan carry at grad_accum_steps > 1 —
        O(params) for DP and plain ZeRO-1, O(params/N) under ZeRO-2
        (`shard_gradients` shards the carry; `grad_accum_shard` was the
        ZeRO-1 opt-in for the same shape). 0 at grad_accum_steps == 1 (no
        carry exists).
      - `exchange_buffer`: the largest flat send buffer the exchange
        materializes beyond the AD-transient per-leaf gradients —
        O(params) for the monolithic ZeRO flat scatter, O(bucket) when
        bucketed (each bucket's concat send — DP included — exists only
        until its collective issues), 0 for monolithic DP (the per-leaf
        pmean consumes leaves in place).

    Gradients are fp32 on the wire frame (4 B/elem; mesh.reduce_dtype
    narrows the WIRE, not the state). ZeRO-3 (r21) keeps ZeRO-2's gradient
    state exactly — its additional win is PARAM state, reported by
    `param_bytes_per_chip`, not here."""
    if sharding not in ("dp", "zero1", "zero2", "zero3"):
        raise ValueError(f"sharding {sharding!r} not one of "
                         "('dp', 'zero1', 'zero2', 'zero3')")
    b = 4.0 * param_count
    shard = b / max(1, n_chips)
    opt = 0.0 if not momentum else (b if sharding == "dp" else shard)
    if grad_accum_steps > 1:
        accum = shard if sharding in ("zero2", "zero3") else b
    else:
        accum = 0.0
    if bucket_bytes > 0:
        # per-bucket concat send buffer — DP's bucketed pmean builds one
        # too (GradBucketLayout._bucket_vector), not just the ZeRO scatter
        exchange = float(min(b, bucket_bytes))
    elif sharding == "dp":
        exchange = 0.0
    else:
        exchange = b
    return {"opt_state_bytes": opt, "grad_accumulator_bytes": accum,
            "exchange_buffer_bytes": exchange,
            "total_bytes": opt + accum + exchange}


def bucketed_exposed_comm_s(t_comm_s: float, num_buckets: int, *,
                            overlappable_s: float,
                            hop_latency_s: float = 1e-6,
                            n_chips: int = 2) -> float:
    """Exposed (un-hidden) exchange time under the bucketed schedule.

    The monolithic exchange exposes max(0, t_comm − overlappable): one
    collective that can only start once EVERY gradient exists, so overlap
    is whatever backward happens to remain (for the flat ZeRO scatter:
    nothing — the committed HLO reports show it depends on the whole
    backward). Bucketing bounds the serial tail by the LAST bucket
    instead: buckets 0..B−2 issue while backward still runs, so the
    exposed time is at least t_comm/B (the final bucket's wire time — its
    gradients finish WITH the backward) and at most the monolithic
    exposure; each extra collective pays one more latency term (the
    many-small-buckets ViT caveat — B λ·hops grows linearly in B)."""
    if num_buckets < 1:
        raise ValueError(f"num_buckets {num_buckets} < 1")
    mono = max(0.0, t_comm_s - overlappable_s)
    exposed = max(t_comm_s / num_buckets, mono)
    return exposed + num_buckets * 2 * torus_hops(n_chips) * hop_latency_s


def approx_num_buckets(param_count: int, bucket_mb: float,
                       num_leaves: int | None = None) -> int:
    """Bucket-count estimate for the analytic tables: ceil(grad bytes /
    target), capped by the leaf count when known (parallel/buckets.py
    keeps leaves atomic, so a tree can never split into more buckets than
    it has leaves — VGG's FC-dominated trees land far below the naive
    byte quotient)."""
    if bucket_mb <= 0:
        return 1
    n = max(1, math.ceil(4.0 * param_count / (bucket_mb * 1024 * 1024)))
    if num_leaves is not None:
        n = min(n, max(1, num_leaves))
    return n


def torus_hops(n_chips: int, dims: int = 3) -> int:
    """Per-direction hop count for a dimension-wise reduction on a `dims`-D
    torus of N chips (≈ dims·(N^(1/dims) − 1)); ring fallback for dims=1."""
    side = n_chips ** (1.0 / dims)
    return max(1, round(dims * (side - 1)))


@dataclasses.dataclass(frozen=True)
class Prediction:
    model: str
    layout: str
    n_chips: int
    step_time_s: float
    comm_time_s: float          # full wire time, before overlap
    exposed_comm_s: float       # what the step actually waits on
    latency_s: float
    efficiency: float           # vs the same chip running alone
    images_per_sec_per_chip: float
    host_bound_images_per_sec_per_chip: float
    binding_constraint: str     # "ici" | "host" | "compute"


def predict(point: ModelPoint, n_chips: int, *, chip: ChipSpec = V4,
            zero1: bool = False, overlap_fraction: float = 0.75,
            collective_utilization: float = 0.8,
            hop_latency_s: float = 1e-6,
            backward_fraction: float = 2.0 / 3.0,
            host_decode_per_core: float = HOST_DECODE_RATE_R9,
            grad_bytes_per_param: int = 4) -> Prediction:
    """Predicted throughput/efficiency for `point` data-parallel over
    `n_chips` of `chip`. Pure arithmetic — see module docstring.

    `grad_bytes_per_param=2` models `mesh.reduce_dtype='bfloat16'`
    (parallel/collectives.py): the GRADIENT wire moves bf16 — the lever for
    the fp32 no-overlap worst case (VGG-16). Under ZeRO-1 only the
    reduce-scatter leg narrows; the param all-gather stays fp32 by design,
    so bf16+ZeRO-1 saves 25 %, not 50 % (matches train/step.py)."""
    t_step = point.step_time_on(chip)
    wire = allreduce_bytes_per_chip(
        point.param_count * grad_bytes_per_param, n_chips, zero1=zero1,
        param_bytes=point.param_count * 4)
    bw = chip.injection_bytes_per_s * collective_utilization
    t_comm = wire / bw
    # 2 traversals (reduce + broadcast phase) of the torus' hop count
    t_lat = 2 * torus_hops(n_chips) * hop_latency_s if n_chips > 1 else 0.0
    overlappable = overlap_fraction * backward_fraction * t_step
    exposed = max(0.0, t_comm - overlappable)
    t_total = t_step + exposed + t_lat
    eff = t_step / t_total
    device_rate = point.per_chip_batch / t_total
    host_rate = (chip.host_cores * host_decode_per_core) / chip.chips_per_host
    if host_rate < device_rate:
        binding = "host"
    elif exposed + t_lat > 0.005 * t_step:
        binding = "ici"
    else:
        binding = "compute"
    return Prediction(point.name, "zero1" if zero1 else "replicated",
                      n_chips, t_step, t_comm, exposed, t_lat, eff,
                      device_rate, host_rate, binding)


def predict_table(n_chips_list: Sequence[int] = (8, 32, 128),
                  points: Sequence[ModelPoint] = MEASURED,
                  **kw) -> list[Prediction]:
    out = []
    for p in points:
        for zero1 in (False, True):
            for n in n_chips_list:
                out.append(predict(p, n, zero1=zero1, **kw))
    return out


@dataclasses.dataclass(frozen=True)
class HostProvisioning:
    model: str
    chip: str
    device_rate_img_s_chip: float   # compute-rescaled single-chip rate
    decode_per_core: float          # measured host decode rate basis
    cores_per_chip_required: float  # bare: device_rate / decode rate
    cores_per_chip_with_margin: float  # x headroom
    stock_cores_per_chip: float     # what the chip's standard host ships
    stock_sufficient: bool          # margin requirement <= stock
    stock_utilization: float        # bare requirement / stock


def host_provisioning_requirement(
        point: ModelPoint, *, chip: ChipSpec = V4,
        decode_per_core: float = HOST_DECODE_RATE_R9,
        headroom: float = 1.2) -> HostProvisioning:
    """The deployable host spec (VERDICT r4 #8): how many host cores per
    chip the input pipeline needs to sustain this model's device rate.

    cores/chip = device_rate × headroom / decode_per_core, against the
    chip's stock host (chip.host_cores / chip.chips_per_host).
    `decode_per_core` defaults to the r8-measured native-loader rate
    (HOST_DECODE_RATE_R8 — the LOWER of the committed u8-wire flagship-
    replacement pair on the quiet-host min-of-6 continuity protocol,
    benchmarks/runs/host_r9/decode_r8_u8_s2d_320noise_run{1,2}.json;
    the r7 rate 991.15, the r6 rate 1031.36, the r5 rate 728.05 and the
    FROZEN r4 baseline 556.34 appear as sensitivity rows so the spec's
    history stays visible). At the r8 rate the v5e margin WIDENS — a
    stock v5e host (28 cores/chip) covers the flagship's 22k img/s/chip
    at 23.7 cores needed incl. 1.2× headroom, a 4.3-core cushion vs the
    1.3-core one at r7 (26.7). `headroom` covers decode-rate variance — the
    measured medians moved ~±5 % between windows across r4-r7, so 1.2
    is two of those swings."""
    if headroom < 1.0:
        raise ValueError(f"headroom {headroom} < 1 would spec a host that "
                         f"stalls at the MEASURED rate")
    device_rate = point.per_chip_batch / point.step_time_on(chip)
    bare = device_rate / decode_per_core
    stock = chip.host_cores / chip.chips_per_host
    return HostProvisioning(
        point.name, chip.name, device_rate, decode_per_core, bare,
        bare * headroom, stock, bare * headroom <= stock, bare / stock)


def host_provisioning_table(points: Sequence[ModelPoint] = MEASURED,
                            **kw) -> list[HostProvisioning]:
    return [host_provisioning_requirement(p, **kw) for p in points]


@dataclasses.dataclass(frozen=True)
class RingAttentionPrediction:
    n_chips: int
    t_local: int
    hop_bytes: float            # K/V block a chip sends per hop
    hop_comm_s: float           # one ppermute hop, neighbor link only
    hop_compute_s: float        # one block's QK^T + PV GEMM work
    compute_to_comm: float      # >1 → the ring hides its own hops
    min_t_local_to_hide: int    # smallest T_local where ratio reaches 1
    ring_time_s: float          # double-buffered: own block, then N−1
    #                             arrivals each costing max(compute, comm)
    comm_exposed_fraction: float  # 1 − N·hop_compute / ring_time


def ring_attention_comm_model(
        t_local: int, n_chips: int, *, head_dim: int = 64, heads: int = 8,
        batch: int = 1, bytes_per_elem: int = 2, chip: ChipSpec = V4,
        mxu_efficiency: float = 0.5, links_used: int = 1,
        collective_utilization: float = 0.8) -> RingAttentionPrediction:
    """Analytic compute/comm balance for ring attention
    (parallel/ring_attention.py, ring_flash.py) — the long-context half of
    the scaling story. Each of the N−1 hops moves this chip's K/V block
    (2·B·T_local·H·D·bytes) to ONE neighbor (`lax.ppermute` rides a single
    ICI link, not the injection aggregate) while the MXU computes the
    current block: the FORWARD hop is two einsums (QKᵀ and P·V) of
    B·H·T_local²·D MACs each → 4·B·H·T_local²·D FLOPs (the backward ring
    does strictly more compute per hop for the same bytes, so forward is
    the conservative leg). The ratio grows LINEARLY in T_local — the
    defining property of ring attention at long context. `ring_time_s`
    models the double-buffered pipeline over `n_chips`: compute the
    resident block, then N−1 arrivals each costing
    max(hop_compute, hop_comm); `comm_exposed_fraction` is the slice of
    that wall time not covered by attention FLOPs (0 above break-even)."""
    d = head_dim
    hop_bytes = 2.0 * batch * t_local * heads * d * bytes_per_elem
    link_bw = chip.ici_link_bytes_per_s * links_used * collective_utilization
    hop_comm = hop_bytes / link_bw
    flops = 4.0 * batch * heads * (t_local ** 2) * d
    hop_compute = flops / (chip.peak_bf16_flops * mxu_efficiency)
    ratio = hop_compute / hop_comm
    # ratio(T) is linear in T — solve ratio == 1 for break-even length
    min_t = math.ceil(t_local / ratio) if ratio > 0 else 0
    ring_time = hop_compute + (n_chips - 1) * max(hop_compute, hop_comm)
    exposed = max(0.0, 1.0 - n_chips * hop_compute / ring_time)
    return RingAttentionPrediction(n_chips, t_local, hop_bytes, hop_comm,
                                   hop_compute, ratio, min_t, ring_time,
                                   exposed)


@dataclasses.dataclass(frozen=True)
class UlyssesCommPrediction:
    n_chips: int
    t_local: int
    a2a_bytes: float            # bytes one chip injects per all_to_all
    wire_bytes_total: float     # 4 all_to_alls (q, k, v, o)
    ring_wire_bytes: float      # the ppermute ring's per-chip total
    bytes_ratio_vs_ring: float  # ring / ulysses injected bytes = n/2
    comm_time_s: float          # hop-distance-serialized, all 4 a2a's
    ring_comm_time_s: float     # the ring's n−1 neighbor hops
    time_ratio_vs_ring: float   # ring / ulysses wire TIME on torus ICI
    compute_s: float            # local attention on (T, H/n) — equals the
    #                             ring's total per-chip attention FLOPs
    #                             times padding_overhead
    comm_exposed_fraction: float  # conservative: a2a's at layer edges,
    #                               nothing overlaps them
    heads_effective: int = 0    # ceil(H/n)·n — zero-padded head count
    padding_overhead: float = 1.0  # heads_effective / heads: the honest
    #                                compute-and-wire multiplier when H
    #                                doesn't divide n (parallel/ulysses.py
    #                                head padding, VERDICT r4 weak #5)


def ulysses_comm_model(
        t_local: int, n_chips: int, *, head_dim: int = 64, heads: int = 8,
        batch: int = 1, bytes_per_elem: int = 2, chip: ChipSpec = V4,
        mxu_efficiency: float = 0.5, links_used: int = 1,
        collective_utilization: float = 0.8,
        mean_hop_distance: float | None = None) -> UlyssesCommPrediction:
    """Analytic comparison of the two SP layouts (parallel/ulysses.py vs
    ring_attention.py) — same conventions as `ring_attention_comm_model`.

    Injected bytes per chip: each of the four all_to_alls (q, k, v in;
    o out) moves (n−1)/n of the local shard s = B·T_local·H·D·bytes →
    4·s·(n−1)/n total, vs the ring's 2·s·(n−1): an n/2× byte advantage.
    On torus ICI that advantage does NOT carry to wire time — all_to_all
    traffic crosses `mean_hop_distance` links (n/4 on a bidirectional
    1-D ring; the default), serializing on shared links, so the time
    advantage collapses to ≈2× — while the ring's neighbor ppermute always
    crosses exactly one link AND overlaps each hop with that block's
    matmuls. The model therefore charges ulysses its full wire time as
    exposed (`comm_exposed_fraction`), the conservative reading: its
    all_to_alls sit at layer boundaries where only cross-layer scheduling
    could hide them. Local attention FLOPs are identical in both layouts
    (H/n heads × (n·T_local)² positions = H × n × T_local² — the ring does
    the same total across its n hops) up to the head-padding overhead, so
    the layouts differ in comm and padding: prefer ulysses while its
    padding-adjusted wire time beats the ring's exposure — for divisible H
    that means T_local below ≈ HALF the
    ring's break-even (there its wire time — (n−1)·hop_comm/2 under the
    default hop-distance model — undercuts the ring's exposed
    (n−1)·(hop_comm − hop_compute); the inequality flips exactly at
    compute_to_comm = 1/2). From half-break-even up the ring is strictly
    better: its exposure shrinks to zero at break-even and stays zero,
    while the ulysses all-to-alls remain fully exposed at any length.

    Head counts that don't divide `n_chips` are zero-padded per shard
    (parallel/ulysses.py): every padded head crosses the wire and burns
    MXU cycles like a real one, so BOTH the a2a bytes and the local
    compute here use heads_effective = ceil(H/n)·n — e.g. ViT-S/16's H=6
    on n=4 is charged 8/6 = 1.33×. The ring comparison keeps the TRUE
    head count (it never pads)."""
    d = head_dim
    h_eff = -(-heads // n_chips) * n_chips
    s = float(batch * t_local * h_eff * d * bytes_per_elem)
    s_ring = float(batch * t_local * heads * d * bytes_per_elem)
    frac = (n_chips - 1) / n_chips
    a2a_bytes = s * frac
    wire_total = 4.0 * a2a_bytes
    if mean_hop_distance is None:
        mean_hop_distance = max(1.0, n_chips / 4.0)
    link_bw = chip.ici_link_bytes_per_s * links_used * collective_utilization
    a2a_time = a2a_bytes * mean_hop_distance / link_bw
    comm_time = 4.0 * a2a_time
    ring_wire = 2.0 * s_ring * (n_chips - 1)
    ring_comm = ring_wire / link_bw
    flops = 4.0 * batch * h_eff * n_chips * (t_local ** 2) * d
    compute = flops / (chip.peak_bf16_flops * mxu_efficiency)
    return UlyssesCommPrediction(
        n_chips, t_local, a2a_bytes, wire_total, ring_wire,
        ring_wire / wire_total, comm_time, ring_comm,
        ring_comm / comm_time, compute,
        comm_time / (comm_time + compute),
        h_eff, h_eff / heads)


def north_star_summary(**kw) -> dict:
    """The single judged claim: predicted v4-8 → v4-128 scaling efficiency
    for the flagship, defined the way the target reads — images/sec/chip at
    128 chips over images/sec/chip at 8 chips (device-limited; the host
    ceiling is reported separately because it binds per-HOST, identically at
    any slice size)."""
    flagship = MEASURED[0]
    at8 = predict(flagship, 8, **kw)
    at128 = predict(flagship, 128, **kw)
    return {
        "model": flagship.name,
        "efficiency_8_to_128": (at128.images_per_sec_per_chip
                                / at8.images_per_sec_per_chip),
        "predicted_at_8": at8,
        "predicted_at_128": at128,
        "host_bound_ceiling_img_s_chip": at128.host_bound_images_per_sec_per_chip,
        "note": "device-rate ratio; the host ceiling (per-host-constant, so "
                "it never bends the 8→128 ratio) cleared the flagship's "
                "device rate with ~2x margin once the r6 SIMD decode rate "
                "landed — host provisioning was the watch item through r5 "
                "and is now covered by stock hosts on both chips",
    }
