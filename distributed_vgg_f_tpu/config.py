"""Typed experiment configuration.

The reference drives everything through CLI flags (SURVEY.md §1 CLI layer, reconstructed:
TF-1.x ``tf.app.flags``/argparse cluster + hyperparameter flags). Here the equivalent is
a tree of frozen dataclasses with named presets — one preset per BASELINE.json config —
plus ``parse_cli`` for ``--key=value`` overrides.
"""

from __future__ import annotations

import argparse
import dataclasses
from dataclasses import dataclass, field
from typing import Any, Mapping, Sequence


@dataclass(frozen=True)
class ModelConfig:
    name: str = "vggf"                 # key into models.registry
    num_classes: int = 1000            # classifier width (ImageNet-1k default)
    dropout_rate: float = 0.5          # FC-head dropout; 0 disables (eval always runs without)
    compute_dtype: str = "bfloat16"    # activations/conv compute; params stay float32
    # model-specific extras (e.g. ViT depth/width overrides); kept generic so the
    # trainer stays model-agnostic (SURVEY.md §7 hard parts).
    extra: Mapping[str, Any] = field(default_factory=dict)


@dataclass(frozen=True)
class OptimConfig:
    base_lr: float = 0.01              # LR at reference batch size, scaled linearly
    reference_batch_size: int = 256    # batch size base_lr was tuned at (linear-scaling anchor)
    momentum: float = 0.9              # SGD momentum coefficient
    nesterov: bool = False             # Nesterov lookahead instead of classical momentum
    weight_decay: float = 5e-4         # L2-in-loss, matching TF coupled semantics
    schedule: str = "step"             # "step" | "cosine" | "constant"
    # step schedule: multiply LR by `decay_factor` at each boundary (in epochs)
    decay_epochs: Sequence[float] = (30.0, 60.0, 80.0)
    decay_factor: float = 0.1          # per-boundary LR multiplier for the step schedule
    warmup_epochs: float = 0.0         # linear LR ramp from 0 over this many epochs; 0 = none
    grad_clip_norm: float = 0.0        # 0 disables


@dataclass(frozen=True)
class SnapshotCacheConfig:
    """Decoded-crop snapshot cache (r9 — the tf.data paper's cache/snapshot
    move, arXiv 2101.12127): the first pass over the dataset writes each
    item's post-decode crop (exactly as the native loader shipped it — u8
    raw pixels on the flagship wire) to a bounded on-disk store keyed by
    (source fingerprint, decode params, native ABI); once every item is
    present, later epochs assemble batches straight from the store with a
    fresh per-epoch horizontal flip and skip libjpeg — entropy decode
    included — entirely. A cache that survives the process serves from
    batch 0 of the NEXT run. Warm epochs re-serve the first pass's crop
    geometry (the documented cache trade; flips stay fresh), so this is a
    throughput lever for decode-bound hosts, not a default. Corrupt or
    source-drifted entries degrade per item to a sequential native decode,
    or to the r9 corrupt-image fill when that also fails — never to stale
    pixels. Counters: prefetch/snapshot_{hits,misses,bytes}."""
    enabled: bool = False   # opt-in: a throughput lever for decode-bound hosts
    # Store directory; "" places it under <data_dir>/.dvggf_snapshot.
    dir: str = ""
    # On-disk budget. Writes stop (and the cache never turns warm) rather
    # than exceed it; stale parameter generations are evicted first.
    capacity_bytes: int = 8 << 30
    # crc32-validate payloads on warm reads (source stat drift is always
    # checked; this additionally catches bit-rot in the store itself).
    validate: bool = True

    def __post_init__(self):
        if self.capacity_bytes <= 0:
            raise ValueError(
                f"data.snapshot_cache.capacity_bytes must be > 0, got "
                f"{self.capacity_bytes}")


@dataclass(frozen=True)
class AutotuneConfig:
    """Closed-loop ingest autotuner (r11, data/autotune.py — tf.data's
    AUTOTUNE, arXiv 2101.12127, with a receipt trail): a per-process
    feedback controller that consumes the stall attributor's per-window
    verdicts and tunes the live pipeline knobs — native decode workers
    (runtime pool resize, ABI v8), host prefetch depth, device ring depth,
    restart fan-out — online, retiring the hand-pinned HOST_DECODE_RATE_R*
    constants as a runtime dependency (they stay bench artifacts). Every
    actuation passes hysteresis (k_windows consecutive verdicts, cooldown,
    bounded steps, hard rails) and is recorded three ways: autotune/*
    registry counters, the trainer JSONL `autotune` block, and the live
    /autotunez endpoint. Off by default; the flagship preset turns it on;
    DVGGF_AUTOTUNE=0 kills it regardless of config (behavior then
    byte-identical to controller-absent)."""
    enabled: bool = False   # off by default; the flagship preset turns it on
    # Consecutive same-direction verdicts required before ANY actuation.
    k_windows: int = 3
    # Quiet windows after an actuation before the next one may fire.
    cooldown_windows: int = 2
    # Windows with no actuation before the controller reports settled
    # (the flag the regression sentinel requires before gating a bench
    # artifact — a mid-convergence window would read as a false
    # regression).
    settled_after_windows: int = 6
    # Sustained compute_bound windows before a controller-RAISED knob steps
    # back down toward its baseline. 0 (default) disables down-steps
    # entirely: a compute-bound workload then produces zero actuations.
    relax_after_windows: int = 0
    # Direction flips on one knob before the oscillation guard freezes it
    # for the run (receipted in autotune/oscillation_freezes).
    freeze_after_flips: int = 2
    # Actuation-log ring size (trainer JSONL carries per-window actuations;
    # this bounds the /autotunez + flight-recorder history).
    history: int = 64
    # Hard rails per knob. max_threads 0 = min(16, host vCPUs).
    min_threads: int = 1                # rail: native decode-worker floor
    max_threads: int = 0                # rail: worker ceiling; 0 = min(16, host vCPUs)
    min_prefetch: int = 1               # rail: host prefetch-depth floor
    max_prefetch: int = 8               # rail: host prefetch-depth ceiling
    min_prefetch_to_device: int = 1     # rail: device ring-depth floor
    max_prefetch_to_device: int = 4     # rail: device ring-depth ceiling
    # 1 = fan-out knob unbound (fan-out trades cores for latency; the
    # throughput-provisioned default never engages it).
    max_restart_fanout: int = 1

    def __post_init__(self):
        if self.k_windows < 1 or self.settled_after_windows < 1:
            raise ValueError(
                "data.autotune.k_windows and settled_after_windows must be "
                f">= 1, got {self.k_windows}/{self.settled_after_windows}")
        if self.cooldown_windows < 0 or self.relax_after_windows < 0:
            raise ValueError(
                "data.autotune.cooldown_windows and relax_after_windows "
                f"must be >= 0, got {self.cooldown_windows}/"
                f"{self.relax_after_windows}")
        if self.freeze_after_flips < 1:
            raise ValueError(
                f"data.autotune.freeze_after_flips must be >= 1, got "
                f"{self.freeze_after_flips}")
        if self.history < 1:
            raise ValueError(
                f"data.autotune.history must be >= 1, got {self.history}")
        # 0-means-auto exists ONLY for max_threads (resolved to
        # min(16, vCPUs) at bind time); a zero prefetch rail would bind a
        # knob with max < min that silently never steers
        if self.min_threads < 1 or (self.max_threads != 0
                                    and self.max_threads < self.min_threads):
            raise ValueError(
                f"data.autotune rails need 1 <= min_threads <= max_threads "
                f"(0 = auto), got {self.min_threads}/{self.max_threads}")
        for lo_name, hi_name in (("min_prefetch", "max_prefetch"),
                                 ("min_prefetch_to_device",
                                  "max_prefetch_to_device")):
            lo, hi = getattr(self, lo_name), getattr(self, hi_name)
            if lo < 1 or hi < lo:
                raise ValueError(
                    f"data.autotune rails need 1 <= {lo_name} <= "
                    f"{hi_name}, got {lo}/{hi}")
        if self.max_restart_fanout < 1 or self.max_restart_fanout > 64:
            raise ValueError(
                f"data.autotune.max_restart_fanout must be in [1, 64], "
                f"got {self.max_restart_fanout}")


@dataclass(frozen=True)
class ServiceConfig:
    """Disaggregated ingest (r16, ROADMAP item 4 — the tf.data-service
    split, arXiv 2101.12127): decode-worker processes run the full native
    stack (`python -m distributed_vgg_f_tpu.data.ingest_service`) and
    serve ready position-keyed crops over length-prefixed sockets; the
    training host runs a thin fetch-and-device_put client
    (data/service_client.py) in place of the local loader. Off by default
    — `enabled=false` never touches the service plane and local ingest is
    byte-identical to pre-r16 (pinned in tests/test_ingest_service.py).
    Batch cursors are split across the fleet by an epoch-keyed SplitMix64
    permutation (static within an epoch, no mid-stream handoff); a dead
    worker's cursors are reassigned to survivors, and with every worker
    dead the client degrades to the ordinary local pipeline (or raises a
    typed DataStallError when `fallback_local` is off). Counters:
    `ingest_service/*`; live state on the exporter's `/ingestz`."""
    enabled: bool = False   # kill-switch: off = local ingest, byte-identical
    # Decode-worker endpoints, "host:port" each, IN WORKER-INDEX ORDER (the
    # epoch-keyed ownership split permutes this list). Per training host:
    # multi-host runs give each trainer process its own fleet serving that
    # process's shard (the hello handshake refuses a shard mismatch).
    workers: Sequence[str] = ()
    # Batches kept in flight across the fleet; 0 = auto (3x worker count —
    # two keep each worker's decode/transfer overlapped, the third absorbs
    # delivery-order jitter; the pipelining that makes N workers aggregate
    # to ~Nx one host's rate).
    fetch_ahead: int = 0
    # Socket connect timeout per worker (startup + reconnects).
    connect_timeout_s: float = 5.0
    # Per-request timeout; a worker slower than this is treated as dead
    # and its cursors fail over (the service-plane analogue of
    # train.data_timeout_s).
    request_timeout_s: float = 60.0
    # With every worker dead, fall back to the ordinary local pipeline at
    # the exact stream position (true, default) or raise DataStallError
    # (false — fleets that would rather page than silently degrade).
    fallback_local: bool = True

    def __post_init__(self):
        # enabled-with-no-workers is rejected at client build time
        # (service_client.py), not here: `--set` overrides apply one field
        # at a time, so a cross-field check in __post_init__ would make
        # `--set data.service.enabled=true --set data.service.workers=...`
        # fail on flag ORDER.
        for e in self.workers:
            host, sep, port = str(e).rpartition(":")
            if not sep or not host or not port.isdigit():
                raise ValueError(
                    f"data.service.workers entry {e!r} is not host:port")
        if self.fetch_ahead < 0:
            raise ValueError(
                f"data.service.fetch_ahead must be >= 0 (0 = auto), got "
                f"{self.fetch_ahead}")
        if self.connect_timeout_s <= 0 or self.request_timeout_s <= 0:
            raise ValueError(
                "data.service.connect_timeout_s and request_timeout_s must "
                f"be > 0, got {self.connect_timeout_s}/"
                f"{self.request_timeout_s}")

    @property
    def label(self) -> str:
        """The ingest basis label — `local` | `service_<N>w` — stamped
        into the trainer start record, bench rows (`ingest_mode`), and the
        regression sentinel's Basis key. Delegates to the single
        formatting implementation (data/ingest_service.ingest_label) so
        the start record and the /ingestz + bench labels can never
        drift apart."""
        from distributed_vgg_f_tpu.data.ingest_service import ingest_label
        return ingest_label(len(self.workers), self.enabled)


@dataclass(frozen=True)
class IteratorStateConfig:
    """Position-exact resumable ingest (r18, data/iterator_state.py — the
    tf.data iterator-checkpointing move, arXiv 2101.12127): the trainer's
    host ingest chain is wrapped in a cursor-counting rebuild surface, a
    schema-validated iterator-state blob (epoch, SplitMix64 shuffle state,
    cursor, in-flight read-ahead set) rides every checkpoint's `extra`,
    restore dispatches on receipt-present (pre-r18 checkpoints keep the
    r17 epoch-boundary replay path unchanged), and `rebuild_live` lets the
    ingest autotuner actuate the host↔u8 wire switch mid-epoch with
    byte-identical stream continuation. `enabled=false` is the kill-switch:
    no wrapper, no blob, no wire knob — the feed path is structurally
    identical to r17 (stream identity pinned in
    tests/test_iterator_state.py)."""
    # On by default: the blob is ~a hundred bytes of JSON per checkpoint
    # and restore still degrades gracefully on receipt-absent checkpoints.
    enabled: bool = True


def resolve_serving_buckets(buckets: Sequence[int],
                            max_batch: int) -> tuple:
    """The serving batch-bucket ladder, validated — THE single
    implementation (ServingConfig validation and serving/engine.py both
    delegate here; schema.validate_serving_row keeps its own literal copy
    by the leaf-module contract). Explicit `buckets` must be unique
    ascending positive ints covering max_batch (each gets one
    AOT-compiled executable; groups pad to the nearest bucket); () = the
    power-of-two ladder up to max_batch — small buckets keep light
    traffic cheap, the top bucket IS max_batch so a full flush never
    splits."""
    if buckets:
        out = tuple(int(b) for b in buckets)
        if list(out) != sorted(set(out)) or out[0] < 1:
            raise ValueError(f"buckets must be unique ascending positive "
                             f"ints, got {list(buckets)}")
        if out[-1] < int(max_batch):
            raise ValueError(
                f"buckets {list(out)} do not cover max_batch={max_batch} "
                "— a full flush would have no executable to run on")
        return out
    out = []
    b = 1
    while b < int(max_batch):
        out.append(b)
        b *= 2
    out.append(int(max_batch))
    return tuple(sorted(set(out)))


#: The serving tier ladder, in descending-fidelity order — the router's
#: `?tier=` vocabulary (serving/tiers.py mirrors this; the schema keeps a
#: literal copy by the leaf-module contract).
SERVING_TIERS = ("fp32", "bf16", "int8", "student")


@dataclass(frozen=True)
class ServingTiersConfig:
    """Latency-tiered serving (r23, serving/tiers.py): per-tier AOT engine
    variants behind the one router — `bf16` (params cast once at load,
    bf16 activations, fp32 logits), `int8` (post-training per-out-channel
    symmetric weight quantization of the FC heads, activation scales from
    a committed calibration pass over the u8 wire; sub-LSB channels are
    elided exactly — they quantize to zero under the per-tensor activation
    scale), and `student` (the half-width `vggf_student` distilled by
    train/distill.py). `serving.tiers.enabled=false` is the kill-switch:
    the router never parses `?tier=`, /v1/models carries no ladder, and
    the server is structurally the fp32-only r22 surface (routing/lowered
    identity pinned in tests/test_serving_tiers.py)."""
    # Kill-switch: off = fp32-only server, tier machinery never imported.
    enabled: bool = False
    # Batches of synthetic u8 wire images the int8 calibration pass runs
    # to record per-layer activation ranges (serving/tiers.py).
    calibration_batches: int = 4
    # Images per calibration batch (clamped to the engine's top bucket).
    calibration_batch_size: int = 8
    # Seed for the synthetic calibration batch stream — part of the
    # committed calibration receipt, so a re-run reproduces the ranges.
    calibration_seed: int = 0
    # Per-tier accuracy contract: largest top-1 drop vs the fp32 tier a
    # committed accuracy-delta receipt may show (schema-enforced).
    max_top1_delta_bf16: float = 0.02
    max_top1_delta_int8: float = 0.05   # see max_top1_delta_bf16
    max_top1_delta_student: float = 0.10  # see max_top1_delta_bf16

    def __post_init__(self):
        if self.calibration_batches < 1 or self.calibration_batch_size < 1:
            raise ValueError(
                "serving.tiers calibration needs >= 1 batches of >= 1 "
                f"images, got {self.calibration_batches}/"
                f"{self.calibration_batch_size}")
        for name in ("max_top1_delta_bf16", "max_top1_delta_int8",
                     "max_top1_delta_student"):
            v = getattr(self, name)
            if not 0 <= v <= 1:
                raise ValueError(
                    f"serving.tiers.{name} must be in [0, 1], got {v}")


@dataclass(frozen=True)
class ServingConfig:
    """Always-on dynamic-batching predict server (r17, serving/ — ROADMAP
    item 1, the serving half of the TF-system training/serving split,
    arXiv 1605.08695): a persistent stdlib-HTTP front end over the jitted
    predict step, fed raw u8 image payloads (1 B/px off the network, the
    u8 wire contract — the device-finish prologue normalizes on device),
    with a bounded admission queue, max-latency + max-batch flush, one
    AOT-lowered executable per batch bucket, per-model routing over the
    models/ingest.py descriptor table, and explicit overload behavior
    (typed 503 shed, never unbounded latency). Off by default — with
    `enabled=false` the serving package is never imported and offline
    predict is byte-identical to r16 (pinned in tests/test_serving.py);
    `--mode serve` refuses to start without the explicit opt-in."""
    enabled: bool = False   # kill-switch: off = no server, predict untouched
    # Bind address. Loopback by default: the predict endpoint is
    # unauthenticated — fronting it beyond the host (an LB, a mesh
    # sidecar) is an explicit decision, same stance as the exporter.
    host: str = "127.0.0.1"
    # 0 = OS-assigned free port (the bound port is printed and returned
    # from start() — the exporter's port-0 contract).
    port: int = 0
    # Largest batch one flush may form; also the top batch bucket.
    max_batch: int = 32
    # Batch buckets (ascending; each gets ONE ahead-of-time-compiled
    # executable; groups pad to the nearest bucket). () = the power-of-two
    # ladder 1,2,4,...,max_batch.
    buckets: Sequence[int] = ()
    # Admission window: max milliseconds the OLDEST queued request waits
    # for company before a partial batch flushes. The controller's knob
    # baseline.
    max_latency_ms: float = 10.0
    # Bounded admission queue: arrivals past this depth shed with the
    # typed 503 payload instead of queueing unboundedly.
    queue_limit: int = 128
    # Server-side cap on one request's total wait (queue + batch + run);
    # exceeded → typed 504.
    request_timeout_s: float = 30.0
    # Retry-After hint (ms) carried in the 503 shed payload.
    shed_retry_after_ms: int = 50
    # AOT-compile every bucket at add_engine time so the first request of
    # any shape pays dispatch, not XLA compile.
    warmup: bool = True
    # Admission controller (serving/controller.py — the r11 autotuner over
    # the batch-window knob, steered by queue-depth/latency verdicts).
    controller: bool = True
    # Hard rails for the controller's admission-window knob (ms).
    window_min_ms: float = 1.0
    window_max_ms: float = 100.0   # see window_min_ms
    # Seconds between controller windows (verdict + gauges + flight ring +
    # serving heartbeat cadence).
    controller_interval_s: float = 2.0
    # Consecutive pressure windows before the controller widens the window
    # (the r11 hysteresis contract).
    controller_k_windows: int = 3
    # Quiet windows after an actuation before the next may fire.
    controller_cooldown_windows: int = 2
    # Sustained steady windows before a controller-raised window steps
    # back down toward max_latency_ms (0 disables relaxation).
    controller_relax_after_windows: int = 4
    # Queue peak (as a fraction of queue_limit) that reads as pressure
    # even before anything sheds.
    queue_pressure_fraction: float = 0.5
    # Tier a request lands on when it carries no explicit `?tier=` (the
    # per-model default class). Ignored — structurally fp32 — while
    # serving.tiers.enabled is false.
    tier_default: str = "fp32"
    # Latency tier ladder (r23): bf16/int8/student engine variants behind
    # the same router — see ServingTiersConfig.
    tiers: ServingTiersConfig = field(default_factory=ServingTiersConfig)

    def __post_init__(self):
        if self.tier_default not in SERVING_TIERS:
            raise ValueError(
                f"serving.tier_default {self.tier_default!r} not one of "
                f"{SERVING_TIERS}")
        if self.max_batch < 1:
            raise ValueError(
                f"serving.max_batch must be >= 1, got {self.max_batch}")
        # one validator for the bucket-ladder contract (shared with the
        # engine's resolution — see resolve_serving_buckets)
        resolve_serving_buckets(self.buckets, self.max_batch)
        if self.queue_limit < 1:
            raise ValueError(
                f"serving.queue_limit must be >= 1, got {self.queue_limit}")
        if self.max_latency_ms <= 0 or self.request_timeout_s <= 0:
            raise ValueError(
                "serving.max_latency_ms and request_timeout_s must be > 0, "
                f"got {self.max_latency_ms}/{self.request_timeout_s}")
        if not 0 < self.window_min_ms <= self.window_max_ms:
            raise ValueError(
                f"serving window rails need 0 < window_min_ms <= "
                f"window_max_ms, got {self.window_min_ms}/"
                f"{self.window_max_ms}")
        if not self.window_min_ms <= self.max_latency_ms \
                <= self.window_max_ms:
            raise ValueError(
                f"serving.max_latency_ms {self.max_latency_ms} outside the "
                f"controller rails [{self.window_min_ms}, "
                f"{self.window_max_ms}] — the knob baseline must be "
                "reachable")
        if self.controller_interval_s <= 0:
            raise ValueError(
                f"serving.controller_interval_s must be > 0, got "
                f"{self.controller_interval_s}")
        if self.controller_k_windows < 1 \
                or self.controller_cooldown_windows < 0 \
                or self.controller_relax_after_windows < 0:
            raise ValueError(
                "serving controller needs k_windows >= 1 and non-negative "
                "cooldown/relax windows, got "
                f"{self.controller_k_windows}/"
                f"{self.controller_cooldown_windows}/"
                f"{self.controller_relax_after_windows}")
        if not 0 < self.queue_pressure_fraction <= 1:
            raise ValueError(
                f"serving.queue_pressure_fraction must be in (0, 1], got "
                f"{self.queue_pressure_fraction}")
        if self.shed_retry_after_ms < 0:
            raise ValueError(
                f"serving.shed_retry_after_ms must be >= 0, got "
                f"{self.shed_retry_after_ms}")


@dataclass(frozen=True)
class AugmentConfig:
    """Fused on-device augmentation (r13, data/augment.py): horizontal
    flip, crop jitter, mixup/cutmix, and a RandAugment-lite elementwise
    subset, applied INSIDE the jitted train step as a pure function of
    (seed, step, replica) — the host wire stays raw u8 and augmentation
    diversity costs zero host cycles (the large-distributed-CNN study's
    host-offload argument, arXiv 1711.00705). Off by default;
    `enabled=false` is structurally absent (the step body is byte-identical
    to a build without the stage — pinned by jaxpr-equality test). The
    flagship preset ships flips + mixup.

    Flip ownership: when `enabled and hflip`, the DEVICE owns the
    horizontal flip and every host-side flip — the native decoder's
    (ABI v9 per-loader switch), tf.data's, grain's, cifar10's, and the
    snapshot cache's warm-path redraw — is disabled by this one predicate
    (`owns_hflip`), so double-flip is structurally impossible.

    Eval and predict NEVER augment: the stage exists only in the train
    step (sentinel test pins the eval jaxpr identical augment-on vs off).
    """
    enabled: bool = False
    # Device-side random horizontal flip (replaces every host flip).
    hflip: bool = True
    # Max |shift| in pixels of the per-image translation jitter (edge
    # pixels replicate). 0 disables.
    crop_jitter: int = 0
    # Beta(alpha, alpha) mixup (arXiv 1710.09412); 0 disables. Labels mix
    # as lam*CE(y) + (1-lam)*CE(y[perm]) — integer labels, no one-hot.
    mixup_alpha: float = 0.0
    # Beta(alpha, alpha) cutmix (arXiv 1905.04899); 0 disables. When both
    # mixup and cutmix are enabled, each step draws one of the two.
    cutmix_alpha: float = 0.0
    # RandAugment-lite: number of elementwise op draws per image from
    # {identity, brightness, contrast, posterize}. 0 disables.
    rand_ops: int = 0
    # Magnitude of the RandAugment-lite ops in [0, 1].
    rand_magnitude: float = 0.5

    @property
    def owns_hflip(self) -> bool:
        """True when the DEVICE owns the horizontal flip — the single
        predicate every host pipeline consults before flipping."""
        return self.enabled and self.hflip

    def describe(self) -> dict:
        """JSON-ready receipt (trainer JSONL `augment` block, bench rows)."""
        return {"enabled": self.enabled, "hflip": self.hflip,
                "crop_jitter": self.crop_jitter,
                "mixup_alpha": self.mixup_alpha,
                "cutmix_alpha": self.cutmix_alpha,
                "rand_ops": self.rand_ops,
                "rand_magnitude": self.rand_magnitude,
                "host_flips_disabled": self.owns_hflip}

    def __post_init__(self):
        if self.crop_jitter < 0:
            raise ValueError(
                f"data.augment.crop_jitter must be >= 0, got "
                f"{self.crop_jitter}")
        if self.mixup_alpha < 0 or self.cutmix_alpha < 0:
            raise ValueError(
                "data.augment.mixup_alpha and cutmix_alpha must be >= 0, "
                f"got {self.mixup_alpha}/{self.cutmix_alpha}")
        if self.rand_ops < 0:
            raise ValueError(
                f"data.augment.rand_ops must be >= 0, got {self.rand_ops}")
        if not 0.0 <= self.rand_magnitude <= 1.0:
            raise ValueError(
                f"data.augment.rand_magnitude must be in [0, 1], got "
                f"{self.rand_magnitude}")


@dataclass(frozen=True)
class DataConfig:
    name: str = "synthetic"  # "synthetic" | "cifar10" | "imagenet" | "teacher" | "synthetic_tokens"
    data_dir: str = ""       # dataset root; "" = synthetic fallback where supported
    image_size: int = 224    # square train/eval resolution after crop+resize
    global_batch_size: int = 256   # across ALL replicas; must divide by replica count
    num_train_examples: int = 1_281_167   # ImageNet-1k default
    num_eval_examples: int = 50_000       # eval split size (ImageNet-1k val default)
    shuffle_buffer: int = 16_384   # tf.data shuffle window (native loader shuffles exactly)
    prefetch: int = 2              # device-prefetch ring depth (batches in flight)
    # dtype of batches handed to the device. "bfloat16" halves H2D volume and
    # skips the on-device cast (models compute in bf16 anyway).
    image_dtype: str = "float32"
    # Host→device ingest wire format (r8): "auto" keeps the host-normalize
    # path in `image_dtype` (eval parity, non-native backends); "host_f32" /
    # "host_bf16" force that path's dtype; "u8" ships RAW resampled uint8
    # pixels from the native loader (1 byte/pixel — 4x less wire+ring than
    # f32, ~2x less than bf16) and finishes normalize/cast/space-to-depth on
    # device, fused into the jitted step (data/device_ingest.py). u8 applies
    # to native TRAIN ingest only and falls back to the host path — with a
    # logged warning, byte-identical to pre-r8 behavior — when the native u8
    # wire is unavailable or kill-switched (DVGGF_WIRE_U8=0 env /
    # -DDVGGF_NO_WIRE_U8 build). Eval/predict always ride the host path;
    # the device-finish prologue dispatches on dtype, so mixed wires can
    # never double-normalize.
    wire: str = "auto"
    # Decode ImageNet training data with the native libjpeg loader
    # (native/jpeg_loader.cc: DCT-scaled partial decode in C++ worker threads
    # — measured ~1.3–1.6x tf.data per host core, run-to-run spread on this
    # shared host; frozen tracking baseline in benchmarks/baseline.json).
    # Covers BOTH layouts:
    # raw-JPEG directory-per-class, and TFRecords via the native indexer
    # (native/tfrecord_index.cc — JPEG byte ranges read straight out of the
    # shards, no TF/proto in the loop). Falls back to tf.data (with a logged
    # warning) when the native build is unavailable. Both streams are
    # deterministic per seed and support exact resume; they draw different
    # (but same-distribution) augmentations.
    native_jpeg: bool = True
    # Use the native loader for EVAL too (deterministic center crop, exact
    # pad-and-mask finite pass). Off by default: the native eval resamples
    # the original-resolution center crop in one bilinear step, while tf.data
    # resizes-then-crops (two steps) — same protocol, slightly different
    # pixel values, so keep the default stable for comparisons.
    native_jpeg_eval: bool = False
    # Decode worker threads for the native loader; 0 = auto (min(8, vCPUs)).
    native_threads: int = 0
    # Host input backend for the imagenet pipeline:
    #   "auto"   — native loader (per native_jpeg/native_jpeg_eval), tf.data
    #              fallback;
    #   "native" — force the native loader (train AND eval);
    #   "tfdata" — force tf.data;
    #   "grain"  — PyGrain DataLoader (data/grain_imagenet.py): deterministic
    #              index sampling + true multiprocess decode workers
    #              (grain_workers), decoding through the native single-image
    #              decoder; falls back to "auto" with a logged warning.
    backend: str = "auto"
    # Grain decode worker PROCESSES (0 = in-process). Real multi-core hosts
    # set this near the core count; tf.data threads and the native loader's
    # C++ threads share one process, grain workers do not.
    grain_workers: int = 0
    # Emit TRAIN batches in the 4x4 space-to-depth layout (S/4, S/4, 48)
    # instead of (S, S, 3) — the host side of the VGG-F stem's packed-input
    # contract (models/vggf.py Conv1SpaceToDepth dispatches on input shape;
    # skipping the on-device relayout measured +3.7% train step at batch 2048
    # on v5e). VGG-F only; eval batches stay (S, S, 3) — the model accepts
    # both. Supported by the synthetic, tf.data-imagenet, and native-loader
    # pipelines; requires image_size % 4 == 0.
    space_to_depth: bool = False
    # Teacher task only: fix the eval split's index base instead of the
    # default "starts at num_train_examples". The default couples the val
    # SET to the train-set size, so a train-size sweep would score each arm
    # on a different 1024-example sample — ±1.5 % top-1 noise, the same
    # order as the effect being measured (code-review r4). A far-offset
    # shared base keeps one fixed held-out set across all arms; must be
    # >= num_train_examples (validated in data/teacher.py).
    eval_index_base: int = 0   # 0 = legacy: num_train_examples
    # Label mapping for the flat-validation-directory ImageNet layout
    # (val/*.JPEG with no class subdirectories). "" auto-detects
    # val_labels.txt / validation_labels.txt / ILSVRC2012_validation_ground_truth.txt
    # next to the data. See data/imagenet.py for the accepted formats.
    val_labels_file: str = ""
    # Per-channel normalization constants (0-255 scale, ImageNet RGB stats);
    # every ingest path — tf.data, native, u8 device-finish — applies these.
    mean_rgb: Sequence[float] = (123.68, 116.78, 103.94)
    stddev_rgb: Sequence[float] = (58.393, 57.12, 57.375)  # see mean_rgb
    # Decoded-crop snapshot cache over the native TRAIN iterator (r9):
    # warm epochs skip libjpeg entirely. See SnapshotCacheConfig.
    snapshot_cache: SnapshotCacheConfig = field(
        default_factory=SnapshotCacheConfig)
    # Closed-loop ingest autotuner (r11): online verdict-driven tuning of
    # decode workers / prefetch depths / fan-out. See AutotuneConfig.
    autotune: AutotuneConfig = field(default_factory=AutotuneConfig)
    # Fused on-device augmentation (r13): flip/jitter/mixup/cutmix/
    # RandAugment-lite inside the jitted train step. See AugmentConfig.
    augment: AugmentConfig = field(default_factory=AugmentConfig)
    # Disaggregated ingest (r16): fetch ready crops from a decode-worker
    # fleet instead of decoding locally. See ServiceConfig; off by default
    # (local ingest byte-identical).
    service: ServiceConfig = field(default_factory=ServiceConfig)
    # Position-exact resumable ingest (r18): checkpointable iterator-state
    # blobs + live position-exact rebuild. See IteratorStateConfig; off =
    # the r17 epoch-boundary replay path, byte-identical.
    iterator_state: IteratorStateConfig = field(
        default_factory=IteratorStateConfig)

    @property
    def host_space_to_depth(self) -> bool:
        """Whether the HOST pipeline packs the 4x4 layout. With the device
        augmentation enabled the stage takes the batch unpacked (its crop
        jitter and RandAugment ops address pixels by (y, x)) and packs it
        itself — the host then always ships unpacked (S, S, 3), for the
        host wires exactly as the u8 wire always did. The single source of
        the packing split; every pipeline builder consults this, never
        `space_to_depth` directly."""
        return self.space_to_depth and not self.augment.enabled

    def __post_init__(self):
        # a typo'd backend must fail loudly, not silently behave as "auto"
        if self.backend not in ("auto", "native", "tfdata", "grain"):
            raise ValueError(
                f"data.backend {self.backend!r} not one of "
                "'auto'|'native'|'tfdata'|'grain'")
        from distributed_vgg_f_tpu.data.dtypes import WIRE_FORMATS
        if self.wire not in WIRE_FORMATS:
            raise ValueError(
                f"data.wire {self.wire!r} not one of {WIRE_FORMATS}")
        if self.image_dtype not in ("float32", "bfloat16"):
            raise ValueError(
                f"data.image_dtype {self.image_dtype!r} not one of "
                "('float32', 'bfloat16') — the uint8 wire is selected via "
                "data.wire='u8', not image_dtype")


@dataclass(frozen=True)
class ElasticConfig:
    """Live elastic resize (r19, parallel/elastic.py — the cross-replica
    weight-resharding move of arXiv 2004.13336 closed into the recovery
    loop): when `PreemptConsensus` fires for k of N data shards, the
    survivors form a shrunken mesh, reshard params/opt-state in place
    through `zero.convert_opt_state` + the r14 bucket-layout receipts, and
    continue through the PR 15 cursor blob — zero replayed batches, no
    process restart. `enabled=false` is the kill-switch: preemption takes
    the r18 checkpoint-and-exit path, structurally identical to pre-r19
    (pinned in tests/test_elastic.py)."""
    # Kill-switch: off = preemption checkpoints and stops (the r18 restart
    # path), byte-identical to pre-r19; on = survivors resize and continue.
    enabled: bool = False
    # What the global batch means across a resize. "keep_global" (default):
    # dead shards' data moves to survivors — global batch and LR unchanged,
    # per-survivor batch grows, loss trajectory identical to a restart on
    # the same survivor count. "scale_lr": per-replica batch is invariant —
    # the global batch shrinks by N'/N and the LR is rescaled by the same
    # factor (linear-scaling rule), with a schedule receipt logged.
    batch_policy: str = "keep_global"
    # Fewest survivors worth resizing onto; below this the resize degrades
    # to the r18 restart path with the `elastic_degraded_restart` flight
    # class (an all-but-one-dead fleet should restart on fresh capacity,
    # not limp on one shard).
    min_survivors: int = 2

    def __post_init__(self):
        if self.batch_policy not in ("keep_global", "scale_lr"):
            raise ValueError(
                f"mesh.elastic.batch_policy {self.batch_policy!r} not one "
                "of ('keep_global', 'scale_lr')")
        if self.min_survivors < 1:
            raise ValueError(
                f"mesh.elastic.min_survivors must be >= 1, got "
                f"{self.min_survivors}")


@dataclass(frozen=True)
class MeshConfig:
    """Device mesh layout. The reference is pure DP (SURVEY.md §2.3); we keep a named
    axis layout so additional axes can be introduced without touching the trainer."""
    data_axis: str = "data"   # name of the mesh's data-parallel axis
    # 0 = use all visible devices on the data axis.
    num_data: int = 0
    # Optimizer-state sharding over the data axis (ZeRO-1-style; PAPERS.md
    # "Automatic Cross-Replica Sharding of Weight Update in Data-Parallel Training").
    shard_opt_state: bool = False
    # ZeRO-2 (r14): gradient state held only as 1/N flat shards — each
    # bucket's psum_scatter consumes its transient gradients directly and,
    # under grad accumulation, the scan accumulator is the 1/N shard (the
    # O(params) -> O(params/N) drop shown in utils/scaling_model.py
    # gradient_state_bytes_per_chip). Wire bytes are unchanged vs ZeRO-1
    # (reduce-scatter + all-gather move what the all-reduce moved);
    # requires shard_opt_state.
    shard_gradients: bool = False
    # ZeRO-3 (r21): parameters held ONLY as 1/N flat shards in the
    # TrainState — the step all-gathers each bucket just-in-time through
    # the single-sourced wire cast (mesh.reduce_dtype applies to the
    # gather leg too, unlike ZeRO-1/2's always-fp32 re-sync gather) and
    # the trailing param all-gather disappears (the optimizer updates the
    # shard in place). Persistent param state drops O(params) ->
    # O(params/N) (utils/scaling_model.py param_bytes_per_chip); the loss
    # trajectory is pinned EQUAL to ZeRO-2 (tests/test_zero3.py).
    # Requires shard_gradients; default off = the ZeRO-2 step,
    # lowered-text-identical (kill-switch pin).
    shard_params: bool = False
    # Bucketed, overlap-capable gradient exchange (r14,
    # parallel/buckets.py): partition the param tree into buckets of ~this
    # many MB in reverse-backward order and issue one collective per
    # bucket as its gradients exist, so XLA's latency-hiding scheduler can
    # run the exchange under the remaining backward (arXiv 1711.00705 /
    # 1603.02339). 0 = single monolithic exchange, byte-identical to the
    # pre-r14 step (kill-switch lowered-text identity pinned). Under
    # sharding the opt-state flat layout becomes bucket-major
    # (checkpoints migrate through parallel/zero.convert_opt_state with
    # the geometry receipt in the checkpoint's `extra`).
    comm_bucket_mb: float = 0.0
    # Gradient all-reduce wire dtype. "float32" (default) reduces at full
    # precision. "bfloat16" halves the per-step collective bytes — the
    # analytic scaling model (utils/scaling_model.py) puts the fp32 worst
    # case at VGG-16's 553 MB gradient, 0.929 no-overlap efficiency at
    # v4-128; bf16 lifts that floor to ~0.96. Opt-in because it perturbs
    # gradients by bf16 rounding (~3 decimal digits): the cast happens
    # AFTER the local backward (fp32) and BEFORE the cross-replica mean;
    # momentum/params stay fp32. ZeRO-1's param all-gather is NOT affected
    # (params must re-sync bit-exactly).
    reduce_dtype: str = "float32"
    # Live elastic resize on preemption consensus (r19,
    # parallel/elastic.py); `mesh.elastic.enabled` is the kill-switch.
    elastic: ElasticConfig = field(default_factory=ElasticConfig)

    def __post_init__(self):
        if self.reduce_dtype not in ("float32", "bfloat16"):
            raise ValueError(
                f"mesh.reduce_dtype {self.reduce_dtype!r} not one of "
                f"('float32', 'bfloat16')")
        if self.comm_bucket_mb < 0:
            raise ValueError(
                f"mesh.comm_bucket_mb {self.comm_bucket_mb} < 0 (0 = "
                "single-bucket kill-switch, >0 = bucket size target)")
        if self.shard_params and not self.shard_gradients:
            raise ValueError(
                "mesh.shard_params (ZeRO-3) requires "
                "mesh.shard_gradients (ZeRO-2) — the sharding ladder is "
                "cumulative: parameter shards only exist inside the "
                "gradient-shard frame (set both, plus shard_opt_state)")

    @property
    def sharding_label(self) -> str:
        """The CONFIGURED (dp | zero1 | zero2 | zero3) basis — what this
        config ASKS for, via the same single derivation
        (parallel/buckets.sharding_basis) the step's runtime `comm`
        receipt uses. The receipt reports the EFFECTIVE basis, which can
        downgrade below this label (single-shard meshes drop zero1, and
        `shard_gradients` without `shard_opt_state` has no 1/N frame to
        live in — as the exchange plan has it, parallel/zero.py, so the
        README-documented `--set mesh.shard_opt_state=false` toggle stays
        valid on presets that ship ZeRO-2/3). Receipts/sentinel rows must
        key on the runtime `comm` block, not this property."""
        from distributed_vgg_f_tpu.parallel.buckets import sharding_basis
        return sharding_basis(self.shard_opt_state, self.shard_gradients,
                              self.shard_params)


@dataclass(frozen=True)
class TrainConfig:
    epochs: float = 90.0               # training length in epochs (fractional allowed)
    steps: int = 0                     # if >0 overrides epochs
    seed: int = 0                      # base RNG seed: params, data order, augmentation
    log_every: int = 100               # steps between train-metric log/JSONL records
    eval_every_steps: int = 0          # 0 = once per epoch
    checkpoint_every_steps: int = 1000 # durable-save cadence (also saves at run end)
    checkpoint_dir: str = ""           # "" disables checkpointing entirely
    keep_checkpoints: int = 3          # retained durable steps; older ones are pruned
    tensorboard_dir: str = ""          # "" disables TF summary output
    profile: bool = False              # jax.profiler trace around a few steps
    profile_dir: str = "/tmp/dvggf_profile"  # where the profiler trace lands
    profile_start_step: int = 10       # relative to the run's first step
    profile_num_steps: int = 5         # profiler window length
    debug_nans: bool = False           # jax_debug_nans (debug-only; see skip_nonfinite)
    # Non-finite step guard (resilience/guard.py; the production replacement
    # for the debug-only jax_debug_nans flag): the jitted step all-reduces an
    # isfinite(loss & grad_norm) flag and drops the optimizer update on a bad
    # step — parameters stay bit-identical, the step counter still advances.
    # After max_nonfinite_steps CONSECUTIVE skips the trainer aborts with a
    # NonFiniteStepError diagnostic instead of burning fleet time on a
    # diverged (or garbage-fed) run. Skip detection costs one select per
    # state leaf inside the step; the host poll is lagged (never blocks
    # dispatch, same idiom as parallel/preempt.py).
    skip_nonfinite: bool = True
    max_nonfinite_steps: int = 10   # consecutive-skip abort threshold (see above)
    # Data-pipeline watchdog (data/prefetch.py): per-batch timeout with
    # bounded exponential-backoff retries — a stalled or crashed host loader
    # surfaces as a typed DataStallError instead of an indefinite hang.
    # 0 disables the timeout (the dead-worker detector stays active);
    # retries double the wait each attempt, so the worst-case wall time is
    # data_timeout_s * (2^(retries+1) - 1). Requires the device-prefetch
    # thread: with prefetch_to_device=0 (or a caller-supplied dataset) the
    # watchdog cannot engage and the trainer logs data_watchdog_inactive.
    data_timeout_s: float = 0.0
    data_timeout_retries: int = 2   # backoff retries before DataStallError (see above)
    # Checkpoint resilience (checkpoint/manager.py): saves retry transient
    # I/O errors this many times (exponential backoff) before giving up;
    # durable steps get a checksum manifest and restores fall back to the
    # newest INTACT step when the latest is truncated or corrupt.
    checkpoint_save_retries: int = 2
    # Fault-injection spec (resilience/faults.py FaultPlan.parse): "" = no
    # injection (production). E.g. "nan@3,stall@5:20,preempt@8" — see the
    # module docstring for the grammar; tests/test_resilience.py is the
    # chaos suite built on it.
    fault_injection: str = ""
    # On-device batches kept ahead of compute by a background H2D thread
    # (data/prefetch.py); 0 disables the overlap and shards synchronously.
    prefetch_to_device: int = 2
    # On checkpoint resume, reproduce the uninterrupted data stream exactly
    # (SURVEY.md §5 checkpoint: data-iterator state). Pipelines with iterator
    # snapshots (imagenet tf.data: symbolic checkpoints written automatically
    # at the checkpoint cadence whenever checkpoint_dir is set) restore in
    # O(1) regardless of this flag. This flag enables the REPLAY fallback for
    # pipelines without snapshot support — one host draw per skipped step,
    # cheap for numpy/native iterators.
    resume_data_fast_forward: bool = True
    # PRNG implementation for the training dropout key. "rbg" generates random
    # bits ~1.6x faster than threefry on TPU for dropout-heavy models (ViT
    # train step measured 218→136 ms/step at batch 256 on v5e); still
    # deterministic per seed. Param init keeps the JAX default regardless.
    dropout_rng_impl: str = "rbg"
    # Micro-batch gradient accumulation inside the jitted step (lax.scan):
    # k>1 splits each device's batch into k micro-batches — 1/k activation
    # memory at an unchanged optimizer batch/LR schedule/sync schedule. The
    # per-device batch must divide by k. See train/step.py.
    grad_accum_steps: int = 1
    # ZeRO-2-flavored accumulation (requires mesh.shard_opt_state AND
    # grad_accum_steps > 1): each micro-gradient is reduce-scattered inside
    # the scan and only this replica's 1/N flat shard accumulates — the
    # persistent accumulator drops from O(params) to O(params/N), at k
    # reduce-scatters per step instead of one (k× the scatter-leg wire
    # bytes: the explicit memory-for-bandwidth trade). See train/step.py.
    grad_accum_shard: bool = False

    # Exponential moving average of params (0 disables). When on, eval and
    # predict score the EMA weights by default (the TF-era ImageNet recipe);
    # the raw weights keep training. EMA state is checkpointed; restoring a
    # pre-EMA checkpoint with EMA enabled re-seeds the average from the
    # restored params.
    ema_decay: float = 0.0

    def __post_init__(self):
        # k=0 (a typo for 10?) would silently train the full-batch path —
        # the opposite of what the user asked for memory-wise
        if self.grad_accum_steps < 1:
            raise ValueError(
                f"train.grad_accum_steps must be >= 1, got "
                f"{self.grad_accum_steps}")
        if not 0.0 <= self.ema_decay < 1.0:
            raise ValueError(
                f"train.ema_decay must be in [0, 1), got {self.ema_decay}")
        if self.max_nonfinite_steps < 1:
            raise ValueError(
                f"train.max_nonfinite_steps must be >= 1, got "
                f"{self.max_nonfinite_steps}")
        if self.data_timeout_s < 0:
            raise ValueError(
                f"train.data_timeout_s must be >= 0, got "
                f"{self.data_timeout_s}")
        if self.data_timeout_retries < 0 or self.checkpoint_save_retries < 0:
            raise ValueError(
                "train.data_timeout_retries and train.checkpoint_save_"
                "retries must be >= 0, got "
                f"{self.data_timeout_retries}/{self.checkpoint_save_retries}")
        # parse errors in a chaos spec must fail at config time, not after
        # the mesh is up and the first steps have run
        from distributed_vgg_f_tpu.resilience.faults import FaultPlan
        FaultPlan.parse(self.fault_injection)
    # Keep the best-eval-top1 checkpoint under <checkpoint_dir>/best (one
    # slot, replaced whenever a periodic eval during fit() sets a new best;
    # Orbax best-metric retention, score in the metadata). Restore it with
    # `train.restore_from_best=true` (eval/predict modes included). Eval
    # results are identical on every host (psum), so the collective save
    # decision is consistent in multi-host runs.
    track_best_eval: bool = True
    # Restore from the best-eval slot (selected by recorded score) instead
    # of the latest checkpoint — for `--mode eval|predict` on the best
    # model, or to branch training from it. Falls back to the latest
    # checkpoint (with a logged notice) when no best slot exists.
    restore_from_best: bool = False
    # Graceful preemption: on SIGTERM (the TPU-VM / k8s preemption signal),
    # finish the in-flight step, force-save a checkpoint, and exit cleanly so
    # the next incarnation resumes exactly where this one stopped. Multi-host
    # runs reach stop-consensus via a per-step asynchronous one-scalar
    # collective (parallel/preempt.py): all hosts stop at the same step
    # within ~3 steps of the signal, independent of log_every and of the
    # logging cadence generally.
    handle_preemption: bool = True


@dataclass(frozen=True)
class CollectorConfig:
    """Fleet metrics collector (r22, telemetry/collector.py): ONE process
    that scrapes every per-process exporter endpoint and serves the merged
    fleet view (/fleetz, one aggregated /metrics, quorum stall verdict
    with stragglers named). Off by default: big fleets run it as its own
    process (`python -m distributed_vgg_f_tpu.telemetry.collector`);
    enabling it here starts an in-process collector on rank 0."""
    # Start the in-process collector on rank 0 (requires telemetry.enabled
    # and, to have anything to scrape, telemetry.exporter on the ranks).
    enabled: bool = False
    # Scrape interval in seconds — every endpoint is polled once per cycle.
    interval_s: float = 1.0
    # Bind host for the fleet view; loopback by default (unauthenticated
    # process internals, same contract as the per-process exporter).
    host: str = "127.0.0.1"
    # Bind port for /fleetz + aggregated /metrics (0 = OS-assigned, logged).
    port: int = 0
    # Static scrape targets beyond sidecar discovery: `host:port`,
    # `role@host:port`, or `role[N]@host:port` entries (a serving box,
    # workers on another host).
    endpoints: Sequence[str] = ()
    # Directory holding exporter_p<rank>.jsonl discovery sidecars
    # ("" = use telemetry.sidecar_dir).
    sidecar_dir: str = ""
    # Append the per-cycle schema-validated fleet JSONL here ("" = off).
    fleet_log: str = ""
    # Seconds without a successful scrape before an endpoint's entry reads
    # `stale` (the entry keeps its last verdict + an age, never vanishes).
    stale_after_s: float = 10.0
    # Per-request scrape timeout — a hanging endpoint costs one cycle this
    # much, then degrades to stale; it never blocks the collector.
    scrape_timeout_s: float = 2.0

    def __post_init__(self):
        if self.interval_s <= 0:
            raise ValueError(
                f"telemetry.collector.interval_s must be > 0, got "
                f"{self.interval_s}")
        if not 0 <= self.port <= 65535:
            raise ValueError(
                f"telemetry.collector.port must be in [0, 65535], got "
                f"{self.port}")
        if self.stale_after_s < 0:
            raise ValueError(
                f"telemetry.collector.stale_after_s must be >= 0, got "
                f"{self.stale_after_s}")
        if self.scrape_timeout_s <= 0:
            raise ValueError(
                f"telemetry.collector.scrape_timeout_s must be > 0, got "
                f"{self.scrape_timeout_s}")


@dataclass(frozen=True)
class TelemetryConfig:
    """Unified observability layer (distributed_vgg_f_tpu/telemetry/):
    always-on span ring buffer + counter registry + per-step stall
    attribution. On by default — the whole design point is that it is cheap
    enough to leave on (the host bench's telemetry-overhead receipt is the
    proof); `enabled=false` is the kill-switch."""
    enabled: bool = True
    # Span ring-buffer capacity (spans, not bytes; ~100 B each). The ring
    # keeps the NEWEST spans — the window a stall diagnosis needs.
    span_capacity: int = 8192
    # Write the span buffer as Chrome trace-event JSON here at the end of
    # fit() ("" = off). Loadable in Perfetto next to (or instead of) a
    # jax.profiler window; multi-process runs insert `_p<rank>` before the
    # extension.
    trace_export: str = ""
    # Per-process telemetry JSONL sidecars under this directory ("" = off):
    # each process writes telemetry_p<rank>.jsonl (full registry snapshot +
    # span stats); process 0 additionally aggregates counters across hosts
    # into telemetry_aggregate.json.
    sidecar_dir: str = ""
    # Per-log-window stall attribution in the "train" step records
    # (telemetry/stall.py verdict taxonomy).
    stall_attribution: bool = True
    # Fraction of a log window spent blocked on the input pipeline /
    # checkpoint machinery before the window is attributed to it.
    infeed_threshold: float = 0.25
    checkpoint_threshold: float = 0.25   # same contract, checkpoint machinery
    # Live observability endpoint (telemetry/exporter.py): a per-process
    # background HTTP server serving /metrics (Prometheus text), /healthz,
    # /stallz, and /trace WHILE the run is alive. Off by default (the
    # fit-finally export covers offline analysis); the committed
    # scrape-under-load receipt (benchmarks/runs/) is the proof it fits
    # the <2 % telemetry budget when on.
    exporter: bool = False
    # 0 = bind an OS-assigned free port (the multi-host default — N
    # processes per host never collide); the bound port is logged and
    # written to the run sidecar (exporter_p<rank>.jsonl).
    exporter_port: int = 0
    # Loopback by default: the exporter serves unauthenticated process
    # internals — exposing it beyond the host is an explicit decision.
    exporter_host: str = "127.0.0.1"
    # /healthz flips to "stalled" (HTTP 503) once the trainer heartbeat is
    # older than this many seconds.
    exporter_stalled_after_s: float = 120.0
    # Flight recorder (telemetry/flight.py): always-on bounded ring of
    # per-log-window summaries, dumped as a schema-validated black box on
    # diagnosed aborts (non-finite abort, data stall, injected crash,
    # unhandled exception).
    flight_windows: int = 64
    # Where the black box lands ("" = first configured of sidecar_dir,
    # then <checkpoint_dir>/flight; with neither, the dump is skipped with
    # a logged event — the ring still serves /stallz).
    flight_dir: str = ""
    # Fleet collector (r22): the cross-process aggregation plane over the
    # per-process exporters — see CollectorConfig.
    collector: CollectorConfig = field(default_factory=CollectorConfig)

    def __post_init__(self):
        if self.span_capacity < 1:
            raise ValueError(
                f"telemetry.span_capacity must be >= 1, got "
                f"{self.span_capacity}")
        for name in ("infeed_threshold", "checkpoint_threshold"):
            v = getattr(self, name)
            if not 0.0 < v <= 1.0:
                raise ValueError(
                    f"telemetry.{name} must be in (0, 1], got {v}")
        if not 0 <= self.exporter_port <= 65535:
            raise ValueError(
                f"telemetry.exporter_port must be in [0, 65535], got "
                f"{self.exporter_port}")
        if self.exporter_stalled_after_s <= 0:
            raise ValueError(
                f"telemetry.exporter_stalled_after_s must be > 0, got "
                f"{self.exporter_stalled_after_s}")
        if self.flight_windows < 1:
            raise ValueError(
                f"telemetry.flight_windows must be >= 1, got "
                f"{self.flight_windows}")


@dataclass(frozen=True)
class ExperimentConfig:
    """The config-tree root: one section dataclass per subsystem, addressed
    from the CLI as `--set <section>.<field>=<value>` (`name` labels the
    preset/run). Sections: `model`, `optim`, `data`, `mesh`, `train`,
    `telemetry`, `serving`."""
    name: str = "vggf_synthetic"
    model: ModelConfig = field(default_factory=ModelConfig)
    optim: OptimConfig = field(default_factory=OptimConfig)
    data: DataConfig = field(default_factory=DataConfig)
    mesh: MeshConfig = field(default_factory=MeshConfig)
    train: TrainConfig = field(default_factory=TrainConfig)
    telemetry: TelemetryConfig = field(default_factory=TelemetryConfig)
    # Always-on dynamic-batching predict server (r17, serving/): off by
    # default; `--mode serve` requires the explicit serving.enabled opt-in.
    serving: ServingConfig = field(default_factory=ServingConfig)

    @property
    def steps_per_epoch(self) -> int:
        return max(1, self.data.num_train_examples // self.data.global_batch_size)

    @property
    def total_steps(self) -> int:
        if self.train.steps > 0:
            return self.train.steps
        return int(self.train.epochs * self.steps_per_epoch)

    @property
    def scaled_lr(self) -> float:
        """Linear LR scaling with global batch (Goyal et al. practice)."""
        return self.optim.base_lr * (
            self.data.global_batch_size / self.optim.reference_batch_size
        )


def _replace(cfg, **kw):
    return dataclasses.replace(cfg, **kw)


#: Datasets whose host pipeline actually implements the packed layout. A
#: dataset outside this set combined with space_to_depth=True must be
#: rejected, not silently fed unpacked (ADVICE r2: cifar10 passed the
#: model/size guard but its builder ignores the flag).
SPACE_TO_DEPTH_DATASETS = frozenset({"synthetic", "imagenet"})


def supports_space_to_depth(model_name: str, image_size: int,
                            dataset_name: str | None = None) -> bool:
    """Packed-input eligibility — the single definition of which configs may
    set `data.space_to_depth`. The MODEL half now comes from the per-model
    ingest descriptor (models/ingest.py, r13: the zoo contract table that
    replaced the VGGF-only wiring); the trainer validates against this and
    the benches use it so they measure the same layout production trains
    with. Pass `dataset_name` to also require a host pipeline that
    implements packing."""
    from distributed_vgg_f_tpu.models.ingest import ingest_descriptor
    return ingest_descriptor(model_name).space_to_depth \
        and image_size % 4 == 0 and (
            dataset_name is None or dataset_name in SPACE_TO_DEPTH_DATASETS)


def zoo_data(base: DataConfig, model_name: str) -> DataConfig:
    """Derive one zoo preset's data config from the flagship's by applying
    the model's ingest descriptor (models/ingest.py) — wire, packed-layout
    eligibility, and normalize constants all come from the per-model
    table, so presets no longer hand-override `data` per model (the r12
    'override `data` back to the raw layout' wiring this replaces). The
    u8 wire, snapshot cache, autotuner, and device-side augmentation all
    ride along unchanged: ONE ingest contract for the whole zoo."""
    from distributed_vgg_f_tpu.models.ingest import ingest_descriptor
    d = ingest_descriptor(model_name)
    return _replace(base, wire=d.wire, space_to_depth=d.space_to_depth,
                    mean_rgb=tuple(d.mean_rgb), stddev_rgb=tuple(d.stddev_rgb))


# ---------------------------------------------------------------------------
# Presets — one per BASELINE.json "configs" entry.
# ---------------------------------------------------------------------------

def _vggf_cifar10_smoke() -> ExperimentConfig:
    """BASELINE config #1: VGG-F on CIFAR-10, single process (CPU/1-chip smoke)."""
    return ExperimentConfig(
        name="vggf_cifar10_smoke",
        model=ModelConfig(name="vggf", num_classes=10, compute_dtype="float32"),
        optim=OptimConfig(base_lr=0.01, weight_decay=5e-4,
                          decay_epochs=(40.0, 70.0), reference_batch_size=128),
        data=DataConfig(name="cifar10", image_size=32, global_batch_size=128,
                        num_train_examples=50_000, num_eval_examples=10_000,
                        mean_rgb=(125.3, 123.0, 113.9), stddev_rgb=(63.0, 62.1, 66.7)),
        train=TrainConfig(epochs=10.0, log_every=50, checkpoint_every_steps=500,
                          resume_data_fast_forward=True),
    )


def _vggf_imagenet_dp() -> ExperimentConfig:
    """BASELINE config #2: VGG-F ImageNet-1k, DP over the full mesh (psum all-reduce)."""
    return ExperimentConfig(
        name="vggf_imagenet_dp",
        model=ModelConfig(name="vggf", num_classes=1000),
        optim=OptimConfig(base_lr=0.01, reference_batch_size=256,
                          weight_decay=5e-4, decay_epochs=(30.0, 60.0, 80.0)),
        # space_to_depth: the stem consumes the packed 4x4 layout (+3.7%
        # device step; per-model declaration in models/ingest.py — the
        # derived zoo presets below apply THEIR descriptors via zoo_data).
        # wire='u8' (r8): the flagship ships the uint8 ingest wire — raw
        # pixels on the host, normalize/cast/s2d fused into the device
        # step — the basis of HOST_DECODE_RATE_R8 and the provisioning
        # table; refused builds fall back to the host wire with a logged
        # warning.
        # autotune on (r11): the flagship self-tunes its ingest from the
        # stall attributor's verdicts instead of inheriting one box's bench
        # pins — heterogeneous host classes feeding the same mesh each
        # converge to their own knob settings. DVGGF_AUTOTUNE=0 kills it.
        # augment (r13): fused on-device flips + mixup — diversity at zero
        # host cost (the host never flips; data/augment.py owns it inside
        # the jitted step). data.augment.enabled=false is the kill-switch
        # (structurally absent, byte-identical trajectory — pinned).
        data=zoo_data(
            DataConfig(name="imagenet", image_size=224,
                       global_batch_size=1024,
                       autotune=AutotuneConfig(enabled=True),
                       augment=AugmentConfig(enabled=True, hflip=True,
                                             mixup_alpha=0.2)),
            "vggf"),
        # ZeRO-1 optimizer-state sharding (r13, ROADMAP item 4 first
        # slice): ~90% of VGG-F's params sit in three FC layers (arXiv
        # 2004.13336's exact workload) — replicated momentum burns per-chip
        # HBM the sharded update reclaims. The step body and checkpoint
        # retopology already compose (parallel/zero.py, r1–r5 tests); this
        # flips the flagship on, with the CPU-mesh loss-trajectory parity
        # pin in tests/test_zero1.py. Single-process CPU smoke runs
        # downgrade themselves (one shard = replicated). The device HBM
        # saving is not measured.
        # ZeRO-2 + bucketed overlap (r14): gradients held only as 1/N
        # shards and the exchange issued as 4 MB buckets in
        # reverse-backward order, so the scatter runs under the remaining
        # backward instead of after it (parallel/buckets.py; CPU
        # loss-trajectory parity + lowered-HLO overlap evidence pinned in
        # tests/test_comm_buckets.py; device step time and HBM are not
        # measured).
        mesh=MeshConfig(shard_opt_state=True, shard_gradients=True,
                        comm_bucket_mb=4.0),
        train=TrainConfig(epochs=90.0),
    )


def _vgg16_imagenet() -> ExperimentConfig:
    """BASELINE config #3: VGG-16 ImageNet-1k (deeper conv stack, same DP path)."""
    base = _vggf_imagenet_dp()
    return _replace(
        base,
        name="vgg16_imagenet",
        model=ModelConfig(name="vgg16", num_classes=1000),
        optim=OptimConfig(base_lr=0.01, reference_batch_size=256, weight_decay=5e-4,
                          decay_epochs=(30.0, 60.0, 80.0), warmup_epochs=2.0),
        # first-class consumer of the SAME u8-wire + device-ingest contract
        # (r13): the model's ingest descriptor decides layout/constants —
        # no hand-override back to the raw layout
        data=zoo_data(base.data, "vgg16"),
    )


def _resnet50_imagenet() -> ExperimentConfig:
    """BASELINE config #4: ResNet-50 ImageNet-1k with cross-replica sync-BN."""
    base = _vggf_imagenet_dp()
    return _replace(
        base,
        name="resnet50_imagenet",
        model=ModelConfig(name="resnet50", num_classes=1000, dropout_rate=0.0),
        optim=OptimConfig(base_lr=0.1, reference_batch_size=256, weight_decay=1e-4,
                          decay_epochs=(30.0, 60.0, 80.0), warmup_epochs=5.0),
        # first-class consumer of the SAME u8-wire + device-ingest contract
        # (r13): the model's ingest descriptor decides layout/constants
        data=zoo_data(base.data, "resnet50"),
    )


def _vit_s16_imagenet() -> ExperimentConfig:
    """BASELINE config #5: ViT-S/16 ImageNet-1k under the same DP all-reduce."""
    base = _vggf_imagenet_dp()
    return _replace(
        base,
        name="vit_s16_imagenet",
        # dropout 0.1 on MLP/residual/embedding; attention-WEIGHT dropout is
        # 0.0 by model default (canonical DeiT-S / official ViT recipes; the
        # (B,H,197,197) mask RNG cost ~10% of the TPU step — r3 trace).
        # Re-enable with --set model.extra.attention_dropout_rate=0.1.
        model=ModelConfig(name="vit_s16", num_classes=1000, dropout_rate=0.1),
        optim=OptimConfig(base_lr=1e-3, reference_batch_size=1024, momentum=0.9,
                          weight_decay=1e-4, schedule="cosine", warmup_epochs=5.0),
        # first-class consumer of the SAME u8-wire + device-ingest contract
        # (r13): the model's ingest descriptor decides layout/constants
        data=zoo_data(base.data, "vit_s16"),
        train=TrainConfig(epochs=300.0),
    )


def _vggf_synthetic() -> ExperimentConfig:
    """Synthetic-data variant used by tests and the throughput benchmark."""
    return ExperimentConfig(
        name="vggf_synthetic",
        model=ModelConfig(name="vggf", num_classes=1000),
        data=DataConfig(name="synthetic", image_size=224, global_batch_size=256,
                        num_train_examples=100_000),
        train=TrainConfig(steps=100, log_every=10),
    )


def _vggf_teacher() -> ExperimentConfig:
    """Offline generalization config (data/teacher.py): fixed random teacher
    labels, augmented+noisy train split, disjoint clean val split — the run
    that demonstrates a real train/val gap without external data
    (VERDICT r2 #3; benchmarks/teacher_generalization.py)."""
    return ExperimentConfig(
        name="vggf_teacher",
        # Tuned to the task's measured dynamics (loss plateaus ~250 steps
        # before breaking through): weight_decay well below the CIFAR preset
        # (a 5e-4 L2 term matches the CE loss in magnitude and pins the net
        # at the zero function — top-1 stuck ≈ 0.13), lr modest (0.05
        # produced a grad spike that killed the ReLUs — gnorm 24 → 0.006),
        # clipping as the spike guard.
        model=ModelConfig(name="vggf", num_classes=10,
                          compute_dtype="float32", dropout_rate=0.2),
        optim=OptimConfig(base_lr=0.02, reference_batch_size=64,
                          weight_decay=5e-5, warmup_epochs=1.0,
                          grad_clip_norm=1.0, decay_epochs=(24.0, 30.0)),
        data=DataConfig(name="teacher", image_size=32, global_batch_size=64,
                        num_train_examples=4096, num_eval_examples=1024),
        train=TrainConfig(epochs=32.0, log_every=64,
                          eval_every_steps=256),
    )


#: `Mistral-Small-4-119B-2603`'s published language-model config
#: (https://huggingface.co/mistralai/Mistral-Small-4-119B-2603/blob/main/
#: config.json, `model_type: mistral4`): every width as published.
MISTRAL_SMALL4_PUBLISHED = {
    "hidden_size": 4096, "num_attention_heads": 32, "q_lora_rank": 1024,
    "kv_lora_rank": 256, "qk_nope_head_dim": 64, "qk_rope_head_dim": 64,
    "v_head_dim": 128, "n_routed_experts": 128, "num_experts_per_tok": 4,
    "moe_intermediate_size": 2048, "n_shared_experts": 1,
    "rms_norm_eps": 1e-6,
    "rope_parameters": {
        "rope_theta": 10000, "factor": 128, "beta_fast": 32, "beta_slow": 1,
        "original_max_position_embeddings": 8192, "mscale": 1,
        "mscale_all_dim": 1, "llama_4_scaling_beta": 0.1,
        "rope_type": "yarn", "type": "yarn"},
}


def _language_model(name: str, extra: dict, vocab_rows: int, steps: int,
                    model: str = "mistral4") -> ExperimentConfig:
    """The language-model presets' common frame (`model`: the registry's
    name, models/mistral4.py, models/nemotron_h.py, models/ling3.py or
    models/solar_open2.py):
    packed int32 tokens
    from the seeded source, one sequence a step, bf16 compute on float32
    weights, SGD-momentum 0.9 at a constant rate, no weight decay, no
    dropout, no augmentation (the catalog gives no recipe). `model.extra`
    carries the published widths, the share (`experts_held`,
    `first_expert`, `num_hidden_layers`; `model.num_classes` is the
    vocabulary rows held) and `seq_len`, which the token source reads.
    Training only: serving, checkpoint re-topology and ZeRO's flat vector
    for this model are out of scope (mesh flags stay off)."""
    return ExperimentConfig(
        name=name,
        model=ModelConfig(name=model, num_classes=vocab_rows,
                          dropout_rate=0.0, extra=extra),
        optim=OptimConfig(base_lr=0.01, reference_batch_size=1, momentum=0.9,
                          weight_decay=0.0, schedule="constant"),
        data=DataConfig(name="synthetic_tokens", global_batch_size=1,
                        num_train_examples=1_000_000),
        train=TrainConfig(steps=steps, log_every=100),
    )


def _mistral_small4_119b_ep16() -> ExperimentConfig:
    """One chip's share of Mistral-Small-4-119B under 16-way expert
    parallelism: 4 of 36 layers, experts [0, 8) of 128 (the router stays
    128 wide, top-4), 16384 of 131072 vocabulary rows, sequences of 4096.
    1.15 B parameters: 12 bytes each (weights, momentum, gradients) fill
    the chip before the first activation, so every block is recomputed in
    the backward pass and the loss goes over the sequence in chunks."""
    return _language_model(
        "mistral_small4_119b_ep16",
        {**MISTRAL_SMALL4_PUBLISHED, "num_hidden_layers": 4,
         "first_expert": 0, "experts_held": 8, "seq_len": 4096},
        vocab_rows=16384, steps=100)


def _mistral_small4_tiny() -> ExperimentConfig:
    """The same block at a size a CPU test holds, every expert held:
    hidden 64, 8 experts top-2, 2 layers, vocabulary 256, sequences of 32,
    float32 compute (off a TPU the attention core is explicit scores, not
    the Pallas kernel: models/mistral4.py)."""
    cfg = _language_model(
        "mistral_small4_tiny",
        {"hidden_size": 64, "num_attention_heads": 4, "q_lora_rank": 32,
         "kv_lora_rank": 16, "qk_nope_head_dim": 8, "qk_rope_head_dim": 8,
         "v_head_dim": 16, "n_routed_experts": 8, "num_experts_per_tok": 2,
         "moe_intermediate_size": 32, "n_shared_experts": 1,
         "rms_norm_eps": 1e-6,
         "rope_parameters": MISTRAL_SMALL4_PUBLISHED["rope_parameters"],
         "num_hidden_layers": 2, "seq_len": 32},
        vocab_rows=256, steps=3)
    return _replace(
        cfg, model=_replace(cfg.model, compute_dtype="float32"),
        data=_replace(cfg.data, global_batch_size=2),
        optim=_replace(cfg.optim, reference_batch_size=2),
        train=_replace(cfg.train, log_every=1))


#: `NVIDIA-Nemotron-3-Nano-30B-A3B-BF16`'s published config
#: (https://huggingface.co/nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16/blob/
#: main/config.json, `model_type: nemotron_h`): every width as published.
#: The pattern is the model's 52 letters; a preset runs a prefix of it.
NEMOTRON3_NANO_PUBLISHED = {
    "hidden_size": 2688, "mamba_num_heads": 64, "mamba_head_dim": 64,
    "n_groups": 8, "ssm_state_size": 128, "conv_kernel": 4,
    "chunk_size": 128, "time_step_min": 0.001, "time_step_max": 0.1,
    "time_step_floor": 0.0001, "num_attention_heads": 32,
    "num_key_value_heads": 2, "head_dim": 128, "n_routed_experts": 128,
    "num_experts_per_tok": 6, "moe_intermediate_size": 1856,
    "moe_shared_expert_intermediate_size": 3712, "n_shared_experts": 1,
    "routed_scaling_factor": 2.5, "layer_norm_epsilon": 1e-5,
    "hybrid_override_pattern":
        "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME",
}


def _nemotron3_nano_30b_ep8() -> ExperimentConfig:
    """One chip's share of Nemotron-3-Nano-30B-A3B under 8-way expert
    parallelism: the first 9 of 52 layers (`MEMEM*EME`: four Mamba-2
    mixers, four expert layers, one attention layer), experts [0, 16) of
    128 (the router stays 128 wide, top-6), 16384 of 131072 vocabulary
    rows, two sequences of 8192 a step. 0.99 B parameters at 12 bytes each
    (weights, momentum, gradients): recomputation per block and the loss
    in chunks, as the Mistral preset and for its reason."""
    cfg = _language_model(
        "nemotron3_nano_30b_ep8",
        {**NEMOTRON3_NANO_PUBLISHED, "hybrid_override_pattern":
         NEMOTRON3_NANO_PUBLISHED["hybrid_override_pattern"][:9],
         "first_expert": 0, "experts_held": 16, "seq_len": 8192},
        vocab_rows=16384, steps=100, model="nemotron_h")
    return _replace(cfg, data=_replace(cfg.data, global_batch_size=2),
                    optim=_replace(cfg.optim, reference_batch_size=2))


def _nemotron3_nano_tiny() -> ExperimentConfig:
    """The same stack at a size a CPU test holds, every expert held, every
    kind of layer (`MEM*E`): hidden 64, 4 Mamba heads of 8 in 2 groups with
    a state of 16, chunks of 8 (sequences of 32: four chunks), 4 query
    heads on 2 key heads, 8 experts top-2, vocabulary 256, float32."""
    cfg = _language_model(
        "nemotron3_nano_tiny",
        {**NEMOTRON3_NANO_PUBLISHED, "hidden_size": 64, "mamba_num_heads": 4,
         "mamba_head_dim": 8, "n_groups": 2, "ssm_state_size": 16,
         "chunk_size": 8, "num_attention_heads": 4, "num_key_value_heads": 2,
         "head_dim": 16, "n_routed_experts": 8, "num_experts_per_tok": 2,
         "moe_intermediate_size": 32,
         "moe_shared_expert_intermediate_size": 48,
         "hybrid_override_pattern": "MEM*E", "seq_len": 32},
        vocab_rows=256, steps=3, model="nemotron_h")
    return _replace(
        cfg, model=_replace(cfg.model, compute_dtype="float32"),
        data=_replace(cfg.data, global_batch_size=2),
        optim=_replace(cfg.optim, reference_batch_size=2),
        train=_replace(cfg.train, log_every=1))


#: `Ling-3.0-flash-VL`'s published language-model config
#: (https://huggingface.co/inclusionAI/Ling-3.0-flash-VL/blob/main/
#: config.json): every width as published. `n_routed_experts` is the
#: source's `num_experts` under the name `mistral4.ExpertShare` and the
#: benchmark's driver know the router's width by.
LING3_FLASH_PUBLISHED = {
    "hidden_size": 2560, "intermediate_size": 6144,
    "num_attention_heads": 32, "head_dim": 128, "layer_group_size": 6,
    "short_conv_kernel_size": 4, "kda_lower_bound": -5,
    "q_lora_rank": None, "kv_lora_rank": 512, "qk_nope_head_dim": 128,
    "qk_rope_head_dim": 64, "v_head_dim": 128, "rope_theta": 6000000,
    "n_routed_experts": 512, "num_experts_per_tok": 8, "n_group": 8,
    "topk_group": 4, "moe_intermediate_size": 768,
    "moe_shared_expert_intermediate_size": 768,
    "routed_scaling_factor": 2.5, "rms_norm_eps": 1e-6,
    "num_hidden_layers": 42, "first_k_dense_replace": 2,
}


def _ling3_flash_ep64() -> ExperimentConfig:
    """One chip's share of Ling-3.0-flash's language stack under 64-way
    expert parallelism: 7 of 42 layers, one leading dense layer (of two)
    and one period of six expert layers, kinds by the published rule
    (`DKKKKLK`: six Kimi-delta layers, one latent), experts [0, 8) of 512
    (the router stays 512 wide, 8 groups, top-8 of the best 4), 19648 of
    157184 vocabulary rows, two sequences of 8192 a step. 0.82 B parameters
    at 12 bytes each: recomputation per block and the loss in chunks, as
    the Mistral preset and for its reason."""
    cfg = _language_model(
        "ling3_flash_ep64",
        {**LING3_FLASH_PUBLISHED, "num_hidden_layers": 7,
         "first_k_dense_replace": 1, "hybrid_override_pattern": "DKKKKLK",
         "first_expert": 0, "experts_held": 8, "seq_len": 8192},
        vocab_rows=19648, steps=100, model="ling3")
    return _replace(cfg, data=_replace(cfg.data, global_batch_size=2),
                    optim=_replace(cfg.optim, reference_batch_size=2))


def _ling3_flash_tiny() -> ExperimentConfig:
    """The same seven kinds of layer at a size a CPU test holds, every
    expert held: hidden 64, 4 heads of 16 (latent: 16 + 8 on values of
    16, rank 16), 16 experts in 4 groups, top-4 of the best 2, vocabulary
    256, sequences of 128 (two chunks of the delta rule), float32."""
    cfg = _language_model(
        "ling3_flash_tiny",
        {**LING3_FLASH_PUBLISHED, "hidden_size": 64, "intermediate_size": 96,
         "num_attention_heads": 4, "head_dim": 16, "kv_lora_rank": 16,
         "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
         "n_routed_experts": 16, "num_experts_per_tok": 4, "n_group": 4,
         "topk_group": 2, "moe_intermediate_size": 32,
         "moe_shared_expert_intermediate_size": 32, "num_hidden_layers": 7,
         "first_k_dense_replace": 1, "hybrid_override_pattern": "DKKKKLK",
         "seq_len": 128},
        vocab_rows=256, steps=3, model="ling3")
    return _replace(
        cfg, model=_replace(cfg.model, compute_dtype="float32"),
        data=_replace(cfg.data, global_batch_size=2),
        optim=_replace(cfg.optim, reference_batch_size=2),
        train=_replace(cfg.train, log_every=1))


#: `Solar-Open2-250B`'s published config (https://huggingface.co/upstage/
#: Solar-Open2-250B/blob/main/config.json, `model_type: solar_open2`):
#: every width as published; `gqa_layers` the rule the kinds follow.
SOLAR_OPEN2_PUBLISHED = {
    "hidden_size": 4096, "num_attention_heads": 64, "head_dim": 128,
    "num_key_value_heads": 8, "intermediate_size": 10240,
    "linear_attn_config": {"short_conv_kernel_size": 4, "head_dim": 128,
                           "num_heads": 64, "num_kv_heads": None},
    "moe_intermediate_size": 1280, "rms_norm_eps": 1e-05,
    "num_hidden_layers": 48, "first_k_dense_replace": 0, "use_rope": False,
    "gqa_interval": 3, "gqa_layers": list(range(0, 48, 4)),
    "use_gqa_gate": True, "kda_use_full_proj": False,
    "kda_allow_neg_eigval": True, "n_routed_experts": 320,
    "n_shared_experts": 1, "norm_topk_prob": True,
    "routed_scaling_factor": 1, "num_experts_per_tok": 8,
}


def _solar_open2_250b_ep40() -> ExperimentConfig:
    """One chip's share of Solar-Open2-250B under 40-way expert
    parallelism: 4 of 48 layers, one period of the published pattern
    (`GKKK`: gated grouped-query attention, then three Kimi-delta layers,
    experts in each), experts [0, 8) of 320 (the router stays 320 wide,
    top-8), 24576 of 196608 vocabulary rows, one sequence of 4096 a step.
    1.30 B parameters: float32 weights and momentum and bf16 gradients
    (10 bytes each) take 12.1 GiB of the chip's 15.75 before the first
    activation; recomputation per block and the loss in chunks, as the
    Mistral preset and for its reason."""
    return _language_model(
        "solar_open2_250b_ep40",
        {**SOLAR_OPEN2_PUBLISHED, "num_hidden_layers": 4,
         "hybrid_override_pattern": "GKKK", "first_expert": 0,
         "experts_held": 8, "seq_len": 4096},
        vocab_rows=24576, steps=100, model="solar_open2")


def _solar_open2_tiny() -> ExperimentConfig:
    """The same four layers at a size a CPU test holds, every expert held:
    hidden 64, 4 delta-rule heads of 16, 4 query heads on 2 key/value heads
    of 16, 16 experts top-4 of width 32, vocabulary 256, sequences of 128
    (two chunks of the delta rule), float32."""
    cfg = _language_model(
        "solar_open2_tiny",
        {**SOLAR_OPEN2_PUBLISHED, "hidden_size": 64,
         "num_attention_heads": 4, "head_dim": 16, "num_key_value_heads": 2,
         "linear_attn_config": {**SOLAR_OPEN2_PUBLISHED["linear_attn_config"],
                                "head_dim": 16, "num_heads": 4},
         "moe_intermediate_size": 32, "n_routed_experts": 16,
         "num_experts_per_tok": 4, "num_hidden_layers": 4,
         "hybrid_override_pattern": "GKKK", "seq_len": 128},
        vocab_rows=256, steps=3, model="solar_open2")
    return _replace(
        cfg, model=_replace(cfg.model, compute_dtype="float32"),
        data=_replace(cfg.data, global_batch_size=2),
        optim=_replace(cfg.optim, reference_batch_size=2),
        train=_replace(cfg.train, log_every=1))


PRESETS = {
    "vggf_cifar10_smoke": _vggf_cifar10_smoke,
    "vggf_imagenet_dp": _vggf_imagenet_dp,
    "vgg16_imagenet": _vgg16_imagenet,
    "resnet50_imagenet": _resnet50_imagenet,
    "vit_s16_imagenet": _vit_s16_imagenet,
    "vggf_synthetic": _vggf_synthetic,
    "vggf_teacher": _vggf_teacher,
    "mistral_small4_119b_ep16": _mistral_small4_119b_ep16,
    "mistral_small4_tiny": _mistral_small4_tiny,
    "nemotron3_nano_30b_ep8": _nemotron3_nano_30b_ep8,
    "nemotron3_nano_tiny": _nemotron3_nano_tiny,
    "ling3_flash_ep64": _ling3_flash_ep64,
    "ling3_flash_tiny": _ling3_flash_tiny,
    "solar_open2_250b_ep40": _solar_open2_250b_ep40,
    "solar_open2_tiny": _solar_open2_tiny,
}


def get_config(name: str) -> ExperimentConfig:
    try:
        return PRESETS[name]()
    except KeyError:
        raise KeyError(f"unknown config {name!r}; available: {sorted(PRESETS)}")


_BOOL_WORDS = {"true": True, "1": True, "yes": True, "on": True,
               "false": False, "0": False, "no": False, "off": False}


def _coerce_override(current: Any, value: Any) -> Any:
    """Cast a CLI override string to the type of the field it replaces.

    bool must be handled before int (bool is an int subclass) and never via
    ``bool(str)``, which is True for any non-empty string including "false".
    Sequence fields accept comma-separated values typed like their current
    elements (e.g. ``optim.decay_epochs=20,40`` -> ``(20.0, 40.0)``).
    """
    if current is None:
        return value
    same_boolness = isinstance(value, bool) == isinstance(current, bool)
    if isinstance(value, type(current)) and same_boolness:
        return value
    if isinstance(current, bool):
        word = str(value).strip().lower()
        if word not in _BOOL_WORDS:
            raise ValueError(
                f"boolean override needs true/false/1/0/yes/no/on/off, got {value!r}")
        return _BOOL_WORDS[word]
    if isinstance(current, (int, float)):
        return type(current)(value)
    if isinstance(current, str):
        return str(value)
    if isinstance(current, Sequence) and not isinstance(current, (str, bytes)):
        elem_type = type(current[0]) if len(current) else str
        if isinstance(value, str):
            return tuple(elem_type(v.strip()) for v in value.split(",") if v.strip())
        if not isinstance(value, Sequence):
            value = (value,)
        return tuple(elem_type(v) for v in value)
    return value


def parse_extra_value(value: Any) -> Any:
    """Public alias of `_parse_literal` for out-of-package callers that
    accept `model.extra`-style KEY=VALUE strings (bench.py --model-extra)."""
    return _parse_literal(value)


def _parse_literal(value: Any) -> Any:
    """Best-effort typing for dict entries with no existing value to mirror
    (e.g. a fresh ``model.extra`` key): numbers first, then the WORD-only
    bool spellings, then the raw string. "1"/"0" must parse as ints here —
    with no existing value there is nothing marking them booleans, and a
    numeric key silently becoming `True` breaks dtype inference downstream
    (code-review r3)."""
    if not isinstance(value, str):
        return value
    for cast in (int, float):
        try:
            return cast(value)
        except ValueError:
            continue
    word = value.strip().lower()
    # (the numeric casts above already returned for "1"/"0", which is what
    # guarantees they parse as ints even though _BOOL_WORDS lists them)
    if word in _BOOL_WORDS:
        return _BOOL_WORDS[word]
    return value


def _set_path(obj: Any, parts: Sequence[str], value: Any) -> Any:
    """Immutably set a dotted path through dataclasses AND Mappings (the
    ``model.extra`` dict takes model-specific keys, so overrides like
    ``model.extra.attention_dropout_rate=0.1`` must descend into it)."""
    name = parts[0]
    if isinstance(obj, Mapping):
        current = obj.get(name)
        if len(parts) == 1:
            new_leaf = (_parse_literal(value) if current is None
                        or isinstance(current, Mapping)
                        else _coerce_override(current, value))
            return {**obj, name: new_leaf}
        if current is None:
            raise KeyError(
                f"cannot descend into missing dict key {name!r} "
                f"(remaining path: {'.'.join(parts[1:])})")
        return {**obj, name: _set_path(current, parts[1:], value)}
    current = getattr(obj, name)
    if len(parts) == 1:
        if not isinstance(current, Mapping):
            value = _coerce_override(current, value)
        return dataclasses.replace(obj, **{name: value})
    return dataclasses.replace(obj, **{name: _set_path(current, parts[1:], value)})


def apply_overrides(cfg: ExperimentConfig, overrides: Mapping[str, Any]) -> ExperimentConfig:
    """Apply dotted-path overrides, e.g. {"data.global_batch_size": 512}."""
    for path, value in overrides.items():
        cfg = _set_path(cfg, path.split("."), value)
    return cfg


def fold_override_items(items: Sequence[str] | None) -> dict:
    """`--set KEY=VALUE` entries → the overrides dict `apply_overrides`
    takes. The ONE folding implementation shared by the trainer CLI
    (parse_cli) and bench.py's --set — duplicate loops drifted on
    validation (one rejected '='-less items, one silently took them as
    empty-string overrides)."""
    overrides = {}
    for item in items or ():
        key, sep, value = item.partition("=")
        if not sep or not key:
            raise ValueError(f"override needs KEY=VALUE, got {item!r}")
        overrides[key] = value
    return overrides


def parse_cli(argv: Sequence[str] | None = None, *, with_mode: bool = False):
    parser = argparse.ArgumentParser(description="distributed_vgg_f_tpu trainer")
    parser.add_argument("--config", default="vggf_cifar10_smoke",
                        help=f"preset name, one of {sorted(PRESETS)}")
    parser.add_argument("--set", action="append", default=[], metavar="KEY=VALUE",
                        help="dotted override, e.g. --set data.global_batch_size=512")
    parser.add_argument("--mode",
                        choices=("train", "eval", "predict", "serve"),
                        default="train",
                        help="train (default), a standalone eval pass from "
                             "the latest checkpoint, predict: classify "
                             "--images files with the latest checkpoint, "
                             "or serve: the always-on dynamic-batching "
                             "predict server (serving/, requires "
                             "serving.enabled=true)")
    parser.add_argument("--images", nargs="*", default=[], metavar="PATH",
                        help="predict mode: JPEG files and/or directories "
                             "(searched for *.jpg/*.jpeg/*.JPEG)")
    args = parser.parse_args(argv)
    cfg = get_config(args.config)
    try:
        cfg = apply_overrides(cfg, fold_override_items(args.set))
    except ValueError as e:
        parser.error(str(e))
    return (cfg, args) if with_mode else cfg
