"""Record-shape validators for the telemetry surfaces — the CI tripwire
that makes schema drift fail a test instead of corrupting run archives.

Three record families, each with a `validate_*` returning a list of error
strings (empty = valid; callers assert `not errors` so a failure names every
problem at once):

- metrics JSONL (utils/logging.py MetricLogger): one JSON object per line,
  an `event` string, values JSON-legal — in particular NO bare
  ``NaN``/``Infinity`` tokens. Python's `json.loads` ACCEPTS those
  non-standard tokens by default, so the validator parses with a strict
  `parse_constant` to catch exactly the records that would break a
  spec-compliant downstream parser (jq, BigQuery, serde).
- Chrome trace-event JSON (telemetry/spans.py export): object format with a
  `traceEvents` list of `ph: "X"` complete events (plus `M` metadata), the
  shape Perfetto and chrome://tracing load.
- bench artifacts (benchmarks/host_pipeline_bench.py --json-out): a JSON
  object with a numeric `metric`/`value` pair and finite numbers
  throughout.
"""

from __future__ import annotations

import json
import math
import os
import re
from typing import Any, List

#: Record-schema version stamped into trainer JSONL records (MetricLogger),
#: bench --json-out artifacts, flight-recorder black boxes, and the perf
#: trajectory file. MAJOR bumps mean a consumer written against the old
#: shape would MISREAD the new one (field renamed/retyped/resemanticized);
#: MINOR bumps are additive. Validators accept any minor of a known major,
#: accept ABSENT (every pre-versioned committed artifact), and reject
#: unknown majors — the drift a silent reader would otherwise misparse.
SCHEMA_VERSION = "1.0"
KNOWN_SCHEMA_MAJORS = (1,)


def validate_schema_version(value: Any, path: str,
                            errors: List[str]) -> None:
    """Shared `schema_version` field check: None (pre-versioned record) is
    legal; a present value must be a "MAJOR.MINOR" string whose major is
    known."""
    if value is None:
        return
    if not isinstance(value, str):
        errors.append(f"{path}: schema_version not a string "
                      f"({type(value).__name__})")
        return
    major_s = value.split(".", 1)[0]
    try:
        major = int(major_s)
    except ValueError:
        errors.append(f"{path}: schema_version {value!r} not MAJOR.MINOR")
        return
    if major not in KNOWN_SCHEMA_MAJORS:
        errors.append(
            f"{path}: unknown schema_version major {major} (known: "
            f"{KNOWN_SCHEMA_MAJORS}) — this reader predates the record; "
            f"refusing to guess at its shape")


def _strict_loads(text: str):
    """json.loads rejecting the non-standard NaN/Infinity/-Infinity tokens
    (JSON-illegal, but emitted by a naive json.dumps of a non-finite float
    — the exact bug the MetricLogger satellite fixed)."""

    def _bad(token: str):
        raise ValueError(f"JSON-illegal constant {token!r}")

    return json.loads(text, parse_constant=_bad)


def _check_finite(value: Any, path: str, errors: List[str]) -> None:
    """Recursively reject non-finite floats — they survive a permissive
    load but re-serialize illegally."""
    if isinstance(value, float) and not math.isfinite(value):
        errors.append(f"{path}: non-finite float {value!r}")
    elif isinstance(value, dict):
        for k, v in value.items():
            if not isinstance(k, str):
                errors.append(f"{path}.{k}: non-string key")
            _check_finite(v, f"{path}.{k}", errors)
    elif isinstance(value, (list, tuple)):
        for i, v in enumerate(value):
            _check_finite(v, f"{path}[{i}]", errors)
    elif value is not None and not isinstance(value, (str, int, float, bool)):
        errors.append(f"{path}: non-JSON value of type "
                      f"{type(value).__name__}")


# ------------------------------------------------------------------ autotune
#: Knobs the ingest autotuner may steer (data/autotune.py) — duplicated as
#: a literal so this module stays a leaf (the import-isolation contract:
#: schema imports neither the data layer nor numpy).
#: "batch_window_ms" is the serving admission controller's knob (r17,
#: serving/controller.py — the same controller class, so its actuations
#: ride the same flight-recorder ring and must validate here).
_AUTOTUNE_KNOBS = ("native_threads", "host_prefetch", "prefetch_to_device",
                   "restart_fanout", "wire_u8", "batch_window_ms")
_AUTOTUNE_BLOCKED = ("hysteresis", "cooldown", "rail")


def validate_autotune_actuation(act: Any, where: str,
                                errors: List[str]) -> None:
    """One actuation record — the unit all three receipt trails (JSONL
    block, /autotunez history, flight black box) share."""
    if not isinstance(act, dict):
        errors.append(f"{where}: not an object")
        return
    if act.get("knob") not in _AUTOTUNE_KNOBS:
        errors.append(f"{where}: 'knob' {act.get('knob')!r} not one of "
                      f"{_AUTOTUNE_KNOBS}")
    if act.get("direction") not in ("up", "down"):
        errors.append(f"{where}: 'direction' {act.get('direction')!r} not "
                      "'up'|'down'")
    for key in ("from", "to", "window"):
        if not isinstance(act.get(key), int):
            errors.append(f"{where}: missing integer '{key}'")


def validate_autotune_block(block: Any, where: str,
                            errors: List[str]) -> None:
    """The per-window `autotune` block in trainer JSONL train records
    (IngestAutotuner.observe shape): every actuation the controller takes
    must be machine-auditable from the run log alone."""
    if not isinstance(block, dict):
        errors.append(f"{where}: 'autotune' not an object")
        return
    if not isinstance(block.get("window"), int):
        errors.append(f"{where}: missing integer 'window'")
    if not isinstance(block.get("settled"), bool):
        errors.append(f"{where}: missing boolean 'settled'")
    knobs = block.get("knobs")
    if knobs is not None:
        if not isinstance(knobs, dict):
            errors.append(f"{where}: 'knobs' not an object")
        else:
            for name, v in knobs.items():
                if name not in _AUTOTUNE_KNOBS:
                    errors.append(f"{where}.knobs: unknown knob {name!r}")
                if not isinstance(v, int):
                    errors.append(f"{where}.knobs.{name}: not an integer")
    blocked = block.get("blocked")
    if blocked is not None and blocked not in _AUTOTUNE_BLOCKED:
        errors.append(f"{where}: 'blocked' {blocked!r} not one of "
                      f"{_AUTOTUNE_BLOCKED}")
    acts = block.get("actuations")
    if acts is not None:
        if not isinstance(acts, list):
            errors.append(f"{where}: 'actuations' not a list")
        else:
            for i, act in enumerate(acts):
                validate_autotune_actuation(act, f"{where}.actuations[{i}]",
                                            errors)


def validate_autotune_receipt(receipt: Any, where: str,
                              errors: List[str]) -> None:
    """The bench-artifact / /autotunez `autotune` receipt
    (IngestAutotuner.describe shape). `settled` is the field the
    regression sentinel gates on: an artifact whose windows overlap
    actuations must refuse gating (a mid-convergence window reads as a
    false regression)."""
    if not isinstance(receipt, dict):
        errors.append(f"{where}: 'autotune' not an object")
        return
    if not isinstance(receipt.get("enabled"), bool):
        errors.append(f"{where}: missing boolean 'enabled'")
    if receipt.get("enabled"):
        if not isinstance(receipt.get("settled"), bool):
            errors.append(f"{where}: missing boolean 'settled'")
        if not isinstance(receipt.get("actuations_total"), int):
            errors.append(f"{where}: missing integer 'actuations_total'")
        hist = receipt.get("history")
        if hist is not None:
            if not isinstance(hist, list):
                errors.append(f"{where}: 'history' not a list")
            else:
                for i, act in enumerate(hist):
                    validate_autotune_actuation(
                        act, f"{where}.history[{i}]", errors)


# ------------------------------------------------------------ iterator state
#: Legal `wire` receipts in iterator-state blobs/blocks — the bench's
#: _WIRE_VALUES, duplicated here by the leaf-module contract (this module
#: imports neither the data layer nor numpy).
_ITER_STATE_WIRES = ("host_f32", "host_bf16", "u8")


def validate_iterator_state_blob(blob: Any, where: str,
                                 errors: List[str]) -> None:
    """The checkpoint-extra `iterator_state` receipt (r18,
    data/iterator_state.py capture_state shape): the serialized stream
    position a restore seeks to. Load-bearing invariants are typed here —
    cursor/epoch agreement under next-item-to-emit semantics, the
    in-flight set exactly [cursor, source_cursor) — so a drifting writer
    fails validation instead of seeking a resumed run to a wrong
    position."""
    if not isinstance(blob, dict):
        errors.append(f"{where}: 'iterator_state' not an object")
        return
    if blob.get("kind") != "ingest_iterator_state":
        errors.append(f"{where}: 'kind' {blob.get('kind')!r} != "
                      "'ingest_iterator_state'")
    for key in ("version", "cursor", "epoch", "batches_per_epoch", "seed",
                "source_cursor", "rebuilds"):
        v = blob.get(key)
        if not isinstance(v, int) or isinstance(v, bool):
            errors.append(f"{where}: missing integer '{key}'")
    cursor, bpe = blob.get("cursor"), blob.get("batches_per_epoch")
    if isinstance(cursor, int) and isinstance(bpe, int) and bpe >= 1 \
            and isinstance(blob.get("epoch"), int):
        # next-item-to-emit semantics: the batch AT cursor k*N opens
        # epoch k (the off-by-one the shared epoch_of helper pins)
        if blob["epoch"] != cursor // bpe:
            errors.append(f"{where}: epoch {blob['epoch']} != "
                          f"cursor//batches_per_epoch ({cursor // bpe}) — "
                          "cursor is next-item-to-emit, not last-emitted")
    shuffle = blob.get("shuffle")
    if not isinstance(shuffle, dict) \
            or shuffle.get("algo") != "splitmix64" \
            or not isinstance(shuffle.get("seed"), int) \
            or not isinstance(shuffle.get("epoch"), int):
        errors.append(f"{where}: 'shuffle' not "
                      "{algo: 'splitmix64', seed: int, epoch: int}")
    inflight = blob.get("in_flight")
    if not isinstance(inflight, list) \
            or not all(isinstance(c, int) for c in inflight):
        errors.append(f"{where}: 'in_flight' not a list of integers")
    elif isinstance(cursor, int) \
            and isinstance(blob.get("source_cursor"), int):
        if inflight != list(range(cursor, blob["source_cursor"])):
            errors.append(
                f"{where}: in_flight != [cursor, source_cursor) — the "
                "read-ahead transplant set must be exactly the undelivered "
                "source draws")
    wire = blob.get("wire")
    if wire is not None and wire not in _ITER_STATE_WIRES:
        errors.append(f"{where}: 'wire' {wire!r} not one of "
                      f"{_ITER_STATE_WIRES}")


def validate_iterator_state_block(block: Any, where: str,
                                  errors: List[str]) -> None:
    """The per-window `iterator_state` JSONL block (r18,
    ResumableIngest.window_receipt shape) in trainer train records."""
    if not isinstance(block, dict):
        errors.append(f"{where}: 'iterator_state' not an object")
        return
    for key in ("cursor", "source_cursor", "in_flight", "epoch",
                "rebuilds"):
        v = block.get(key)
        if not isinstance(v, int) or isinstance(v, bool) or v < 0:
            errors.append(f"{where}: '{key}' not a non-negative integer")
    wire = block.get("wire")
    if wire is not None and wire not in _ITER_STATE_WIRES:
        errors.append(f"{where}: 'wire' {wire!r} not one of "
                      f"{_ITER_STATE_WIRES}")


# ------------------------------------------------------------------- elastic
#: Legal topology basis labels (r19): `static` (every pre-r19 row) or the
#: elastic resize's `elastic_<N>to<M>`. Mirrors
#: parallel/elastic.ResizePlan.topology_label — duplicated as a literal,
#: leaf-module contract as everywhere in this file.
_TOPOLOGY_RE = re.compile(r"static|elastic_\d+to\d+")

#: Legal batch-policy labels (mirrors config.ElasticConfig.batch_policy).
_BATCH_POLICIES = ("keep_global", "scale_lr")


def validate_elastic_block(block: Any, where: str,
                           errors: List[str]) -> None:
    """The per-window `elastic` JSONL block (r19, trainer train records,
    emitted only when `mesh.elastic.enabled`): the window's topology basis
    plus the cumulative resize receipts — resizes performed, total
    downtime, opt-state shards evacuated off dead ranks, data shards
    reassigned to survivors, and the active LR scale."""
    if not isinstance(block, dict):
        errors.append(f"{where}: 'elastic' not an object")
        return
    topo = block.get("topology")
    if not isinstance(topo, str) or not _TOPOLOGY_RE.fullmatch(topo):
        errors.append(f"{where}: 'topology' {topo!r} not "
                      "static|elastic_<N>to<M>")
    policy = block.get("batch_policy")
    if policy not in _BATCH_POLICIES:
        errors.append(f"{where}: 'batch_policy' {policy!r} not one of "
                      f"{_BATCH_POLICIES}")
    for key in ("resizes", "downtime_ns", "evacuated_shards",
                "reassigned_data_shards"):
        v = block.get(key)
        if not isinstance(v, int) or isinstance(v, bool) or v < 0:
            errors.append(f"{where}: '{key}' not a non-negative integer")
    v = block.get("lr_scale")
    if not isinstance(v, (int, float)) or isinstance(v, bool) or v <= 0:
        errors.append(f"{where}: 'lr_scale' not a positive number")


# ------------------------------------------------------------------- augment
def validate_augment_block(block: Any, where: str,
                           errors: List[str]) -> None:
    """The per-window `augment` block (r13, AugmentConfig.describe shape):
    the receipt that a run's augmentation diversity was DEVICE-side — in
    trainer JSONL train records and bench-artifact rows. `enabled` and
    `host_flips_disabled` are the load-bearing booleans (the flip-ownership
    contract); the knob echoes are typed so a drifting config serializer
    fails validation instead of corrupting run archives."""
    if not isinstance(block, dict):
        errors.append(f"{where}: 'augment' not an object")
        return
    for key in ("enabled", "host_flips_disabled"):
        if not isinstance(block.get(key), bool):
            errors.append(f"{where}: missing boolean '{key}'")
    if "hflip" in block and not isinstance(block["hflip"], bool):
        errors.append(f"{where}: 'hflip' not a boolean")
    for key in ("crop_jitter", "rand_ops"):
        v = block.get(key)
        if v is not None and (not isinstance(v, int)
                              or isinstance(v, bool) or v < 0):
            errors.append(f"{where}: '{key}' not a non-negative integer")
    for key in ("mixup_alpha", "cutmix_alpha"):
        v = block.get(key)
        if v is not None and (not isinstance(v, (int, float))
                              or isinstance(v, bool) or v < 0):
            errors.append(f"{where}: '{key}' not a non-negative number")
    v = block.get("rand_magnitude")
    if v is not None and (not isinstance(v, (int, float))
                          or isinstance(v, bool) or not 0 <= v <= 1):
        errors.append(f"{where}: 'rand_magnitude' not in [0, 1]")


#: Zoo models a bench row's `model` field may carry (mirrors
#: models/ingest.INGEST_DESCRIPTORS — duplicated as a literal so this
#: module stays a leaf; the drift is guarded by test).
_ZOO_MODELS = ("vggf", "vgg16", "resnet50", "vit_s16", "vggf_student",
               "mistral4", "nemotron_h", "ling3", "solar_open2", "jamba")


# ---------------------------------------------------------------------- comm
#: Legal gradient-exchange sharding bases (r14, +zero3 r21; mirrors
#: config.MeshConfig.sharding_label — duplicated as a literal, leaf-module
#: contract as above).
_COMM_SHARDINGS = ("dp", "zero1", "zero2", "zero3")


def validate_comm_block(block: Any, where: str,
                        errors: List[str]) -> None:
    """The per-window `comm` block (r14, train/step.py comm_meta shape):
    the receipt for the gradient-exchange geometry a run actually traced —
    sharding basis (dp | zero1 | zero2 | zero3), whether the bucketed
    exchange was on, the bucket count, the logical collective payload
    bytes per step, and (r21) the per-step param all-gather count. In
    trainer JSONL train records and comm-bench artifact rows."""
    if not isinstance(block, dict):
        errors.append(f"{where}: 'comm' not an object")
        return
    sharding = block.get("sharding")
    if sharding not in _COMM_SHARDINGS:
        errors.append(f"{where}: 'sharding' {sharding!r} not one of "
                      f"{_COMM_SHARDINGS}")
    if not isinstance(block.get("bucketed"), bool):
        errors.append(f"{where}: missing boolean 'bucketed'")
    v = block.get("buckets")
    if not isinstance(v, int) or isinstance(v, bool) or v < 1:
        errors.append(f"{where}: 'buckets' not a positive integer")
    v = block.get("bucket_mb")
    if not isinstance(v, (int, float)) or isinstance(v, bool) or v < 0:
        errors.append(f"{where}: 'bucket_mb' not a non-negative number")
    for key in ("wire_bytes", "scatter_bytes", "gather_bytes",
                "allreduce_bytes"):
        v = block.get(key)
        if key == "wire_bytes" and v is None:
            errors.append(f"{where}: missing 'wire_bytes'")
        if v is not None and (not isinstance(v, int) or isinstance(v, bool)
                              or v < 0):
            errors.append(f"{where}: '{key}' not a non-negative integer")
    v = block.get("grad_accum_steps")
    if v is not None and (not isinstance(v, int) or isinstance(v, bool)
                          or v < 1):
        errors.append(f"{where}: 'grad_accum_steps' not a positive integer")
    # r21 (ZeRO-3): per-step param all-gather count — 0 under dp, 1 under
    # zero1/zero2 (the trailing re-sync), num_buckets under bucketed zero3
    v = block.get("gathers")
    if v is not None and (not isinstance(v, int) or isinstance(v, bool)
                          or v < 0):
        errors.append(f"{where}: 'gathers' not a non-negative integer")


# ------------------------------------------------------------ critical path
#: Critical-path buckets (r22, trainer per-window split). Mirrors the
#: trainer's span-category mapping (infeed / checkpoint / coord→exchange /
#: device-residual) — duplicated as a literal, leaf-module contract.
_CRITICAL_PATH_PARTS = ("infeed_s", "device_s", "checkpoint_s",
                        "exchange_s")


def validate_critical_path_block(block: Any, where: str,
                                 errors: List[str]) -> None:
    """The per-window `critical_path` JSONL block (r22, trainer train
    records): the window's wall clock attributed {infeed, device,
    checkpoint, exchange} with the dominant bucket named. The load-bearing
    invariant is typed — the four parts must SUM to the window wall clock
    (the trainer computes device as the residual, so a drifting writer
    that double-counts fails here instead of producing splits that read
    as >100% of the window)."""
    if not isinstance(block, dict):
        errors.append(f"{where}: 'critical_path' not an object")
        return
    wall = block.get("window_s")
    if not isinstance(wall, (int, float)) or isinstance(wall, bool) \
            or not math.isfinite(wall) or wall < 0:
        errors.append(f"{where}: 'window_s' not a non-negative finite "
                      "number")
        return
    total = 0.0
    ok = True
    for key in _CRITICAL_PATH_PARTS:
        v = block.get(key)
        if not isinstance(v, (int, float)) or isinstance(v, bool) \
                or not math.isfinite(v) or v < 0:
            errors.append(f"{where}: '{key}' not a non-negative finite "
                          "number")
            ok = False
        else:
            total += v
    if ok and abs(total - wall) > max(1e-3, 1e-3 * wall):
        errors.append(
            f"{where}: parts sum to {total:.6f}s but window_s is "
            f"{wall:.6f}s — the split must account for the whole window")
    dom = block.get("dominant")
    if not isinstance(dom, str) or f"{dom}_s" not in _CRITICAL_PATH_PARTS:
        errors.append(f"{where}: 'dominant' {dom!r} not one of "
                      f"{tuple(p[:-2] for p in _CRITICAL_PATH_PARTS)}")


# ------------------------------------------------------------- metrics JSONL
def validate_metrics_record(record: Any) -> List[str]:
    """One MetricLogger record (already parsed)."""
    errors: List[str] = []
    if not isinstance(record, dict):
        return [f"record is {type(record).__name__}, expected object"]
    event = record.get("event")
    if not isinstance(event, str) or not event:
        errors.append("missing/empty 'event' string")
    validate_schema_version(record.get("schema_version"), "record", errors)
    if "autotune" in record:
        validate_autotune_block(record["autotune"], "record", errors)
    if event == "train" and "augment" in record:
        validate_augment_block(record["augment"], "record", errors)
    if event == "train" and "comm" in record:
        validate_comm_block(record["comm"], "record", errors)
    if event == "train" and "iterator_state" in record:
        validate_iterator_state_block(record["iterator_state"], "record",
                                      errors)
    if event == "train" and "elastic" in record:
        validate_elastic_block(record["elastic"], "record", errors)
    if event == "train" and "critical_path" in record:
        validate_critical_path_block(record["critical_path"], "record",
                                     errors)
    _check_finite(record, "record", errors)
    return errors


def validate_metrics_jsonl(path: str, max_errors: int = 20) -> List[str]:
    """Whole-file check: every line parses strictly and validates."""
    errors: List[str] = []
    with open(path) as f:
        for lineno, line in enumerate(f, 1):
            line = line.strip()
            if not line:
                continue
            try:
                record = _strict_loads(line)
            except ValueError as e:
                errors.append(f"line {lineno}: {e}")
            else:
                errors.extend(f"line {lineno}: {err}"
                              for err in validate_metrics_record(record))
            if len(errors) >= max_errors:
                errors.append("... (truncated)")
                break
    return errors


# -------------------------------------------------------------- Chrome trace
def validate_chrome_trace(trace: Any) -> List[str]:
    """Trace-event JSON object format (the spans.py export shape)."""
    errors: List[str] = []
    if not isinstance(trace, dict):
        return [f"trace is {type(trace).__name__}, expected object"]
    events = trace.get("traceEvents")
    if not isinstance(events, list):
        return ["missing 'traceEvents' list"]
    for i, ev in enumerate(events):
        where = f"traceEvents[{i}]"
        if not isinstance(ev, dict):
            errors.append(f"{where}: not an object")
            continue
        ph = ev.get("ph")
        if not isinstance(ev.get("name"), str):
            errors.append(f"{where}: missing 'name' string")
        # "s"/"t"/"f" are the flow-event phases the stitched multi-process
        # trace carries (r22, telemetry/stitch.py) — the arrows linking a
        # client span to the remote span that served it
        if ph not in ("X", "M", "B", "E", "i", "C", "s", "t", "f"):
            errors.append(f"{where}: unsupported ph {ph!r}")
        if not isinstance(ev.get("pid"), int):
            errors.append(f"{where}: missing integer 'pid'")
        if ph in ("s", "t", "f"):
            if not isinstance(ev.get("id"), (int, str)):
                errors.append(f"{where}: flow event missing 'id'")
            v = ev.get("ts")
            if not isinstance(v, (int, float)) or not math.isfinite(v):
                errors.append(f"{where}: 'ts' not a finite number")
            if not isinstance(ev.get("tid"), int):
                errors.append(f"{where}: missing integer 'tid'")
        if ph == "X":
            for key in ("ts", "dur"):
                v = ev.get(key)
                if not isinstance(v, (int, float)) or not math.isfinite(v):
                    errors.append(f"{where}: '{key}' not a finite number")
            if isinstance(ev.get("dur"), (int, float)) and ev["dur"] < 0:
                errors.append(f"{where}: negative duration")
            if not isinstance(ev.get("tid"), int):
                errors.append(f"{where}: missing integer 'tid'")
            if not isinstance(ev.get("cat"), str):
                errors.append(f"{where}: missing 'cat' string")
        if len(errors) >= 20:
            errors.append("... (truncated)")
            break
    return errors


def validate_trace_file(path: str) -> List[str]:
    with open(path) as f:
        try:
            trace = _strict_loads(f.read())
        except ValueError as e:
            return [f"{os.path.basename(path)}: {e}"]
    return validate_chrome_trace(trace)


# ------------------------------------------------------------ bench artifacts
#: Legal `wire` values in decode-bench rows (r8). Mirrors
#: data/dtypes.WIRE_FORMATS minus 'auto' (the bench resolves auto before
#: recording) — duplicated as a literal because this module must import
#: neither numpy nor the data layer (the import-isolation test).
_WIRE_VALUES = ("host_f32", "host_bf16", "u8")


#: Legal serving-row basis labels (r17): `off` (the default every decode
#: row gets) or the open-loop bench's `openloop_b<max_batch>`.
_SERVING_MODE_RE = re.compile(r"off|openloop_b\d+")

#: Legal serving-tier labels (r23, serving/tiers.py TIERS — duplicated as
#: a literal, leaf-module contract as _ZOO_MODELS above; drift guarded by
#: tests/test_serving_tiers.py).
_SERVING_TIERS = ("fp32", "bf16", "int8", "student")


def _check_tier_accuracy_block(row: dict, where: str,
                               errors: List[str]) -> None:
    """The per-tier accuracy-delta receipt (r23): top-1 on a fixed eval
    shard for THIS tier and for the fp32 tier of the same weights, the
    delta between them, and the configured bound the delta must respect.
    A committed row whose delta exceeds its own declared bound is not a
    receipt — it is the regression the tier ladder exists to catch, so
    validation fails it."""
    acc = row.get("accuracy")
    if acc is None:
        return
    if not isinstance(acc, dict):
        errors.append(f"{where}: 'accuracy' not an object")
        return
    for key in ("top1", "fp32_top1"):
        v = acc.get(key)
        if not isinstance(v, (int, float)) or isinstance(v, bool) \
                or not 0 <= v <= 1:
            errors.append(f"{where}.accuracy: '{key}' not in [0, 1]")
    for key in ("delta", "bound"):
        v = acc.get(key)
        if not isinstance(v, (int, float)) or isinstance(v, bool):
            errors.append(f"{where}.accuracy: '{key}' not a number")
    n = acc.get("eval_examples")
    if not isinstance(n, int) or isinstance(n, bool) or n < 1:
        errors.append(f"{where}.accuracy: 'eval_examples' not a positive "
                      "integer")
    delta, bound = acc.get("delta"), acc.get("bound")
    if isinstance(delta, (int, float)) and isinstance(bound, (int, float)) \
            and not isinstance(delta, bool) and not isinstance(bound, bool):
        if bound < 0:
            errors.append(f"{where}.accuracy: negative 'bound'")
        elif delta > bound:
            errors.append(
                f"{where}.accuracy: top-1 delta {delta} exceeds the "
                f"declared bound {bound} — the tier broke its accuracy "
                "contract")


def validate_serving_row(row: Any, where: str, errors: List[str]) -> None:
    """One serving-bench layout row (benchmarks/serving_bench.py shape):
    the open-loop latency/throughput receipt the r17 sentinel basis keys
    on. The load-bearing claims are typed — admitted rate positive, shed
    rates in [0, 1], latency quantiles ordered p50 <= p95 <= p99, queue
    peak bounded by the configured limit — so a drifting bench serializer
    fails validation instead of committing an unreadable receipt. Tier
    rows (r23) additionally carry the `tier` label plus the accuracy-delta
    receipt block, both typed here."""
    if not isinstance(row, dict):
        errors.append(f"{where}: not an object")
        return
    v = row.get("admitted_rps")
    if not isinstance(v, (int, float)) or isinstance(v, bool) or v <= 0:
        errors.append(f"{where}: 'admitted_rps' not a positive number")
    tier = row.get("tier")
    if tier is not None and tier not in _SERVING_TIERS:
        errors.append(f"{where}: 'tier' {tier!r} not one of "
                      f"{_SERVING_TIERS}")
    _check_tier_accuracy_block(row, where, errors)
    sv = row.get("serving")
    if not isinstance(sv, dict):
        errors.append(f"{where}: missing 'serving' config-echo object")
    else:
        bk = sv.get("buckets")
        if not (isinstance(bk, list) and bk
                and all(isinstance(b, int) and b >= 1 for b in bk)
                and bk == sorted(set(bk))):
            errors.append(f"{where}.serving: 'buckets' not unique "
                          "ascending positive ints")
        for key in ("max_batch", "queue_limit"):
            b = sv.get(key)
            if not isinstance(b, int) or isinstance(b, bool) or b < 1:
                errors.append(f"{where}.serving: '{key}' not a positive "
                              "integer")
    qp = row.get("queue_peak")
    if qp is not None:
        if not isinstance(qp, int) or isinstance(qp, bool) or qp < 0:
            errors.append(f"{where}: 'queue_peak' not a non-negative "
                          "integer")
        elif isinstance(sv, dict) and isinstance(sv.get("queue_limit"),
                                                 int) \
                and qp > sv["queue_limit"]:
            errors.append(f"{where}: queue_peak {qp} exceeds the "
                          f"configured queue_limit {sv['queue_limit']} — "
                          "the bounded-admission contract was violated")
    stages = row.get("stages")
    if not isinstance(stages, list) or not stages:
        errors.append(f"{where}: missing non-empty 'stages' list")
        return
    for i, st in enumerate(stages):
        w = f"{where}.stages[{i}]"
        if not isinstance(st, dict):
            errors.append(f"{w}: not an object")
            continue
        for key in ("offered_rps", "duration_s"):
            v = st.get(key)
            if not isinstance(v, (int, float)) or isinstance(v, bool) \
                    or v <= 0:
                errors.append(f"{w}: '{key}' not a positive number")
        v = st.get("admitted_rps")
        if not isinstance(v, (int, float)) or isinstance(v, bool) or v < 0:
            errors.append(f"{w}: 'admitted_rps' not a non-negative number")
        sr = st.get("shed_rate")
        if not isinstance(sr, (int, float)) or isinstance(sr, bool) \
                or not 0 <= sr <= 1:
            errors.append(f"{w}: 'shed_rate' not in [0, 1]")
        quant = [st.get(k) for k in ("p50_ms", "p95_ms", "p99_ms")]
        present = [q for q in quant if q is not None]
        if present:
            if any(not isinstance(q, (int, float)) or isinstance(q, bool)
                   or q < 0 for q in present):
                errors.append(f"{w}: latency quantiles must be "
                              "non-negative numbers")
            elif len(present) == 3 and not (quant[0] <= quant[1]
                                            <= quant[2]):
                errors.append(f"{w}: quantiles not ordered "
                              "p50 <= p95 <= p99")


def validate_resume_row(row: Any, where: str, errors: List[str]) -> None:
    """One resume-bench layout row (r18, benchmarks/resume_bench.py
    shape): the kill-at-window-k / resume receipt. The load-bearing
    contract is typed: an `exact`-mode row MUST report zero replayed
    batches — the whole claim of position-exact resume — while a `replay`
    control row must replay exactly its cursor's epoch offset."""
    if not isinstance(row, dict):
        errors.append(f"{where}: not an object")
        return
    mode = row.get("resume_mode")
    if mode not in ("replay", "exact"):
        errors.append(f"{where}: 'resume_mode' {mode!r} not replay|exact")
    rb = row.get("replayed_batches")
    if not isinstance(rb, int) or isinstance(rb, bool) or rb < 0:
        errors.append(f"{where}: 'replayed_batches' not a non-negative "
                      "integer")
    elif mode == "exact" and rb != 0:
        errors.append(f"{where}: exact-mode resume replayed {rb} batches "
                      "— the position-exact contract is zero replay")
    for key in ("resume_seconds",):
        v = row.get(key)
        if not isinstance(v, (int, float)) or isinstance(v, bool) or v < 0:
            errors.append(f"{where}: '{key}' not a non-negative number")
    for key in ("kill_cursor", "batches_per_epoch"):
        v = row.get(key)
        if not isinstance(v, int) or isinstance(v, bool) or v < 1:
            errors.append(f"{where}: '{key}' not a positive integer")
    if not isinstance(row.get("first_batch_matches"), bool):
        errors.append(f"{where}: missing boolean 'first_batch_matches' "
                      "(the resumed stream's first batch vs the "
                      "uninterrupted one)")
    elif not row["first_batch_matches"]:
        errors.append(f"{where}: first_batch_matches=false — the resumed "
                      "stream diverged from the uninterrupted one")


def validate_elastic_row(row: Any, where: str, errors: List[str]) -> None:
    """One elastic-bench layout row (r19, benchmarks/elastic_bench.py
    shape): the preempt-k-of-N downtime receipt. The load-bearing
    contract is typed: the live resize must replay ZERO batches (the
    cursor-handoff claim) and must beat the restart-from-checkpoint
    control by >= 3x — a committed receipt below that is a regression,
    not a receipt."""
    if not isinstance(row, dict):
        errors.append(f"{where}: not an object")
        return
    policy = row.get("batch_policy")
    if policy not in _BATCH_POLICIES:
        errors.append(f"{where}: 'batch_policy' {policy!r} not one of "
                      f"{_BATCH_POLICIES}")
    for key in ("downtime_seconds", "restart_seconds"):
        v = row.get(key)
        if not isinstance(v, (int, float)) or isinstance(v, bool) or v <= 0:
            errors.append(f"{where}: '{key}' not a positive number")
    rb = row.get("replayed_batches")
    if not isinstance(rb, int) or isinstance(rb, bool) or rb < 0:
        errors.append(f"{where}: 'replayed_batches' not a non-negative "
                      "integer")
    elif rb != 0:
        errors.append(f"{where}: elastic resize replayed {rb} batches — "
                      "the cursor-handoff contract is zero replay")
    sp = row.get("speedup_vs_restart")
    if not isinstance(sp, (int, float)) or isinstance(sp, bool) or sp <= 0:
        errors.append(f"{where}: 'speedup_vs_restart' not a positive "
                      "number")
    elif sp < 3:
        errors.append(f"{where}: speedup_vs_restart {sp} < 3 — the elastic "
                      "path must beat restart-from-checkpoint by >= 3x")
    v = row.get("resizes")
    if not isinstance(v, int) or isinstance(v, bool) or v < 1:
        errors.append(f"{where}: 'resizes' not a positive integer")


def _check_decode_row(row: Any, where: str, errors: List[str]) -> None:
    """r8 wire-format fields of one decode-bench layout row, when present:
    `wire` from the legal set, `wire_bytes_per_image` a positive number,
    and the phase split (`profile`) carrying positive per-image times —
    the fields the host_r9 receipts and the README wire table read."""
    if not isinstance(row, dict):
        return
    wire = row.get("wire")
    if wire is not None and wire not in _WIRE_VALUES:
        errors.append(f"{where}: 'wire' {wire!r} not one of {_WIRE_VALUES}")
    model = row.get("model")
    if model is not None and model not in _ZOO_MODELS:
        # r13 zoo rows: the per-model basis key the regression sentinel
        # gates on — an unknown model name is a labeling bug, not a row
        errors.append(f"{where}: 'model' {model!r} not one of "
                      f"{_ZOO_MODELS}")
    if "augment" in row:
        validate_augment_block(row["augment"], where, errors)
    if "comm" in row:
        validate_comm_block(row["comm"], where, errors)
    sharding = row.get("sharding")
    if sharding is not None:
        # r14/r21 comm-bench rows: (dp|zero1|zero2|zero3)[_bucketed] basis
        # key the regression sentinel gates on
        base = str(sharding).replace("_bucketed", "")
        if base not in _COMM_SHARDINGS:
            errors.append(f"{where}: 'sharding' {sharding!r} not "
                          f"<dp|zero1|zero2|zero3>[_bucketed]")
    ingest_mode = row.get("ingest_mode")
    if ingest_mode is not None and not re.fullmatch(
            r"local|service_\d+w", str(ingest_mode)):
        # r16 disaggregated-ingest rows: the `local` | `service_<N>w`
        # topology basis the sentinel keys on (Basis.ingest)
        errors.append(f"{where}: 'ingest_mode' {ingest_mode!r} not "
                      f"local|service_<N>w")
    serving_mode = row.get("serving_mode")
    if serving_mode is not None and not _SERVING_MODE_RE.fullmatch(
            str(serving_mode)):
        # r17 serving rows: the `off` | `openloop_b<N>` admission basis
        # the sentinel keys on (Basis.serving)
        errors.append(f"{where}: 'serving_mode' {serving_mode!r} not "
                      f"off|openloop_b<N>")
    resume_mode = row.get("resume_mode")
    if resume_mode is not None and resume_mode not in ("replay", "exact"):
        # r18 resume rows: the `replay` | `exact` restart basis the
        # sentinel keys on (Basis.resume)
        errors.append(f"{where}: 'resume_mode' {resume_mode!r} not "
                      "replay|exact")
    topology = row.get("topology")
    if topology is not None and (
            not isinstance(topology, str)
            or not _TOPOLOGY_RE.fullmatch(topology)):
        # r19 elastic rows: the `static` | `elastic_<N>to<M>` topology
        # basis the sentinel keys on (Basis.topology)
        errors.append(f"{where}: 'topology' {topology!r} not "
                      "static|elastic_<N>to<M>")
    if row.get("mode") == "serving_bench":
        validate_serving_row(row, where, errors)
    if row.get("mode") == "resume_bench":
        validate_resume_row(row, where, errors)
    if row.get("mode") == "elastic_bench":
        validate_elastic_row(row, where, errors)
    bpi = row.get("wire_bytes_per_image")
    if bpi is not None and (not isinstance(bpi, (int, float)) or bpi <= 0):
        errors.append(f"{where}: 'wire_bytes_per_image' not a positive "
                      "number")
    profile = row.get("profile")
    if isinstance(profile, dict):
        for key in ("jpeg_us_per_image", "resample_us_per_image"):
            v = profile.get(key)
            if v is not None and (not isinstance(v, (int, float)) or v < 0):
                errors.append(f"{where}.profile: '{key}' not a "
                              "non-negative number")
    rst = row.get("restart_receipt")
    if isinstance(rst, dict):
        # r9 entropy-path receipt: counts non-negative ints, fractions in
        # [0, 1] (or null when the window decoded nothing)
        for key in ("images", "marker_absent", "unsupported", "misaligned",
                    "scan_failures", "excerpt_fallbacks", "no_gain",
                    "segments_used", "segments_skipped", "fanout_images"):
            v = rst.get(key)
            if v is not None and (not isinstance(v, int) or v < 0):
                errors.append(f"{where}.restart_receipt: '{key}' not a "
                              "non-negative integer")
        for key in ("engaged_fraction", "segments_skipped_fraction"):
            v = rst.get(key)
            if v is not None and (not isinstance(v, (int, float))
                                  or not 0 <= v <= 1):
                errors.append(f"{where}.restart_receipt: '{key}' not in "
                              "[0, 1]")
    if row.get("mode") == "decode_bench_autotune":
        # r11 convergence row: crippled start → controller-settled rate,
        # with the actuation log as the receipt
        for key in ("settled_images_per_sec", "pinned_images_per_sec"):
            v = row.get(key)
            if v is not None and (not isinstance(v, (int, float)) or v <= 0):
                errors.append(f"{where}: '{key}' not a positive number")
        vs = row.get("vs_pinned")
        if vs is not None and (not isinstance(vs, (int, float)) or vs <= 0):
            errors.append(f"{where}: 'vs_pinned' not a positive number")
        if "autotune" not in row:
            errors.append(f"{where}: autotune row missing 'autotune' "
                          "receipt object")
        else:
            validate_autotune_receipt(row["autotune"], where, errors)
    if row.get("mode") == "decode_bench_snapshot":
        # r9 snapshot warm-vs-cold row: rates positive, hit receipts sane
        for key in ("warm_images_per_sec_per_core",
                    "cold_images_per_sec_per_core",
                    "cold_fill_images_per_sec"):
            v = row.get(key)
            if v is not None and (not isinstance(v, (int, float)) or v <= 0):
                errors.append(f"{where}: '{key}' not a positive number")
        snap = row.get("snapshot")
        if not isinstance(snap, dict):
            errors.append(f"{where}: snapshot row missing 'snapshot' "
                          "receipt object")
        else:
            for key in ("hits", "misses", "bytes_served", "items"):
                v = snap.get(key)
                if v is not None and (not isinstance(v, int) or v < 0):
                    errors.append(f"{where}.snapshot: '{key}' not a "
                                  "non-negative integer")
            hr = snap.get("hit_rate")
            if hr is not None and (not isinstance(hr, (int, float))
                                   or not 0 <= hr <= 1):
                errors.append(f"{where}.snapshot: 'hit_rate' not in [0, 1]")


def validate_bench_artifact(obj: Any) -> List[str]:
    """A --json-out style artifact: object, finite numbers, and when it
    carries a contract metric the value must be numeric — unless the
    artifact is an explicit failure record (`error` present), where a null
    value is the documented shape (bench.py writes value=null +
    error=bench_failed when the TPU run died). Decode-bench layout rows
    additionally get their r8 wire-format fields checked."""
    errors: List[str] = []
    if not isinstance(obj, dict):
        return [f"artifact is {type(obj).__name__}, expected object"]
    _check_finite(obj, "artifact", errors)
    validate_schema_version(obj.get("schema_version"), "artifact", errors)
    if "metric" in obj and "error" not in obj \
            and not isinstance(obj.get("value"), (int, float)):
        errors.append("artifact: 'metric' present but 'value' not numeric")
    if "autotune" in obj:
        validate_autotune_receipt(obj["autotune"], "artifact", errors)
    layouts = obj.get("layouts")
    if isinstance(layouts, list):
        for i, row in enumerate(layouts):
            _check_decode_row(row, f"artifact.layouts[{i}]", errors)
    return errors


def validate_bench_artifact_file(path: str) -> List[str]:
    with open(path) as f:
        try:
            obj = _strict_loads(f.read())
        except ValueError as e:
            return [f"{os.path.basename(path)}: {e}"]
    return validate_bench_artifact(obj)


# --------------------------------------------------------- flight black box
#: Crash classes a flight-recorder black box may carry. Mirrors
#: flight.CRASH_KINDS — duplicated as a literal so the validator stays a
#: leaf module (flight.py imports schema, never the reverse).
_FLIGHT_REASONS = ("nonfinite_abort", "data_stall", "injected_crash",
                   "elastic_degraded_restart", "unhandled_exception")


def validate_flight_record(record: Any) -> List[str]:
    """One flight-recorder black box (telemetry/flight.py dump shape): the
    artifact a post-crash triage reads FIRST, so its shape drifting
    silently would break the tooling exactly when it is needed."""
    errors: List[str] = []
    if not isinstance(record, dict):
        return [f"record is {type(record).__name__}, expected object"]
    if record.get("kind") != "flight_black_box":
        errors.append(f"'kind' is {record.get('kind')!r}, expected "
                      f"'flight_black_box'")
    validate_schema_version(record.get("schema_version"), "record", errors)
    if record.get("schema_version") is None:
        errors.append("missing 'schema_version' (flight records are "
                      "versioned from birth — no pre-versioned cohort)")
    if record.get("reason") not in _FLIGHT_REASONS:
        errors.append(f"'reason' {record.get('reason')!r} not one of "
                      f"{_FLIGHT_REASONS}")
    if not isinstance(record.get("process"), int):
        errors.append("missing integer 'process'")
    windows = record.get("windows")
    if not isinstance(windows, list):
        errors.append("missing 'windows' list")
    else:
        for i, w in enumerate(windows):
            where = f"windows[{i}]"
            if not isinstance(w, dict):
                errors.append(f"{where}: not an object")
                continue
            if not isinstance(w.get("step"), int):
                errors.append(f"{where}: missing integer 'step'")
            wall = w.get("wall_s")
            if not isinstance(wall, (int, float)) or wall < 0 \
                    or not math.isfinite(wall):
                errors.append(f"{where}: 'wall_s' not a non-negative "
                              "finite number")
            stall = w.get("stall")
            if stall is not None and not (
                    isinstance(stall, dict)
                    and isinstance(stall.get("verdict"), str)):
                errors.append(f"{where}: 'stall' present but carries no "
                              "'verdict' string")
            if len(errors) >= 20:
                errors.append("... (truncated)")
                break
    exc = record.get("exception")
    if exc is not None and not (isinstance(exc, dict)
                                and isinstance(exc.get("type"), str)):
        errors.append("'exception' present but carries no 'type' string")
    acts = record.get("autotune_actuations")
    if acts is not None:
        # r11: the last-N autotune actuations ride the black box so a
        # post-crash triage can see whether the controller moved before
        # the abort
        if not isinstance(acts, list):
            errors.append("'autotune_actuations' present but not a list")
        else:
            for i, act in enumerate(acts):
                validate_autotune_actuation(
                    act, f"autotune_actuations[{i}]", errors)
    _check_finite(record, "record", errors)
    return errors


def validate_flight_file(path: str) -> List[str]:
    with open(path) as f:
        try:
            record = _strict_loads(f.read())
        except ValueError as e:
            return [f"{os.path.basename(path)}: {e}"]
    return validate_flight_record(record)


# ----------------------------------------------------------- perf trajectory
def validate_trajectory(obj: Any) -> List[str]:
    """The machine-readable perf trajectory (telemetry/regress.py
    build_trajectory → benchmarks/runs/trajectory.json): per-pin committed
    evidence the regression sentinel gates against."""
    errors: List[str] = []
    if not isinstance(obj, dict):
        return [f"trajectory is {type(obj).__name__}, expected object"]
    if obj.get("kind") != "perf_trajectory":
        errors.append(f"'kind' is {obj.get('kind')!r}, expected "
                      "'perf_trajectory'")
    validate_schema_version(obj.get("schema_version"), "trajectory", errors)

    def check_rounds(rounds, section):
        for i, r in enumerate(rounds):
            where = f"{section}[{i}]"
            if not isinstance(r, dict):
                errors.append(f"{where}: not an object")
                continue
            for key in ("pin", "round"):
                if not isinstance(r.get(key), str):
                    errors.append(f"{where}: missing '{key}' string")
            v = r.get("value")
            if not isinstance(v, (int, float)) or v <= 0:
                errors.append(f"{where}: 'value' not a positive number")
            arts = r.get("artifacts")
            if not isinstance(arts, list) or not arts:
                errors.append(f"{where}: missing non-empty 'artifacts' "
                              "list")
                continue
            for j, a in enumerate(arts):
                if not (isinstance(a, dict)
                        and isinstance(a.get("path"), str)
                        and isinstance(a.get("value"), (int, float))):
                    errors.append(f"{where}.artifacts[{j}]: needs 'path' "
                                  "string + numeric 'value'")

    rounds = obj.get("host_decode")
    if not isinstance(rounds, list) or not rounds:
        errors.append("missing non-empty 'host_decode' list")
        return errors
    check_rounds(rounds, "host_decode")
    serving = obj.get("serving")
    if serving is not None:
        # r17: the serving chain's rounds — same per-round shape, its own
        # pin sequence (absent entirely only in pre-r17 trajectories)
        if not isinstance(serving, list):
            errors.append("'serving' present but not a list")
        else:
            check_rounds(serving, "serving")
    _check_finite(obj, "trajectory", errors)
    return errors


# ------------------------------------------------------------- fleet JSONL
#: Legal per-process entry statuses in fleet records (r22,
#: telemetry/collector.py). Mirrors FleetCollector's entry lifecycle —
#: duplicated as a literal, leaf-module contract.
_FLEET_STATUSES = ("live", "stale")

#: Legal fleet/per-process verdicts — stall.VERDICTS duplicated as a
#: literal (same contract; the drift is guarded by test).
_FLEET_VERDICTS = ("guard_stalled", "checkpoint_bound", "infeed_bound",
                   "compute_bound")


def validate_fleet_record(record: Any) -> List[str]:
    """One fleet-collector JSONL cycle record (r22,
    FleetCollector.collect_once shape): the quorum verdict + per-process
    roll call the fleet log archives per scrape cycle."""
    errors: List[str] = []
    if not isinstance(record, dict):
        return [f"record is {type(record).__name__}, expected object"]
    if record.get("event") != "fleet_window":
        errors.append(f"'event' is {record.get('event')!r}, expected "
                      "'fleet_window'")
    validate_schema_version(record.get("schema_version"), "record", errors)
    if record.get("schema_version") is None:
        errors.append("missing 'schema_version' (fleet records are "
                      "versioned from birth — no pre-versioned cohort)")
    v = record.get("cycle")
    if not isinstance(v, int) or isinstance(v, bool) or v < 1:
        errors.append("'cycle' not a positive integer")
    fleet = record.get("fleet")
    if not isinstance(fleet, dict):
        errors.append("missing 'fleet' object")
    else:
        verdict = fleet.get("verdict")
        if verdict is not None and verdict not in _FLEET_VERDICTS:
            errors.append(f"fleet: 'verdict' {verdict!r} not one of "
                          f"{_FLEET_VERDICTS}")
        for key in ("quorum", "of"):
            v = fleet.get(key)
            if not isinstance(v, int) or isinstance(v, bool) or v < 0:
                errors.append(f"fleet: '{key}' not a non-negative integer")
        if isinstance(fleet.get("quorum"), int) \
                and isinstance(fleet.get("of"), int) \
                and fleet["quorum"] > fleet["of"]:
            errors.append("fleet: quorum exceeds the process count it was "
                          "taken over")
        stragglers = fleet.get("stragglers")
        if not isinstance(stragglers, dict) or not all(
                isinstance(k, str) and s in _FLEET_VERDICTS
                for k, s in stragglers.items()):
            errors.append("fleet: 'stragglers' not an object of "
                          "name -> verdict")
        if not isinstance(fleet.get("detail"), str):
            errors.append("fleet: missing 'detail' string")
    procs = record.get("processes")
    if not isinstance(procs, list):
        errors.append("missing 'processes' list")
    else:
        for i, p in enumerate(procs):
            where = f"processes[{i}]"
            if not isinstance(p, dict):
                errors.append(f"{where}: not an object")
                continue
            if not isinstance(p.get("role"), str) or not p.get("role"):
                errors.append(f"{where}: missing 'role' string")
            if not isinstance(p.get("ident"), int) \
                    or isinstance(p.get("ident"), bool):
                errors.append(f"{where}: missing integer 'ident'")
            if p.get("status") not in _FLEET_STATUSES:
                errors.append(f"{where}: 'status' {p.get('status')!r} not "
                              f"one of {_FLEET_STATUSES}")
            verdict = p.get("verdict")
            if verdict is not None and verdict not in _FLEET_VERDICTS:
                errors.append(f"{where}: 'verdict' {verdict!r} not one of "
                              f"{_FLEET_VERDICTS}")
            age = p.get("age_s")
            if age is not None and (not isinstance(age, (int, float))
                                    or isinstance(age, bool) or age < 0
                                    or not math.isfinite(age)):
                errors.append(f"{where}: 'age_s' not a non-negative finite "
                              "number")
            if len(errors) >= 20:
                errors.append("... (truncated)")
                break
    _check_finite(record, "record", errors)
    return errors


def validate_fleet_jsonl(path: str, max_errors: int = 20) -> List[str]:
    """Whole-file check over a collector fleet log."""
    errors: List[str] = []
    with open(path) as f:
        for lineno, line in enumerate(f, 1):
            line = line.strip()
            if not line:
                continue
            try:
                record = _strict_loads(line)
            except ValueError as e:
                errors.append(f"line {lineno}: {e}")
            else:
                errors.extend(f"line {lineno}: {err}"
                              for err in validate_fleet_record(record))
            if len(errors) >= max_errors:
                errors.append("... (truncated)")
                break
    return errors


# ----------------------------------------------------------- stitch manifest
def validate_stitch_manifest(obj: Any) -> List[str]:
    """The stitched-trace manifest (r22, telemetry/stitch.py): which input
    traces landed at which Perfetto pids and which correlation ids became
    flow arrows — the committed receipt's machine-checkable half (the
    other half is the stitched trace itself, validate_chrome_trace)."""
    errors: List[str] = []
    if not isinstance(obj, dict):
        return [f"manifest is {type(obj).__name__}, expected object"]
    if obj.get("kind") != "stitched_trace_manifest":
        errors.append(f"'kind' is {obj.get('kind')!r}, expected "
                      "'stitched_trace_manifest'")
    validate_schema_version(obj.get("schema_version"), "manifest", errors)
    if obj.get("schema_version") is None:
        errors.append("missing 'schema_version' (stitch manifests are "
                      "versioned from birth — no pre-versioned cohort)")
    inputs = obj.get("inputs")
    if not isinstance(inputs, list) or not inputs:
        errors.append("missing non-empty 'inputs' list")
        inputs = []
    pids = set()
    for i, inp in enumerate(inputs):
        where = f"inputs[{i}]"
        if not isinstance(inp, dict):
            errors.append(f"{where}: not an object")
            continue
        if not isinstance(inp.get("path"), str):
            errors.append(f"{where}: missing 'path' string")
        pid = inp.get("pid")
        if not isinstance(pid, int) or isinstance(pid, bool) or pid < 1:
            errors.append(f"{where}: 'pid' not a positive integer")
        elif pid in pids:
            # the whole point of the remap: two in-process workers share
            # an OS pid but MUST occupy distinct Perfetto process lanes
            errors.append(f"{where}: duplicate pid {pid} — stitched "
                          "inputs must land on distinct process lanes")
        else:
            pids.add(pid)
        if not isinstance(inp.get("process_name"), str) \
                or not inp.get("process_name"):
            errors.append(f"{where}: missing 'process_name' string")
        v = inp.get("events")
        if not isinstance(v, int) or isinstance(v, bool) or v < 0:
            errors.append(f"{where}: 'events' not a non-negative integer")
    flows = obj.get("flows")
    if not isinstance(flows, list):
        errors.append("missing 'flows' list")
        flows = []
    for i, fl in enumerate(flows):
        where = f"flows[{i}]"
        if not isinstance(fl, dict):
            errors.append(f"{where}: not an object")
            continue
        v = fl.get("id")
        if not isinstance(v, int) or isinstance(v, bool) or v < 1:
            errors.append(f"{where}: 'id' not a positive integer")
        if not isinstance(fl.get("trace_id"), str) or not fl.get("trace_id"):
            errors.append(f"{where}: missing 'trace_id' string")
        src = fl.get("src")
        if not (isinstance(src, dict) and isinstance(src.get("pid"), int)
                and isinstance(src.get("name"), str)):
            errors.append(f"{where}: 'src' not {{pid: int, name: str}}")
        elif src["pid"] not in pids and pids:
            errors.append(f"{where}: src pid {src['pid']} names no input")
        dst = fl.get("dst")
        if not isinstance(dst, list) or not dst:
            errors.append(f"{where}: missing non-empty 'dst' list")
        else:
            for j, d in enumerate(dst):
                if not (isinstance(d, dict)
                        and isinstance(d.get("pid"), int)
                        and isinstance(d.get("name"), str)):
                    errors.append(f"{where}.dst[{j}]: not "
                                  "{pid: int, name: str}")
                elif d["pid"] not in pids and pids:
                    errors.append(f"{where}.dst[{j}]: pid {d['pid']} "
                                  "names no input")
        if len(errors) >= 20:
            errors.append("... (truncated)")
            break
    v = obj.get("events_total")
    if not isinstance(v, int) or isinstance(v, bool) or v < 0:
        errors.append("'events_total' not a non-negative integer")
    _check_finite(obj, "manifest", errors)
    return errors


def validate_stitch_manifest_file(path: str) -> List[str]:
    with open(path) as f:
        try:
            obj = _strict_loads(f.read())
        except ValueError as e:
            return [f"{os.path.basename(path)}: {e}"]
    return validate_stitch_manifest(obj)
