"""Receipt-driven perf regression sentinel — the machine half of the r5–r10
benchmarking discipline (ISSUE 8; tf.data, arXiv 2101.12127, makes the case
that pipeline guarding must ride measured, machine-checked signals, not
hand-read tables).

Three jobs, all over COMMITTED evidence:

1. **Trajectory** (`build_trajectory`): parse every committed
   `benchmarks/runs/host_r*/` decode artifact
   into one machine-readable file (`benchmarks/runs/trajectory.json`) — per
   round: the pinned constant, its provenance artifacts (the exact files the
   `HOST_DECODE_RATE_R*` docstrings cite), every other artifact in the round
   dir with its measured basis, and the tolerance band derived below.
2. **Committed-consistency check** (`check_committed`): each pin equals the
   LOWER of its provenance artifacts (the committed convention), every
   provenance artifact schema-validates and carries the pin's basis, and the
   pin sequence is monotone EXCEPT transitions carrying an explicit drift
   receipt (r6→r7 is box drift, receipted in host_r7/README.md with
   same-session worktree controls). Runs in tier-1: a PR that edits a pin
   without committing matching receipts — or commits receipts that no longer
   back the pin — fails before it merges.
3. **New-artifact gate** (`check_artifact`): a fresh `--json-out` bench
   artifact is matched to the newest gating pin with the same measured basis
   (wire, space-to-depth, source size/kind, restart markers) and must land
   within the tolerance band BELOW the pin — the pre-commit/CI gate that
   stops the next ingest PR from silently giving back r6–r10's wins.

Tolerance-band derivation (documented here because the number IS the
policy): each committed artifact records `spread` = (max−min)/median over
its min-of-N alternating windows — same-session, same-box noise on ONE
window. The committed value is the best-of-N window, whose downside noise
is roughly half the window spread (the best window sits at the top of the
window distribution; a regression has to drag the BEST window down). So

    tolerance = clamp(0.5 · max(spread over the pin's provenance runs),
                      0.02, 0.06)

floor 2 % (below that, any box hiccup would page), cap 6 % (above that the
band would swallow a real −10 % regression — the acceptance case). The band
covers SAME-BOX noise only: committed READMEs show this host drifting
±5–8 % between sessions, which is exactly why the r6–r10 protocol pairs
every claim with same-session worktree controls; a sentinel failure on a
drifted box means "re-measure with controls", not necessarily "regressed".

Stdlib-only. The pin VALUES are imported from utils/scaling_model.py (the
single source since r5) — itself stdlib-only, so the telemetry package's
import-isolation contract holds through this module too.
"""

from __future__ import annotations

import dataclasses
import glob
import json
import os
import re
from typing import Any, Dict, List, Mapping, Optional, Sequence, Tuple

from distributed_vgg_f_tpu.telemetry import schema

#: The contract metric every host decode artifact carries.
HOST_METRIC = "host_native_decode_images_per_sec_per_core"

#: The contract metric of a serving open-loop receipt (r17,
#: benchmarks/serving_bench.py): peak admitted requests/sec among RPS-ramp
#: stages whose admitted p99 stayed within the SLO budget — throughput
#: that was actually served within latency, not offered load.
SERVING_METRIC = "serving_admitted_rps"

#: The contract metric of a resume receipt (r18,
#: benchmarks/resume_bench.py): batches REPLAYED by a kill-at-window-k /
#: resume cycle. The position-exact contract is value == 0 — enforced by
#: the artifact schema (an exact-mode row with replayed batches fails
#: validation), not by a pin floor: zero is a correctness claim, not a
#: rate to band.
RESUME_METRIC = "resume_replayed_batches"

#: The contract metric of an elastic-resize receipt (r19,
#: benchmarks/elastic_bench.py): seconds of downtime between the
#: preemption consensus firing and the first training step executing on
#: the survivor mesh. Schema-gated like the resume chain (the elastic_bench
#: row must replay zero batches AND beat the restart-from-checkpoint
#: control by >= 3x — validate_elastic_row), never pin-gated: the claim is
#: a ratio against a same-box control, not a rate to band.
ELASTIC_METRIC = "elastic_resize_downtime_seconds"

TOLERANCE_FLOOR = 0.02
TOLERANCE_CAP = 0.06


def tolerance_band(spreads: Sequence[float]) -> float:
    """clamp(0.5·max(spread), floor, cap) — see the module docstring for
    why half a window spread bounds the best-of-N estimator's noise."""
    worst = max([float(s) for s in spreads if s is not None] or [0.0])
    return min(TOLERANCE_CAP, max(TOLERANCE_FLOOR, 0.5 * worst))


# ---------------------------------------------------------------------------
# Basis: the measured configuration a rate is only comparable within.
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Basis:
    """What the window actually measured. `wire` folds the host dtype for
    host wires (host_f32/host_bf16 ARE the dtype contract); the u8 wire's
    recorded image_dtype only names the device-finish comparison column —
    host work is identical — so it is deliberately NOT part of the key
    (the committed r9 u8 rows say float32 where the r10 rows say bfloat16,
    same host pipeline).

    r13 adds `model` and `augment` so the zoo rows gate independently of
    the VGG-F line: a vgg16-labeled row compares against the vgg16 pin,
    and an augment-on row (host flips deleted — data/augment.py owns them)
    against the augment-on pin, never cross-wise. Defaults reproduce the
    pre-r13 basis for every committed artifact that predates the fields
    (unlabeled rows measured the flagship, flips-on-host).

    r14 adds `sharding` — the gradient-exchange basis
    (<dp|zero1|zero2>[_bucketed], train/step.py comm_meta) — so step-time
    receipts for the overlapped bucketed exchange gate per layout, never
    cross-wise (a zero2_bucketed step and a dp step are different
    machines). Host-decode rows never touch the exchange, so the pre-r14
    default "dp" keeps every committed artifact on its existing key.
    r21 grows the value set with `zero3[_bucketed]` (mesh.shard_params —
    the just-in-time param-gather step IS a different machine from
    zero2's trailing re-sync); the field itself and the pre-r14 default
    are unchanged, so every committed key stays where it is.

    r16 adds `ingest` — `local` | `service_<N>w` (the disaggregated
    data-service topology, data/ingest_service.py) — so N-worker scaling
    receipts gate independently of the single-host line: a 4-worker
    aggregate rate compared against a local-decode pin would gate on
    topology, not code. Rows carry it as `ingest_mode` (the row key
    `ingest` already names the r13 per-model descriptor dict); the
    pre-r16 default `local` keeps every committed receipt on its key.

    r17 adds `serving` — `off` | `openloop_b<max_batch>` (the predict
    server's admission basis, serving/ + benchmarks/serving_bench.py; rows
    carry it as `serving_mode`) — so the open-loop RPS/latency receipts
    gate on their own chain (SERVING_PINS, SERVING_METRIC): an
    admitted-RPS number and a decode rate are different machines, and the
    admission geometry (bucket ladder) is part of what the number
    measured. The pre-r17 default `off` keeps every committed decode
    receipt on its existing key.

    r18 adds `resume` — `replay` | `exact` (the restart basis,
    data/iterator_state.py + benchmarks/resume_bench.py; rows carry it as
    `resume_mode`) — so the kill-and-resume receipts label which restart
    semantics a number was measured under. The pre-r18 default `replay`
    (the r17 behavior every committed receipt implicitly measured) keeps
    every existing key.

    r19 adds `topology` — `static` | `elastic_<N>to<M>` (the live-resize
    basis, parallel/elastic.py ResizePlan.topology_label; rows carry it as
    `topology`) — so a rate measured across an in-flight mesh shrink gates
    on its own key: a post-resize survivor mesh and a static mesh are
    different machines. The pre-r19 default `static` keeps every committed
    receipt on its existing key.

    r20 adds `tier` — `fp32` | `bf16` | `int8` | `student` (the serving
    ladder rung, serving/tiers.py; rows carry it as `tier`) — so each
    tier's admitted-RPS receipt gates against ITS OWN pin: an int8
    engine's number regressing to the fp32 pin's level is exactly the
    regression the tier exists to prevent, and it would be invisible on a
    shared key. The default `fp32` keeps every committed serving receipt
    (r17's pre-tier rows) on its existing key."""
    wire: str
    space_to_depth: bool
    source_kind: str
    source_hw: Tuple[int, int]
    restart_markers: bool
    model: str = "vggf"
    augment: bool = False
    sharding: str = "dp"
    ingest: str = "local"
    serving: str = "off"
    resume: str = "replay"
    topology: str = "static"
    tier: str = "fp32"

    def describe(self) -> dict:
        return {"wire": self.wire, "space_to_depth": self.space_to_depth,
                "source_kind": self.source_kind,
                "source_hw": list(self.source_hw),
                "restart_markers": self.restart_markers,
                "model": self.model, "augment": self.augment,
                "sharding": self.sharding, "ingest": self.ingest,
                "serving": self.serving, "resume": self.resume,
                "topology": self.topology, "tier": self.tier}


def row_basis(row: Mapping) -> Basis:
    """Basis of one decode-bench layout row. Pre-r7 artifacts carry no
    `source` (the protocol was fixed at 320x256 noise), pre-r8 ones no
    `wire` (the host dtype WAS the wire), and pre-r13 ones no `model` /
    `augment` (every row measured the flagship with host-owned flips)."""
    wire = row.get("wire")
    if wire is None:
        wire = ("host_bf16" if row.get("image_dtype") == "bfloat16"
                else "host_f32")
    src = row.get("source") or {}
    hw = tuple(src.get("source_hw") or (320, 256))
    interval = src.get("restart_interval")
    restart = (row.get("restart_kind") == "restart"
               and interval is not None and interval >= 0)
    aug = row.get("augment")
    return Basis(wire=wire, space_to_depth=bool(row.get("space_to_depth")),
                 source_kind=src.get("source_kind") or "noise",
                 source_hw=(int(hw[0]), int(hw[1])),
                 restart_markers=restart,
                 model=row.get("model") or "vggf",
                 augment=bool(isinstance(aug, Mapping)
                              and aug.get("enabled")),
                 sharding=row.get("sharding") or "dp",
                 ingest=row.get("ingest_mode") or "local",
                 serving=row.get("serving_mode") or "off",
                 resume=row.get("resume_mode") or "replay",
                 topology=row.get("topology") or "static",
                 tier=row.get("tier") or "fp32")


def artifact_contract_row(obj: Mapping) -> Optional[Mapping]:
    """The decode-bench row the top-level contract value is read against:
    the tfrecord layout when present (the frozen contract layout), else the
    first decode_bench row."""
    rows = [r for r in obj.get("layouts") or []
            if isinstance(r, Mapping) and r.get("mode") == "decode_bench"]
    if not rows:
        return None
    for r in rows:
        if r.get("layout") == "tfrecord":
            return r
    return rows[0]


def serving_contract_row(obj: Mapping) -> Optional[Mapping]:
    """The serving-bench row (r17) a SERVING_METRIC contract value is read
    against — the first (in practice only) serving_bench layout row."""
    for r in obj.get("layouts") or []:
        if isinstance(r, Mapping) and r.get("mode") == "serving_bench":
            return r
    return None


def resume_contract_row(obj: Mapping) -> Optional[Mapping]:
    """The resume-bench row (r18) a RESUME_METRIC value is read against —
    the EXACT-mode row (the contract row; the replay row is its control)."""
    rows = [r for r in obj.get("layouts") or []
            if isinstance(r, Mapping) and r.get("mode") == "resume_bench"]
    for r in rows:
        if r.get("resume_mode") == "exact":
            return r
    return rows[0] if rows else None


def elastic_contract_row(obj: Mapping) -> Optional[Mapping]:
    """The elastic-bench row (r19) an ELASTIC_METRIC value is read against
    — the first (in practice only) elastic_bench layout row."""
    for r in obj.get("layouts") or []:
        if isinstance(r, Mapping) and r.get("mode") == "elastic_bench":
            return r
    return None


# ---------------------------------------------------------------------------
# Pins: HOST_DECODE_RATE_R* with their committed provenance.
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Pin:
    name: str                  # constant name in utils/scaling_model.py
    round: str                 # receipt round ("r9" = benchmarks round tag)
    run_dir: str               # repo-relative committed receipt directory
    provenance: Tuple[str, ...]  # the files the pin docstring cites
    basis: Basis
    #: False = trajectory row only, never gates a new artifact: the r5
    #: number was measured on a 1-vCPU host class that no longer exists
    #: (scaling_model docstring) — comparing this box against it would gate
    #: on hardware, not code.
    gating: bool = True
    #: Present when pin[n] < pin[n-1] on purpose: the committed receipt
    #: explaining the decrease (box drift with same-session controls).
    drift_note: Optional[str] = None


PINS: Tuple[Pin, ...] = (
    Pin("HOST_DECODE_RATE_R5", "r5", "benchmarks/runs/host_r5",
        ("host_pipeline_run1.json", "host_pipeline_run2.json"),
        Basis("host_f32", False, "noise", (320, 256), False),
        gating=False),
    Pin("HOST_DECODE_RATE_R6", "r6", "benchmarks/runs/host_r6",
        ("decode_simd_bf16s2d_run1.json", "decode_simd_bf16s2d_run2.json"),
        Basis("host_bf16", True, "noise", (320, 256), False)),
    Pin("HOST_DECODE_RATE_R7", "r7", "benchmarks/runs/host_r7",
        # runs 3/4 — the FINAL alternating drift-controlled pair the
        # constant's docstring cites; runs 1/2 were the pre-control warmup
        ("decode_r7_bf16s2d_320noise_run3.json",
         "decode_r7_bf16s2d_320noise_run4.json"),
        Basis("host_bf16", True, "noise", (320, 256), False),
        drift_note="host_r7/README.md: r7 ≡ r6 code within noise on this "
                   "config; the −3.9% step vs R6 is box drift, receipted "
                   "with same-session r6-code worktree controls "
                   "(989.3–1047.1)"),
    Pin("HOST_DECODE_RATE_R8", "r8", "benchmarks/runs/host_r9",
        ("decode_r8_u8_s2d_320noise_run1.json",
         "decode_r8_u8_s2d_320noise_run2.json"),
        Basis("u8", True, "noise", (320, 256), False)),
    Pin("HOST_DECODE_RATE_R9", "r9", "benchmarks/runs/host_r10",
        ("decode_r10_on_320noise_rst1_run1.json",
         "decode_r10_on_320noise_rst1_run2.json",
         "decode_r10_on_320noise_rst1_run3.json"),
        Basis("u8", True, "noise", (320, 256), True)),
    # r13 (feature round r10): the (model, augment) bases — zoo rows and
    # the augment-on flagship gate independently of the VGG-F
    # flips-on-host line. Each sits below HOST_DECODE_RATE_R9 because the
    # box drifted between sessions (host_r13/README.md: the SAME-session
    # augment receipt shows augment-on ≥ augment-off, and zoo host work
    # is identical to the flagship's by construction), so each carries
    # the drift note the monotone check requires.
    Pin("HOST_DECODE_RATE_R10_AUG", "r10", "benchmarks/runs/host_r13",
        ("decode_r13_augment_on_run1.json",
         "decode_r13_augment_on_run2.json"),
        Basis("u8", True, "noise", (320, 256), True, "vggf", True),
        drift_note="host_r13/README.md: new augment-on basis on a box "
                   "~9-14% below its r10-session windows; the same-session "
                   "alternating receipt (augment_overhead in run1) shows "
                   "augment-on 1209.1 vs off 1181.2 — no host cost, wire "
                   "bytes identical"),
    Pin("HOST_ZOO_RATE_R10_VGG16", "r10", "benchmarks/runs/host_r13",
        ("decode_r13_zoo_vgg16_run1.json",
         "decode_r13_zoo_vgg16_run2.json"),
        Basis("u8", False, "noise", (320, 256), True, "vgg16", False),
        drift_note="host_r13/README.md: new per-model basis (identical "
                   "host pipeline to the flagship u8 row, unpacked "
                   "descriptor) on a drifted box"),
    Pin("HOST_ZOO_RATE_R10_RESNET50", "r10", "benchmarks/runs/host_r13",
        ("decode_r13_zoo_resnet50_run1.json",
         "decode_r13_zoo_resnet50_run2.json"),
        Basis("u8", False, "noise", (320, 256), True, "resnet50", False),
        drift_note="host_r13/README.md: new per-model basis (identical "
                   "host pipeline to the flagship u8 row, unpacked "
                   "descriptor) on a drifted box"),
    Pin("HOST_ZOO_RATE_R10_VIT_S16", "r10", "benchmarks/runs/host_r13",
        ("decode_r13_zoo_vit_s16_run1.json",
         "decode_r13_zoo_vit_s16_run2.json"),
        Basis("u8", False, "noise", (320, 256), True, "vit_s16", False),
        drift_note="host_r13/README.md: new per-model basis (identical "
                   "host pipeline to the flagship u8 row, unpacked "
                   "descriptor) on a drifted box"),
)


#: The r17 serving chain — its own pin sequence with its own metric
#: (SERVING_METRIC): an admitted-RPS number must never sit in the decode
#: chain's monotone check (the two measure different machines). Same
#: committed convention: pin == LOWER of the provenance pair.
SERVING_PINS: Tuple[Pin, ...] = (
    Pin("SERVING_RPS_R14", "r14", "benchmarks/runs/host_r16",
        ("serving_openloop_run1.json", "serving_openloop_run2.json"),
        Basis("u8", False, "u8_payload", (128, 128), False, "vggf",
              serving="openloop_b8")),
    # The r18 tier ladder (benchmarks/runs/host_r23): trained weights on
    # the teacher task's native 32px geometry — where the FC heads
    # dominate (fc6_in=256), i.e. the paper's actual compute profile —
    # one pin per (vggf, tier). A new 32px basis, NOT comparable to the
    # 128px fresh-init R14 chain above; every pin carries the drift note
    # saying so.
    Pin("SERVING_RPS_R18_FP32", "r18", "benchmarks/runs/host_r23",
        ("serving_r18_tier_fp32_run1.json",
         "serving_r18_tier_fp32_run2.json"),
        Basis("u8", False, "u8_payload", (32, 32), False, "vggf",
              serving="openloop_b8"),
        drift_note="host_r23/README.md: new 32px trained-weights basis "
                   "(teacher-task geometry, FC-head-dominated) — not the "
                   "128px fresh-init R14 line"),
    Pin("SERVING_RPS_R18_BF16", "r18", "benchmarks/runs/host_r23",
        ("serving_r18_tier_bf16_run1.json",
         "serving_r18_tier_bf16_run2.json"),
        Basis("u8", False, "u8_payload", (32, 32), False, "vggf",
              serving="openloop_b8", tier="bf16"),
        drift_note="host_r23/README.md: bf16 is EMULATED on XLA:CPU "
                   "(measured within noise of fp32 at equal architecture "
                   "— no MXU to cash the narrower dtype); the tier's "
                   "latency claim needs an MXU and is not measured; "
                   "this pin guards the CPU baseline only"),
    Pin("SERVING_RPS_R18_INT8", "r18", "benchmarks/runs/host_r23",
        ("serving_r18_tier_int8_run1.json",
         "serving_r18_tier_int8_run2.json"),
        Basis("u8", False, "u8_payload", (32, 32), False, "vggf",
              serving="openloop_b8", tier="int8"),
        drift_note="host_r23/README.md: own (vggf, int8) basis — "
                   "calibrated sub-LSB channel elision over the quantized "
                   "heads; strictly above the fp32 pin by the frontier "
                   "receipt"),
    Pin("SERVING_RPS_R18_STUDENT", "r18", "benchmarks/runs/host_r23",
        ("serving_r18_tier_student_run1.json",
         "serving_r18_tier_student_run2.json"),
        Basis("u8", False, "u8_payload", (32, 32), False, "vggf",
              serving="openloop_b8", tier="student"),
        drift_note="host_r23/README.md: own (vggf, student) basis — "
                   "half-width distilled vggf_student serving the "
                   "flagship route; strictly above the fp32 pin by the "
                   "frontier receipt"),
)


def pin_value(pin: Pin) -> float:
    """The constant's CURRENT value — read from utils/scaling_model.py (the
    single source), so the sentinel can never drift from what provisioning
    actually uses."""
    from distributed_vgg_f_tpu.utils import scaling_model
    return float(getattr(scaling_model, pin.name))


def gating_pin_for(basis: Basis,
                   pins: Sequence[Pin] = PINS) -> Optional[Pin]:
    """The NEWEST gating pin measured on this basis (later pins supersede
    earlier ones on the same basis — r7 supersedes r6 for bf16+s2d).
    `pins` selects the chain (decode PINS or SERVING_PINS — an artifact's
    metric decides which chain may gate it)."""
    match = None
    for pin in pins:
        if pin.gating and pin.basis == basis:
            match = pin
    return match


# ---------------------------------------------------------------------------
# Committed-artifact parsing.
# ---------------------------------------------------------------------------

def _read_json(path: str) -> Optional[Any]:
    try:
        with open(path) as f:
            return json.load(f)
    except (OSError, ValueError):
        return None


def _contract_value_from_jsonl(path: str) -> Optional[dict]:
    """Pre-r6 run logs (host_r4/r5) are JSONL: one line per pipeline plus
    the contract line carrying the frozen metric."""
    try:
        with open(path) as f:
            lines = [ln.strip() for ln in f if ln.strip()]
    except OSError:
        return None
    for ln in lines:
        try:
            obj = json.loads(ln)
        except ValueError:
            continue
        if isinstance(obj, dict) and obj.get("metric") == HOST_METRIC:
            return obj
    return None


def parse_host_artifact(path: str) -> Optional[dict]:
    """One committed host artifact → {path, value, spread, basis} or None
    when the file carries no contract value (READMEs, session scripts,
    telemetry-only receipts keep their value field — those pass through
    with basis from their layout rows when present)."""
    obj = _read_json(path)
    if obj is None:
        line = _contract_value_from_jsonl(path)
        if line is None:
            return None
        return {"path": path, "value": line.get("value"),
                "spread": line.get("spread"),
                "basis": Basis("host_f32", False, "noise", (320, 256),
                               False).describe(),
                "format": "contract_jsonl"}
    if not isinstance(obj, dict) or "metric" not in obj:
        return None
    if obj.get("metric") == SERVING_METRIC:
        # r17 serving receipt: the basis lives in its serving_bench row
        row = serving_contract_row(obj)
        return {"path": path, "value": obj.get("value"),
                "spread": row.get("spread") if row else None,
                "basis": row_basis(row).describe() if row else None,
                "format": "serving_bench"}
    if obj.get("metric") == RESUME_METRIC:
        # r18 resume receipt: value is REPLAYED BATCHES (0 by contract,
        # schema-enforced), never pin-gated — it rides the trajectory as
        # an unpinned round with the exact-mode row's basis
        row = resume_contract_row(obj)
        return {"path": path, "value": obj.get("value"),
                "spread": row.get("spread") if row else None,
                "basis": row_basis(row).describe() if row else None,
                "format": "resume_bench"}
    if obj.get("metric") == ELASTIC_METRIC:
        # r19 elastic receipt: value is resize DOWNTIME SECONDS; the
        # >=3x-vs-restart and zero-replay contracts are schema-enforced
        # (validate_elastic_row), never pin-gated — it rides the
        # trajectory as an unpinned round with the elastic row's basis
        row = elastic_contract_row(obj)
        return {"path": path, "value": obj.get("value"),
                "spread": row.get("spread") if row else None,
                "basis": row_basis(row).describe() if row else None,
                "format": "elastic_bench"}
    row = artifact_contract_row(obj)
    out = {"path": path, "value": obj.get("value"),
           "spread": row.get("spread") if row else None,
           "basis": row_basis(row).describe() if row else None,
           "format": "decode_bench"}
    if "telemetry_overhead" in obj:
        out["telemetry_overhead_pct"] = \
            obj["telemetry_overhead"].get("overhead_pct")
    if "exporter_overhead" in obj:
        out["exporter_overhead_pct"] = \
            obj["exporter_overhead"].get("overhead_pct")
    return out


def _round_sort_key(dirname: str):
    m = re.search(r"host_r(\d+)$", dirname)
    return int(m.group(1)) if m else 0


def build_trajectory(repo: str) -> dict:
    """Every committed host_r*/ artifact, one file. No
    timestamps on purpose: regeneration from the same tree is byte-stable,
    so `--check-committed` can diff the committed trajectory.json against a
    fresh build."""
    rounds: List[dict] = []
    by_dir: Dict[str, List[dict]] = {}
    for run_dir in sorted(glob.glob(os.path.join(
            repo, "benchmarks", "runs", "host_r*")), key=_round_sort_key):
        entries = []
        for path in sorted(glob.glob(os.path.join(run_dir, "*.json"))):
            parsed = parse_host_artifact(path)
            if parsed is not None:
                parsed["path"] = os.path.relpath(path, repo)
                entries.append(parsed)
        by_dir[os.path.relpath(run_dir, repo)] = entries
    def pin_round(pin: Pin) -> dict:
        entries = by_dir.get(pin.run_dir, [])
        prov_paths = {os.path.join(pin.run_dir, name)
                      for name in pin.provenance}
        spreads = []
        for e in entries:
            e_is_prov = e["path"] in prov_paths
            e["pin_provenance"] = e_is_prov
            if e_is_prov and e.get("spread") is not None:
                spreads.append(e["spread"])
        return {
            "round": pin.round, "pin": pin.name, "value": pin_value(pin),
            "gating": pin.gating, "basis": pin.basis.describe(),
            "tolerance": round(tolerance_band(spreads), 4),
            "drift_note": pin.drift_note,
            "run_dir": pin.run_dir,
            "artifacts": entries,
        }

    rounds = [pin_round(pin) for pin in PINS]
    # the r17 serving chain rides its own section: its metric and pin
    # sequence are disjoint from the decode chain's, but the artifact
    # parsing/provenance machinery is the same
    serving_rounds = [pin_round(pin) for pin in SERVING_PINS]
    # round dirs that back no pin (controls, telemetry receipts) still ride
    # the trajectory — receipts must be findable by machine, not only by
    # knowing which README cites them
    pinned_dirs = {p.run_dir for p in PINS} \
        | {p.run_dir for p in SERVING_PINS}
    extra = [{"round": os.path.basename(d).replace("host_", ""),
              "run_dir": d, "artifacts": entries}
             for d, entries in by_dir.items()
             if d not in pinned_dirs and entries]
    return {"schema_version": schema.SCHEMA_VERSION,
            "kind": "perf_trajectory", "metric": HOST_METRIC,
            "serving_metric": SERVING_METRIC,
            "tolerance_rule": "clamp(0.5*max(provenance window spreads), "
                              f"{TOLERANCE_FLOOR}, {TOLERANCE_CAP}); "
                              "same-box bands — cross-session claims need "
                              "worktree controls (host_r7 README protocol)",
            "host_decode": rounds, "serving": serving_rounds,
            "unpinned_rounds": extra}


# ---------------------------------------------------------------------------
# Checks.
# ---------------------------------------------------------------------------

def _check_pin_chain(repo: str, pins: Sequence[Pin],
                     errors: List[str]) -> None:
    """One pin chain's committed-consistency pass — the monotone check is
    PER CHAIN (decode rates and serving RPS are different machines; a
    cross-chain comparison would gate nothing meaningful)."""
    prev: Optional[Tuple[Pin, float]] = None
    for pin in pins:
        value = pin_value(pin)
        best_values = []
        for name in pin.provenance:
            path = os.path.join(repo, pin.run_dir, name)
            if not os.path.exists(path):
                errors.append(f"{pin.name}: provenance artifact missing: "
                              f"{pin.run_dir}/{name}")
                continue
            parsed = parse_host_artifact(path)
            if parsed is None or parsed.get("value") is None:
                errors.append(f"{pin.name}: {name} carries no contract "
                              "value")
                continue
            if parsed["format"] in ("decode_bench", "serving_bench"):
                ferrs = schema.validate_bench_artifact_file(path)
                if ferrs:
                    errors.append(f"{pin.name}: {name} fails artifact "
                                  f"schema: {ferrs[:2]}")
                if parsed.get("basis") != pin.basis.describe():
                    errors.append(
                        f"{pin.name}: {name} basis {parsed.get('basis')} "
                        f"!= pin basis {pin.basis.describe()} — the pin "
                        "cites a receipt that measured something else")
            best_values.append(float(parsed["value"]))
        if best_values:
            committed_min = min(best_values)
            # the committed convention: pin == LOWER of the provenance pair
            if abs(committed_min - value) > 0.01:
                errors.append(
                    f"{pin.name}={value} != min(provenance)="
                    f"{committed_min} — pin and receipts have drifted "
                    "apart (re-derive the constant or fix the provenance "
                    "list)")
        if prev is not None and pin.gating:
            prev_pin, prev_value = prev
            if value < prev_value and pin.drift_note is None:
                errors.append(
                    f"{pin.name}={value} < {prev_pin.name}={prev_value} "
                    "with NO drift receipt — a silent trajectory decrease "
                    "(add the controls receipt + drift_note, or fix the "
                    "regression)")
        if pin.gating or prev is None:
            prev = (pin, value)


def check_committed(repo: str) -> List[str]:
    """Consistency of pins vs committed receipts (tier-1). Returns error
    strings, [] = green."""
    errors: List[str] = []
    _check_pin_chain(repo, PINS, errors)
    _check_pin_chain(repo, SERVING_PINS, errors)
    return errors


def check_trajectory_file(repo: str,
                          path: Optional[str] = None) -> List[str]:
    """The committed trajectory.json must schema-validate AND match a fresh
    build from the committed receipts — a stale trajectory is a wrong map
    wearing a machine-readable label."""
    path = path or os.path.join(repo, "benchmarks", "runs",
                                "trajectory.json")
    if not os.path.exists(path):
        return [f"trajectory file missing: {os.path.relpath(path, repo)} "
                "(generate with benchmarks/regression_sentinel.py "
                "--write-trajectory)"]
    committed = _read_json(path)
    errors = schema.validate_trajectory(committed)
    if errors:
        return [f"trajectory: {e}" for e in errors]
    fresh = build_trajectory(repo)
    if committed != fresh:
        return ["trajectory.json is stale: a fresh build from the "
                "committed receipts differs — regenerate with "
                "benchmarks/regression_sentinel.py --write-trajectory"]
    return []


def check_artifact(obj_or_path, repo: str, *,
                   require_pin: bool = False) -> Tuple[List[str], dict]:
    """Gate one NEW --json-out artifact against the pinned trajectory.
    Returns (errors, report). `require_pin=True` makes an unmatched basis
    an error (CI wants 'this config is gated' to be a property of the
    invocation, not of whether someone remembered to pin it)."""
    if isinstance(obj_or_path, str):
        obj = _read_json(obj_or_path)
        if obj is None:
            return ([f"unreadable artifact: {obj_or_path}"], {})
        label = os.path.basename(obj_or_path)
    else:
        obj, label = obj_or_path, "<inline>"
    errors = [f"{label}: {e}" for e in schema.validate_bench_artifact(obj)]
    report: Dict[str, Any] = {"artifact": label}
    metric = obj.get("metric")
    if metric not in (HOST_METRIC, SERVING_METRIC, RESUME_METRIC,
                      ELASTIC_METRIC):
        errors.append(f"{label}: metric {metric!r} is not "
                      f"{HOST_METRIC!r}, {SERVING_METRIC!r}, "
                      f"{RESUME_METRIC!r} or {ELASTIC_METRIC!r}")
        return (errors, report)
    value = obj.get("value")
    if not isinstance(value, (int, float)):
        errors.append(f"{label}: no numeric contract value "
                      f"(error={obj.get('error')!r})")
        return (errors, report)
    if metric == RESUME_METRIC:
        # r18 resume receipts are SCHEMA-gated (the zero-replay contract
        # lives in validate_resume_row, already applied above), never
        # pin-gated — there is no rate to band, only a correctness claim.
        # The claim needs an EXACT-mode row to exist: a replay-only
        # artifact measured nothing position-exact and must not pass as
        # a resume receipt.
        row = resume_contract_row(obj)
        if row is None or row.get("resume_mode") != "exact":
            errors.append(f"{label}: no exact-mode resume_bench layout "
                          "row — the zero-replay contract was never "
                          "measured")
            return (errors, report)
        if value != row.get("replayed_batches"):
            errors.append(
                f"{label}: contract value {value} != the exact row's "
                f"replayed_batches {row.get('replayed_batches')} — the "
                "headline number must BE the measured one")
        report["basis"] = row_basis(row).describe()
        report["value"] = value
        report["pin"] = None
        report["note"] = (f"{label}: resume receipt — schema-gated "
                          "(exact mode must replay 0), not pin-gated")
        return (errors, report)
    if metric == ELASTIC_METRIC:
        # r19 elastic receipts are SCHEMA-gated (zero replay + the >=3x
        # speedup-vs-restart floor live in validate_elastic_row, already
        # applied above), never pin-gated: the claim is a same-box ratio
        # against the restart control, not a rate to band. The claim
        # needs an elastic_bench row to exist — a rowless artifact
        # measured nothing.
        row = elastic_contract_row(obj)
        if row is None:
            errors.append(f"{label}: no elastic_bench layout row — the "
                          "resize-vs-restart contract was never measured")
            return (errors, report)
        if value != row.get("downtime_seconds"):
            errors.append(
                f"{label}: contract value {value} != the elastic row's "
                f"downtime_seconds {row.get('downtime_seconds')} — the "
                "headline number must BE the measured one")
        report["basis"] = row_basis(row).describe()
        report["value"] = value
        report["pin"] = None
        report["note"] = (f"{label}: elastic receipt — schema-gated "
                          "(zero replay, >=3x vs restart), not pin-gated")
        return (errors, report)
    if metric == SERVING_METRIC:
        # the serving chain gates on its own pins; none of the decode
        # machinery below (autotune settled-state, decode rows) applies
        row = serving_contract_row(obj)
        if row is None:
            errors.append(f"{label}: no serving_bench layout row — "
                          "nothing to match a pin basis against")
            return (errors, report)
        serving_cfg = row.get("serving") or {}
        if serving_cfg.get("controller"):
            # the decode chain's refuse-to-gate-mid-autotune discipline:
            # a window the admission controller was steering mid-stage is
            # not a steady-state measurement of any one configuration
            report["controller"] = True
            errors.append(
                f"{label}: REFUSED — measured with the admission "
                "controller ON (row.serving.controller=true): the batch "
                "window was a moving knob, not a pinned basis. Re-run "
                "serving_bench without --controller to gate.")
            return (errors, report)
        return _gate_against_pin(repo, label, value, row_basis(row),
                                 SERVING_PINS, errors, report,
                                 require_pin=require_pin)
    row = artifact_contract_row(obj)
    if row is None:
        errors.append(f"{label}: no decode_bench layout row — nothing to "
                      "match a pin basis against")
        return (errors, report)
    # r11: an artifact measured while the ingest autotuner was still
    # actuating is not a steady-state number — its windows sample a moving
    # knob configuration, and a mid-convergence window would read as a
    # false regression (or mask a real one). The settled-state flag in the
    # artifact schema is the receipt; refuse to gate without it.
    at = obj.get("autotune")
    if isinstance(at, Mapping) and at.get("enabled"):
        report["autotune"] = {"enabled": True,
                              "settled": bool(at.get("settled")),
                              "actuations_total":
                                  at.get("actuations_total")}
        if not at.get("settled"):
            errors.append(
                f"{label}: REFUSED — the artifact's windows overlap "
                f"ingest-autotuner actuations (autotune.enabled=true, "
                f"settled=false, {at.get('actuations_total')} actuations): "
                f"a mid-convergence window is not a steady-state "
                f"measurement. Re-run after the controller settles, or "
                f"bench with --autotune off.")
            return (errors, report)
    return _gate_against_pin(repo, label, value, row_basis(row), PINS,
                             errors, report, require_pin=require_pin)


def _gate_against_pin(repo: str, label: str, value: float, basis: Basis,
                      pins: Sequence[Pin], errors: List[str],
                      report: Dict[str, Any], *,
                      require_pin: bool = False) -> Tuple[List[str], dict]:
    """The tolerance-band gate shared by the decode and serving chains —
    one floor policy, two pin sequences."""
    report["basis"] = basis.describe()
    report["value"] = value
    pin = gating_pin_for(basis, pins)
    if pin is None:
        report["pin"] = None
        msg = (f"{label}: no gating pin for basis {basis.describe()} — "
               "not gated")
        if require_pin:
            errors.append(msg)
        else:
            report["note"] = msg
        return (errors, report)
    pinned = pin_value(pin)
    spreads = []
    for name in pin.provenance:
        parsed = parse_host_artifact(os.path.join(repo, pin.run_dir, name))
        if parsed and parsed.get("spread") is not None:
            spreads.append(parsed["spread"])
    tol = tolerance_band(spreads)
    floor = pinned * (1.0 - tol)
    report.update({"pin": pin.name, "pin_value": pinned,
                   "tolerance": round(tol, 4),
                   "floor": round(floor, 2),
                   "vs_pin": round(value / pinned, 4)})
    if value < floor:
        errors.append(
            f"{label}: REGRESSION — {value:.2f} is "
            f"{(1 - value / pinned) * 100:.1f}% below {pin.name}="
            f"{pinned} (tolerance {tol * 100:.1f}%, floor {floor:.2f}). "
            f"If this box has drifted, re-measure with same-session "
            f"worktree controls (host_r7 README protocol) before "
            f"believing either number.")
    return (errors, report)
