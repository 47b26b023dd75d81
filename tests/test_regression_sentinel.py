"""Regression sentinel (telemetry/regress.py + benchmarks/
regression_sentinel.py): committed-receipt consistency as a tier-1 gate
(ISSUE 8 satellite), tolerance-band derivation, basis matching, the
synthetically-degraded-artifact failure (acceptance: −10% must exit
non-zero), and trajectory freshness."""

import copy
import json
import os
import subprocess
import sys

import pytest

from distributed_vgg_f_tpu.telemetry import regress, schema
from distributed_vgg_f_tpu.telemetry.regress import (
    PINS,
    Basis,
    build_trajectory,
    check_artifact,
    check_committed,
    check_trajectory_file,
    gating_pin_for,
    pin_value,
    row_basis,
    tolerance_band,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SENTINEL = os.path.join(REPO, "benchmarks", "regression_sentinel.py")
R9_RUN = os.path.join(REPO, "benchmarks", "runs", "host_r10",
                      "decode_r10_on_320noise_rst1_run1.json")


# ------------------------------------------------------------ tier-1 gates
def test_committed_receipts_back_every_pin():
    """ISSUE 8 satellite: pins == committed receipts, schema-valid, basis-
    matched, monotone-or-receipted — the fast consistency gate."""
    assert check_committed(REPO) == []


def test_committed_trajectory_is_fresh():
    assert check_trajectory_file(REPO) == []


# ------------------------------------------------------------------- bands
def test_tolerance_band_derivation():
    assert tolerance_band([0.06]) == pytest.approx(0.03)   # half the spread
    assert tolerance_band([0.01]) == 0.02                  # floor
    assert tolerance_band([0.30]) == 0.06                  # cap
    assert tolerance_band([]) == 0.02
    assert tolerance_band([0.04, 0.08, None]) == pytest.approx(0.04)


# ------------------------------------------------------------------- basis
def test_row_basis_extraction_pre_and_post_r8():
    # pre-r8 row: no wire, no source — the dtype WAS the wire, the
    # protocol WAS 320x256 noise
    b = row_basis({"image_dtype": "bfloat16", "space_to_depth": True})
    assert b == Basis("host_bf16", True, "noise", (320, 256), False)
    # r9+ row: u8 wire with restart-marked sources; image_dtype is the
    # device-finish column, NOT host work — excluded from the key
    row = {"wire": "u8", "image_dtype": "bfloat16", "space_to_depth": True,
           "restart_kind": "restart",
           "source": {"source_hw": [320, 256], "source_kind": "noise",
                      "restart_interval": 1}}
    assert row_basis(row) == Basis("u8", True, "noise", (320, 256), True)
    # restart path enabled but markerless sources = sequential basis
    row2 = dict(row, source={"source_hw": [320, 256],
                             "source_kind": "noise",
                             "restart_interval": -1})
    assert not row_basis(row2).restart_markers


def test_newest_gating_pin_wins_per_basis():
    bf16 = Basis("host_bf16", True, "noise", (320, 256), False)
    assert gating_pin_for(bf16).name == "HOST_DECODE_RATE_R7"  # not R6
    u8 = Basis("u8", True, "noise", (320, 256), False)
    assert gating_pin_for(u8).name == "HOST_DECODE_RATE_R8"
    u8r = Basis("u8", True, "noise", (320, 256), True)
    assert gating_pin_for(u8r).name == "HOST_DECODE_RATE_R9"
    # r5's f32 basis is deliberately non-gating (dead host class)
    f32 = Basis("host_f32", False, "noise", (320, 256), False)
    assert gating_pin_for(f32) is None


# ---------------------------------------------------------- artifact gating
def _degraded(factor):
    obj = json.load(open(R9_RUN))
    obj["value"] = round(obj["value"] * factor, 2)
    for row in obj["layouts"]:
        if row.get("mode") == "decode_bench":
            row["images_per_sec_per_core"] *= factor
    return obj


def test_healthy_committed_artifact_passes_as_new():
    errors, report = check_artifact(R9_RUN, REPO)
    assert errors == []
    assert report["pin"] == "HOST_DECODE_RATE_R9"
    assert report["vs_pin"] == pytest.approx(1.0, abs=0.001)


def test_ten_percent_degradation_fails():
    """The acceptance case: −10% must land below every derivable band."""
    errors, report = check_artifact(_degraded(0.9), REPO)
    assert any("REGRESSION" in e for e in errors)
    assert report["tolerance"] <= 0.06 < 0.10


def test_within_band_wobble_passes():
    errors, _ = check_artifact(_degraded(0.99), REPO)
    assert errors == []


def test_unpinned_basis_is_note_unless_required():
    obj = _degraded(1.0)
    for row in obj["layouts"]:
        row["source"] = {"source_hw": [768, 768], "source_kind": "textured",
                        "restart_interval": 1}
    errors, report = check_artifact(obj, REPO)
    assert errors == [] and report["pin"] is None
    errors, _ = check_artifact(obj, REPO, require_pin=True)
    assert any("no gating pin" in e for e in errors)


def test_failed_bench_artifact_is_rejected():
    errors, _ = check_artifact(
        {"metric": regress.HOST_METRIC, "value": None,
         "error": "no_accelerator"}, REPO)
    assert any("no numeric contract value" in e for e in errors)


def test_schema_version_major_rejected_in_artifact():
    obj = _degraded(1.0)
    obj["schema_version"] = "9.9"
    errors, _ = check_artifact(obj, REPO)
    assert any("major" in e for e in errors)


# --------------------------------------------------- drift / pin corruption
def test_silent_pin_decrease_is_caught(monkeypatch):
    """A pin moved DOWN without a drift receipt must fail the committed
    check — that is the 'silently giving back r6-r10's wins' case."""
    from distributed_vgg_f_tpu.utils import scaling_model
    monkeypatch.setattr(scaling_model, "HOST_DECODE_RATE_R9", 1100.0)
    errors = check_committed(REPO)
    # the pin no longer equals its provenance AND breaks monotonicity
    assert any("min(provenance)" in e for e in errors)
    assert any("NO drift receipt" in e for e in errors)


def test_receipted_drift_is_allowed():
    """r6→r7 decreases (991.15 < 1031.36) and passes ONLY because the pin
    carries the committed drift receipt."""
    r7 = next(p for p in PINS if p.name == "HOST_DECODE_RATE_R7")
    r6 = next(p for p in PINS if p.name == "HOST_DECODE_RATE_R6")
    assert pin_value(r7) < pin_value(r6)
    assert r7.drift_note and "host_r7" in r7.drift_note


# -------------------------------------------------------------- trajectory
def test_trajectory_shape_and_provenance_marking():
    t = build_trajectory(REPO)
    assert schema.validate_trajectory(t) == []
    rounds = {r["pin"]: r for r in t["host_decode"]}
    assert set(rounds) == {p.name for p in PINS}
    r9 = rounds["HOST_DECODE_RATE_R9"]
    prov = [a for a in r9["artifacts"] if a["pin_provenance"]]
    assert len(prov) == 3
    assert min(a["value"] for a in prov) == pytest.approx(r9["value"])
    # controls in the same dir ride along unmarked
    assert any(not a["pin_provenance"] for a in r9["artifacts"])
    # host receipts only: a device number comes from a chip run, never
    # from a committed file the sentinel re-reads
    assert "device" not in t
    # deterministic: a second build is byte-identical (no timestamps)
    assert build_trajectory(REPO) == t


# --------------------------------------------------------------------- CLI
def test_cli_exit_codes(tmp_path):
    """One subprocess pass covering the CI contract: --check-committed
    exits 0; a degraded artifact exits 1."""
    degraded = tmp_path / "degraded.json"
    degraded.write_text(json.dumps(_degraded(0.9)))
    ok = subprocess.run(
        [sys.executable, SENTINEL, "--check-committed"],
        capture_output=True, text=True, timeout=120)
    assert ok.returncode == 0, ok.stdout + ok.stderr
    assert '"sentinel": "pass"' in ok.stdout
    bad = subprocess.run(
        [sys.executable, SENTINEL, "--check", str(degraded)],
        capture_output=True, text=True, timeout=120)
    assert bad.returncode == 1
    assert "REGRESSION" in bad.stdout
