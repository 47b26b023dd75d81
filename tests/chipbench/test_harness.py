"""The harness is driven by data: names resolve to files, a new cell is a
new file, no chip means no result, and a broken timed path is not correct."""

import glob
import json
import os
import re
import shutil
import subprocess
import sys

import pytest

from _tiny import ROOT, context
from chipbench import run as harness

BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
CELLS = sorted(os.path.basename(p)[:-5] for p in glob.glob(
    os.path.join(ROOT, "chipbench", "workloads", "*.json")))


@pytest.mark.parametrize("cell_name", CELLS)
def test_cell_names_files_that_exist(cell_name):
    # a cell file may wait in workloads/ for a later PR's BENCHMARK.json
    # entry; one that is entered has to resolve all the way down
    if cell_name not in {w["name"] for w in BENCH["workloads"]}:
        pytest.skip("not entered in BENCHMARK.json")
    _, cell, config = harness.load_cell(ROOT, cell_name)
    for kind, name in (("drivers", cell["driver"]),
                       ("reference", config["reference"])):
        assert os.path.isfile(os.path.join(ROOT, "chipbench", kind,
                                           f"{name}.py")), (kind, name)
    known = {"loss_gap", "probe_grad_diff"} | {
        f"{name}{tail}" for name in ("first_grad_gap", "first_grad_diff",
                                     "change_gap") for tail in ("", "_median")}
    assert cell["limits"] and set(cell["limits"]) <= known
    assert all(limit > 0 for limit in cell["limits"].values())
    assert set(config["reduced"]) <= set(config) | set(config["recipe"])


def test_every_entered_cell_has_its_file():
    assert {w["name"] for w in BENCH["workloads"]} <= set(CELLS)
    assert {w["config"] for w in BENCH["workloads"]} \
        == {c["name"] for c in BENCH["configs"]}


def test_names_and_units_are_well_formed():
    metrics = BENCH["end_to_end"] + BENCH["per_layer"]
    names = [m["name"] for m in metrics] \
        + [w["name"] for w in BENCH["workloads"]] \
        + [w["traffic"] for w in BENCH["workloads"]] \
        + [c["name"] for c in BENCH["configs"]]
    assert all(NAME.match(n) for n in names), names
    assert len({m["name"] for m in metrics}) == len(metrics)
    assert all(UNIT.match(m["unit"]) for m in metrics)
    assert all(m["better"] in ("lower", "higher") for m in metrics)
    cells = {w["name"] for w in BENCH["workloads"]}
    ends = {m["name"] for m in BENCH["end_to_end"]}
    for m in BENCH["per_layer"]:
        assert m["moves"] in ends
        assert set(m.get("workloads", cells)) <= cells
        assert os.path.isfile(os.path.join(
            ROOT, "chipbench", "layer_metrics", f"{m['name']}.py"))
    assert "setup_s" in ends and 10 <= BENCH["run_seconds"] <= 51
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) <= 1


def test_without_a_chip_there_is_no_result():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    done = subprocess.run(
        [sys.executable, os.path.join(ROOT, "chipbench", "run.py"),
         "--workload", BENCH["workloads"][0]["name"], "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        env=env, capture_output=True, text=True, timeout=120)
    assert done.returncode != 0
    assert "metrics" not in done.stdout


def test_a_dropped_in_cell_is_found_and_runs(tmp_path):
    """A later PR adds a cell with one new file and one new entry: nothing
    that is there is edited. The new cell then runs, and is correct."""
    root = tmp_path / "root"
    shutil.copytree(os.path.join(ROOT, "chipbench"), root / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {p: open(p, "rb").read() for p in glob.glob(
        str(root / "chipbench" / "**" / "*.*"), recursive=True)}
    cell = json.load(open(root / "chipbench" / "workloads"
                          / "vggf_b1024_step.json"))
    cell["batch_per_chip"] = 512
    (root / "chipbench" / "workloads" / "vggf_b512_step.json").write_text(
        json.dumps(cell))
    bench = json.loads(json.dumps(BENCH))
    bench["workloads"].append({
        "name": "vggf_b512_step", "config": "vggf_imagenet",
        "traffic": "b512_step", "chips": 1, "why": "a later PR's cell"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    _, found, _ = harness.load_cell(str(root), "vggf_b512_step")
    assert found["batch_per_chip"] == 512 and found["driver"] == "train"
    assert all(open(p, "rb").read() == b for p, b in before.items())

    ctx = context("vggf_b512_step", tmp_path, root=str(root))
    line = harness.run_cell(ctx)
    assert line["correct"] is True, line["checks"]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert set(line["metrics"]) == {"train_images_per_s", "peak_hbm_gib",
                                    "setup_s"}
    assert list(line)[-1] == "checks"


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch"])
def test_a_broken_timed_path_is_not_correct(tmp_path, fault):
    """The rest of a run, with the trainer's step broken underneath."""
    line = harness.run_cell(context("vggf_b1024_step", tmp_path,
                                    fault=fault))
    assert line["correct"] is False
    failing = {k for k, c in line["checks"].items()
               if c["value"] > c["limit"]}
    assert failing & {"first_grad_gap", "first_grad_diff", "change_gap"}, \
        line["checks"]
