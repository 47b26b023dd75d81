"""The language-model cell through the harness, at a tiny size on the CPU:
it resolves by name, its timed path agrees with the plain reference, a
broken timed path does not, and its four readers read a hand-made table
(and give None, never 0, on a trace without the declared names)."""

import json
import os

import pytest

from _tiny_lm import CELL, ROOT, context, tiny
from chipbench import run as harness
from chipbench.drivers import lm_train
from chipbench.layer_metrics import (mla_core_roofline_pct, mla_pct,
                                     moe_experts_roofline_pct, moe_pct)

READERS = {"mla_pct": mla_pct, "moe_pct": moe_pct,
           "moe_experts_roofline_pct": moe_experts_roofline_pct,
           "mla_core_roofline_pct": mla_core_roofline_pct}


def test_the_cell_resolves_and_states_its_cut():
    bench, cell, config = harness.load_cell(ROOT, CELL)
    assert cell["driver"] == "lm_train" and cell["chips"] == 1
    assert config["reference"] == "mistral4"
    assert os.path.isfile(os.path.join(ROOT, "chipbench", "reference",
                                       "lm_step.py"))
    assert config["reduced"] == ["num_hidden_layers", "n_routed_experts",
                                 "vocab_size"]
    assert config["published"] == {"num_hidden_layers": 36,
                                   "n_routed_experts": 128,
                                   "vocab_size": 131072}
    assert (config["num_hidden_layers"], config["n_routed_experts"],
            config["vocab_size"]) == (4, 8, 16384)
    assert config["probe_leaf"] in config["probe_leaves"]
    # the four metrics this cell brings are restricted to it
    mine = [m for m in bench["per_layer"] if m.get("workloads") == [CELL]]
    assert {m["name"] for m in mine} == set(READERS)
    # the preset states the file's numbers, at every published width
    from distributed_vgg_f_tpu.config import get_config
    cfg = get_config(config["preset"])
    recipe = lm_train.recipe_of(cfg, config)
    assert recipe["seq_len"] == 4096 and recipe["global_batch"] == 1
    assert lm_train.arch_of(config)["n_routed_experts"] == 128


def test_the_timed_path_agrees_with_the_reference(tmp_path):
    line = harness.run_cell(context(tmp_path))
    assert line["correct"] is True, line["checks"]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert set(line["metrics"]) == {"train_images_per_s", "peak_hbm_gib",
                                    "setup_s"}
    checks = line["checks"]
    assert checks["dropped_assignments"]["value"] == 0
    assert checks["expert_load_diff"]["value"] == 0     # float32: no flip
    assert checks["compiles_in_window"]["value"] == 0
    assert {"loss_gap_step3", "first_grad_gap", "change_gap",
            "first_grad_diff", "probe_grad_diff"} <= set(checks)


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch"])
def test_a_broken_timed_path_is_not_correct(tmp_path, fault):
    line = harness.run_cell(context(tmp_path, fault=fault))
    assert line["correct"] is False
    failing = {k for k, c in line["checks"].items()
               if c["value"] > c["limit"]}
    assert failing & {"first_grad_gap", "first_grad_diff", "change_gap",
                      "probe_grad_diff"}, line["checks"]


def _facts(scopes: dict) -> dict:
    """Facts as the driver hands them, with a hand-made table: 10 traced
    steps, 1 s of device self time."""
    _, _, config = harness.load_cell(ROOT, CELL)
    lm = {"arch": lm_train.arch_of(config), "layers": 4, "vocab_rows": 16384,
          "experts_held": 8, "seq_len": 4096, "rows": 1,
          "assignments_held": [1024.0] * 4}
    table = {"total_s": 1.0, "phases_found": ["loss"], "scopes": {
        name: {"forward": t / 4, "backward": 3 * t / 4}
        for name, t in scopes.items()}}
    return {"lm": lm, "scopes": table, "traced": {"steps": 10},
            "device_kind": "TPU v5 lite", "chips": 1}


def test_readers_on_a_hand_made_table():
    facts = _facts({"mla_q": 0.02, "mla_kv": 0.01, "mla_core": 0.1,
                    "mla_out": 0.03, "moe_router": 0.01,
                    "moe_dispatch": 0.04, "moe_experts": 0.2,
                    "moe_combine": 0.05, "moe_shared": 0.1, "loss": 0.44})
    assert mla_pct.read(facts) == pytest.approx(16.0)
    assert moe_pct.read(facts) == pytest.approx(40.0)
    # the core: 3 passes x 4 layers x 4 S^2 heads 128 / 2 at 197 TFLOP/s
    core_s = 3 * 4 * (4 * 4096 ** 2 * 32 * 128 / 2) / 197e12
    assert mla_core_roofline_pct.read(facts) == pytest.approx(
        100 * core_s * 10 / 0.1, rel=1e-6)
    # the experts at 1024 assignments: bound by their weights' bytes
    product_s = 2 * (8 * 4096 * 2048 + 1024 * (4096 + 2048)) / 819e9
    assert product_s > 2 * 1024 * 4096 * 2048 / 197e12
    assert moe_experts_roofline_pct.read(facts) == pytest.approx(
        100 * (3 * 4 * 3 * product_s) * 10 / 0.2, rel=1e-6)
    assert all(0 < reader.read(facts) < 100 for reader in READERS.values())


@pytest.mark.parametrize("name", sorted(READERS))
def test_readers_give_none_without_the_declared_names(name):
    """Another cell's facts; an untraced run; a trace with none of the
    names: None, never 0."""
    reader = READERS[name]
    assert reader.read({"trace_dir": None}) is None
    untraced = {**_facts({"loss": 1.0}), "scopes": None, "traced": None}
    assert reader.read(untraced) is None
    assert reader.read(_facts({"loss": 0.5, "conv1": 0.5})) is None


def test_the_names_file_declares_what_the_readers_sum():
    from distributed_vgg_f_tpu import scopes
    names = lm_train.names()
    # the benchmark's own copy, held to the program's declared list here
    assert set(names["layers"]) == set(scopes.LM_LAYERS) | {"embed_tokens"}
    assert set(names["mla"]) | set(names["moe"]) <= set(names["layers"])
    assert {"loss", "optimizer", "step_metrics"} <= set(names["phases"])
    with open(os.path.join(ROOT, "chipbench", "scopes.json")) as f:
        assert json.load(f)["phases"] == names["phases"]


def test_limits_readings_at_a_tiny_size():
    """`lm_limits.readings`: the program agrees, the fp8 control and both
    faults do not, judged by the limits as a run judges."""
    from chipbench import lm_limits
    _, cell, config = tiny()
    rows = []
    out = lm_limits.readings(cell, config, seeds=[11], controls=1,
                             emit=lambda line, **kw: rows.append(
                                 json.loads(line)))
    assert out["correct"] == {
        "program": [1, 1], "control_fp8": [0, 1],
        "fault_half_batch": [0, 1], "fault_state_unchanged": [0, 1]}
    assert out["worst"]["fault_state_unchanged"]["change_gap"][0] == 1.0
    assert "summary_min_max" in rows[-1]
