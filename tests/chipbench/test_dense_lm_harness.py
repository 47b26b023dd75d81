"""A language model with no routed experts through the hybrid driver, at a
tiny size on the CPU: its timed path agrees with the plain reference and
its line carries no routing check, each planted fault is flagged (the
delta rule's own among them), and the limits script tells the program from
the control and the faults with no `expert_load_diff` among its readings."""

import json

import pytest

from _tiny_dense_lm import PATTERN, context, expert_free_program, tiny
from chipbench import run as harness
from chipbench.drivers import hybrid_lm_train as driver

ROUTING = {"expert_load_diff", "dropped_assignments"}


def test_the_configuration_holds_no_expert():
    _, cell, config = tiny()
    assert driver.expert_layers(config) == []
    assert "n_routed_experts" not in driver.arch_of(config)
    assert "first_expert" not in config["recipe"]
    assert "load_diff_limit" not in cell
    assert config["hybrid_override_pattern"] == PATTERN


def test_the_timed_path_agrees_with_the_reference(tmp_path):
    with expert_free_program():
        line = harness.run_cell(context(tmp_path))
    assert line["correct"] is True, line["checks"]
    assert line["attempted"] > 0 and line["failed"] == 0
    checks = line["checks"]
    assert not ROUTING & set(checks), checks
    assert checks["compiles_in_window"]["value"] == 0
    assert {"loss_gap_step3", "first_grad_gap", "change_gap",
            "first_grad_diff", "probe_grad_diff"} <= set(checks)


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch",
                                   "chunk_reset"])
def test_a_broken_timed_path_is_not_correct(tmp_path, fault):
    with expert_free_program():
        line = harness.run_cell(context(tmp_path, fault=fault))
    assert line["correct"] is False
    assert not ROUTING & set(line["checks"])
    failing = {k for k, c in line["checks"].items()
               if c["value"] > c["limit"]}
    assert failing & {"first_grad_gap", "first_grad_diff", "change_gap",
                      "probe_grad_diff"}, line["checks"]


def test_limits_readings_at_a_tiny_size():
    """`hybrid_lm_limits.readings`: the program agrees; the fp8 control and
    the three faults do not; no row reads `expert_load_diff`."""
    from chipbench import hybrid_lm_limits
    _, cell, config = tiny()
    rows = []
    with expert_free_program():
        out = hybrid_lm_limits.readings(
            cell, config, seeds=[11], controls=1,
            emit=lambda line, **kw: rows.append(json.loads(line)))
    assert out["correct"] == {
        "program": [1, 1], "control_fp8": [0, 1],
        "fault_half_batch": [0, 1], "fault_state_unchanged": [0, 1],
        "fault_chunk_reset": [0, 1]}
    assert out["worst"]["fault_state_unchanged"]["change_gap"][0] == 1.0
    assert all("expert_load_diff" not in row for row in rows[:-1])
    assert all("expert_load_diff" not in worst
               for worst in out["worst"].values())
    assert rows[-1]["load_diff_limit"] is None
