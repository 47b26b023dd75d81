"""At a tiny size on the CPU: the plain references agree with the
program's own first steps (loss, first gradient, change of the weights),
and the control, the reference computed in fp8, and every planted fault do
not: judged by the limits as a run judges."""

import json

import pytest

from _tiny import LIMITS, tiny
from chipbench import limits


@pytest.mark.parametrize("cell_name", ["vggf_b1024_step",
                                       "resnet50_b256_step"])
def test_program_agrees_and_the_control_does_not(cell_name):
    _, cell, config = tiny(cell_name)
    rows = []
    out = limits.readings(cell, config, seeds=[11], controls=1,
                          emit=lambda line: rows.append(json.loads(line)))
    worst = out["worst"]
    for name, limit in LIMITS.items():
        assert worst["program"][name][1] <= limit, (name, worst["program"])
    assert out["correct"] == {
        "program": [1, 1], "control_fp8": [0, 1],
        "fault_half_batch": [0, 1], "fault_state_unchanged": [0, 1]}
    assert worst["fault_state_unchanged"]["change_gap"][0] == 1.0
    assert {r["side"]: r["correct"] for r in rows if "side" in r} == {
        "program": True, "control_fp8": False, "fault_half_batch": False,
        "fault_state_unchanged": False}
