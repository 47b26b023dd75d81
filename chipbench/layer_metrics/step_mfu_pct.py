"""The whole step's share of the chip's peak: the operations the forward
and backward passes need (convolutions and matrix products of the plain
reference's loss gradient, counted from shapes; the update, augmentation
and anything recomputed not counted) times the steps of the traced
sub-window, over that window's length in the trace (`window_s`: the
driver's span around the traced steps, synced at both ends), over chips
times the peak of the `device_kind`."""

from chipbench import counts
from chipbench.layer_metrics import _step_ops


def read(facts: dict):
    trace, traced = facts.get("trace"), facts.get("traced")
    if not trace or not traced or trace["window_s"] <= 0:
        return None
    flops = sum(op["flops"] for op in _step_ops.of(facts))
    peak = counts.peaks(facts["device_kind"])["bf16_flops_per_s"]
    return 100.0 * flops * traced["steps"] / trace["window_s"] \
        / (facts["chips"] * peak)
