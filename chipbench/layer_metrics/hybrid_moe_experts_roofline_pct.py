"""`moe_experts_roofline_pct` for the hybrid language-model cell: the
routed experts' grouped products against their roofline, per expert layer
and pass the TWO products' (up, down) max(operations / peak, bytes / memory
rate), with 2 x hidden x width operations for each assignment the share
holds (from the reference's routing of the seed's batch) and, as bytes, the
held experts' bf16 weights once and the rows in and out; times the traced
steps, over the device time under `moe_experts` (which holds the casts of
the weights and the recomputed forward pass too)."""

from chipbench.layer_metrics import _hybrid_lm


def read(facts: dict):
    return _hybrid_lm.roofline(facts, "moe_experts", "expert_step_ops")
