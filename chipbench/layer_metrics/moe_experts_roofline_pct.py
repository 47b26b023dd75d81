"""The routed experts' grouped products against their roofline: per layer
and pass (forward, input gradient, weight gradient) the three products'
max(operations / peak, bytes / memory rate), with 2 x hidden x width
operations for each assignment the share holds (counted from the
reference's routing of the seed's batch) and, as bytes, the held experts'
bf16 weights once and the rows in and out; times the traced steps, over
the device time under `moe_experts`. That time holds what the program does
there beyond the products (the float32-to-bf16 casts of the weights, the
recomputed forward pass), so the share reads what the layer gets of the
chip, not the kernel alone."""

from chipbench.layer_metrics import _lm


def read(facts: dict):
    return _lm.roofline(facts, "moe_experts", _lm.expert_step_ops)
