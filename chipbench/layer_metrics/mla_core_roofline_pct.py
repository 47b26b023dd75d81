"""The causal attention core against its roofline: per layer the lower
triangle only (half of 4 x S^2 x heads x head size operations forward,
twice that backward; the scores the kernel makes again in its backward
pass and the block's recomputed forward pass are not counted), max of
operations / peak and bytes / memory rate, times the traced steps, over
the device time under `mla_core`."""

from chipbench.layer_metrics import _lm


def read(facts: dict):
    return _lm.roofline(facts, "mla_core", _lm.attention_core_step_ops)
