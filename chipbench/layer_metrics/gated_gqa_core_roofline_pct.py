"""The flash attention core of Solar-Open2's gated grouped-query layer
against its roofline: 64 query heads on 8 key/value heads of 128 over
4,096 tokens, the lower triangle only (half of 4 x S^2 x query heads x
head size operations forward, twice that backward; nothing recomputed
counted), the bytes of the key and value heads there are
(`solar_lm_counts.attention_core_ops`); max of operations / peak and
bytes / memory rate, times the traced steps, over the device time under
`gqa_core` (the gate is `gqa_gate`'s, not the core's)."""

from chipbench.layer_metrics import _solar_lm


def read(facts: dict):
    return _solar_lm.roofline(facts, "gqa_core", "attention_core_step_ops")
