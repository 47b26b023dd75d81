"""The grouped-query attention core against its roofline, as
`mla_core_roofline_pct`: per attention layer the lower triangle only (half
of 4 x S^2 x query heads x head size operations forward, twice that
backward; nothing recomputed counted), with the bytes of the key and value
heads there are (2, not one a query head); max of operations / peak and
bytes / memory rate, times the traced steps, over the device time under
`gqa_core`."""

from chipbench.layer_metrics import _hybrid_lm


def read(facts: dict):
    return _hybrid_lm.roofline(facts, "gqa_core", "attention_core_step_ops")
