"""The delta-rule recurrence under Kimi Linear's unbounded (softplus) gate
against its roofline, in Solar-Open2's cell: per KDA layer and pass
(forward, input gradient, weight gradient) the chunked WY form's products
at chunks of 64 and, as bytes, q, k, v, g and beta in and o out once
(`solar_lm_counts.kda_core_ops`, which is `ling_lm_counts.kda_core_ops`
at the linear layers' 64 heads of 128: the same work whatever implements
it, so the diagonal sub-blocks made pair by pair count as what the
implementation pays); max of operations / peak and bytes / memory rate,
times the traced steps, over the device time under `kda_core`."""

from chipbench.layer_metrics import _solar_lm


def read(facts: dict):
    return _solar_lm.roofline(facts, "kda_core", "kda_core_step_ops")
