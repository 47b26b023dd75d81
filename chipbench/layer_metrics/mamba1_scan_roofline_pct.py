"""Mamba-1's selective scan against its roofline, in AI21-Jamba2-3B's cell:
per Mamba layer and pass (forward, input gradient, weight gradient) the
recurrence's elementwise operations and, as bytes, x, dt, B and C in and y
out once with the state entering every chunk (`jamba_lm_counts.scan_ops`);
max of operations / peak and bytes / memory rate, times the traced steps,
over the device time under `mamba1_scan`. The recurrence has no matrix
product and `peaks.json` no vector-unit rate, so the bytes bound it here,
and the vector unit's own bound, which lies above, keeps the share below
100 % (PERF.md, section 3)."""

from chipbench.layer_metrics import _jamba_lm


def read(facts: dict):
    return _jamba_lm.roofline(facts, "mamba1_scan", "scan_step_ops")
