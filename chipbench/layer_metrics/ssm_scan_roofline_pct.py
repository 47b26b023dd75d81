"""The state-space recurrence against its roofline: per Mamba layer and
pass (forward, input gradient, weight gradient) the chunked form's products
at the configuration's chunk size (`hybrid_lm_counts.scan_ops`: C B^T a
group, the masked product with x, the chunk's state and its read-out a
head) and, as bytes, x, B, C and dt in and y out, once; max of operations /
peak and bytes / memory rate, times the traced steps, over the device time
under `ssm_scan`. That time holds everything the program does there (the
decays, their running sums and exponentials, the chunk x chunk decay
product written to and read from HBM, the block's recomputed forward
pass), so the share reads what the recurrence gets of the chip, and an
implementation that materialises the decay product shows here."""

from chipbench.layer_metrics import _hybrid_lm


def read(facts: dict):
    return _hybrid_lm.roofline(facts, "ssm_scan", "scan_step_ops")
