"""The delta-rule recurrence against its roofline: per KDA layer and pass
(forward, input gradient, weight gradient) the chunked WY form's products at
chunks of 64 (`ling_lm_counts.kda_core_ops`: the key scores and the
read-out's scores, the solve, W and U, the two products with the entering
state, the read-out and the chunk's state, a head) and, as bytes, q, k, v,
g and beta in and o out, once; max of operations / peak and bytes / memory
rate, times the traced steps, over the device time under `kda_core`. That
time holds everything the program does there (the decays, their running
sums and exponentials, the decayed copies of q and k written to and read
from HBM, the scan over the chunks, the block's recomputed forward pass
and the groups of chunks made again inside it), so the share reads what
the recurrence gets of the chip: what a kernel that keeps a chunk on the
chip would start from."""

from chipbench.layer_metrics import _hybrid_lm


def read(facts: dict):
    if "kda" not in facts.get("lm_names", {}):
        return None
    return _hybrid_lm.roofline(facts, "kda_core", "kda_core_step_ops")
