"""The latent attention core at two head sizes against its roofline, as
`mla_core_roofline_pct`: per latent layer the lower triangle only (q k^T at
nope + rope = 192, p v at 128: half of 2 x S^2 x heads x (192 + 128)
operations forward, twice that backward; nothing recomputed counted), q, k,
v in and o out as bytes; max of operations / peak and bytes / memory rate,
times the traced steps, over the device time under `mla_core`. The counts
are of the true 192: a kernel that pads the keys to 256 lanes shows the
padding here."""

from chipbench.layer_metrics import _hybrid_lm


def read(facts: dict):
    if "kda" not in facts.get("lm_names", {}):
        return None
    return _hybrid_lm.roofline(facts, "mla_core", "attention_core_step_ops")
