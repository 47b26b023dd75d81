"""`moe_pct` for Ling-3.0-flash's cell: share of the traced window's device
self time under the expert layer's names (`moe_router`, `moe_dispatch`,
`moe_experts`, `moe_combine`, `moe_shared`: `chipbench/ling_lm_scopes.json`,
`moe`), forward and backward, the blocks' recomputed forward pass included.
The same code of the program as `moe_pct` and `hybrid_moe_pct` read in the
other two language cells (models/mistral4.py `ExpertShare`), with sigmoid
scoring under a group limit and 8 of 512 experts held. None where the
trace holds none of the names, or is another configuration's."""

from chipbench.layer_metrics import _hybrid_lm


def read(facts: dict):
    if "kda" not in facts.get("lm_names", {}):
        return None
    return _hybrid_lm.share(facts, "moe")
