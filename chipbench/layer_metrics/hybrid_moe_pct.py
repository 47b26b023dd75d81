"""`moe_pct` for the hybrid language-model cell: share of the traced
window's device self time under the expert layer's names (`moe_router`,
`moe_dispatch`, `moe_experts`, `moe_combine`, `moe_shared`:
`chipbench/hybrid_lm_scopes.json`, `moe`), forward and backward, the
blocks' recomputed forward pass included. The same code of the program as
`moe_pct` reads in the Mistral cell (models/mistral4.py `ExpertShare`),
with sigmoid scoring and two-matrix relu^2 experts. None where the trace
holds none of the names."""

from chipbench.layer_metrics import _hybrid_lm


def read(facts: dict):
    return _hybrid_lm.share(facts, "moe")
