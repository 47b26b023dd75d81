"""Share of the traced window's device self time under the expert layer's
names in Solar-Open2's cell (`moe_router`, `moe_dispatch`, `moe_experts`,
`moe_combine`, `moe_shared`: `chipbench/solar_lm_scopes.json`, `moe`):
the router over 320 experts, the dispatch, the 8 held experts and the
shared one, in all four layers, forward and backward, the blocks'
recomputed forward pass included."""

from chipbench.layer_metrics import _solar_lm


def read(facts: dict):
    return _solar_lm.share(facts, "moe")
