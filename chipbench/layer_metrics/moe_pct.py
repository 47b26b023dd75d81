"""Share of the traced window's device self time under the expert layer's
names (`moe_router`, `moe_dispatch`, `moe_experts`, `moe_combine`,
`moe_shared`: `chipbench/lm_scopes.json`, `moe`), forward and backward, the
blocks' recomputed forward pass included. None where the trace holds none
of them."""

from chipbench.layer_metrics import _lm


def read(facts: dict):
    return _lm.share(facts, "moe")
