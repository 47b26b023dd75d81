"""Share of the traced window's device self time under Kimi Delta
Attention's names in Solar-Open2's cell (`kda_qkv`, `kda_conv`,
`kda_gates`, `kda_core`, `kda_out`: `chipbench/solar_lm_scopes.json`,
`kda`), forward and backward, the blocks' recomputed forward pass
included: three of the cell's four layers, under the unbounded gate."""

from chipbench.layer_metrics import _solar_lm


def read(facts: dict):
    return _solar_lm.share(facts, "kda")
