"""Share of the traced window's device self time under Kimi Delta
Attention's names (`kda_qkv`, `kda_conv`, `kda_gates`, `kda_core`,
`kda_out`: `chipbench/ling_lm_scopes.json`, `kda`), forward and backward,
the blocks' recomputed forward pass included. None where the trace holds
none of them."""

from chipbench.layer_metrics import _hybrid_lm


def read(facts: dict):
    if "kda" not in facts.get("lm_names", {}):
        return None
    return _hybrid_lm.share(facts, "kda")
