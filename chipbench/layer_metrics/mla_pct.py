"""Share of the traced window's device self time under the latent
attention's names (`mla_q`, `mla_kv`, `mla_core`, `mla_out`:
`chipbench/lm_scopes.json`, `mla`), forward and backward, the blocks'
recomputed forward pass included. None where the trace holds none of them."""

from chipbench.layer_metrics import _lm


def read(facts: dict):
    return _lm.share(facts, "mla")
