"""Share of the traced window's device self time under the Mamba-2 mixer's
names (`ssm_in`, `ssm_conv`, `ssm_scan`, `ssm_gate_out`:
`chipbench/hybrid_lm_scopes.json`, `ssm`), forward and backward, the
blocks' recomputed forward pass included. None where the trace holds none
of them."""

from chipbench.layer_metrics import _hybrid_lm


def read(facts: dict):
    return _hybrid_lm.share(facts, "ssm")
