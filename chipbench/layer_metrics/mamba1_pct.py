"""Share of the traced window's device self time under the Mamba-1 mixer's
names in AI21-Jamba2-3B's cell (`mamba1_in`, `mamba1_conv`, `mamba1_xdt`,
`mamba1_scan`, `mamba1_gate_out`: `chipbench/jamba_lm_scopes.json`,
`mamba1`), forward and backward, the blocks' recomputed forward pass
included: thirteen of the cell's fourteen layers."""

from chipbench.layer_metrics import _jamba_lm


def read(facts: dict):
    return _jamba_lm.share(facts, "mamba1")
