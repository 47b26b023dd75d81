"""Share of the traced window's device self time spent in VGG-F's local
response normalisations and max pools, forward and backward: the scopes
`chipbench/scopes.json` lists under `lrn_pool`. A fusion is its root's, so
a pool's select-and-scatter that XLA fused into the LRN's banded matrix
product counts under the LRN: the sum is what the metric reads. None where
the trace holds none of the declared phases."""

from chipbench import scope_reduce


def read(facts: dict):
    return scope_reduce.read_share(facts, scope_reduce.declared()["lrn_pool"])
