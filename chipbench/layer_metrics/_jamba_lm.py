"""What AI21-Jamba2-3B's two readers share: `_hybrid_lm`'s share and
roofline, asked only of a run whose configuration states Jamba's counts
(`facts["lm_counts"]`, which the driver hands on from the file); on
another configuration's facts, an untraced run or a trace without the
names, None, never 0."""

from chipbench.layer_metrics import _hybrid_lm

COUNTS = "jamba_lm_counts"


def share(facts: dict, group: str):
    if facts.get("lm_counts") != COUNTS:
        return None
    return _hybrid_lm.share(facts, group)


def roofline(facts: dict, group: str, ops_of: str):
    if facts.get("lm_counts") != COUNTS:
        return None
    return _hybrid_lm.roofline(facts, group, ops_of)
