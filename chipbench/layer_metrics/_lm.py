"""What the language-model cell's four readers share: the trace by the
cell's own names (`facts["scopes"]`, which the driver made with
`chipbench/lm_scopes.json`; None on a trace without the declared names,
and every reader then returns None, never 0) and the kernels' least times
from `chipbench/lm_counts.py`."""

from chipbench import counts, lm_counts, scope_reduce
from chipbench.drivers import lm_train


def share(facts: dict, group: str):
    """Share of the traced device self time under the names the cell's
    file lists as `group`, forward and backward."""
    if "lm" not in facts:
        return None
    table, scopes = scope_reduce.of(facts), lm_train.names()[group]
    if table is None or not set(scopes) & set(table["scopes"]):
        return None
    return scope_reduce.share_pct(table, scopes)


def roofline(facts: dict, group: str, ops_of) -> float | None:
    """100 x the least time of `ops_of(lm)` (one step's products) x traced
    steps / the device time under `group`."""
    table = scope_reduce.of(facts) if "lm" in facts else None
    traced = facts.get("traced")
    if table is None or not traced:
        return None
    spent = sum(sum(table["scopes"].get(s, {}).values())
                for s in lm_train.names()[group])
    if spent <= 0:
        return None
    least = counts.roofline_seconds(ops_of(facts["lm"]),
                                    counts.peaks(facts["device_kind"]))
    return 100.0 * least["seconds"] * traced["steps"] / spent


def expert_step_ops(lm: dict) -> list:
    return [op for held in lm["assignments_held"]
            for op in lm_counts.expert_ops(lm["arch"], lm["experts_held"],
                                           held)
            for _ in range(lm_counts.PASSES)]


def attention_core_step_ops(lm: dict) -> list:
    return [op for _ in range(lm["layers"])
            for op in lm_counts.attention_core_ops(
                lm["arch"], lm["seq_len"], lm["rows"])
            for _ in range(lm_counts.PASSES)]
