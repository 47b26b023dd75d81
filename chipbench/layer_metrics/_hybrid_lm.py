"""What the hybrid language-model cell's five readers share
(`layer_metrics/_lm.py`'s scheme for `drivers/hybrid_lm_train.py`): the
trace by the configuration's own names (`facts["scopes"]`, reduced with
the names file the configuration states, which the driver hands on as
`facts["lm_names"]`) and the kernels' least times from the counts module it
states (`facts["lm_counts"]`). On another driver's facts, an untraced run or
a trace without the names, every reader returns None, never 0."""

import importlib

from chipbench import counts, scope_reduce


def _table(facts: dict):
    if "lm_names" not in facts:
        return None
    return scope_reduce.of(facts)


def share(facts: dict, group: str):
    """Share of the traced device self time under the names the
    configuration's file lists as `group`, forward and backward."""
    table = _table(facts)
    if table is None:
        return None
    scopes = facts["lm_names"][group]
    if not set(scopes) & set(table["scopes"]):
        return None
    return scope_reduce.share_pct(table, scopes)


def roofline(facts: dict, group: str, ops_of: str):
    """100 x the least time of one step's `ops_of(lm)` (a function of the
    configuration's counts module) x traced steps / the device time under
    `group`."""
    table, traced = _table(facts), facts.get("traced")
    if table is None or not traced:
        return None
    spent = sum(sum(table["scopes"].get(s, {}).values())
                for s in facts["lm_names"][group])
    if spent <= 0:
        return None
    ops = getattr(importlib.import_module(f"chipbench.{facts['lm_counts']}"),
                  ops_of)(facts["lm"])
    least = counts.roofline_seconds(ops, counts.peaks(facts["device_kind"]))
    return 100.0 * least["seconds"] * traced["steps"] / spent
