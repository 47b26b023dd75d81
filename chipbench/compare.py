"""The comparison that decides `correct` for a training cell.

Both sides give, for the first steps on one batch: each step's loss, the
norm of every leaf of the first gradient as the optimiser got it, and the
norm of every leaf's change over the steps; and the first gradient itself,
compared by the norm of the difference (`leaf_diffs`). A norm is compared
by the gap between the two sides' norms, measured against the reference's
norm of that leaf or of the median leaf, whichever is larger (some
gradients are all but zero), and a tree by its worst leaf. A leaf whose
reference gradient is under a thousandth of the median leaf's is left out
of the change: only round-off moves it. `probe_grad_diff` is the norm of
the difference at the one leaf a configuration names (`probe_leaf`: the
last layer's kernel, which the forward pass's rounding reaches in first
order and the backward pass's not at all), against that leaf's own norm.
"""

from __future__ import annotations

import statistics

import jax
import numpy as np

from chipbench.inputs import leaf_name


def _flat(tree, leaf=lambda x: float(np.asarray(x))) -> dict:
    return {leaf_name(path): leaf(x) for path, x in
            jax.tree_util.tree_leaves_with_path(tree)}


def _summary(per_leaf: dict) -> dict:
    """{"worst": (gap, leaf), "median": (gap, "")} of per-leaf gaps; a NaN
    anywhere is the worst."""
    worst, where = 0.0, ""
    for name, gap in per_leaf.items():
        if not gap <= worst:
            worst, where = gap, name
    return {"worst": (worst, where),
            "median": (statistics.median(per_leaf.values()), "")}


def leaf_gaps(got, want, skip=()) -> dict:
    """Per leaf, the gap between two trees of norms, against the
    reference's norm of that leaf or of the median leaf."""
    got, want = _flat(got), _flat(want)
    if set(got) != set(want):
        raise ValueError(f"trees differ: {sorted(set(got) ^ set(want))[:4]}")
    floor = statistics.median(want.values())
    return {name: abs(got[name] - ref) / max(ref, floor, 1e-30)
            for name, ref in want.items() if name not in skip}


def leaf_diffs(got, want) -> dict:
    """Per leaf, the norm of the difference of two trees of arrays, against
    the reference's norm of that leaf or of the median leaf. Unlike a gap
    of norms, which rounding moves only in second order, this moves in
    first order with the precision a side computes in: it is the number
    that tells bf16 from fp8."""
    as_array = lambda x: np.asarray(x, np.float32)
    got, want = _flat(got, as_array), _flat(want, as_array)
    norm = lambda x: float(np.sqrt(np.sum(np.square(x, dtype=np.float64))))
    norms = {name: norm(x) for name, x in want.items()}
    floor = statistics.median(norms.values())
    return {name: norm(got[name] - ref) / max(norms[name], floor, 1e-30)
            for name, ref in want.items()}


def training_gaps(got: dict, want: dict, probe: str | None = None) -> dict:
    """`got`, `want`: {"losses", "grad_norms", "change_norms",
    "first_grad"} of the program and of the reference. Returns
    name -> (gap, worst leaf): each tree by its worst leaf and, under
    `<name>_median`, by its median leaf; `probe_grad_diff` at the leaf
    `probe` names."""
    out = {}
    for i, (a, b) in enumerate(zip(got["losses"], want["losses"])):
        a, b = float(a), float(b)
        out[f"loss_gap_step{i + 1}"] = (abs(a - b) / abs(b), "")
    grads = _flat(want["grad_norms"])
    floor = statistics.median(grads.values())
    still = {name for name, g in grads.items() if g < 1e-3 * floor}
    trees = {"first_grad_gap": leaf_gaps(got["grad_norms"],
                                         want["grad_norms"]),
             "change_gap": leaf_gaps(got["change_norms"],
                                     want["change_norms"], skip=still)}
    if "first_grad" in got and "first_grad" in want:
        trees["first_grad_diff"] = leaf_diffs(got["first_grad"],
                                              want["first_grad"])
    for name, per_leaf in trees.items():
        summary = _summary(per_leaf)
        out[name] = summary["worst"]
        out[f"{name}_median"] = summary["median"]
    if probe is not None and "first_grad_diff" in trees:
        a, b = (_flat(side["first_grad"], lambda x: np.asarray(
            x, np.float64))[probe] for side in (got, want))
        out["probe_grad_diff"] = (float(np.linalg.norm(a - b)
                                        / max(np.linalg.norm(b), 1e-30)),
                                  probe)
    return out


def judge(gaps: dict, limits: dict) -> list:
    """One check for every gap the cell's `limits` names (`loss_gap` holds
    for each step's loss); a gap it does not name is not compared. A cell
    that names none is not correct."""
    checks = []
    for name, (value, where) in gaps.items():
        key = "loss_gap" if name.startswith("loss_gap") else name
        if key in limits:
            checks.append({"name": name, "value": value,
                           "limit": limits[key],
                           "ok": bool(value <= limits[key]), "where": where})
    if not checks:
        checks.append({"name": "numbers_compared", "value": 0, "limit": 1,
                       "ok": False, "where": ""})
    return checks
