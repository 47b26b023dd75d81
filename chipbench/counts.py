"""Operations and bytes of a traced computation, from shapes alone.

Copied from the program's `utils/flops.py` (`walk_matmul_eqns`,
`jaxpr_flops`) and `utils/mxu_model.py` (`views_from_jaxpr`) so that no
later PR can move the yardstick; `tests/chipbench/test_counts.py` pins the
copy to the original. Counted: `conv_general_dilated` and `dot_general`,
2 x output elements x contraction length each. Elementwise work, pooling,
normalisation, the optimiser's update and anything recomputed are not.

`peaks()` reads `peaks.json`: a `device_kind` that is not listed is an
error, never a default.
"""

from __future__ import annotations

import json
import math
import os

import jax
from jax.extend import core as jex_core

_HERE = os.path.dirname(os.path.abspath(__file__))


def peaks(device_kind: str) -> dict:
    with open(os.path.join(_HERE, "peaks.json")) as f:
        table = json.load(f)["device_kinds"]
    if device_kind not in table:
        raise KeyError(f"device_kind {device_kind!r} is not in peaks.json "
                       f"(known: {sorted(table)})")
    return table[device_kind]


def _sub_jaxprs(params: dict) -> list:
    subs = []
    for v in params.values():
        for item in (v if isinstance(v, (list, tuple)) else [v]):
            if isinstance(item, jex_core.ClosedJaxpr):
                subs.append(item.jaxpr)
            elif isinstance(item, jex_core.Jaxpr):
                subs.append(item)
    return subs


def _op(eqn) -> tuple:
    """(kind, flops, elements moved) of one conv or dot equation."""
    out = eqn.outvars[0].aval
    lhs, rhs = eqn.invars[0].aval, eqn.invars[1].aval
    if eqn.primitive.name == "conv_general_dilated":
        dn = eqn.params["dimension_numbers"]
        taps = math.prod(rhs.shape[d] for d in dn.rhs_spec[2:])
        flops = 2.0 * math.prod(out.shape) * taps * rhs.shape[dn.rhs_spec[1]]
        # the input gradient of a strided convolution is written as one
        # over an input dilated with zeros: products with those are no work
        flops /= math.prod(eqn.params.get("lhs_dilation") or (1,))
        kind = "conv"
    else:
        (lc, _), (lb, _) = eqn.params["dimension_numbers"]
        k = math.prod(lhs.shape[d] for d in lc)
        flops = 2.0 * math.prod(out.shape) * k
        kind = "dot"
    return kind, flops, float(lhs.size + rhs.size + out.size)


def walk(jaxpr, visit, mult: float = 1.0) -> None:
    """`visit(eqn, mult)` for every conv and dot; scan multiplies by its
    trip count, cond takes the widest branch, shard_map the mesh size."""
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name in ("conv_general_dilated", "dot_general"):
            visit(eqn, mult)
        elif name == "scan":
            for sub in _sub_jaxprs(eqn.params):
                walk(sub, visit, mult * float(eqn.params.get("length", 1)))
        elif name == "cond":
            branches = eqn.params.get("branches", [])
            if branches:
                walk(max(branches, key=lambda b: _flops(b.jaxpr)).jaxpr,
                     visit, mult)
        elif name == "shard_map":
            size = float(getattr(eqn.params.get("mesh"), "size", 1) or 1)
            for sub in _sub_jaxprs(eqn.params):
                walk(sub, visit, mult * size)
        else:
            for sub in _sub_jaxprs(eqn.params):
                walk(sub, visit, mult)


def _flops(jaxpr) -> float:
    return sum(op["flops"] for op in _ops(jaxpr))


def _ops(jaxpr) -> list:
    found = []

    def visit(eqn, mult):
        kind, flops, elements = _op(eqn)
        found.append({"kind": kind, "flops": mult * flops,
                      "elements": mult * elements})

    walk(jaxpr, visit)
    return found


def jaxpr_ops(fn, *args) -> list:
    """Every conv and dot of `fn(*args)` (arrays or ShapeDtypeStructs):
    `[{"kind", "flops", "elements"}]`, nothing compiled."""
    return _ops(jax.make_jaxpr(fn)(*args).jaxpr)


def jaxpr_flops(fn, *args) -> float:
    return sum(op["flops"] for op in jaxpr_ops(fn, *args))


def roofline_seconds(ops: list, peak: dict, bytes_per_element: int = 2
                     ) -> dict:
    """The least time the chip could take over `ops`, each op by the larger
    of its operations over the peak rate and its bytes over the memory
    rate; and how much of that sum each of the two bounds."""
    by_flops = by_bytes = 0.0
    for op in ops:
        t_f = op["flops"] / peak["bf16_flops_per_s"]
        t_b = op["elements"] * bytes_per_element / peak["hbm_bytes_per_s"]
        if t_f >= t_b:
            by_flops += t_f
        else:
            by_bytes += t_b
    return {"seconds": by_flops + by_bytes, "compute_bound_s": by_flops,
            "memory_bound_s": by_bytes}
