"""The step's convolutions and matrix products against their roofline:
the sum over them of max(operations / peak, bytes / memory rate), from
shapes with two bytes an element, times the traced steps, over the device
time of the trace events that implement them, summed over chips.

An event counts as implementing them when `trace_reduce.category` calls
it `matmul` (a convolution or dot, or a fusion built on one: the program's
own banded matrix products for LRN are among these, and are no operation
the step needs) or cannot tell what it is: what cannot be told apart is
counted in, so the share can read low and never high. The numerator
counts what the plain reference needs, each convolution once and none
rematerialised; the denominator every such event that ran."""

from chipbench import counts
from chipbench.layer_metrics import _step_ops


def implements_matmul(category: str) -> bool:
    return category in ("", "matmul")


def read(facts: dict):
    trace, traced = facts.get("trace"), facts.get("traced")
    if not trace or not traced:
        return None
    spent = sum(s for c, s in trace["categories"].items()
                if implements_matmul(c))
    if spent <= 0:
        return None
    least = counts.roofline_seconds(_step_ops.of(facts),
                                    counts.peaks(facts["device_kind"]))
    facts["roofline"] = least
    return 100.0 * least["seconds"] * traced["steps"] / spent
