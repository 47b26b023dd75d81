"""Seconds JAX spent tracing functions to jaxprs and lowering them to
MLIR before the window's first step: the union of the program's
`compile:trace:*` (1 ms and more) and `compile:lower:*` spans up to the
cut. Paid warm and cold alike: the persistent cache is asked only after
both. None where the ring cannot say (`_startup.py`)."""

from chipbench.layer_metrics import _startup


def read(facts: dict):
    return _startup.read(facts, "setup_trace_lower_s")
