"""Seconds of set-up the program cannot name yet: from the process's start
(the gauge `startup/process_start_ns`) to the cut, less the union of every
`startup`, `compile` and `dispatch` span in between. What is left is the
interpreter's start, the harness's own imports and `jax.devices()`, the
seed's state and batch, and the waits for the first steps' device time.
None where the ring cannot say (`_startup.py`)."""

from chipbench.layer_metrics import _startup


def read(facts: dict):
    return _startup.read(facts, "setup_unspanned_s")
