"""Seconds in the last stage of a compile before the window's first step:
the union of the program's `compile:backend:*` (XLA compiled) and
`compile:cache_read:*` (the persistent cache held the executable) spans up
to the cut. None where the ring cannot say (`_startup.py`)."""

from chipbench.layer_metrics import _startup


def read(facts: dict):
    return _startup.read(facts, "setup_backend_s")
