"""Programs XLA compiled before the window's first step: the count of the
program's `compile:backend:*` spans up to the cut (a cache read is a
`compile:cache_read:*` span and is not counted). 0 on a warm run; the
number that tells a cold `setup_s` from a slow one. None where the ring
cannot say (`_startup.py`)."""

from chipbench.layer_metrics import _startup


def read(facts: dict):
    return _startup.read(facts, "setup_programs_compiled")
