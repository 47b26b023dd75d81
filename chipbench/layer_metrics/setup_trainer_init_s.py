"""Seconds of host work in building the trainer and its state: the
program's `startup:trainer_init` and `startup:init_state` spans up to the
cut, less the `compile:*` intervals inside them on their thread (those are
`setup_trace_lower_s` and `setup_backend_s`). None where the ring cannot
say (`_startup.py`)."""

from chipbench.layer_metrics import _startup


def read(facts: dict):
    return _startup.read(facts, "setup_trainer_init_s")
