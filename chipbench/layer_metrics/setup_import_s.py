"""Seconds the trainer module's own import chain took (jax, flax, optax,
orbax, the package's `data`, `models`, `checkpoint`; less what the harness
had imported before it): the program's `startup:import_trainer` span.
None where the ring cannot say (`_startup.py`)."""

from chipbench.layer_metrics import _startup


def read(facts: dict):
    return _startup.read(facts, "setup_import_s")
