"""Convolutions and matrix products one training step needs, from the
plain reference's loss gradient at the cell's global batch (shapes only,
nothing compiled or run). A reference that rematerialises (`remat`) is
traced with that off: what is computed twice is needed once."""

import inspect

import jax
import jax.numpy as jnp

from chipbench import counts
from chipbench.reference import step as ref_step
from chipbench.reference.ops import Ops


def of(facts: dict) -> list:
    if "step_ops" in facts:
        return facts["step_ops"]
    model = ref_step.load_model(facts["model"])
    recipe = facts["recipe"]
    rows, size = facts["rows_per_step"], recipe["image_size"]
    shapes = facts["shapes"]

    once = {"remat": False} if "remat" in inspect.signature(
        model.forward).parameters else {}

    def loss(params, stats, x):
        logits, _ = model.forward(params, stats, x, ops=Ops("float32"),
                                  train=True, masks=None, **once)
        return jnp.sum(logits)

    x = jax.ShapeDtypeStruct((rows, size, size, 3), jnp.float32)
    facts["step_ops"] = counts.jaxpr_ops(
        jax.grad(loss), shapes["params"], shapes["stats"], x)
    return facts["step_ops"]
