"""Train state pytree: step counter, params, mutable model state (BN stats), and
optimizer state — the unit that is updated per step, checkpointed, and restored
(SURVEY.md §3.5)."""

from __future__ import annotations

from typing import Any

import flax.struct
import jax
import jax.numpy as jnp
import optax


@flax.struct.dataclass
class TrainState:
    step: jnp.ndarray            # scalar int32
    params: Any
    batch_stats: Any             # {} for models without BN (VGG-F/VGG-16/ViT)
    opt_state: optax.OptState
    # Exponential moving average of params (train.ema_decay > 0); None when
    # disabled — None is an EMPTY pytree subtree, so states and checkpoints
    # written without EMA keep their exact structure. BN moving statistics
    # are averaged too (ema_batch_stats — the TF-era recipe averages
    # `moving_average_variables`, which includes BN moving mean/var; eval
    # with averaged weights against raw-trajectory BN stats would silently
    # mismatch the activation distribution).
    ema_params: Any = None
    ema_batch_stats: Any = None

    @classmethod
    def create(cls, model, tx, rng: jax.Array, sample_input: jnp.ndarray,
               *, ema: bool = False, exchange=None) -> "TrainState":
        """`exchange` (parallel/zero.py `Exchange`, the run's exchange plan)
        lays out what it shards: under ZeRO the optimizer state is
        initialized over the flat parameter vector in the plan's layout, and
        under ZeRO-3 the params themselves — and the EMA seed — are stored
        as that same vector. None = the replicated tree layout with `tx`'s
        state over it. `ema=True` starts the parameter EMA at the initial
        params (no zero-debias needed)."""
        variables = model.init({"params": rng}, sample_input, train=False)
        params = variables["params"]
        batch_stats = variables.get("batch_stats", {})
        if exchange is not None:
            params, opt_state = exchange.layout_state(params)
        else:
            opt_state = tx.init(params)
        return cls(step=jnp.zeros((), jnp.int32), params=params,
                   batch_stats=batch_stats, opt_state=opt_state,
                   ema_params=params if ema else None,
                   ema_batch_stats=batch_stats if ema else None)
