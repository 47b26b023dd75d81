"""The return of the expert share's window rows to their tokens
(models/mistral4.py `routed_experts`): the forward pass's weighted rows into
the tokens' float32 sum, and the backward pass's row cotangents into x's.

    sum[t] = into[t] + sum_{r live, token[r] = t} weight[r] rows[r]   forward
    d_x[t] =           sum_{r live, token[r] = t} rows[r]             backward

Rows behind the window's last held assignment (`live` false) reach nothing.

Two implementations behind `add_weighted` and `to_tokens`, chosen by what
the call can observe:

- the Pallas kernel of ops/routed_combine_pallas.py where hidden is whole
  lane tiles, the window whole row tiles and the tokens whole blocks, and
  the backend is a TPU (or the Pallas interpreter a test switched on): every
  token written once, each live row read once, a token's rows summed in
  float32 on the chip (PERF.md section 5);
- XLA's scatter-add `.at[token].add` everywhere else (the CPU, the tiny
  presets' hidden of 64): the form the kernel is tested against.

Both open the scope the benchmark's readers go by: `moe_combine` forward,
`moe_dispatch` backward.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def takes_kernel(x_shape, dtype, capacity: int) -> bool:
    """Whether windows of `capacity` rows into an x (tokens, hidden) of
    `dtype` run the Pallas kernel here: shapes and backend decide, nothing
    else."""
    from distributed_vgg_f_tpu.ops import routed_combine_pallas
    tokens, hidden = x_shape
    return (jax.default_backend() == "tpu" or routed_combine_pallas.INTERPRET) \
        and routed_combine_pallas.applies(tokens, capacity, hidden, dtype)


def empty_sum(x, capacity: int):
    """Where the forward pass's windows add up, (tokens, hidden) float32:
    zeros for XLA's scatter; for the kernel a buffer the first window writes
    whole, so that no zero fill goes before it."""
    if takes_kernel(x.shape, x.dtype, capacity):
        from distributed_vgg_f_tpu.ops import routed_combine_pallas
        with jax.named_scope("moe_combine"):
            return routed_combine_pallas.unwritten(x.shape)
    return jnp.zeros(x.shape, jnp.float32)


def add_weighted(into, outs, weight, token, live, *, first):
    """`into` plus each live row of `outs` (capacity, hidden) times its
    `weight` (capacity, 1) float32, added at its `token`, as float32.
    `into` comes from `empty_sum` or an earlier window; `first`: this is
    the first window (the kernel does not read `into` then)."""
    if takes_kernel(into.shape, outs.dtype, outs.shape[0]):
        from distributed_vgg_f_tpu.ops import routed_combine_pallas
        return routed_combine_pallas.summed(
            outs, token, live[:, 0], weight[:, 0], into, first,
            tokens=into.shape[0], scope="moe_combine")
    with jax.named_scope("moe_combine"):
        return into.at[token].add(
            jnp.where(live, outs, 0).astype(jnp.float32) * weight,
            mode="promise_in_bounds")


def to_tokens(rows, token, live, x):
    """Each live row of `rows` (capacity, hidden, x's dtype) added at its
    `token`, in x's shape and dtype: the window's part of x's cotangent."""
    if takes_kernel(x.shape, x.dtype, rows.shape[0]):
        from distributed_vgg_f_tpu.ops import routed_combine_pallas
        return routed_combine_pallas.summed(
            rows, token, live[:, 0], tokens=x.shape[0], scope="moe_dispatch")
    with jax.named_scope("moe_dispatch"):
        return jnp.zeros_like(x).at[token].add(
            jnp.where(live, rows, 0), mode="promise_in_bounds")
