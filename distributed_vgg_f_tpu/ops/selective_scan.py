"""Mamba-1's selective scan: a recurrence whose decay differs by channel and
by state.

    h_t = exp(dt_t A) * h_{t-1} + (dt_t x_t) (x) B_t       h_0 = 0
    y_t = h_t C_t + D x_t

with x, dt (t, d) a channel each, A (d, n) one decay rate a channel and
state, B, C (t, n) shared by all channels, and the state h (d, n). Unlike
Mamba-2's scalar decay a head (ops/ssd.py), exp(dt_t A) is no scalar times
a fixed matrix, so the recurrence has no chunked matrix form: it is walked
position by position. The state, the decays and the sums are float32.

Two implementations behind `selective_scan`, chosen by what the call can
observe:

- the Pallas kernels of ops/selective_scan_pallas.py, forward and
  hand-written backward, where the channels are whole lane tiles and the
  positions whole chunks (`selective_scan_pallas.applies`) and the backend
  is a TPU (or the Pallas interpreter a test switched on): the state lives
  in VMEM and no (t, d, n) array reaches HBM;
- `selective_scan_xla` everywhere else (the CPU, the tiny preset's 64
  channels): a `lax.scan` over chunks of a `lax.scan` over positions, each
  chunk under `jax.checkpoint`, so that its backward pass keeps the states
  entering the chunks and makes a chunk's own again. It is also the
  function the kernels are tested against.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def takes_kernels(x_shape, state_size: int, chunk: int) -> bool:
    """Whether `selective_scan` runs the Pallas kernels for an `x` of this
    shape here: shapes and backend decide, nothing else."""
    from distributed_vgg_f_tpu.ops import selective_scan_pallas
    return (jax.default_backend() == "tpu"
            or selective_scan_pallas.INTERPRET) \
        and selective_scan_pallas.applies(x_shape, state_size, chunk)


def selective_scan(x, dt, A, B, C, D, chunk: int = 128):
    """`x` (b, t, d) in the compute dtype; `dt` (b, t, d) float32, after its
    softplus; `A` (d, n) float32, negative; `B`, `C` (b, t, n); `D` (d,)
    float32. Returns `y` (b, t, d) float32. `t` is a whole number of chunks
    (or shorter than one)."""
    t = x.shape[1]
    if t % min(chunk, t):
        raise ValueError(f"{t} positions in chunks of {chunk} leave a rest")
    if takes_kernels(x.shape, A.shape[1], chunk):
        from distributed_vgg_f_tpu.ops import selective_scan_pallas
        return selective_scan_pallas.scan(x, dt, A, B, C, D, chunk=chunk)
    return selective_scan_xla(x, dt, A, B, C, D, chunk)


def selective_scan_xla(x, dt, A, B, C, D, chunk: int = 128):
    """`selective_scan` as plain XLA loops, differentiated by JAX."""
    b, t, d = x.shape
    n = A.shape[1]
    q = min(chunk, t)
    f32 = jnp.float32
    A = A.astype(f32)

    def position(h, inputs):
        x_t, dt_t, b_t, c_t = inputs                   # (b, d) / (b, n)
        h = jnp.exp(dt_t[..., None] * A) * h \
            + (dt_t * x_t)[..., None] * b_t[:, None, :]
        return h, jnp.sum(h * c_t[:, None, :], -1)

    @jax.checkpoint
    def one_chunk(h, inputs):
        return jax.lax.scan(position, h, inputs)

    # (chunks, positions, b, ...): both loops walk the leading axes
    split = lambda v: v.astype(f32).reshape(b, t // q, q, -1).transpose(
        1, 2, 0, 3)
    _, y = jax.lax.scan(one_chunk, jnp.zeros((b, d, n), f32),
                        tuple(split(v) for v in (x, dt, B, C)))
    y = y.transpose(2, 0, 1, 3).reshape(b, t, d)
    return y + x.astype(f32) * D.astype(f32)

