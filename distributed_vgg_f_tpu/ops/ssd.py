"""Mamba-2's state-space recurrence by its chunked dual form (SSD).

The recurrence, one scalar decay a head and a state of `p x n` a head:

    h_t = exp(dt_t A) h_{t-1} + dt_t x_t (x) B_t        h_0 = 0
    y_t = h_t C_t + D x_t

is computed `chunk` positions at a time and never position by position.
With a_t = dt_t A and s its running sum inside a chunk:

    within a chunk   y_i += sum_{j <= i} (C_i . B_j) exp(s_i - s_j) dt_j x_j
                     (one masked `chunk x chunk` product a head: C B^T a
                     group, times the decays, against the chunk's x)
    a chunk's state  S = sum_j exp(s_last - s_j) dt_j x_j (x) B_j
    between chunks   H_{c+1} = exp(s_last) H_c + S_c    (a scan over chunks)
    from the past    y_i += exp(s_i) C_i H_c

B and C come in groups, a group serving `heads / groups` consecutive heads.
The decays, their running sums and the carried state are float32; the four
products take operands in x's dtype and accumulate in float32.

Two implementations behind `ssd`, chosen by what the call can observe:

- the Pallas kernels of ops/ssd_pallas.py, forward and hand-written
  backward, where the chunk, a group's heads and the state are whole tiles
  of the chip (`ssd_pallas.applies`) and the backend is a TPU (or the Pallas
  interpreter a test switched on): a chunk's masked decay product is made
  in VMEM and never reaches HBM (PERF.md, section 6, PR 33);
- `ssd_xla` everywhere else (the CPU, the tiny preset's chunks of 8 and
  heads of 8): plain XLA products over the chunks, differentiated by JAX;
  the masked `chunk x chunk` decay product of every head is one fusion's
  output in x's dtype. It is also the function the kernels are tested
  against.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def takes_kernels(x_shape, group_shape, chunk: int) -> bool:
    """Whether `ssd` runs the Pallas kernels for arguments of these shapes
    (`x`'s and `B`'s) here: shapes and backend decide, nothing else."""
    from distributed_vgg_f_tpu.ops import ssd_pallas
    return (jax.default_backend() == "tpu" or ssd_pallas.INTERPRET) \
        and ssd_pallas.applies(x_shape, group_shape, chunk)


def ssd(x, dt, A, B, C, D, chunk: int = 128):
    """`x` (b, t, h, p) in the compute dtype; `dt` (b, t, h) float32, after
    its softplus; `A` (h,) float32, negative; `B`, `C` (b, t, g, n) with
    h a multiple of g; `D` (h,) float32. Returns `y` (b, t, h, p) float32.
    `t` is a whole number of chunks (or shorter than one)."""
    t, h, g = x.shape[1], x.shape[2], B.shape[2]
    if t % min(chunk, t) or h % g:
        raise ValueError(f"{t} positions in chunks of {chunk}, {h} heads in "
                         f"{g} groups: neither may leave a rest")
    if takes_kernels(x.shape, B.shape, chunk):
        from distributed_vgg_f_tpu.ops import ssd_pallas
        return ssd_pallas.scan(x, dt, A, B, C, D, chunk=chunk)
    return ssd_xla(x, dt, A, B, C, D, chunk)


def ssd_xla(x, dt, A, B, C, D, chunk: int = 128):
    """`ssd` as plain XLA products, differentiated by JAX."""
    b, t, h, p = x.shape
    g, n = B.shape[2:]
    q = min(chunk, t)
    c, r, dtype = t // q, h // g, x.dtype
    f32 = jnp.float32

    a = (dt.astype(f32) * A.astype(f32)).reshape(b, c, q, g, r)
    s = jnp.cumsum(a, axis=2)                              # (b, c, q, g, r)
    last = s[:, :, -1]                                     # (b, c, g, r)
    x_dt = x.astype(f32).reshape(b, c, q, g, r, p) \
        * dt.astype(f32).reshape(b, c, q, g, r, 1)
    B = B.astype(dtype).reshape(b, c, q, g, n)
    C = C.astype(dtype).reshape(b, c, q, g, n)

    # within a chunk: (C B^T a group) x (the decays a head), masked
    cb = jnp.einsum("bcign,bcjgn->bcgij", C, B, preferred_element_type=f32)
    rows = s.transpose(0, 1, 3, 4, 2)                      # (b, c, g, r, q)
    seen = jnp.tril(jnp.ones((q, q), bool))
    decay = jnp.exp(jnp.where(seen, rows[..., :, None] - rows[..., None, :],
                              -jnp.inf))                   # (b, c, g, r, i, j)
    y = jnp.einsum("bcgrij,bcjgrp->bcigrp",
                   (cb[:, :, :, None] * decay).astype(dtype),
                   x_dt.astype(dtype), preferred_element_type=f32)

    # a chunk's own state, and the states the chunks hand on
    to_end = jnp.exp(last[:, :, None] - s)                 # (b, c, q, g, r)
    own = jnp.einsum("bcjgn,bcjgrp->bcgrpn", B,
                     (x_dt * to_end[..., None]).astype(dtype),
                     preferred_element_type=f32)

    def hand_on(state, chunk_):
        keep, add = chunk_
        return keep[..., None, None] * state + add, state

    _, entering = jax.lax.scan(
        hand_on, jnp.zeros((b, g, r, p, n), f32),
        (jnp.exp(last).swapaxes(0, 1), own.swapaxes(0, 1)))
    entering = entering.swapaxes(0, 1)                     # (b, c, g, r, p, n)
    y = y + jnp.einsum("bcign,bcgrpn->bcigrp", C, entering.astype(dtype),
                       preferred_element_type=f32) * jnp.exp(s)[..., None]

    y = y.reshape(b, t, h, p)
    return y + x.astype(f32) * D.astype(f32)[:, None]


def smallest_decay(dt, A):
    """The smallest `exp(dt A)` of a batch: 0 where a state dies within a
    position, 1 where nothing ever decays."""
    return jnp.exp(jnp.min(dt.astype(jnp.float32) * A.astype(jnp.float32)))
