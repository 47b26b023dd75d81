"""The short convolution in front of a linear-attention head: a causal
depthwise convolution of a few taps over the sequence, silu, and (for q and
k) each head's L2 norm with a scale.

    p_t = sum_j taps[j] x[t - (width - 1) + j]     zeros before the start
    s   = silu(p)
    y   = s * scale * rsqrt(sum_head(s^2) + 1e-6)  where a scale is given

x and y are in the compute dtype; everything between the cast of x and the
one rounding of y is float32. Nothing but x and the taps is kept for the
backward pass: the float32 intermediates (tokens x channels, a quarter of a
GiB each at the benchmark's widths) are made again there.

Two implementations behind `conv_silu_heads`, chosen by what the call can
observe:

- the Pallas kernels of ops/short_conv_pallas.py, one pass forward and one
  backward, where a head is one lane tile (channels / heads = 128), the
  sequence is a whole number of the kernels' row steps and the backend is a
  TPU (or the Pallas interpreter a test switched on): x (and y's cotangent)
  is read from HBM once and y (x's cotangent) written once, in the
  `(b, t, h*128)` layout the recurrence's kernels read (PERF.md section 6,
  PR 37);
- `conv_silu_heads_xla` everywhere else (the CPU, the tiny preset's heads of
  16): plain XLA under `jax.checkpoint`, differentiated by JAX. It is also
  the function the kernels are tested against.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

#: under the square root of a head's sum of squares
EPS = 1e-6


def takes_kernels(x_shape, taps_shape, heads: int) -> bool:
    """Whether `conv_silu_heads` runs the Pallas kernels for an x and taps
    of these shapes here: shapes and backend decide, nothing else."""
    from distributed_vgg_f_tpu.ops import short_conv_pallas
    return (jax.default_backend() == "tpu" or short_conv_pallas.INTERPRET) \
        and short_conv_pallas.applies(x_shape, taps_shape, heads)


def conv_silu_heads(x, taps, heads: int, scale: float | None):
    """silu of the causal depthwise convolution of x (b, t, channels) with
    `taps` (width, channels; `taps[-1]` is on the position itself) and zeros
    before the sequence's start, as (b, t, heads, channels / heads) in x's
    dtype; with a `scale`, each head L2-normalised (eps 1e-6) and multiplied
    by it."""
    b, t, channels = x.shape
    if takes_kernels(x.shape, taps.shape, heads):
        from distributed_vgg_f_tpu.ops import short_conv_pallas
        y = short_conv_pallas.convolved(x, taps, scale)
        return y.reshape(b, t, heads, channels // heads)
    return conv_silu_heads_xla(x, taps, heads, scale)


@functools.partial(jax.checkpoint, static_argnums=(2, 3))
def conv_silu_heads_xla(x, taps, heads: int, scale: float | None):
    """`conv_silu_heads` as plain XLA, differentiated by JAX; its float32
    intermediates are made again in the backward pass."""
    b, t, channels = x.shape
    width = taps.shape[0]
    padded = jnp.pad(x.astype(jnp.float32), ((0, 0), (width - 1, 0), (0, 0)))
    y = jax.nn.silu(sum(padded[:, j:j + t] * taps[j] for j in range(width)))
    y = y.reshape(b, t, heads, channels // heads)
    if scale is not None:
        y = y * (scale * jax.lax.rsqrt(
            jnp.sum(y * y, axis=-1, keepdims=True) + EPS))
    return y.astype(x.dtype)
