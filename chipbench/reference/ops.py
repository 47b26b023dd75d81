"""The arithmetic every plain reference model is written in.

Float32 throughout, `jax.numpy` and `lax` only, nothing of the program.
`Ops("float32")` multiplies at `precision=HIGHEST` (on a TPU a float32
matmul otherwise runs in bf16 passes). `Ops("fp8")` is the control of "How
`correct` is decided": the same mathematics with both operands of every
conv and matmul rounded to float8 (e4m3, one scale per tensor), the
precision step below the bf16 the configurations state, and the cotangent
that enters each of them in the backward pass rounded to float8 (e5m2): the
usual fp8 training recipe, so forward, input-gradient and weight-gradient
products all see fp8 operands.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

_HIGHEST = lax.Precision.HIGHEST
_E4M3_MAX = 448.0


_E5M2_MAX = 57344.0


def _to_fp8(x, dtype, top):
    scale = top / jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    return (x * scale).astype(dtype).astype(jnp.float32) / scale


def _round_fp8(x):
    """x rounded to e4m3 (one scale per tensor); the gradient passes."""
    return x + lax.stop_gradient(_to_fp8(x, jnp.float8_e4m3fn, _E4M3_MAX) - x)


@jax.custom_vjp
def _round_cotangent_fp8(y):
    return y


_round_cotangent_fp8.defvjp(
    lambda y: (y, None),
    lambda _, g: (_to_fp8(g, jnp.float8_e5m2, _E5M2_MAX),))


class Ops:
    def __init__(self, mode: str = "float32"):
        if mode not in ("float32", "fp8"):
            raise ValueError(f"unknown reference precision {mode!r}")
        self.mode = mode

    def _operand(self, x):
        x = x.astype(jnp.float32)
        return _round_fp8(x) if self.mode == "fp8" else x

    def _result(self, y):
        return _round_cotangent_fp8(y) if self.mode == "fp8" else y

    def conv(self, x, kernel, stride: int, padding):
        """NHWC x HWIO convolution; `padding` is 'SAME', 'VALID' or pairs."""
        return self._result(lax.conv_general_dilated(
            self._operand(x), self._operand(kernel), (stride, stride),
            padding, dimension_numbers=("NHWC", "HWIO", "NHWC"),
            precision=_HIGHEST))

    def dense(self, x, kernel):
        return self._result(jnp.matmul(
            self._operand(x), self._operand(kernel), precision=_HIGHEST))


def max_pool(x, window: int, stride: int, padding):
    """Max pool over H and W; `padding` is ((lo, hi), (lo, hi))."""
    return lax.reduce_window(
        x, -jnp.inf, lax.max, (1, window, window, 1),
        (1, stride, stride, 1), ((0, 0), *padding, (0, 0)))


def ceil_pool_3x3s2(x):
    """3x3/2 max pool with Caffe's ceil-mode output size (pads bottom and
    right with -inf): 54 -> 27 -> 13 -> 6 at 224 input."""
    pads = []
    for n in x.shape[1:3]:
        out = max(1, -(-(n - 3) // 2) + 1)
        pads.append((0, max(0, (out - 1) * 2 + 3 - n)))
    return max_pool(x, 3, 2, tuple(pads))


def lrn(x, radius: int = 2, bias: float = 2.0, alpha: float = 1e-4,
        beta: float = 0.75):
    """AlexNet-paper local response normalisation over channels:
    x / (bias + alpha * sum_{|j-c|<=radius} x_j^2) ** beta."""
    sums = lax.reduce_window(
        x * x, 0.0, lax.add, (1, 1, 1, 2 * radius + 1), (1, 1, 1, 1),
        ((0, 0), (0, 0), (0, 0), (radius, radius)))
    return x / (bias + alpha * sums) ** beta


def dropout(x, mask, rate: float):
    """Inverted dropout with a given keep mask (None: no dropout)."""
    if mask is None or rate == 0.0:
        return x
    return jnp.where(mask, x / (1.0 - rate), 0.0)


def batch_norm(x, p, stats, *, train: bool, momentum=0.9, eps=1e-5):
    """Batch norm over (N, H, W) with the batch's own statistics in
    training; returns (y, new_running_stats)."""
    if train:
        axes = tuple(range(x.ndim - 1))
        mean = jnp.mean(x, axes)
        var = jnp.mean(jnp.square(x - mean), axes)
        new = {"mean": momentum * stats["mean"] + (1 - momentum) * mean,
               "var": momentum * stats["var"] + (1 - momentum) * var}
    else:
        mean, var, new = stats["mean"], stats["var"], stats
    y = (x - mean) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]
    return y, new
