"""3x3/2 ceil-mode (Caffe-semantics) max pooling.

Forward: `lax.reduce_window` over an explicitly padded input — identical
numerics to `nn.max_pool`; at 224 input this ceil-mode sizing is what yields
VGG-F's canonical 6x6x256 conv5 output / 9216-wide fc6 (~61M params).

Also in-tree: a hand-written backward (`set_maxpool_impl("custom_vjp")`) that
was a MEASURED NON-WIN and is kept as the documented counter-example.
Motivation: autodiff of reduce_window-max lowers to `lax.select_and_scatter`,
which the profile put at ~7% of the VGG-F train step, so a scatter-free
backward looked attractive: route each output's cotangent to the FIRST
maximum in its window (row-major tap order — the same winner
select_and_scatter picks) with nine stride-2 slices + dilated `lax.pad`s.
Result on v5e, full VGG-F train step, batch 1024 bf16: **92.1 vs 50.1
ms/step** — the nine strided spatial slices and nine full-size dilated
pad+adds cost far more than the fused select_and_scatter they replace.
Together with the shifted-slice LRN result (ops/lrn.py, module docstring), the
repeated TPU lesson: XLA's structured window ops are already well-lowered;
manual decompositions into slices/pads lose to them even when they look
cheaper on paper.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

_WINDOW = 3
_STRIDE = 2


def _ceil_pads(shape) -> tuple:
    """Right/bottom padding for ceil-mode output size (>=1 for tiny inputs)."""
    pads = []
    for dim in (1, 2):
        n = shape[dim]
        out = max(1, -(-(n - _WINDOW) // _STRIDE) + 1)
        pads.append((0, max(0, (out - 1) * _STRIDE + _WINDOW - n)))
    return tuple(pads)


def _pool_valid(xp: jnp.ndarray) -> jnp.ndarray:
    return lax.reduce_window(
        xp, -jnp.inf if jnp.issubdtype(xp.dtype, jnp.floating)
        else jnp.iinfo(xp.dtype).min,
        lax.max, (1, _WINDOW, _WINDOW, 1), (1, _STRIDE, _STRIDE, 1), "VALID")


@jax.custom_vjp
def _pool_vjp(xp):
    return _pool_valid(xp)


def _pool_vjp_fwd(xp):
    y = _pool_valid(xp)
    return y, (xp, y)


def _pool_vjp_bwd(res, g):
    xp, y = res
    n, hp, wp, c = xp.shape
    ho, wo = y.shape[1], y.shape[2]
    grad = jnp.zeros(xp.shape, g.dtype)
    claimed = jnp.zeros(y.shape, jnp.bool_)
    for a in range(_WINDOW):
        for b in range(_WINDOW):
            h_end = a + _STRIDE * (ho - 1) + 1
            w_end = b + _STRIDE * (wo - 1) + 1
            xs = lax.slice(xp, (0, a, b, 0), (n, h_end, w_end, c),
                           (1, _STRIDE, _STRIDE, 1))
            sel = jnp.logical_and(xs == y, jnp.logical_not(claimed))
            claimed = jnp.logical_or(claimed, sel)
            m = jnp.where(sel, g, jnp.zeros((), g.dtype))
            # stride-2 scatter = interior (dilation) padding of the tap grid
            grad = grad + lax.pad(
                m, jnp.zeros((), g.dtype),
                ((0, 0, 0),
                 (a, hp - h_end, _STRIDE - 1),
                 (b, wp - w_end, _STRIDE - 1),
                 (0, 0, 0)))
    return (grad,)


_pool_vjp.defvjp(_pool_vjp_fwd, _pool_vjp_bwd)

_IMPL_OVERRIDE: str | None = None


def set_maxpool_impl(impl: str | None) -> None:
    """'autodiff' | 'custom_vjp' | None (auto: autodiff — the custom VJP is a
    measured non-win on TPU, see module docstring)."""
    global _IMPL_OVERRIDE
    if impl not in (None, "custom_vjp", "autodiff"):
        raise ValueError(f"unknown maxpool impl: {impl!r}")
    _IMPL_OVERRIDE = impl


def maxpool_3x3s2_ceil(x: jnp.ndarray) -> jnp.ndarray:
    """3x3/2 ceil-mode max pool — what models should call. At 224 input this
    yields VGG-F's canonical 6x6x256 conv5 output / 9216-wide fc6 (~61M
    params); floor-mode VALID pooling would silently lose ~12M fc6 params."""
    pads = _ceil_pads(x.shape)
    impl = _IMPL_OVERRIDE or "autodiff"
    if impl == "autodiff":
        import flax.linen as nn
        return nn.max_pool(x, window_shape=(_WINDOW, _WINDOW),
                           strides=(_STRIDE, _STRIDE), padding=pads)
    fill = (-jnp.inf if jnp.issubdtype(x.dtype, jnp.floating)
            else jnp.iinfo(x.dtype).min)
    xp = jnp.pad(x, ((0, 0), pads[0], pads[1], (0, 0)),
                 constant_values=fill)
    return _pool_vjp(xp)
