"""Local Response Normalization across channels.

VGG-F (CNN-F, Chatfield et al. 2014) applies LRN after conv1 and conv2
(SURVEY.md §3.3). JAX/Flax ship no LRN layer (SURVEY.md §7 hard parts), so this is
implemented directly. The implementations in this package:

- `local_response_norm` (here): squared-sum over a sliding channel window via
  `lax.reduce_window`. Exact fp32 numerics — this is the test oracle. On the
  TPU its windows cross the 128-lane axis and `**0.75` lowers to exp/log.
- `local_response_norm_matmul_vjp` (here): the channel-window sum recast as
  a banded C×C matmul, `S = (x*x) @ B` with `B[i,j] = |i-j| <= r`, so that
  the window sum rides the MXU; for `beta=0.75` the power is
  `rsqrt(d)*sqrt(rsqrt(d))`; with a hand-written VJP whose only residual is
  `x`. The default wherever the kernel pair does not apply. What it costs on the TPU, compiled (PERF.md, PR 29): its backward
  recomputes the normaliser, but XLA merges that recomputation with the
  forward's identical product, so the compiled forward fusion has a second,
  float32 output of the activation's size which the backward reads back; and
  with the merge stopped the backward writes that float32 tensor itself,
  between its two band products. 15.7 ms of VGG-F's 53.5 ms step at batch
  1024, against 4.7 ms of bf16 traffic.
- `ops/lrn_pallas.py`: one Pallas TPU kernel pass each way, bf16 in and out,
  called through the view that is a bitcast of the layout XLA keeps the
  activation in. `lrn()` takes it where it applies (bf16, batch a multiple
  of 128, on a TPU): 6.4 ms for both sites, both ways (PERF.md, PR 29).

Measured and gone (PR 30): the window sum as 2r+1 shifted slices and adds made
the VGG-F step 74.2 ms against the band product's 50.1 at batch 1024 on a v5e
(offset slices in the lane dimension cost the VPU per-element rotations, while
the MXU eats the band's redundant FLOPs at HBM speed), and the band product
under plain autodiff kept more residuals than the hand VJP.

Two parameterizations exist in the wild; both are supported so parity oracles are
exact:
- TF / AlexNet-paper style (`alpha_scaled=False`):  d = (k + alpha     * sum)^beta
- Caffe / torch style      (`alpha_scaled=True`):   d = (k + alpha/n   * sum)^beta
(`n = 2*depth_radius + 1` is the window size.)
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax


def local_response_norm(x: jnp.ndarray,
                        depth_radius: int = 2,
                        bias: float = 2.0,
                        alpha: float = 1e-4,
                        beta: float = 0.75,
                        *,
                        alpha_scaled: bool = False,
                        channel_axis: int = -1) -> jnp.ndarray:
    """LRN over the channel axis (NHWC default).

    out[c] = x[c] / (bias + a * sum_{j=c-r..c+r} x[j]^2) ** beta
    with a = alpha/n when `alpha_scaled` else alpha.
    """
    if channel_axis < 0:
        channel_axis += x.ndim
    n = 2 * depth_radius + 1
    a = alpha / n if alpha_scaled else alpha

    # LRN numerics are fp32-sensitive (x^4-ish dynamic range); compute the
    # normalizer in float32 regardless of the activation dtype.
    orig_dtype = x.dtype
    xf = x.astype(jnp.float32)
    sq = xf * xf

    window = [1] * x.ndim
    window[channel_axis] = n
    padding = [(0, 0)] * x.ndim
    padding[channel_axis] = (depth_radius, depth_radius)
    sums = lax.reduce_window(sq, 0.0, lax.add,
                             window_dimensions=tuple(window),
                             window_strides=(1,) * x.ndim,
                             padding=tuple(padding))
    denom = (bias + a * sums) ** beta
    return (xf / denom).astype(orig_dtype)


def band_matrix_np(num_channels: int, depth_radius: int) -> np.ndarray:
    """C×C banded matrix of ones: B[i, j] = 1 iff |i - j| <= depth_radius.
    Right-multiplying squared activations by B computes the LRN window sum;
    B is symmetric, so the backward pass reuses it unchanged. Numpy on purpose:
    the Pallas path builds (block-diagonal copies of) it inside jit traces,
    where jnp constants would become tracers."""
    i = np.arange(num_channels)
    return (np.abs(i[:, None] - i[None, :]) <= depth_radius).astype(np.float32)


def band_matrix(num_channels: int, depth_radius: int,
                dtype=jnp.float32) -> jnp.ndarray:
    return jnp.asarray(band_matrix_np(num_channels, depth_radius), dtype=dtype)


def _pow_neg_beta(d: jnp.ndarray, beta: float) -> jnp.ndarray:
    """d ** -beta, with a sqrt/rsqrt fast path for the canonical beta=0.75
    (VPU sqrt/rsqrt vs transcendental exp/log)."""
    if beta == 0.75:
        inv = lax.rsqrt(d)           # d^-1/2
        return inv * jnp.sqrt(inv)   # d^-3/4
    if beta == 0.5:
        return lax.rsqrt(d)
    return d ** -beta


def _lrn_mm_core(x: jnp.ndarray, depth_radius: int, bias: float, a: float,
                 beta: float):
    """Shared fwd math for the custom-VJP matmul LRN. Returns (out, d, t) with
    d = bias + a*S (f32 normalizer) and t = d^-beta (f32 scale).

    For bf16 inputs the band matmul runs natively in bf16 on the MXU (f32
    accumulation): the window sum error (~2^-8 relative) enters d scaled by
    `a` (1e-4-ish) against the O(1) bias term, so it is negligible — while a
    f32 matmul would cost multiple MXU passes. f32 inputs keep the exact
    HIGHEST-precision path so oracle tests stay bit-tight."""
    band_dtype = x.dtype if x.dtype == jnp.bfloat16 else jnp.float32
    band = band_matrix(x.shape[-1], depth_radius, band_dtype)
    sq = (x * x) if band_dtype == jnp.bfloat16 else None
    if band_dtype == jnp.bfloat16:
        S = lax.dot_general(sq, band, (((x.ndim - 1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
    else:
        xf = x.astype(jnp.float32)
        S = lax.dot_general(xf * xf, band, (((x.ndim - 1,), (0,)), ((), ())),
                            precision=lax.Precision.HIGHEST)
    d = bias + a * S
    t = _pow_neg_beta(d, beta)
    out = (x.astype(jnp.float32) * t).astype(x.dtype)
    return out, d, t


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3, 4))
def _lrn_matmul_vjp(x, depth_radius, bias, a, beta):
    return _lrn_mm_core(x, depth_radius, bias, a, beta)[0]


def _lrn_matmul_vjp_fwd(x, depth_radius, bias, a, beta):
    out, _, _ = _lrn_mm_core(x, depth_radius, bias, a, beta)
    return out, (x,)


def _lrn_matmul_vjp_bwd(depth_radius, bias, a, beta, res, g):
    """Hand-derived backward whose only residual is x; d and t are made
    again with one more band matmul:

        grad_i = g_i * t_i - 2*a*beta * x_i * sum_j B_ij (g_j x_j t_j / d_j)

    Compiled for the TPU, XLA merges that recomputation with the forward's
    product and stores d in float32 after all (module docstring)."""
    (x,) = res
    _, d, t = _lrn_mm_core(x, depth_radius, bias, a, beta)
    xf = x.astype(jnp.float32)
    gf = g.astype(jnp.float32)
    u = (gf * xf * (t / d)).astype(x.dtype)
    band_dtype = x.dtype if x.dtype == jnp.bfloat16 else jnp.float32
    band = band_matrix(x.shape[-1], depth_radius, band_dtype)
    if band_dtype == jnp.bfloat16:
        v = lax.dot_general(u, band, (((x.ndim - 1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
    else:
        v = lax.dot_general(u.astype(jnp.float32), band,
                            (((x.ndim - 1,), (0,)), ((), ())),
                            precision=lax.Precision.HIGHEST)
    grad = gf * t - 2.0 * a * beta * xf * v
    return (grad.astype(x.dtype),)


_lrn_matmul_vjp.defvjp(_lrn_matmul_vjp_fwd, _lrn_matmul_vjp_bwd)


def local_response_norm_matmul_vjp(x: jnp.ndarray,
                                   depth_radius: int = 2,
                                   bias: float = 2.0,
                                   alpha: float = 1e-4,
                                   beta: float = 0.75,
                                   *,
                                   alpha_scaled: bool = False) -> jnp.ndarray:
    """Banded-matmul LRN with a hand-written VJP: what `lrn()` falls back to
    where the kernel pair (`ops/lrn_pallas.py`) does not apply. Not
    twice-differentiable; use the autodiff forms for higher-order grads."""
    n = 2 * depth_radius + 1
    a = alpha / n if alpha_scaled else alpha
    return _lrn_matmul_vjp(x, depth_radius, float(bias), float(a), float(beta))


_IMPL_OVERRIDE: str | None = None

# Call sites of `lrn()` traced so far in this process, by what they lowered
# to: the fused kernel pair, or an XLA form. A step's builder reads the
# difference across its own trace (train/step.py, gauges `lrn/fused_sites`
# and `lrn/fallback_sites`).
_SITES = {"fused": 0, "fallback": 0}


def lrn_site_counts() -> dict:
    return dict(_SITES)


def set_lrn_impl(impl: str | None) -> None:
    """Force an LRN implementation globally: 'matmul_vjp' | 'pallas' |
    'reduce_window' | None (auto: the fused kernel pair where it applies,
    else the custom-VJP banded-matmul form — see `lrn`)."""
    global _IMPL_OVERRIDE
    if impl not in (None, "matmul_vjp", "pallas", "reduce_window"):
        raise ValueError(f"unknown LRN impl: {impl!r}")
    _IMPL_OVERRIDE = impl


def lrn(x: jnp.ndarray,
        depth_radius: int = 2,
        bias: float = 2.0,
        alpha: float = 1e-4,
        beta: float = 0.75,
        *,
        alpha_scaled: bool = False,
        relu_input: bool = False) -> jnp.ndarray:
    """Dispatching LRN over the last axis — what models should call.
    `relu_input` normalises `relu(x)`: the kernel pair then makes the relu
    and its gradient's mask in VMEM, where XLA would write the convolution's
    output twice (before and after the relu) for the pair to read one.

    Auto mode decides from what the call can observe, at trace time: a bf16
    NHWC activation whose batch fills the lanes, on a TPU, takes the fused
    kernel pair (`ops/lrn_pallas.py`) through the view its channel count
    selects; anything else (float32 as the fp32 serving tier has, a batch
    that is not a multiple of 128 as the server's small buckets have, another
    backend) keeps the XLA banded-matmul form. 'pallas' forces the pair and
    fails where no view applies."""
    from distributed_vgg_f_tpu.ops import lrn_pallas
    impl = _IMPL_OVERRIDE
    if impl is None:
        on_tpu = jax.default_backend() == "tpu" or lrn_pallas.INTERPRET
        fused = on_tpu and lrn_pallas.fused_view(x.shape, x.dtype) is not None
        impl = "pallas" if fused else "matmul_vjp"
    _SITES["fused" if impl == "pallas" else "fallback"] += 1
    if impl == "pallas":
        return lrn_pallas.local_response_norm_pallas(
            x, depth_radius, bias, alpha, beta, alpha_scaled=alpha_scaled,
            relu_input=relu_input)
    if relu_input:
        x = jax.nn.relu(x)
    if impl == "matmul_vjp":
        return local_response_norm_matmul_vjp(x, depth_radius, bias, alpha,
                                              beta, alpha_scaled=alpha_scaled)
    return local_response_norm(x, depth_radius, bias, alpha, beta,
                               alpha_scaled=alpha_scaled)
