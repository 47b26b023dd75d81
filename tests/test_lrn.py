"""LRN numerics vs a NumPy oracle and vs torch.nn.LocalResponseNorm
(SURVEY.md §4 numerical parity tests)."""

import numpy as np
import jax.numpy as jnp

from distributed_vgg_f_tpu.ops.lrn import local_response_norm


def _numpy_lrn(x, depth_radius=2, bias=2.0, alpha=1e-4, beta=0.75,
               alpha_scaled=False):
    n = 2 * depth_radius + 1
    a = alpha / n if alpha_scaled else alpha
    out = np.empty_like(x)
    C = x.shape[-1]
    for c in range(C):
        lo, hi = max(0, c - depth_radius), min(C, c + depth_radius + 1)
        s = np.sum(x[..., lo:hi] ** 2, axis=-1)
        out[..., c] = x[..., c] / (bias + a * s) ** beta
    return out


def test_lrn_matches_numpy_oracle():
    rng = np.random.default_rng(0)
    x = rng.standard_normal((2, 5, 5, 16), dtype=np.float32)
    got = np.asarray(local_response_norm(jnp.asarray(x)))
    want = _numpy_lrn(x)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


def test_lrn_matches_torch():
    import torch

    rng = np.random.default_rng(1)
    x = rng.standard_normal((2, 7, 7, 8), dtype=np.float32) * 3.0
    # torch LRN: NCHW, size=n, denom = (k + alpha/n * sum)^beta  → alpha_scaled.
    n, k, alpha, beta = 5, 2.0, 1e-4, 0.75
    t = torch.nn.LocalResponseNorm(size=n, alpha=alpha, beta=beta, k=k)
    want = t(torch.from_numpy(x.transpose(0, 3, 1, 2))).numpy().transpose(0, 2, 3, 1)
    got = np.asarray(local_response_norm(
        jnp.asarray(x), depth_radius=2, bias=k, alpha=alpha, beta=beta,
        alpha_scaled=True))
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)


def test_lrn_bf16_input_preserves_dtype():
    x = jnp.ones((1, 2, 2, 8), jnp.bfloat16)
    y = local_response_norm(x)
    assert y.dtype == jnp.bfloat16


def test_matmul_vjp_forward_matches_oracle():
    from distributed_vgg_f_tpu.ops.lrn import local_response_norm_matmul_vjp

    rng = np.random.default_rng(2)
    x = rng.standard_normal((2, 5, 5, 32), dtype=np.float32)
    got = np.asarray(local_response_norm_matmul_vjp(jnp.asarray(x)))
    np.testing.assert_allclose(got, _numpy_lrn(x), rtol=1e-5, atol=1e-6)


def test_matmul_vjp_gradient_matches_autodiff_oracle():
    """The hand-derived backward (the default training path) against autodiff
    of the reduce_window oracle, f32."""
    import jax

    from distributed_vgg_f_tpu.ops.lrn import local_response_norm_matmul_vjp

    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.standard_normal((2, 6, 6, 64), dtype=np.float32))
    cot = jnp.asarray(rng.standard_normal((2, 6, 6, 64), dtype=np.float32))

    g_oracle = jax.grad(lambda v: (local_response_norm(v) * cot).sum())(x)
    g_vjp = jax.grad(lambda v: (local_response_norm_matmul_vjp(v) * cot).sum())(x)
    np.testing.assert_allclose(np.asarray(g_vjp), np.asarray(g_oracle),
                               rtol=1e-4, atol=1e-6)


def test_dispatcher_default_is_custom_vjp():
    import jax

    from distributed_vgg_f_tpu.ops import lrn as lrn_mod

    # The default impl must be differentiable under jit (the train step is
    # grad-of-jitted) and numerically match the oracle.
    x = jnp.asarray(np.random.default_rng(4).standard_normal(
        (1, 4, 4, 16), dtype=np.float32))
    g = jax.jit(jax.grad(lambda v: lrn_mod.lrn(v).sum()))(x)
    g_o = jax.grad(lambda v: local_response_norm(v).sum())(x)
    np.testing.assert_allclose(np.asarray(g), np.asarray(g_o), rtol=1e-4,
                               atol=1e-6)


def test_matmul_vjp_bf16_band_within_tolerance_of_oracle():
    """The bf16 band-matmul path (bf16 operands, fp32 MXU accumulation —
    VERDICT r2 #8): window-sum error ~2^-8 relative enters the normalizer
    scaled by alpha≈1e-4 against the O(1) bias, so forward AND backward stay
    within bf16 representation error of the fp32 oracle."""
    import jax

    from distributed_vgg_f_tpu.ops.lrn import local_response_norm_matmul_vjp

    rng = np.random.default_rng(3)
    x32 = rng.standard_normal((2, 5, 5, 64), dtype=np.float32) * 2.0
    x16 = jnp.asarray(x32, jnp.bfloat16)
    # compare against the oracle ON THE SAME (bf16-rounded) inputs so the
    # measured error is the bf16 PATH's, not the input rounding's
    x_rounded = np.asarray(x16, np.float32)

    got = np.asarray(local_response_norm_matmul_vjp(x16), np.float32)
    want = _numpy_lrn(x_rounded)
    np.testing.assert_allclose(got, want, rtol=2e-2, atol=2e-2)

    def f16(v):
        return jnp.sum(local_response_norm_matmul_vjp(v) ** 2)

    def f32(v):
        return jnp.sum(local_response_norm(v) ** 2)

    g16 = np.asarray(jax.grad(f16)(x16), np.float32)
    g32 = np.asarray(jax.grad(f32)(jnp.asarray(x_rounded)))
    np.testing.assert_allclose(g16, g32, rtol=5e-2, atol=5e-2)
