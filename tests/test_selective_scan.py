"""Mamba-1's selective scan (ops/selective_scan.py): the XLA form against a
position-by-position loop, the Pallas kernels (ops/selective_scan_pallas.py,
in the interpreter at small tiles) against the XLA form, forward and every
gradient, to float32 rounding, and a planted state reset at a chunk
boundary that the comparison catches."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_vgg_f_tpu.ops import selective_scan as ss
from distributed_vgg_f_tpu.ops import selective_scan_pallas as sp

#: two sequences, two chunks of 32, two blocks of 128 channels, state 16
B, T, D, N, CHUNK = 2, 64, 256, 16, 32


def _inputs(seed=0, t=T, d=D, dtype=jnp.float32):
    k = jax.random.split(jax.random.key(seed), 6)
    x = jax.random.normal(k[0], (B, t, d)).astype(dtype)
    dt = jax.nn.softplus(jax.random.normal(k[1], (B, t, d)) - 1.0)
    a = -jnp.exp(jax.random.normal(k[2], (d, N)))
    b_in = jax.random.normal(k[3], (B, t, N)).astype(dtype)
    c_out = jax.random.normal(k[4], (B, t, N)).astype(dtype)
    skip = jax.random.normal(k[5], (d,))
    return x, dt, a, b_in, c_out, skip


def _loop(x, dt, a, b_in, c_out, skip, *, reset_every=None):
    """The recurrence written out, one position after the other."""
    h = jnp.zeros((x.shape[0], x.shape[2], a.shape[1]))
    ys = []
    for t in range(x.shape[1]):
        if reset_every and t % reset_every == 0:
            h = jnp.zeros_like(h)
        h = jnp.exp(dt[:, t, :, None] * a) * h \
            + (dt[:, t] * x[:, t])[..., None] * b_in[:, t, None, :]
        ys.append(jnp.sum(h * c_out[:, t, None, :], -1))
    return jnp.stack(ys, 1) + x * skip


def _gap(got, want):
    return float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))


@pytest.fixture
def interpret(monkeypatch):
    monkeypatch.setattr(sp, "INTERPRET", True)


def test_xla_form_is_the_recurrence():
    args = _inputs()
    assert _gap(ss.selective_scan_xla(*args, CHUNK), _loop(*args)) < 1e-6


def test_a_state_reset_at_a_chunk_boundary_is_caught():
    """What a chunked scan that forgets to hand its state on would give:
    the comparison above tells it by four orders of magnitude."""
    args = _inputs()
    assert _gap(_loop(*args, reset_every=CHUNK),
                ss.selective_scan_xla(*args, CHUNK)) > 1e-2


def test_kernels_forward_agrees_with_the_xla_form(interpret):
    args = _inputs(1)
    assert sp.applies(args[0].shape, N, CHUNK)
    assert ss.takes_kernels(args[0].shape, N, CHUNK)
    got = sp.scan(*args, chunk=CHUNK)
    assert got.dtype == jnp.float32
    assert _gap(got, ss.selective_scan_xla(*args, CHUNK)) < 1e-6


def test_kernels_gradients_agree_with_the_xla_form(interpret):
    """Every input's gradient under a weighted sum of y: float32 rounding
    apart (the kernels sum dA, dB and dC over blocks and chunks in another
    order)."""
    args = _inputs(2)
    weights = jax.random.normal(jax.random.key(9), (B, T, D))

    def grads(fn):
        return jax.grad(lambda *a: jnp.sum(fn(*a) * weights),
                        argnums=tuple(range(6)))(*args)

    want = grads(lambda *a: ss.selective_scan_xla(*a, CHUNK))
    got = grads(lambda *a: sp.scan(*a, chunk=CHUNK))
    for name, g, w in zip(("x", "dt", "A", "B", "C", "D"), got, want):
        assert g.shape == w.shape and g.dtype == w.dtype, name
        assert _gap(g, w) < 1e-5, name


def test_kernels_take_the_compute_dtype_and_the_reset_is_caught(interpret):
    """bf16 x, B and C as the model hands them; the reset fault read off
    the kernels' own output."""
    args = _inputs(3, dtype=jnp.bfloat16)
    got = sp.scan(*args, chunk=CHUNK)
    assert _gap(got, ss.selective_scan_xla(*args, CHUNK)) < 1e-6
    as_f32 = [a.astype(jnp.float32) for a in args]
    assert _gap(_loop(*as_f32, reset_every=CHUNK), got) > 1e-2


@pytest.mark.parametrize("shape, state, chunk, takes", [
    ((1, 4096, 5120), 16, 128, True),     # the cell's
    ((2, 256, 64), 16, 128, False),       # the tiny preset's 64 channels
    ((1, 100, 256), 16, 128, False),      # positions no whole chunk
    ((1, 256, 256), 12, 128, False),      # a state no sublane tile
])
def test_the_kernels_apply_by_shape(shape, state, chunk, takes):
    assert sp.applies(shape, state, chunk) is takes
    assert not ss.takes_kernels(shape, state, chunk)   # the CPU's backend


def test_an_uneven_chunk_is_refused():
    with pytest.raises(ValueError):
        ss.selective_scan(*_inputs(t=48), chunk=CHUNK)
