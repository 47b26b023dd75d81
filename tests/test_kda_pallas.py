"""The delta rule's Pallas kernels (ops/kda_pallas.py) in the Pallas
interpreter on the CPU, at the smallest sizes they take (heads of 128 in
pairs, chunks of 64): output and the five gradients against the
XLA form they replace on the chip (`kda.kda_xla`) and against the literal
recurrence, at the gate's bound too; that the state is handed from chunk to
chunk; which shapes and backends take them; and a whole
`KimiDeltaAttention` layer through them against the plain reference."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.reference import ling3 as ref
from chipbench.reference.ops import Ops
from distributed_vgg_f_tpu.config import ModelConfig, get_config
from distributed_vgg_f_tpu.models import ling3
from distributed_vgg_f_tpu.models.registry import build_model
from distributed_vgg_f_tpu.ops import kda, kda_pallas

TINY = get_config("ling3_flash_tiny")
#: two heads share a register in the kernels' solve: the fewest they take
HEADS, WIDTH = 2, kda_pallas.LANES
#: float32 on both sides: what is left is the order of the sums
TOLERANCE = 1e-4


@pytest.fixture
def interpreted(monkeypatch):
    monkeypatch.setattr(kda_pallas, "INTERPRET", True)


def _rel(a, b):
    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    return float(jnp.linalg.norm(a - b) / (jnp.linalg.norm(b) + 1e-30))


def _inputs(batch=1, seq=128, dtype=jnp.float32, g=None, beta=None, seed=0,
            heads=HEADS):
    keys = jax.random.split(jax.random.key(seed), 5)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    shape = (batch, seq, heads, WIDTH)
    if g is None:
        g = kda.LOWER_BOUND * jax.nn.sigmoid(
            2 * jax.random.normal(keys[3], shape))
    if beta is None:
        beta = jax.nn.sigmoid(jax.random.normal(keys[4], shape[:3]))
    return ((unit(jax.random.normal(keys[0], shape)) * WIDTH ** -0.5
             ).astype(dtype),
            unit(jax.random.normal(keys[1], shape)).astype(dtype),
            jax.random.normal(keys[2], shape).astype(dtype),
            jnp.broadcast_to(g, shape).astype(jnp.float32),
            jnp.broadcast_to(beta, shape[:3]).astype(jnp.float32))


def _value_and_grads(fn, args):
    weigh = jax.random.normal(jax.random.key(9), args[2].shape)
    return jax.jit(jax.value_and_grad(
        lambda *a: jnp.sum(fn(*a).astype(jnp.float32) * weigh),
        argnums=(0, 1, 2, 3, 4)))(*args)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("batch,chunks,heads", [(2, 2, 2), (1, 6, 4)])
def test_kernels_equal_the_xla_form_and_the_recurrence(
        batch, chunks, heads, dtype, interpreted, monkeypatch):
    """Values and the gradients of q, k, v, g and beta; one grid step, and
    two groups of two heads over two blocks of three chunks. In bf16 both
    chunked forms round their products' operands where the recurrence
    (float32 inside) rounds none."""
    monkeypatch.setattr(kda_pallas, "HEADS", 2)
    monkeypatch.setattr(kda_pallas, "CHUNKS", 3)
    args = _inputs(batch, 64 * chunks, jnp.dtype(dtype), heads=heads)
    assert kda.takes_kernels(args[1].shape, args[2].shape, 64)
    got, d_got = _value_and_grads(kda.kda, args)
    limit = TOLERANCE if dtype == "float32" else 5e-2
    for other in (kda.kda_xla, kda.kda_recurrent):
        want, d_want = _value_and_grads(other, args)
        assert _rel(jax.jit(kda.kda)(*args), jax.jit(other)(*args)) < limit
        assert abs(float(got) - float(want)) < limit * (abs(float(want)) + 1)
        for name, a, b in zip("qkvgb", d_got, d_want):
            assert a.dtype == b.dtype and a.shape == b.shape, name
            assert _rel(a, b) < limit, (other.__name__, name)


def test_a_gate_at_its_bound_stays_finite_and_equals_the_recurrence(
        interpreted):
    """g = -5 at every position of three chunks: exp(-G_j) alone would
    overflow float32 after 17 positions."""
    args = _inputs(seq=192, g=jnp.float32(kda.LOWER_BOUND))
    got, d_got = _value_and_grads(kda.kda, args)
    want, d_want = _value_and_grads(kda.kda_recurrent, args)
    assert np.isfinite(float(got))
    assert all(bool(jnp.all(jnp.isfinite(d))) for d in d_got)
    assert _rel(jax.jit(kda.kda)(*args),
                jax.jit(kda.kda_recurrent)(*args)) < TOLERANCE
    for name, a, b in zip("qkvgb", d_got, d_want):
        # g's gradient is a difference of large terms at the bound
        assert _rel(a, b) < (1e-3 if name == "g" else TOLERANCE), name


#: gates a position from 0 down to where the bounded form no longer holds,
#: (b, t, h, dk) from a key: Kimi Linear's softplus gate at a seeded
#: decay rate of up to 16 (its tail near -60), gates spread down to -60,
#: and gates that forget at once (-1e4 at a tenth of the entries: exp
#: underflows to 0, and nothing may turn into a NaN)
GATES = {
    "softplus": lambda key, shape: -jnp.exp(jax.random.uniform(
        key, shape[2:3] + (1,), minval=0.0, maxval=jnp.log(16.0)))
    * jax.nn.softplus(2 * jax.random.normal(key, shape)),
    "to_minus_60": lambda key, shape: -60.0 * jax.random.uniform(key, shape),
    "minus_1e4": lambda key, shape: jnp.where(
        jax.random.uniform(key, shape) < 0.1, -1e4,
        -0.3 * jax.random.uniform(jax.random.fold_in(key, 1), shape)),
}


@pytest.mark.parametrize("impl", ["xla", "pallas"])
@pytest.mark.parametrize("gate", sorted(GATES))
def test_unbounded_gate_and_doubled_beta_equal_the_recurrence(
        impl, gate, monkeypatch):
    """The unbounded form (`bounded=False`) against the literal recurrence,
    values and the five gradients, float32, over three chunks, with beta
    in (0, 2) (negative eigenvalues); the bounded form on the same gates is
    not the recurrence. At -1e4 the running sums of g near -6e4 hold 4e-3
    of absolute rounding, which the pairwise decays of small gates carry
    (2e-3 measured, the XLA form's dg 1e-2): its tolerance is wider."""
    if impl == "pallas":
        monkeypatch.setattr(kda_pallas, "INTERPRET", True)
        monkeypatch.setattr(kda_pallas, "HEADS", 2)
        monkeypatch.setattr(kda_pallas, "CHUNKS", 2)
    keys = jax.random.split(jax.random.key(21), 2)
    shape = (1, 192, HEADS, WIDTH)
    args = _inputs(seq=192, g=GATES[gate](keys[0], shape),
                   beta=2 * jax.nn.sigmoid(jax.random.normal(keys[1],
                                                             shape[:3])))
    unbounded = functools.partial(
        kda.kda if impl == "pallas" else kda.kda_xla, bounded=False)
    assert kda.takes_kernels(args[1].shape, args[2].shape, 64) \
        == (impl == "pallas")
    got, d_got = _value_and_grads(unbounded, args)
    want, d_want = _value_and_grads(kda.kda_recurrent, args)
    limit = 2e-2 if gate == "minus_1e4" else TOLERANCE
    assert np.isfinite(float(got))
    assert abs(float(got) - float(want)) < limit * abs(float(want))
    for name, a, b in zip("qkvgb", d_got, d_want):
        assert bool(jnp.all(jnp.isfinite(a))), name
        assert _rel(a, b) < limit, name
    if gate == "softplus":
        _, d_bounded = _value_and_grads(kda.kda_xla, args)
        assert max(_rel(a, b) for a, b in zip(d_bounded, d_want)) \
            > 100 * TOLERANCE


def test_the_bounded_form_gives_what_it_gave_before(monkeypatch):
    """Ling's path (`bounded`, the default) is the parent's program: the
    digest of the kernels' (in the interpreter) and the XLA form's values
    and gradients on these seeded bf16 inputs, to the last bit, as the
    implementation before the unbounded form gave them."""
    import hashlib
    monkeypatch.setattr(kda_pallas, "INTERPRET", True)
    monkeypatch.setattr(kda_pallas, "HEADS", 2)
    monkeypatch.setattr(kda_pallas, "CHUNKS", 3)
    shape = (1, 192, 4, WIDTH)
    keys = jax.random.split(jax.random.key(7), 6)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    args = ((unit(jax.random.normal(keys[0], shape)) * WIDTH ** -0.5
             ).astype(jnp.bfloat16),
            unit(jax.random.normal(keys[1], shape)).astype(jnp.bfloat16),
            jax.random.normal(keys[2], shape).astype(jnp.bfloat16),
            kda.LOWER_BOUND * jax.nn.sigmoid(
                2 * jax.random.normal(keys[3], shape)),
            jax.nn.sigmoid(jax.random.normal(keys[4], shape[:3])))
    weigh = jax.random.normal(keys[5], shape)
    for fn, before in ((kda.kda, "20abdbc8ae232a53"),
                       (kda.kda_xla, "cb7f45aff674337d")):
        out = jax.jit(jax.value_and_grad(
            lambda *a: jnp.sum(fn(*a) * weigh), argnums=(0, 1, 2, 3, 4)))(
                *args)
        digest = hashlib.sha1()
        for x in jax.tree.leaves(out):
            digest.update(np.asarray(x).tobytes())
        assert digest.hexdigest()[:16] == before, fn.__name__


def test_nothing_written_gives_exactly_nothing(interpreted):
    args = _inputs(beta=jnp.float32(0.0))
    assert float(jnp.max(jnp.abs(jax.jit(kda.kda)(*args)))) == 0.0


def test_the_state_is_handed_from_chunk_to_chunk(interpreted):
    args = _inputs(seq=192)
    forgetful = jax.jit(lambda *a: kda.kda_recurrent(*a, reset_every=64))
    assert _rel(forgetful(*args), jax.jit(kda.kda)(*args)) > 100 * TOLERANCE


@pytest.mark.parametrize("shape,taken", [
    (dict(t=128, h=HEADS, dk=128, dv=128, chunk=64), True),
    (dict(t=8192, h=32, dk=128, dv=128, chunk=64), True),      # the cell's
    (dict(t=128, h=4, dk=16, dv=16, chunk=64), False),    # the tiny preset
    (dict(t=128, h=HEADS, dk=128, dv=128, chunk=32), False),
    (dict(t=96, h=HEADS, dk=128, dv=128, chunk=64), False),
    (dict(t=128, h=3, dk=128, dv=128, chunk=64), False),
    (dict(t=128, h=HEADS, dk=128, dv=64, chunk=64), False)])
def test_shapes_and_backend_choose_the_kernels(shape, taken, monkeypatch):
    k = (2, shape["t"], shape["h"], shape["dk"])
    v = (2, shape["t"], shape["h"], shape["dv"])
    assert kda_pallas.applies(k, v, shape["chunk"]) == taken
    assert not kda.takes_kernels(k, v, shape["chunk"])       # the CPU
    monkeypatch.setattr(kda_pallas, "INTERPRET", True)
    assert kda.takes_kernels(k, v, shape["chunk"]) == taken


def test_off_the_kernels_kda_is_the_xla_form():
    """On the CPU with the interpreter off `kda.kda` lowers to what
    `kda.kda_xla` lowers to, at sizes the kernels would take too."""
    args = _inputs()
    text = lambda fn: jax.jit(lambda *a: fn(*a)).lower(*args).as_text()
    assert text(kda.kda) == text(kda.kda_xla)
    assert "pallas" not in text(kda.kda)


def test_layer_through_the_kernels_matches_the_reference(interpreted):
    """`KimiDeltaAttention` with two heads of 128 on two sequences of two
    chunks, float32: value and the gradients of the input and of every
    weight against chipbench/reference/ling3.py's literal recurrence."""
    extra = {**TINY.model.extra, "num_attention_heads": HEADS,
             "head_dim": WIDTH}
    model = build_model(ModelConfig(
        name="ling3", num_classes=TINY.model.num_classes,
        compute_dtype="float32", extra=extra))
    layer = ling3.KimiDeltaAttention(**model.layers["kda"],
                                     compute_dtype=jnp.float32)
    u = jax.random.normal(jax.random.key(4), (2, 128, extra["hidden_size"]))
    weigh = jax.random.normal(jax.random.key(5), u.shape)
    p = layer.init(jax.random.key(1), u)["params"]
    _, sown = layer.apply({"params": p}, u, mutable=["counters"])
    assert sown["counters"]["kda_kernel"][0] == 1

    def program(p, u):
        return jnp.sum(layer.apply({"params": p}, u) * weigh)

    def reference(p, u):
        f32 = Ops("float32")
        return jnp.sum(jnp.stack([ref.kda(p, row, extra, f32)
                                  for row in u]) * weigh)

    got, (d_p, d_u) = jax.jit(jax.value_and_grad(
        program, argnums=(0, 1)))(p, u)
    want, (want_p, want_u) = jax.jit(jax.value_and_grad(
        reference, argnums=(0, 1)))(p, u)
    assert abs(float(got) - float(want)) < TOLERANCE * abs(float(want)) + 1e-4
    assert _rel(d_u, want_u) < TOLERANCE
    gaps = jax.tree.map(_rel, d_p, want_p)
    assert max(jax.tree.leaves(gaps)) < TOLERANCE, gaps
