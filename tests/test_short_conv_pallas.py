"""The short convolution's Pallas kernels (ops/short_conv_pallas.py) in the
Pallas interpreter on the CPU, at the smallest sizes they take (heads of
128, whole loop steps of rows): y, dx and dtaps against the XLA form they
replace on the chip (`short_conv.conv_silu_heads_xla`), with and without
the head norm, over several blocks of rows and groups of heads so that the
rows before and after cross a block's edge both ways; that a sequence's
start sees zeros and no other sequence's rows; which shapes and backends
take them; and a whole `KimiDeltaAttention` layer through both pairs of
kernels against the plain reference."""

import jax
import jax.numpy as jnp
import pytest

from chipbench.reference import ling3 as ref
from chipbench.reference.ops import Ops
from distributed_vgg_f_tpu.config import ModelConfig, get_config
from distributed_vgg_f_tpu.models import ling3
from distributed_vgg_f_tpu.models.registry import build_model
from distributed_vgg_f_tpu.ops import kda_pallas, short_conv
from distributed_vgg_f_tpu.ops import short_conv_pallas as kernels

TINY = get_config("ling3_flash_tiny")
HEADS, WIDTH = 2, kernels.LANES
#: float32 on both sides: what is left is the order of the sums
TOLERANCE = 1e-4
#: q's, k's and v's in the layer
SCALES = {"q_scale": WIDTH ** -0.5, "k_scale": 1.0, "no_norm": None}


@pytest.fixture
def interpreted(monkeypatch):
    """The interpreter on, and grid steps of 64 rows of one head walked 32
    rows a loop step, so that small sizes have several blocks, groups and
    steps."""
    monkeypatch.setattr(kernels, "INTERPRET", True)
    monkeypatch.setattr(kernels, "ROWS", 64)
    monkeypatch.setattr(kernels, "SUB", 32)
    monkeypatch.setattr(kernels, "STEP", 32)
    monkeypatch.setattr(kernels, "HEADS", 1)


def _rel(a, b):
    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    return float(jnp.linalg.norm(a - b) / (jnp.linalg.norm(b) + 1e-30))


def _inputs(batch, seq, dtype=jnp.float32, heads=HEADS, taps=4, seed=0):
    keys = jax.random.split(jax.random.key(seed), 3)
    return (jax.random.normal(keys[0], (batch, seq, heads * WIDTH)
                              ).astype(dtype),
            0.5 * jax.random.normal(keys[1], (taps, heads * WIDTH)),
            jax.random.normal(keys[2], (batch, seq, heads, WIDTH)))


def _value_and_grads(fn, x, taps, weigh, scale):
    heads = weigh.shape[2]
    y = jax.jit(lambda x, taps: fn(x, taps, heads, scale))(x, taps)
    grads = jax.jit(jax.grad(lambda x, taps: jnp.sum(
        fn(x, taps, heads, scale).astype(jnp.float32) * weigh),
        argnums=(0, 1)))(x, taps)
    return (y, *grads)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("scale", list(SCALES))
@pytest.mark.parametrize("batch,seq,taps", [(2, 192, 4), (1, 96, 3)])
def test_kernels_equal_the_xla_form(batch, seq, taps, scale, dtype,
                                    interpreted):
    """y, dx and dtaps; three blocks of 64 rows (two loop steps each) of two
    groups of one head, and three blocks of one loop step with three taps.
    In bf16 both forms round x, y and dx once, at the same places."""
    x, taps, weigh = _inputs(batch, seq, jnp.dtype(dtype), taps=taps)
    assert short_conv.takes_kernels(x.shape, taps.shape, HEADS)
    got = _value_and_grads(short_conv.conv_silu_heads, x, taps, weigh,
                           SCALES[scale])
    want = _value_and_grads(short_conv.conv_silu_heads_xla, x, taps, weigh,
                            SCALES[scale])
    # (a bf16 y or dx differs where a float32 sum's last bits decide its
    # rounding: one step of 2^-8 on a few elements)
    limit = TOLERANCE if dtype == "float32" else 2e-3
    for name, a, b in zip(("y", "dx", "dtaps"), got, want):
        assert a.dtype == b.dtype and a.shape == b.shape, name
        assert _rel(a, b) < (TOLERANCE if name == "dtaps" else limit), name


@pytest.mark.parametrize("scale", ["k_scale", "no_norm"])
def test_a_sequence_s_start_sees_zeros_not_its_neighbour(scale, interpreted):
    """Two sequences in a batch against each alone: the second's first
    three positions (and every other) see nothing of the first's last rows,
    and the first's cotangents nothing of the second's first rows."""
    x, taps, weigh = _inputs(2, 128)
    both = _value_and_grads(short_conv.conv_silu_heads, x, taps, weigh,
                            SCALES[scale])
    alone = [_value_and_grads(short_conv.conv_silu_heads, x[i:i + 1], taps,
                              weigh[i:i + 1], SCALES[scale])
             for i in range(2)]
    for i in range(2):
        assert bool(jnp.all(both[0][i, :3] == alone[i][0][0, :3]))
        assert bool(jnp.all(both[0][i] == alone[i][0][0]))
        assert bool(jnp.all(both[1][i] == alone[i][1][0]))
    assert _rel(both[2], alone[0][2] + alone[1][2]) < TOLERANCE
    # and the rows before a block's edge do reach over it: zeroing them
    # changes the next block's first rows
    cut = x.at[:, 61:64].set(0.0)
    moved = jax.jit(lambda x: short_conv.conv_silu_heads(
        x, taps, HEADS, SCALES[scale]))(cut)
    assert not bool(jnp.all(moved[:, 64:67] == both[0][:, 64:67]))
    assert bool(jnp.all(moved[:, 67:] == both[0][:, 67:]))


@pytest.mark.parametrize("shape,taken", [
    (dict(t=128, channels=256, heads=2, taps=4), True),
    (dict(t=8192, channels=4096, heads=32, taps=4), True),     # the cell's
    (dict(t=64, channels=64, heads=4, taps=4), False),    # the tiny preset
    (dict(t=8192, channels=6144, heads=96, taps=4), False),    # heads of 64
    (dict(t=100, channels=256, heads=2, taps=4), False),  # no whole steps
    (dict(t=128, channels=256, heads=1, taps=4), False),      # heads of 256
    (dict(t=128, channels=256, heads=2, taps=12), False)])
def test_shapes_and_backend_choose_the_kernels(shape, taken, monkeypatch):
    x = (2, shape["t"], shape["channels"])
    taps = (shape["taps"], shape["channels"])
    assert kernels.applies(x, taps, shape["heads"]) == taken
    assert not short_conv.takes_kernels(x, taps, shape["heads"])   # the CPU
    monkeypatch.setattr(kernels, "INTERPRET", True)
    assert short_conv.takes_kernels(x, taps, shape["heads"]) == taken
    monkeypatch.setattr(kernels, "INTERPRET", False)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert short_conv.takes_kernels(x, taps, shape["heads"]) == taken


def test_off_the_kernels_the_op_is_the_xla_form():
    """On the CPU with the interpreter off `conv_silu_heads` lowers to what
    the XLA form lowers to, at sizes the kernels would take too."""
    x, taps, _ = _inputs(2, 128)
    text = lambda fn: jax.jit(lambda x, taps: fn(
        x, taps, HEADS, 1.0)).lower(x, taps).as_text()
    assert text(short_conv.conv_silu_heads) \
        == text(short_conv.conv_silu_heads_xla)
    assert "pallas" not in text(short_conv.conv_silu_heads)


def test_q_and_k_share_one_trace_and_v_has_its_own(interpreted):
    """The scale is an operand: two variants, with and without the norm."""
    x, taps, _ = _inputs(1, 64)
    kernels.convolved.clear_cache()
    for scale in (WIDTH ** -0.5, 1.0, None, 0.25):
        kernels.convolved(x, taps, scale)
    assert kernels.convolved._cache_size() == 2


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_layer_through_both_kernels_matches_the_reference(dtype, interpreted,
                                                          monkeypatch):
    """`KimiDeltaAttention` with two heads of 128 on two sequences of two
    chunks, the convolutions and the recurrence both through their kernels:
    value and the gradients of the input and of every weight against
    chipbench/reference/ling3.py's literal recurrence in float32; in bf16
    against the layer with the convolutions' XLA form (both round at the
    same places)."""
    monkeypatch.setattr(kda_pallas, "INTERPRET", True)
    dtype = jnp.dtype(dtype)
    extra = {**TINY.model.extra, "num_attention_heads": HEADS,
             "head_dim": WIDTH}
    model = build_model(ModelConfig(
        name="ling3", num_classes=TINY.model.num_classes,
        compute_dtype=dtype.name, extra=extra))
    layer = ling3.KimiDeltaAttention(**model.layers["kda"],
                                     compute_dtype=dtype)
    u = jax.random.normal(jax.random.key(4), (2, 128, extra["hidden_size"]))
    weigh = jax.random.normal(jax.random.key(5), u.shape)
    p = layer.init(jax.random.key(1), u)["params"]
    _, sown = layer.apply({"params": p}, u, mutable=["counters"])
    assert sown["counters"]["kda_conv_kernel"][0] == 1
    assert sown["counters"]["kda_kernel"][0] == 1

    def program(p, u):
        return jnp.sum(layer.apply({"params": p}, u) * weigh)

    def reference(p, u):
        f32 = Ops("float32")
        return jnp.sum(jnp.stack([ref.kda(p, row, extra, f32)
                                  for row in u]) * weigh)

    got, (d_p, d_u) = jax.jit(jax.value_and_grad(
        program, argnums=(0, 1)))(p, u)
    if dtype == jnp.float32:
        limit = TOLERANCE
        want, (want_p, want_u) = jax.jit(jax.value_and_grad(
            reference, argnums=(0, 1)))(p, u)
    else:
        limit = 3e-2
        monkeypatch.setattr(kernels, "INTERPRET", False)
        want, (want_p, want_u) = jax.jit(jax.value_and_grad(
            program, argnums=(0, 1)))(p, u)
    assert abs(float(got) - float(want)) < limit * abs(float(want)) + limit
    assert _rel(d_u, want_u) < limit
    gaps = jax.tree.map(_rel, d_p, want_p)
    assert max(jax.tree.leaves(gaps)) < limit, gaps
