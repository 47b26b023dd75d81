"""The return of the expert share's rows to their tokens through the Pallas
kernel of ops/routed_combine_pallas.py, in the Pallas interpreter on the
CPU: the weighted forward sum (first window and a later one), x's cotangent
in its own dtype, and `jax.grad` through `mistral4.routed_experts` and a
whole `ExpertShare`, each against XLA's scatter-add they replace on the chip
(ops/routed_combine.py); tokens with several held rows, dead rows whose
tokens repeat, an empty window, two passes, rows sliced by lanes; and which
shapes and backends take the kernel."""

import jax
import jax.numpy as jnp
import pytest

from distributed_vgg_f_tpu.config import get_config
from distributed_vgg_f_tpu.models import mistral4
from distributed_vgg_f_tpu.ops import routed_combine
from distributed_vgg_f_tpu.ops import routed_combine_pallas as kernels

TOKENS, HIDDEN, CAPACITY = 64, 256, 48
#: float32 on both sides: what is left is the order of a token's sums
TOLERANCE = 1e-6


@pytest.fixture
def interpreted(monkeypatch):
    """The interpreter on, and blocks of 16 tokens, so that small sizes
    have several; the jitted op traced afresh under them."""
    monkeypatch.setattr(kernels, "INTERPRET", True)
    monkeypatch.setattr(kernels, "BLOCK", 16)
    kernels.summed.clear_cache()
    yield
    kernels.summed.clear_cache()


def _rel(a, b):
    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    return float(jnp.linalg.norm(a - b) / (jnp.linalg.norm(b) + 1e-30))


def _window(kind, dtype, seed=0):
    """rows, weight (capacity, 1), token, live (capacity, 1) of a window:
    `mixed` 30 live rows, several on one token, and dead rows that repeat
    a few tokens (as the absent experts' assignments do); `empty` no live
    row; `full` every row live."""
    keys = jax.random.split(jax.random.key(seed), 3)
    rows = jax.random.normal(keys[0], (CAPACITY, HIDDEN)).astype(dtype)
    weight = jax.random.uniform(keys[1], (CAPACITY, 1))
    token = jax.random.randint(keys[2], (CAPACITY,), 0, TOKENS)
    token = token.at[:4].set(5)                        # one token, 4 rows
    token = token.at[30:].set(jnp.arange(18) % 3 + 40)  # dead: 3 tokens
    held = {"mixed": 30, "empty": 0, "full": CAPACITY}[kind]
    live = (jnp.arange(CAPACITY) < held)[:, None]
    return rows, weight, token, live


def _xla_sum(into, rows, weight, token, live):
    return into.at[token].add(jnp.where(live, rows, 0).astype(jnp.float32)
                              * weight)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("kind", ["mixed", "empty", "full"])
def test_forward_sum_equals_the_scatter(kind, dtype, interpreted):
    """The first window into nothing (the buffer `empty_sum` gives, never
    read), and a later one added to the sum so far, as float32."""
    rows, weight, token, live = _window(kind, jnp.dtype(dtype))
    x = jnp.zeros((TOKENS, HIDDEN), dtype)
    assert routed_combine.takes_kernel(x.shape, x.dtype, CAPACITY)
    first = jax.jit(lambda *a: routed_combine.add_weighted(
        routed_combine.empty_sum(x, CAPACITY), *a, first=True))(
        rows, weight, token, live)
    want = _xla_sum(jnp.zeros((TOKENS, HIDDEN)), rows, weight, token, live)
    assert first.dtype == jnp.float32
    assert _rel(first, want) < TOLERANCE
    into = jax.random.normal(jax.random.key(9), (TOKENS, HIDDEN))
    later = jax.jit(lambda *a: routed_combine.add_weighted(
        *a, first=False))(into, rows, weight, token, live)
    assert _rel(later, _xla_sum(into, rows, weight, token, live)) < TOLERANCE
    if kind == "empty":
        assert bool(jnp.all(first == 0)) and bool(jnp.all(later == into))


def test_the_first_window_never_reads_the_sum(interpreted):
    """With `first` the kernel writes every token from zeros: NaN in the
    buffer reaches nothing."""
    rows, weight, token, live = _window("mixed", jnp.float32)
    junk = jnp.full((TOKENS, HIDDEN), jnp.nan)
    got = routed_combine.add_weighted(junk, rows, weight, token, live,
                                      first=True)
    want = _xla_sum(jnp.zeros((TOKENS, HIDDEN)), rows, weight, token, live)
    assert _rel(got, want) < TOLERANCE


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("kind", ["mixed", "empty", "full"])
def test_row_cotangents_to_tokens_equal_the_scatter(kind, dtype,
                                                    interpreted):
    """x's cotangent in x's dtype. The kernel sums a token's rows in float32
    and rounds once: in bf16 it is the float32 sum rounded, where XLA's
    scatter on the CPU may round after each add."""
    dtype = jnp.dtype(dtype)
    rows, _, token, live = _window(kind, dtype)
    x = jnp.zeros((TOKENS, HIDDEN), dtype)
    got = jax.jit(routed_combine.to_tokens)(rows, token, live, x)
    exact = jnp.zeros((TOKENS, HIDDEN)).at[token].add(
        jnp.where(live, rows, 0).astype(jnp.float32))
    want = jnp.zeros_like(x).at[token].add(jnp.where(live, rows, 0))
    assert got.dtype == dtype and got.shape == x.shape
    assert _rel(got, want) < (TOLERANCE if dtype == jnp.float32 else 4e-3)
    assert _rel(got, exact) <= _rel(want, exact) + TOLERANCE


@pytest.mark.parametrize("direction", ["forward", "backward"])
def test_rows_sliced_by_lanes_sum_alike(direction, interpreted,
                                        monkeypatch):
    """A VMEM budget that holds the window one lane tile wide: the grid
    walks two slices of lanes, each over every block of tokens."""
    dtype = jnp.bfloat16
    monkeypatch.setattr(kernels, "VMEM_BUDGET", kernels.vmem_bytes(
        CAPACITY, kernels.LANES, dtype))
    assert kernels.lanes_a_step(CAPACITY, HIDDEN, dtype) == kernels.LANES
    rows, weight, token, live = _window("mixed", dtype)
    x = jnp.zeros((TOKENS, HIDDEN), dtype)
    if direction == "forward":
        into = jax.random.normal(jax.random.key(9), (TOKENS, HIDDEN))
        got = routed_combine.add_weighted(into, rows, weight, token, live,
                                          first=False)
        want = _xla_sum(into, rows, weight, token, live)
    else:
        got = routed_combine.to_tokens(rows, token, live, x)
        want = jnp.zeros((TOKENS, HIDDEN)).at[token].add(
            jnp.where(live, rows, 0).astype(jnp.float32)).astype(dtype)
    assert _rel(got, want) < TOLERANCE


def _share_inputs(dtype, passes):
    """An expert share of 4 of 8 experts, top-2, over 64 tokens: its rows,
    the held matrices and the routing as `ExpertShare` makes them, with
    buffers of `routed_capacity` rows that take the load in `passes`."""
    held, k, width = 4, 2, 64
    keys = jax.random.split(jax.random.key(3), 6)
    x = jax.random.normal(keys[0], (TOKENS, HIDDEN)).astype(dtype)
    mats = tuple((jax.random.normal(keys[i], shape) * 0.1).astype(dtype)
                 for i, shape in ((1, (held, HIDDEN, width)),
                                  (2, (held, HIDDEN, width)),
                                  (3, (held, width, HIDDEN))))
    weights, local = mistral4.route(jax.nn.softmax(
        jax.random.normal(keys[4], (TOKENS, 8)), axis=-1), k, 0, held)
    flat = local.reshape(-1)
    order = jnp.argsort(flat, stable=True)
    load = jnp.bincount(flat, length=held + 1)[:held].astype(jnp.int32)
    capacity = int(-(-int(load.sum()) // passes) + 15) // 16 * 16
    assert int(mistral4.routed_passes(load, capacity)) == passes
    order = jnp.pad(order, (0, -(TOKENS * k) % capacity))
    return x, mats, weights, order, load, capacity


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("passes", [1, 2])
def test_grad_through_routed_experts_equals_the_scatter(passes, dtype,
                                                        interpreted,
                                                        monkeypatch):
    """`routed_experts` with its hand-written backward pass: the value and
    the gradients of x, the three matrices and the weights, the kernel
    against XLA's scatter-add; two passes add the second window to the
    first's sum (forward) and the windows' cotangents (backward)."""
    dtype = jnp.dtype(dtype)
    x, mats, weights, order, load, capacity = _share_inputs(dtype, passes)
    weigh = jax.random.normal(jax.random.key(8), (TOKENS, HIDDEN))

    def loss(x, mats, weights):
        return jnp.sum(mistral4.routed_experts(
            x, mats, weights, order, load, capacity) * weigh)

    run = lambda: jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2)))(
        x, mats, weights)
    assert routed_combine.takes_kernel(x.shape, dtype, capacity)
    got, got_grads = run()
    monkeypatch.setattr(kernels, "INTERPRET", False)
    assert not routed_combine.takes_kernel(x.shape, dtype, capacity)
    want, want_grads = run()
    limit = 1e-5 if dtype == jnp.float32 else 1e-2
    assert abs(float(got) - float(want)) < limit * abs(float(want))
    gaps = jax.tree.map(_rel, got_grads, want_grads)
    assert max(jax.tree.leaves(gaps)) < limit, gaps


def test_a_share_sows_which_form_it_took(interpreted, monkeypatch):
    """`combine_kernel` 1 where both directions take the kernel, 0 where
    XLA's scatter; the layer's value and gradient alike either way."""
    layer = mistral4.ExpertShare(
        n_routed_experts=8, num_experts_per_tok=2, moe_intermediate_size=64,
        n_shared_experts=1, first_expert=0, experts_held=4,
        compute_dtype=jnp.float32)
    u = jax.random.normal(jax.random.key(1), (1, TOKENS, HIDDEN))
    params = layer.init(jax.random.key(2), u)["params"]

    def run():
        (out, _), sown = layer.apply({"params": params}, u,
                                     mutable=["counters"])
        grad = jax.grad(lambda p, u: jnp.sum(layer.apply(
            {"params": p}, u)[0] ** 2), argnums=(0, 1))(params, u)
        return int(sown["counters"]["combine_kernel"][0]), out, grad

    taken, out, grad = run()
    monkeypatch.setattr(kernels, "INTERPRET", False)
    plain, want, want_grad = run()
    assert (taken, plain) == (1, 0)
    assert _rel(out, want) < 1e-5
    gaps = jax.tree.map(_rel, grad, want_grad)
    assert max(jax.tree.leaves(gaps)) < 1e-5, gaps


def test_every_call_site_shares_one_trace_a_direction(interpreted):
    """One jitted op: windows of any layer trace it once forward and once
    backward (the scope is a static name, the rest operands)."""
    rows, weight, token, live = _window("mixed", jnp.bfloat16)
    x = jnp.zeros((TOKENS, HIDDEN), jnp.bfloat16)
    for seed in range(3):
        routed_combine.add_weighted(
            routed_combine.empty_sum(x, CAPACITY), rows * seed, weight,
            token, live, first=seed == 0)
        routed_combine.to_tokens(rows * seed, token, live, x)
    assert kernels.summed._cache_size() == 2


def test_off_the_kernel_the_sums_are_xla_s_scatter():
    """On the CPU with the interpreter off: `empty_sum` is zeros and both
    sums lower to scatters, with no Pallas call."""
    rows, weight, token, live = _window("mixed", jnp.bfloat16)
    x = jnp.zeros((TOKENS, HIDDEN), jnp.bfloat16)
    assert not routed_combine.takes_kernel(x.shape, x.dtype, CAPACITY)
    assert bool(jnp.all(routed_combine.empty_sum(x, CAPACITY) == 0))
    for fn in (lambda: routed_combine.add_weighted(
            routed_combine.empty_sum(x, CAPACITY), rows, weight, token, live,
            first=True),
            lambda: routed_combine.to_tokens(rows, token, live, x)):
        text = jax.jit(fn).lower().as_text()
        assert "scatter" in text and "pallas" not in text


def _cell_shape(preset):
    """(tokens, hidden as the routed path has it, capacity) of a preset."""
    cfg = get_config(preset)
    e = cfg.model.extra
    held = e.get("experts_held", e["n_routed_experts"])
    tokens = cfg.data.global_batch_size * e["seq_len"]
    hidden = e["hidden_size"] + mistral4._to_whole_tiles(e["hidden_size"])
    return tokens, hidden, mistral4.routed_capacity(
        tokens * e["num_experts_per_tok"], held, e["n_routed_experts"])


@pytest.mark.parametrize("preset,taken,lanes", [
    ("ling3_flash_ep64", True, 2560),
    ("mistral_small4_119b_ep16", True, 4096),
    ("solar_open2_250b_ep40", True, 4096),
    ("nemotron3_nano_30b_ep8", True, 768),    # 24,576 rows: a quarter wide
    ("mistral_small4_tiny", False, None),     # hidden of 64
    ("nemotron3_nano_tiny", False, None),
    ("ling3_flash_tiny", False, None),
    ("solar_open2_tiny", False, None)])
def test_shapes_and_backend_choose_the_kernel(preset, taken, lanes,
                                              monkeypatch):
    """The four language cells take the kernel on a TPU, the tiny presets
    never; on the CPU none does unless the interpreter is on."""
    tokens, hidden, capacity = _cell_shape(preset)
    dtype = jnp.dtype(get_config(preset).model.compute_dtype)
    shape = (tokens, hidden)
    assert kernels.applies(tokens, capacity, hidden, dtype) == taken
    if taken:
        assert kernels.lanes_a_step(capacity, hidden, dtype) == lanes
    assert not routed_combine.takes_kernel(shape, dtype, capacity)
    monkeypatch.setattr(kernels, "INTERPRET", True)
    assert routed_combine.takes_kernel(shape, dtype, capacity) == taken
    monkeypatch.setattr(kernels, "INTERPRET", False)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert routed_combine.takes_kernel(shape, dtype, capacity) == taken


@pytest.mark.parametrize("shape,taken", [
    (dict(tokens=512, capacity=64, hidden=256, dtype="bfloat16"), True),
    (dict(tokens=512, capacity=64, hidden=200, dtype="bfloat16"), False),
    (dict(tokens=512, capacity=56, hidden=256, dtype="bfloat16"), False),
    (dict(tokens=500, capacity=64, hidden=256, dtype="bfloat16"), False),
    (dict(tokens=512, capacity=64, hidden=256, dtype="float16"), False),
    (dict(tokens=512, capacity=64, hidden=256, dtype="float32"), True)])
def test_whole_tiles_decide(shape, taken):
    """Lanes, row tiles, blocks of tokens and the dtypes a row is read in."""
    assert kernels.applies(shape["tokens"], shape["capacity"],
                           shape["hidden"], jnp.dtype(shape["dtype"])) == taken
