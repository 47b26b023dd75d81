"""The return of the expert share's window rows to their tokens
(ops/routed_combine.py) as one Pallas TPU kernel: every token written once,
each live row read once, the rows of a token summed on the chip.

Why a kernel. XLA:TPU makes `sum.at[token].add(rows)` a scatter that sorts
the window's indices and then adds one row at a time into the (tokens,
hidden) sum, whose separate zero fill comes first. At Ling-3.0-flash's
shapes (4,096 window rows, 2,048 of them live, into (16384, 2560) float32)
that scatter took 6.98 ms a layer for 42 MB of rows on a TPU v5e, where
the same rows into 2048, 2688 or 3072 lanes take 1.1-1.7 ms (PERF.md
section 5). The work needs the live rows in, and the sum out, once.

Layout and walk:

    rows            (capacity, hidden), bf16 or float32: the window's rows as
                    the grouped product (or its gradient) left them; a block
                    is every row, `lanes_a_step` of the hidden lanes wide,
                    fetched into VMEM once
    weight          (capacity,) float32 in SMEM (the forward pass only)
    order, token    (capacity,) int32, scalar-prefetched: the window's rows
                    sorted by token (dead rows keyed past the last token),
                    and each sorted row's token
    starts          (tokens / BLOCK + 1,) int32, scalar-prefetched: where
                    each block of tokens starts in that order
    into            (tokens, hidden) float32 in HBM, aliased to the output:
                    the sum of the earlier windows (a second pass only)
    out             (tokens, hidden): float32 forward, the rows' dtype
                    backward; a block is BLOCK tokens by the rows' lanes

The grid is (slice of lanes, block of tokens), the lanes outermost, so a
slice of the rows stays in VMEM while every block of tokens passes: the
whole width at Ling's, Mistral's and Solar's windows (20 or 16 MiB of bf16),
a quarter of it at Nemotron's 24,576 rows of 3072. A grid step zeroes its
block (or copies in its part of `into`), then walks its rows in token order:
a row is one sublane of `lanes / 128` registers, loaded at its dynamic row,
weighted, and added in float32 into its token's row of the block. So a token
that chose several held experts has its rows summed in VMEM and its row
written once, and dead rows are never read. A bf16 row is read as the 32-bit
words that hold it and its neighbour (Mosaic loads one row of a packed type
only at whole tiles): row 2k is a word's low half, row 2k+1 its high half,
and either half shifted into the high bits is the float32 value. A float32
block is the output itself; a bf16 output is summed in a float32 scratch
block and rounded once.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Tests on the CPU flip this to run the kernel in the Pallas interpreter;
# `routed_combine` then also takes it off a TPU.
INTERPRET = False

LANES = 128
#: tokens a grid step writes
BLOCK = 256
#: rows a window must be a whole number of: one tile of bf16
ROW_TILE = 16
#: what the kernel may ask of VMEM, and what the rows and blocks may take
VMEM_LIMIT = 64 * 1024 * 1024
VMEM_BUDGET = 48 * 1024 * 1024

_F32 = jnp.float32
_HIGH = 0xFFFF0000


def vmem_bytes(capacity: int, lanes: int, dtype) -> int:
    """The window's rows `lanes` wide once, and three float32 blocks of
    tokens as wide (the output's two buffers and the scratch sum)."""
    return capacity * lanes * jnp.dtype(dtype).itemsize \
        + 3 * BLOCK * lanes * 4


def lanes_a_step(capacity: int, hidden: int, dtype) -> int | None:
    """The widest slice of the rows, whole lane tiles that divide `hidden`,
    whose window fits VMEM: all of it at Ling's, Mistral's and Solar's
    windows, 768 of 3072 at Nemotron's 24,576 rows; None where none fits."""
    fits = [n for n in range(LANES, hidden + 1, LANES)
            if hidden % n == 0 and vmem_bytes(capacity, n, dtype)
            <= VMEM_BUDGET]
    return max(fits, default=None)


def applies(tokens: int, capacity: int, hidden: int, dtype) -> bool:
    """Whether the kernel takes a window of `capacity` rows of `hidden`
    into `tokens` tokens: by shape alone. Rows of whole lane tiles, a window
    of whole row tiles, whole blocks of tokens, and a slice of the rows
    within VMEM."""
    return (jnp.dtype(dtype) in (jnp.dtype(jnp.bfloat16), jnp.dtype(_F32))
            and hidden % LANES == 0 and capacity % ROW_TILE == 0
            and tokens % BLOCK == 0
            and lanes_a_step(capacity, hidden, dtype) is not None)


def _row_reader(rows_ref):
    """j -> row j of `rows_ref` as float32 (1, hidden)."""
    if rows_ref.dtype == _F32:
        return lambda j: rows_ref[pl.ds(j, 1), :]
    words = rows_ref.bitcast(jnp.uint32)

    def read(j):
        word = words[pl.ds(j // 2, 1), :]
        high = (word << (16 - 16 * (j % 2)).astype(jnp.uint32)) \
            & jnp.uint32(_HIGH)
        return pltpu.bitcast(high, _F32)
    return read


def _kernel(first_ref, order_ref, token_ref, starts_ref, rows_ref, *refs,
            weighted, adds, staged):
    refs = list(refs)
    weight_ref = refs.pop(0) if weighted else None
    into_ref = refs.pop(0) if adds else None
    out_ref = refs.pop(0)
    sum_ref = refs.pop(0) if staged else out_ref
    lanes = out_ref.shape[1]
    block = pl.program_id(1)
    first_token = pl.multiple_of(block * BLOCK, BLOCK)
    first_lane = pl.multiple_of(pl.program_id(0) * lanes, LANES)

    def zero():
        sum_ref[...] = jnp.zeros_like(sum_ref)

    if adds:
        pl.when(first_ref[0] != 0)(zero)

        @pl.when(first_ref[0] == 0)
        def _():
            copy = pltpu.make_async_copy(
                into_ref.at[pl.ds(first_token, BLOCK),
                            pl.ds(first_lane, lanes)], sum_ref, refs[0])
            copy.start()
            copy.wait()
    else:
        zero()

    read = _row_reader(rows_ref)

    def add(i, carry):
        j = order_ref[i]
        row = read(j)
        if weighted:
            row = row * weight_ref[j]
        at = token_ref[i] - first_token
        sum_ref[pl.ds(at, 1), :] += row
        return carry

    lax.fori_loop(starts_ref[block], starts_ref[block + 1], add, None)
    if staged:
        out_ref[...] = sum_ref[...].astype(out_ref.dtype)


def _combine(rows, weight, into, first, order, token, starts, tokens):
    capacity, hidden = rows.shape
    lanes = lanes_a_step(capacity, hidden, rows.dtype)
    weighted, adds = weight is not None, into is not None
    dtype = _F32 if weighted else rows.dtype
    staged = dtype != _F32
    inputs = [rows] + [weight] * weighted + [into] * adds
    # the slice of lanes outermost: its rows come into VMEM once, while the
    # blocks of tokens pass
    in_specs = [pl.BlockSpec((capacity, lanes), lambda c, b, *_: (0, c),
                             pipeline_mode=pl.Buffered(1))]
    in_specs += [pl.BlockSpec(memory_space=pltpu.SMEM)] * weighted
    in_specs += [pl.BlockSpec(memory_space=pl.ANY)] * adds
    scratch = [pltpu.VMEM((BLOCK, lanes), _F32)] * staged
    scratch += [pltpu.SemaphoreType.DMA(())] * adds
    return pl.pallas_call(
        functools.partial(_kernel, weighted=weighted, adds=adds,
                          staged=staged),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4, grid=(hidden // lanes, tokens // BLOCK),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((BLOCK, lanes), lambda c, b, *_: (b, c)),
            scratch_shapes=scratch),
        out_shape=jax.ShapeDtypeStruct((tokens, hidden), dtype),
        # operands: the four prefetched, rows, the weight, `into`
        input_output_aliases={4 + len(inputs) - 1: 0} if adds else {},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=INTERPRET,
        name="routed_combine",
    )(first, order, token, starts, *inputs)


@functools.partial(jax.jit, static_argnames=("tokens", "scope"))
def summed(rows, token, live, weight=None, into=None, first=None, *,
           tokens: int, scope: str):
    """The window's rows summed at their tokens, (tokens, hidden). With a
    `weight` (capacity,): each live row times its weight, added to `into`
    (read only where `first` is false), in float32: the forward pass.
    Without: the live rows' sum in the rows' dtype, summed in float32 and
    rounded once: x's cotangent. `token` (capacity,) int32, `live`
    (capacity,) bool. One jitted function, so that every expert layer
    shares one trace and lowering a direction; it opens `scope`
    (`moe_combine`, `moe_dispatch`) itself, the name the benchmark's readers
    go by."""
    with jax.named_scope(scope):
        capacity = rows.shape[0]
        key = jnp.where(live, token, tokens)
        key, order = lax.sort(
            (key, jnp.arange(capacity, dtype=jnp.int32)), num_keys=1,
            is_stable=True)
        starts = jnp.searchsorted(
            key, jnp.arange(0, tokens + 1, BLOCK, dtype=jnp.int32),
            method="compare_all").astype(jnp.int32)
        first = jnp.ones((1,), jnp.int32) if first is None \
            else jnp.asarray(first, jnp.int32).reshape(1)
        return _combine(rows, weight, into, first, order, key, starts,
                        tokens)


def unwritten(shape):
    """A float32 buffer that no one has written, for a first window to write
    whole (`summed` with `first` true): XLA allocates it and fills nothing."""
    return pl.pallas_call(
        lambda out_ref: None, out_specs=pl.BlockSpec(memory_space=pl.ANY),
        out_shape=jax.ShapeDtypeStruct(shape, _F32), interpret=INTERPRET,
        name="routed_sum")()
