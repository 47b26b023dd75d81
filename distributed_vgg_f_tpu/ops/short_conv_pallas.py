"""The short convolution, silu and head norm of ops/short_conv.py as a pair of
Pallas TPU kernels: one pass over x forward, one over x and y's cotangent
backward, every float32 intermediate in registers or VMEM.

Why a kernel. The XLA form casts x to float32, pads it, makes four shifted
products, silu, a head's sum of squares over its 128 lanes, a reciprocal
square root and a product, as several float32 passes over tokens x channels
through HBM (268 MB an array at the benchmark's widths), three times forward
and once backward a call site. The work needs x in and y out, once.

Layout, as the mixer has it and as ops/kda_pallas.py reads it:

    x, y, dy, dx    (b, t, h*128)   a block is `ROWS` rows of a group of up
                    to `HEADS` heads' lanes; a head is one static lane tile
    taps, dtaps     (width, h*128) float32; a block is the group's lanes

The grid is (group, batch, block of rows). Inside a grid step the heads go
one after another. A head's rows are first copied as float32 into VMEM
scratch of one lane tile's width, under the 8 rows before the block; a loop
then walks them up to `STEP` at a time (many rows a step, so that the
scheduler has independent chains to fill its four slots with), and a step's
chain of some thirty (backward sixty) elementwise operations a register
stays in registers between its loads and its one store.

**The rows before and after.** A position reads the `width - 1` rows before
it. The copies of x shifted by k rows are LOADS from the scratch at a row
offset of -k: in a block one lane tile wide consecutive rows are consecutive
in VMEM, so a load off the tiling costs the vector unit nothing (as sublane
rotations and selects the three shifts were six of the forward's vector
operations a register: PERF.md section 6, PR 37). Above a block's first row
the rows come from a second block spec on the previous block's last 16 rows
(a bf16 tile) and are zeros at a sequence's start, so every grid step of the
forward is its own. The backward needs dp at the `width - 1` rows AFTER a
position: it writes a head's dp into a second scratch above 8 rows that the
block after it left (it walks a sequence's blocks from the last to the
first; zeros at a sequence's end), and a second loop makes dx from loads at
+k. No row of one sequence reaches another's.

Forward, a head's rows (float32 from the cast of x to the one rounding of y):

    p = sum_j taps[j] x[t - (width - 1) + j];   s = p sigmoid(p)
    y = s * scale * rsqrt(sum_head(s^2) + 1e-6)         where a scale is given

Backward (p, s and the sum made again; residuals are x and the taps):

    r  = rsqrt(sum_head(s^2) + 1e-6)
    ds = scale r (dy - s r^2 sum_head(dy s))            (dy without a scale)
    dp = ds (sigmoid(p) + s (1 - sigmoid(p)))
    dx[t]    = sum_j taps[j] dp[t + (width - 1) - j]
    dtaps[j] = sum over batch and time of dp[t] x[t - (width - 1) + j]

dtaps is accumulated in float32 in an output block that stays where it is
while a group's sequences and blocks pass, 8 partial sums (a register's
sublanes) a tap, which XLA adds up (8 x width x channels floats).

A head's sum over its 128 lanes is the XLU's lane reduction (float32, no
rounding but the sum's own): as a product with a matrix of ones on the MXU,
the summand split three ways into bf16 parts (`kda_pallas._by_ones`), the
split's seven operations a register and the ones pushed again for every
product cost more than the reduction (1.44 against 1.23 ms forward, 1.87
against 1.57 backward on the op alone: PERF.md section 6, PR 37).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from distributed_vgg_f_tpu.ops.short_conv import EPS

# Tests on the CPU flip this to run the kernels in the Pallas interpreter;
# `short_conv.conv_silu_heads` then also takes them off a TPU.
INTERPRET = False

LANES = 128
#: rows of the previous block a grid step reads: one tile of bf16
HALO = 16
#: rows of a float32 register: what is kept of the rows before and after
_ROWS = 8
#: what a sequence has to be a whole number of: the fewest rows a step of
#: the kernels' loops takes through the whole chain; and the most (the more,
#: the more independent chains the scheduler has to fill its four slots
#: with: the forward with the norm took 0.59 ms at 256 rows an iteration
#: against 1.30 at 64, PERF.md section 6, PR 37)
SUB = 64
STEP = 256
#: the most rows and heads (lane tiles) a grid step holds
ROWS = 512
HEADS = 8

_F32 = jnp.float32


def applies(x_shape, taps_shape, heads: int) -> bool:
    """Whether the kernels take an x (b, t, channels) with these taps: by
    shape alone. A head one lane tile, the sequence whole loop steps, the
    rows a position reads within one register."""
    _, t, channels = x_shape
    return (channels == heads * LANES and t % SUB == 0
            and taps_shape[0] - 1 <= _ROWS)


def _rows_a_step(t: int) -> int:
    return max(n for n in range(SUB, ROWS + 1, SUB) if t % n == 0)


def _rows_a_loop_step(rows: int) -> int:
    return max(n for n in range(SUB, STEP + 1, SUB) if rows % n == 0)


def _heads_a_step(heads: int) -> int:
    return max(n for n in range(1, HEADS + 1) if heads % n == 0)


def _head_sums(x):
    """(rows, 1): a head's sums over its 128 lanes."""
    return jnp.sum(x, axis=-1, keepdims=True)


def _taps_sum(taps, copies):
    """sum_j taps[j] copies[width - 1 - j], j ascending."""
    width = len(taps)
    total = taps[0] * copies[width - 1]
    for j in range(1, width):
        total = total + taps[j] * copies[width - 1 - j]
    return total


def _rows(i, n):
    """The i-th loop step's `n` rows of a block."""
    return pl.ds(pl.multiple_of(i * n, n), n)


def _stage(x_ref, before_ref, taps_ref, xs_ref, at_start, head):
    """A head of a grid step's block: its lanes, its taps a row each, and
    x's float32 copy below the 8 rows before the block (zeros where the
    block starts a sequence) in `xs_ref`, from which `_copies` loads."""
    of = slice(head * LANES, (head + 1) * LANES)
    taps = [taps_ref[j:j + 1, of] for j in range(taps_ref.shape[0])]
    xs_ref[:_ROWS, :] = jnp.where(
        at_start, 0.0, before_ref[:, of].astype(_F32)[HALO - _ROWS:])
    xs_ref[_ROWS:, :] = x_ref[:, of].astype(_F32)
    return of, taps


def _copies(ref, i, n, width, *, back):
    """[ref's rows t - k (`back`; else t + k) for k < width] at the i-th
    loop step's `n` rows t, `ref` a float32 block of one lane tile with 8
    more rows before it (`back`; else after it): loads off the tiling by k
    rows, which cost the vector unit nothing."""
    start = pl.multiple_of(i * n, n)
    return [ref[pl.ds(start + _ROWS - k if back else start + k, n), :]
            for k in range(width)]


def _fwd_kernel(*refs, normed):
    scale = refs[0][0] if normed else None
    x_ref, before_ref, taps_ref, y_ref, xs_ref = refs[-5:]
    width = taps_ref.shape[0]
    n = _rows_a_loop_step(x_ref.shape[0])
    at_start = pl.program_id(2) == 0

    for head in range(x_ref.shape[1] // LANES):
        of, taps = _stage(x_ref, before_ref, taps_ref, xs_ref, at_start, head)

        def step(i, carry):
            p = _taps_sum(taps, _copies(xs_ref, i, n, width, back=True))
            s = p * jax.nn.sigmoid(p)
            if normed:
                s = s * (scale * lax.rsqrt(_head_sums(s * s) + EPS))
            y_ref[_rows(i, n), of] = s.astype(y_ref.dtype)
            return carry

        lax.fori_loop(0, x_ref.shape[0] // n, step, None)


def _bwd_kernel(*refs, normed):
    scale = refs[0][0] if normed else None
    (x_ref, before_ref, taps_ref, dy_ref, dx_ref, dtaps_ref, after_ref,
     xs_ref, dps_ref) = refs[-9:]
    width = taps_ref.shape[0]
    rows_here = x_ref.shape[0]
    n = _rows_a_loop_step(rows_here)
    # the blocks come from a sequence's last to its first
    block = pl.program_id(2)
    at_start = block == pl.num_programs(2) - 1

    @pl.when((pl.program_id(1) == 0) & (block == 0))
    def _():
        dtaps_ref[...] = jnp.zeros_like(dtaps_ref)

    @pl.when(block == 0)
    def _():
        after_ref[...] = jnp.zeros_like(after_ref)

    for head in range(x_ref.shape[1] // LANES):
        of, taps = _stage(x_ref, before_ref, taps_ref, xs_ref, at_start, head)
        dps_ref[rows_here:, :] = after_ref[:, of]

        def make_dp(i, sums):
            copies = _copies(xs_ref, i, n, width, back=True)
            p = _taps_sum(taps, copies)
            gate = jax.nn.sigmoid(p)
            d = dy_ref[_rows(i, n), of].astype(_F32)
            s = p * gate
            if normed:
                r = lax.rsqrt(_head_sums(s * s) + EPS)
                d = (scale * r) * (d - s * (r * r) * _head_sums(d * s))
            dp = d * (gate + s * (1.0 - gate))
            dps_ref[_rows(i, n), :] = dp
            # a register's 8 sublanes are 8 partial sums a tap
            return tuple(
                total + sum((dp * copies[width - 1 - j])[at:at + _ROWS]
                            for at in range(0, n, _ROWS))
                for j, total in enumerate(sums))

        sums = lax.fori_loop(0, rows_here // n, make_dp,
                             (jnp.zeros((_ROWS, LANES), _F32),) * width)
        for j, total in enumerate(sums):
            dtaps_ref[j * _ROWS:(j + 1) * _ROWS, of] += total

        def make_dx(i, carry):
            dx_ref[_rows(i, n), of] = _taps_sum(taps, _copies(
                dps_ref, i, n, width, back=False)).astype(dx_ref.dtype)
            return carry

        lax.fori_loop(0, rows_here // n, make_dx, None)
        after_ref[:, of] = dps_ref[:_ROWS, :]


def _specs(t: int, heads: int, width: int, *, backward):
    """The grid (group, batch, block of rows) and its block specs by name,
    the blocks walked from the last to the first where `backward`."""
    rows, lanes = _rows_a_step(t), _heads_a_step(heads) * LANES
    blocks = t // rows
    at = (lambda ti: blocks - 1 - ti) if backward else (lambda ti: ti)
    return (heads * LANES // lanes, blocks), {
        "scale": pl.BlockSpec(memory_space=pltpu.SMEM),
        "wide": pl.BlockSpec((None, rows, lanes),
                             lambda gi, bi, ti: (bi, at(ti), gi)),
        "before": pl.BlockSpec(
            (None, HALO, lanes), lambda gi, bi, ti: (
                bi, jnp.maximum(at(ti) * (rows // HALO) - 1, 0), gi)),
        "taps": pl.BlockSpec((width, lanes), lambda gi, bi, ti: (0, gi)),
        "sums": pl.BlockSpec((width * _ROWS, lanes),
                             lambda gi, bi, ti: (0, gi))}


def _staging(spec):
    """A head's rows of a block in float32 with 8 rows more."""
    return pltpu.VMEM((spec["wide"].block_shape[-2] + _ROWS, LANES), _F32)


def _forward(x, taps, scale):
    b, t, channels = x.shape
    scales = () if scale is None else (scale,)
    (groups, blocks), spec = _specs(t, channels // LANES, taps.shape[0],
                                    backward=False)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, normed=bool(scales)),
        grid=(groups, b, blocks),
        in_specs=[spec["scale"] for _ in scales] + [
            spec["wide"], spec["before"], spec["taps"]],
        out_specs=spec["wide"],
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        scratch_shapes=[_staging(spec)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",) * 3),
        interpret=INTERPRET,
    )(*scales, x, x, taps)


@jax.custom_vjp
def _core(x, taps, scale):
    return _forward(x, taps, scale)


def _core_fwd(x, taps, scale):
    return _forward(x, taps, scale), (x, taps, scale)


def _core_bwd(residuals, dy):
    x, taps, scale = residuals
    b, t, channels = x.shape
    width, scales = taps.shape[0], () if scale is None else (scale,)
    (groups, blocks), spec = _specs(t, channels // LANES, width,
                                    backward=True)
    # the name `convolved` opens around the forward: this function is traced
    # outside it (the row `kda_conv` reads both passes)
    with jax.named_scope("kda_conv"):
        dx, sums = pl.pallas_call(
            functools.partial(_bwd_kernel, normed=bool(scales)),
            grid=(groups, b, blocks),
            in_specs=[spec["scale"] for _ in scales] + [
                spec["wide"], spec["before"], spec["taps"], spec["wide"]],
            out_specs=[spec["wide"], spec["sums"]],
            out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype),
                       jax.ShapeDtypeStruct((width * _ROWS, channels), _F32)],
            scratch_shapes=[pltpu.VMEM(
                (_ROWS, spec["wide"].block_shape[-1]), _F32),
                _staging(spec), _staging(spec)],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary", "arbitrary")),
            interpret=INTERPRET,
        )(*scales, x, x, taps, dy)
        dtaps = sums.reshape(width, _ROWS, channels).sum(axis=1)
    return dx, dtaps, None if scale is None else jnp.zeros_like(scale)


_core.defvjp(_core_fwd, _core_bwd)


@jax.jit
def convolved(x, taps, scale=None):
    """`short_conv.conv_silu_heads` at sizes `applies` admits, as (b, t,
    channels): `scale` a number (an operand: one trace for q's and k's), or
    None for no norm. One jitted function, so that a model's layers share
    one trace and lowering of each kernel; JAX lowers such a function once,
    apart from its call sites, so what is inside carries no name stack but
    its own: hence the scope, which the benchmark's readers go by (as
    ops/kda_pallas.py `chunked`)."""
    with jax.named_scope("kda_conv"):
        return _core(x, taps.astype(_F32), None if scale is None
                     else jnp.asarray(scale, _F32).reshape(1))
