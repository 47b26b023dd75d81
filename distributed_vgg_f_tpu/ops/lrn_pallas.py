"""Fused Pallas TPU kernels for Local Response Normalization: one pass each
way, on bf16 activations, in the layout the neighbouring convolutions use.

Why a kernel. The XLA banded form (`ops/lrn.py`, `matmul_vjp`) puts one band
product in a fusion. The backward has two (window sum, then the adjoint
window sum), so what lies between them goes to HBM in float32; and XLA
merges the backward's recomputed normaliser with the forward's identical
product, so the compiled forward writes a second, float32 output that the
backward reads back. At VGG-F's two sites (1024x54x54x64 and 1024x27x27x256,
382 MB each in bf16) that is 764 MB written and read a site which the
arithmetic does not need. Here each direction reads and writes bf16 only:
the forward `x -> y`, the backward `(x, g) -> dx`, the normaliser made again
in VMEM. `x` is the only residual.

Why two views. On the TPU, XLA keeps the two activations in different
orders: after conv1 physically (H, W, C, B), batch in the lanes and channels
in the sublanes; after conv2 (H, W, B, C), channels in the lanes. A kernel
that asks for another order pays a relayout copy of the whole activation for
every operand and result. The kernel that stood here before flattened NHWC
to rows of 128 lanes, whatever the order: eight such copies a step, each
dearer than the LRN fusion it replaced, and it took the pools out of their
layout too. It lost to its call convention, not to the idea. So the same
body is called through one of two views, each a bitcast of what XLA has:

    "rows"      C a multiple of 128: (H*W*B, C) through transpose(1, 2, 0, 3);
                the band is applied from the right, `rows @ band`
    "sublanes"  C a divisor of 128: (H*W*C, B) through transpose(1, 2, 3, 0);
                the band is applied from the left over the sublane axis,
                `band @ block`, 128/C pixels to one product through a
                block-diagonal band (the MXU is 128 deep)

`tests/test_chip_compile.py` holds that both transposes compile to bitcasts
between conv and pool, forward and backward, for a described v5e.

Why the relu comes inside (`relu_input`). XLA's form redid the relu inside
its LRN fusions and read the convolution's one output. A kernel is opaque to
it: given `lrn(relu(conv))` it writes the convolution's output twice, before
the relu (for the relu's mask, which it packs into bits in a further pass)
and after it (for the kernel): 2.3 ms a VGG-F step at batch 1024. With the
relu and its mask made in VMEM the convolution writes once, as before.

Numerics are those of `matmul_vjp` for bf16: operands of a band product are
bf16 (`x*x` and `g*x*d^-(beta+1)` rounded), accumulation, `d`, the power and
the final products float32. Nothing between the passes is stored.

    y  = x * d^-b,   d = k + a*S,   S = window sum of x^2
    dx = g * d^-b  -  2ab * x * window sum of (g * x * d^-(b+1))
    (`relu_input`: x = max(input, 0), and dx = 0 where the input is not > 0)

(the band is symmetric, so one matrix serves both passes).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from distributed_vgg_f_tpu.ops.lrn import _pow_neg_beta, band_matrix_np

# Tests on the CPU flip this to run the kernels in the Pallas interpreter;
# `lrn()` then also takes the kernel off a TPU (ops/lrn.py).
INTERPRET = False

LANES = 128
# Block sizes, from a microbenchmark on a v5e of the region conv -> relu ->
# lrn -> pool with its gradient at batch 1024 (PERF.md, PR 29). A block is
# what one grid step moves between HBM and VMEM: 2 MiB of bf16 an operand,
# whole rows of the "rows" view, up to 1024 lanes of the "sublanes" view (a
# narrower block is a strided copy: 512 lanes cost 11 to 22 % more, 256
# lanes 38 to 75 %). Inside it the body walks chunks of rows, so that its
# float32 intermediates stay small: 512 rows of the "rows" view, one band
# product's 128 of the "sublanes" view.
BLOCK_ELEMENTS = 1 << 20
LANES_BLOCK = 1024
ROWS_CHUNK = 512
_VMEM_LIMIT = 64 * 1024 * 1024


def fused_view(shape, dtype) -> str | None:
    """The view through which the kernel pair takes an NHWC activation, or
    None where it does not apply: by shape and dtype alone. The batch has to
    fill the lanes (or tile the sublanes) for XLA to keep the order the view
    assumes, so smaller batches (the server's buckets) keep the XLA form."""
    if len(shape) != 4 or dtype != jnp.bfloat16:
        return None
    batch, _, _, channels = shape
    if batch % LANES:
        return None
    if channels % LANES == 0:
        return "rows"
    if LANES % channels == 0 and channels >= 16:
        return "sublanes"
    return None


def _window_sum(view: str, v, band):
    """Sum over the channel window of a chunk, float32. `v` bf16: one MXU
    pass; float32 (tests only): the exact product."""
    precision = None if v.dtype == jnp.bfloat16 else lax.Precision.HIGHEST
    operands = (v, band) if view == "rows" else (band, v)
    return jnp.dot(*operands, preferred_element_type=jnp.float32,
                   precision=precision)


def _normaliser(view, band, x, *, a, bias, relu):
    """(x or relu(x) in float32, d = k + a * window sum of its square)."""
    xf = x.astype(jnp.float32)
    if relu:
        xf = jnp.maximum(xf, 0.0)
    return xf, bias + a * _window_sum(view, (xf * xf).astype(x.dtype), band)


def _fwd_chunk(view, band, x, *, a, bias, beta, relu):
    xf, d = _normaliser(view, band, x, a=a, bias=bias, relu=relu)
    return (xf * _pow_neg_beta(d, beta)).astype(x.dtype)


def _bwd_chunk(view, band, x, g, *, a, bias, beta, relu):
    xf, d = _normaliser(view, band, x, a=a, bias=bias, relu=relu)
    gf = g.astype(jnp.float32)
    t = _pow_neg_beta(d, beta)
    u = (gf * xf * (t / d)).astype(x.dtype)
    dx = gf * t - (2.0 * a * beta) * xf * _window_sum(view, u, band)
    if relu:    # xf > 0 exactly where the input was
        dx = jnp.where(xf > 0.0, dx, 0.0)
    return dx.astype(x.dtype)


def _kernel(*refs, chunk_fn, view, chunk, valid_rows):
    """`chunk_fn` over the block's rows, `chunk` at a time. `valid_rows` is
    the array's row count where its last chunk is cut short and the band
    would carry what lies behind it into valid rows (0 x NaN), else None."""
    *in_refs, band_ref, out_ref = refs
    band = band_ref[...]
    block_start = pl.program_id(0) * out_ref.shape[0]

    def body(i, carry):
        start = pl.multiple_of(i * chunk, chunk)
        rows = pl.ds(start, chunk)
        chunks = [r[rows, :] for r in in_refs]
        if valid_rows is not None:
            row = (block_start + start
                   + lax.broadcasted_iota(jnp.int32, chunks[0].shape, 0))
            chunks = [jnp.where(row < valid_rows, c, jnp.zeros_like(c))
                      for c in chunks]
        out_ref[rows, :] = chunk_fn(view, band, *chunks)
        return carry

    lax.fori_loop(0, out_ref.shape[0] // chunk, body, None)


def _call(chunk_fn, view, channels, depth_radius, operands):
    """One pass of `chunk_fn` over same-shaped 2-D operands in `view`'s
    order: (H*W*B, C) or (H*W*C, B)."""
    rows, cols = operands[0].shape
    dtype = operands[0].dtype
    band = band_matrix_np(channels, depth_radius)
    if view == "rows":
        block = (min(BLOCK_ELEMENTS // cols, rows), cols)
        chunk = ROWS_CHUNK if block[0] % ROWS_CHUNK == 0 else LANES
        valid_rows = None                   # rows do not mix
    else:
        # 128 / C pixels to one product through a block-diagonal band: the
        # MXU is 128 deep, and a pixel's window never leaves its block
        chunk = min(LANES, rows)
        band = np.kron(np.eye(chunk // channels, dtype=np.float32), band)
        lanes = min(LANES_BLOCK, cols)
        block = (min(BLOCK_ELEMENTS // lanes, rows) // chunk * chunk, lanes)
        valid_rows = rows if rows % chunk else None
    spec = pl.BlockSpec(block, lambda i, j: (i, j))
    return pl.pallas_call(
        functools.partial(_kernel, chunk_fn=chunk_fn, view=view, chunk=chunk,
                          valid_rows=valid_rows),
        grid=(pl.cdiv(rows, block[0]), pl.cdiv(cols, block[1])),
        in_specs=[spec] * len(operands)
        + [pl.BlockSpec(band.shape, lambda i, j: (0, 0))],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((rows, cols), dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=INTERPRET,
    )(*operands, jnp.asarray(band, dtype))


def _to_view(view: str, x):
    b, h, w, c = x.shape
    if view == "rows":
        return x.transpose(1, 2, 0, 3).reshape(h * w * b, c)
    return x.transpose(1, 2, 3, 0).reshape(h * w * c, b)


def _from_view(view: str, y, shape):
    b, h, w, c = shape
    if view == "rows":
        return y.reshape(h, w, b, c).transpose(2, 0, 1, 3)
    return y.reshape(h, w, c, b).transpose(3, 0, 1, 2)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3, 4, 5, 6))
def _lrn_fused(x, view, depth_radius, bias, a, beta, relu):
    chunk_fn = functools.partial(_fwd_chunk, a=a, bias=bias, beta=beta,
                                 relu=relu)
    y = _call(chunk_fn, view, x.shape[-1], depth_radius, (_to_view(view, x),))
    return _from_view(view, y, x.shape)


def _lrn_fused_fwd(x, view, depth_radius, bias, a, beta, relu):
    return _lrn_fused(x, view, depth_radius, bias, a, beta, relu), x


def _lrn_fused_bwd(view, depth_radius, bias, a, beta, relu, x, g):
    chunk_fn = functools.partial(_bwd_chunk, a=a, bias=bias, beta=beta,
                                 relu=relu)
    dx = _call(chunk_fn, view, x.shape[-1], depth_radius,
               (_to_view(view, x), _to_view(view, g)))
    return (_from_view(view, dx, x.shape),)


_lrn_fused.defvjp(_lrn_fused_fwd, _lrn_fused_bwd)


def local_response_norm_pallas(x: jnp.ndarray,
                               depth_radius: int = 2,
                               bias: float = 2.0,
                               alpha: float = 1e-4,
                               beta: float = 0.75,
                               *,
                               alpha_scaled: bool = False,
                               relu_input: bool = False,
                               view: str | None = None) -> jnp.ndarray:
    """LRN over the last axis of an NHWC activation as the fused kernel pair;
    with `relu_input`, of `relu(x)`, the relu and its mask made in VMEM.

    `view` defaults to what `fused_view` picks and is an error where that is
    None; tests name one to run the body on float32."""
    view = view or fused_view(x.shape, x.dtype)
    if view is None:
        raise ValueError(
            f"no fused LRN view for {x.dtype}{list(x.shape)}: bf16 NHWC, "
            f"batch a multiple of {LANES}, channels a multiple or a divisor "
            f"(>= 16) of {LANES}")
    n = 2 * depth_radius + 1
    a = alpha / n if alpha_scaled else alpha
    return _lrn_fused(x, view, depth_radius, float(bias), float(a),
                      float(beta), bool(relu_input))
