"""Mamba-1's selective scan (ops/selective_scan.py) as a pair of Pallas TPU
kernels in which no (t, d, n) array ever reaches HBM.

Why a kernel. The recurrence's decay exp(dt_t A) differs by channel and
state, so it has no chunked matrix form. As an associative scan in XLA it
keeps (t, d, n) float32 values, 1.34 GB a layer and pass at 4,096
positions of 5,120 channels and a state of 16, and walks them in
log-depth passes over HBM; as XLA's loop over positions it is 4,096 tiny
steps a layer. Here the state of a block of channels is held in VMEM and
walked position by position, and only x, dt, B, C in and y out cross HBM,
with the state at every chunk's start for the backward pass.

Layouts:

    x, y, dt (b, t, d)        a block is a chunk's rows (positions in the
                              sublanes) of `lanes` channels
    B, C     (b, t, n, 128)   each position's n values repeated over a lane
                              tile (made by XLA, t n 128 elements: the
                              kernel reads a position's tile by its index
                              and needs no lane broadcast)
    A        (n, d)           transposed, the state in the sublanes
    state    (n, lanes)       float32, VMEM scratch

A grid step is one chunk of one block of channels of one sequence; the
chunk axis is innermost and sequential, and carries the state. Inside, a
loop walks the chunk eight positions at a time (one sublane tile of rows,
loaded and stored whole), each of the eight unrolled.

Forward, a position:   a = exp(dt_t A);  h = a h + (dt_t x_t) B_t;
                       y_t = sum_n h C_t   (+ D x_t for the chunk at once)

Backward: the chunks from the last to the first, the cotangent of the
state carried in VMEM (g_t = C_t dy_t + a_{t+1} g_{t+1}). A chunk's states
are made again from the one saved at its start into VMEM scratch, then
walked backwards:

    dC_t = sum_d h_t dy_t          dB_t = sum_d g_t dt_t x_t
    d(dt_t x_t) = sum_n g_t B_t    w = g_t h_{t-1} a_t
    d dt_t += sum_n w A            dA += w dt_t

dB and dC come out a block of channels at a time (summed outside), dA and
D's gradient a sequence at a time. Numerics are `selective_scan_xla`'s:
everything float32, the same order of operations a position.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Tests on the CPU flip this to run the kernels in the Pallas interpreter;
# `selective_scan.selective_scan` then also takes them off a TPU.
INTERPRET = False

LANES = 128
ROWS = 8            # positions a step of the loop: one sublane tile
#: channels a grid step, the widest that divides d
BLOCKS = (512, 256, 128)
# grid (batch, block of channels, chunk): the chunk axis carries the state
_COMPILER_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary"),
    vmem_limit_bytes=64 * 1024 * 1024)
_F32 = jnp.float32


def applies(x_shape, state_size: int, chunk: int) -> bool:
    """Whether the kernels take `selective_scan`'s arguments: by shape
    alone. Channels whole lane tiles, positions whole chunks of whole
    sublane tiles, the state whole sublane tiles."""
    _, t, d = x_shape
    return (d % LANES == 0 and chunk % ROWS == 0 and t % chunk == 0
            and state_size % ROWS == 0)


def _block(d: int) -> int:
    return next(lanes for lanes in BLOCKS if d % lanes == 0)


def _tiled(tile, lanes: int):
    """An (n, 128) tile repeated over `lanes` lanes, float32."""
    tile = tile.astype(_F32)
    return jnp.concatenate([tile] * (lanes // LANES), axis=1)


def _fwd_kernel(x_ref, dt_ref, at_ref, b_ref, c_ref, d_ref, y_ref, *rest,
                save_states):
    states_ref = rest[0] if save_states else None
    h_ref, u_ref = rest[-2:]           # (n, lanes) state; (q, lanes) dt x
    q, lanes = x_ref.shape

    @pl.when(pl.program_id(2) == 0)
    def _():
        h_ref[...] = jnp.zeros_like(h_ref)

    if save_states:
        states_ref[...] = h_ref[...]
    u_ref[...] = dt_ref[...] * x_ref[...].astype(_F32)
    a_t = at_ref[...]
    row = lax.broadcasted_iota(jnp.int32, (ROWS, lanes), 0)

    def rows(j, h):
        at = pl.multiple_of(j * ROWS, ROWS)
        dt8 = dt_ref[pl.ds(at, ROWS), :]
        u8 = u_ref[pl.ds(at, ROWS), :]
        y8 = jnp.zeros((ROWS, lanes), _F32)
        for i in range(ROWS):
            h = jnp.exp(dt8[i:i + 1] * a_t) * h \
                + u8[i:i + 1] * _tiled(b_ref[at + i], lanes)
            y8 = jnp.where(row == i, jnp.sum(
                h * _tiled(c_ref[at + i], lanes), axis=0, keepdims=True), y8)
        y_ref[pl.ds(at, ROWS), :] = y8
        return h

    h_ref[...] = lax.fori_loop(0, q // ROWS, rows, h_ref[...])
    y_ref[...] += d_ref[...] * x_ref[...].astype(_F32)


def _bwd_kernel(x_ref, dt_ref, at_ref, b_ref, c_ref, d_ref, states_ref,
                dy_ref, dx_ref, ddt_ref, dbt_ref, dct_ref, da_ref, dd_ref,
                g_ref, u_ref, hs_ref, du_ref, dlog_ref):
    q, lanes = x_ref.shape
    n = at_ref.shape[0]

    @pl.when(pl.program_id(2) == 0)
    def _():
        g_ref[...] = jnp.zeros_like(g_ref)
        da_ref[...] = jnp.zeros_like(da_ref)
        dd_ref[...] = jnp.zeros_like(dd_ref)

    x = x_ref[...].astype(_F32)
    dt = dt_ref[...]
    u_ref[...] = dt * x
    a_t = at_ref[...]

    # the chunk's states again: hs[i] is the state before position i
    hs_ref[0] = states_ref[...]

    def remake(j, h):
        at = pl.multiple_of(j * ROWS, ROWS)
        dt8 = dt_ref[pl.ds(at, ROWS), :]
        u8 = u_ref[pl.ds(at, ROWS), :]
        for i in range(ROWS):
            h = jnp.exp(dt8[i:i + 1] * a_t) * h \
                + u8[i:i + 1] * _tiled(b_ref[at + i], lanes)
            hs_ref[at + i + 1] = h
        return h

    lax.fori_loop(0, q // ROWS, remake, states_ref[...])

    row = lax.broadcasted_iota(jnp.int32, (ROWS, lanes), 0)
    column = lax.broadcasted_iota(jnp.int32, (n, q), 1)

    def back(k, carry):
        g, d_a, dbt, dct = carry
        at = pl.multiple_of((q // ROWS - 1 - k) * ROWS, ROWS)
        dt8 = dt_ref[pl.ds(at, ROWS), :]
        u8 = u_ref[pl.ds(at, ROWS), :]
        dy8 = dy_ref[pl.ds(at, ROWS), :]
        du8 = jnp.zeros((ROWS, lanes), _F32)
        dlog8 = jnp.zeros((ROWS, lanes), _F32)
        for i in reversed(range(ROWS)):
            a = jnp.exp(dt8[i:i + 1] * a_t)
            b_in = _tiled(b_ref[at + i], lanes)
            dy = dy8[i:i + 1]
            g = _tiled(c_ref[at + i], lanes) * dy + g
            dct = jnp.where(column == at + i, jnp.sum(
                hs_ref[at + i + 1] * dy, axis=1, keepdims=True), dct)
            dbt = jnp.where(column == at + i, jnp.sum(
                g * u8[i:i + 1], axis=1, keepdims=True), dbt)
            du8 = jnp.where(row == i, jnp.sum(g * b_in, axis=0,
                                              keepdims=True), du8)
            w = g * hs_ref[at + i] * a
            dlog8 = jnp.where(row == i, jnp.sum(w * a_t, axis=0,
                                                keepdims=True), dlog8)
            d_a = d_a + w * dt8[i:i + 1]
            g = a * g
        du_ref[pl.ds(at, ROWS), :] = du8
        dlog_ref[pl.ds(at, ROWS), :] = dlog8
        return g, d_a, dbt, dct

    zeros = jnp.zeros((n, q), _F32)
    g, d_a, dbt, dct = lax.fori_loop(
        0, q // ROWS, back,
        (g_ref[...], jnp.zeros((n, lanes), _F32), zeros, zeros))
    g_ref[...] = g
    da_ref[...] += d_a
    dbt_ref[...] = dbt
    dct_ref[...] = dct
    du, dy = du_ref[...], dy_ref[...]
    dx_ref[...] = (du * dt + dy * d_ref[...]).astype(dx_ref.dtype)
    ddt_ref[...] = dlog_ref[...] + du * x
    dd_ref[...] += jnp.sum(dy * x, axis=0, keepdims=True)


def _specs(t, n, q, lanes, backward):
    """Block specs by name for a grid (batch, block of channels, chunk),
    the chunk axis walked from the last to the first where `backward`."""
    c = t // q
    at = (lambda ci: c - 1 - ci) if backward else (lambda ci: ci)
    return {
        "rows": pl.BlockSpec((None, q, lanes),
                             lambda bi, ki, ci: (bi, at(ci), ki)),
        "tiles": pl.BlockSpec((None, q, n, LANES),
                              lambda bi, ki, ci: (bi, at(ci), 0, 0)),
        "state": pl.BlockSpec((n, lanes), lambda bi, ki, ci: (0, ki)),
        "channel": pl.BlockSpec((1, lanes), lambda bi, ki, ci: (0, ki)),
        "states": pl.BlockSpec((None, None, n, lanes),
                               lambda bi, ki, ci: (bi, at(ci), 0, ki)),
        "columns": pl.BlockSpec((None, None, n, q),
                                lambda bi, ki, ci: (bi, ki, 0, at(ci))),
        "per_sequence": pl.BlockSpec((None, n, lanes),
                                     lambda bi, ki, ci: (bi, 0, ki)),
        "per_sequence_row": pl.BlockSpec((None, 1, lanes),
                                         lambda bi, ki, ci: (bi, 0, ki)),
    }


def _tiles(v):
    """(b, t, n) -> (b, t, n, 128): each value over a lane tile."""
    return jnp.broadcast_to(v[..., None], (*v.shape, LANES))


def _forward(x, dt, a_t, b_in, c_in, d_row, chunk, save_states):
    b, t, d = x.shape
    n = a_t.shape[0]
    lanes = _block(d)
    spec = _specs(t, n, chunk, lanes, backward=False)
    out_shape = [jax.ShapeDtypeStruct((b, t, d), _F32)]
    out_specs = [spec["rows"]]
    if save_states:
        out_shape.append(jax.ShapeDtypeStruct((b, t // chunk, n, d), _F32))
        out_specs.append(spec["states"])
    out = pl.pallas_call(
        functools.partial(_fwd_kernel, save_states=save_states),
        grid=(b, d // lanes, t // chunk),
        in_specs=[spec["rows"], spec["rows"], spec["state"], spec["tiles"],
                  spec["tiles"], spec["channel"]],
        out_specs=out_specs, out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((n, lanes), _F32),
                        pltpu.VMEM((chunk, lanes), _F32)],
        compiler_params=_COMPILER_PARAMS, interpret=INTERPRET,
    )(x, dt, a_t, _tiles(b_in), _tiles(c_in), d_row)
    return out if save_states else out[0]


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def _scan(x, dt, a_t, b_in, c_in, d_row, chunk):
    return _forward(x, dt, a_t, b_in, c_in, d_row, chunk, False)


def _scan_fwd(x, dt, a_t, b_in, c_in, d_row, chunk):
    y, states = _forward(x, dt, a_t, b_in, c_in, d_row, chunk, True)
    return y, (x, dt, a_t, b_in, c_in, d_row, states)


def _scan_bwd(chunk, residuals, dy):
    x, dt, a_t, b_in, c_in, d_row, states = residuals
    b, t, d = x.shape
    n = a_t.shape[0]
    lanes = _block(d)
    blocks = d // lanes
    spec = _specs(t, n, chunk, lanes, backward=True)
    # the name `scan` opens around the forward: this function is traced
    # outside it, and the row `mamba1_scan` reads both passes
    with jax.named_scope("mamba1_scan"):
        dx, ddt, dbt, dct, da, dd = pl.pallas_call(
            _bwd_kernel,
            grid=(b, blocks, t // chunk),
            in_specs=[spec["rows"], spec["rows"], spec["state"],
                      spec["tiles"], spec["tiles"], spec["channel"],
                      spec["states"], spec["rows"]],
            out_specs=[spec["rows"], spec["rows"], spec["columns"],
                       spec["columns"], spec["per_sequence"],
                       spec["per_sequence_row"]],
            out_shape=[
                jax.ShapeDtypeStruct(x.shape, x.dtype),
                jax.ShapeDtypeStruct((b, t, d), _F32),
                jax.ShapeDtypeStruct((b, blocks, n, t), _F32),
                jax.ShapeDtypeStruct((b, blocks, n, t), _F32),
                jax.ShapeDtypeStruct((b, n, d), _F32),
                jax.ShapeDtypeStruct((b, 1, d), _F32)],
            scratch_shapes=[pltpu.VMEM((n, lanes), _F32),
                            pltpu.VMEM((chunk, lanes), _F32),
                            pltpu.VMEM((chunk + 1, n, lanes), _F32),
                            pltpu.VMEM((chunk, lanes), _F32),
                            pltpu.VMEM((chunk, lanes), _F32)],
            compiler_params=_COMPILER_PARAMS, interpret=INTERPRET,
        )(x, dt, a_t, _tiles(b_in), _tiles(c_in), d_row, states, dy)
        d_b = jnp.sum(dbt, axis=1).swapaxes(1, 2).astype(b_in.dtype)
        d_c = jnp.sum(dct, axis=1).swapaxes(1, 2).astype(c_in.dtype)
        return (dx, ddt, jnp.sum(da, axis=0), d_b, d_c,
                jnp.sum(dd, axis=0))


_scan.defvjp(_scan_fwd, _scan_bwd)


@functools.partial(jax.jit, static_argnames="chunk")
def scan(x, dt, A, B, C, D, chunk: int):
    """`selective_scan`'s arguments, at sizes `applies` admits. One jitted
    function, so that the layers of a model share one trace and lowering
    of each kernel (as `ssd_pallas.scan`); JAX lowers it apart from its
    call sites, so what is inside carries no name stack but its own: hence
    the scope, which the benchmark's readers go by."""
    with jax.named_scope("mamba1_scan"):
        y = _scan(x, dt.astype(_F32), A.astype(_F32).T, B, C,
                  D.astype(_F32).reshape(1, -1), chunk)
        return y
