"""Mamba-2's chunked recurrence (ops/ssd.py) as a pair of Pallas TPU kernels
in which nothing of size chunk x chunk a head ever reaches HBM.

Why a kernel. The XLA form writes every head's masked decay product
`(C B^T) exp(s_i - s_j)` to HBM (chunk x chunk a head and chunk: 134 M
elements a layer at the benchmark's widths), reads it back for a batched
product, and does so again, several times in float32, in JAX's own backward.
The recurrence needs x, B, C, dt in and y out, once. Here a grid step holds
one chunk of one group of heads; the chunk axis is innermost and sequential,
and the group's state (float32, `n x heads*p`) is carried in VMEM scratch.

Layouts, all as the mixer already has them (no copy of x, B, C or y):

    x, y    (b, t, h*p)    a block is a chunk's rows of one group's r*p lanes
    B, C    (b, t, g*n)    a block is a chunk's rows of one group's n lanes
    dt, s   (b, c, g, r, q) float32, positions in the lanes ("rows": a block
            is one (8, 128) tile at the benchmark's sizes); s the running sum
            of dt A inside a chunk, which XLA makes (4 MB a layer)
    state   (n, r*p), transposed, so that a head's decay multiplies lanes

What a head's s_i and dt_i multiply has positions in the sublanes, and a
lane broadcast of a column costs the vector unit a shuffle a vreg. The MXU
does it instead: the rows of s and dt, split three ways into bf16 parts that
add up to the float32 value, are turned once a chunk (one 128 x 128
transpose) and multiplied by two constant 0/1 matrices that repeat a head's
s over lanes and spread a head's dt over the head's own lanes; the float32
accumulator adds the parts up again.

A head of p = 64 fills half a lane tile, so the work goes a lane tile (128
lanes: 128 / p heads) at a time: a product against one head's masked matrix
is made on the whole tile and the head's lanes are selected from it, which
costs the MXU nothing (a 64-wide result uses half its columns anyway) and
needs no lane shuffle.

Numerics are those of `ssd.ssd_xla`: decays, running sums, exponentials
and the carried state float32; the products take operands in x's dtype and
accumulate in float32, and `(cb * decay)`, `dt x`, `dt x exp(s_last - s_j)`
and the entering state are rounded to x's dtype before their products;
`s_i - s_j` is taken before the exponential. The backward rounds the
cotangents that enter a product the same way.

Forward, per chunk and lane tile (M_k a head's masked matrix):

    y    = sum_k [M_k (dt x)]_k  +  exp(s_i) (C H^T)  +  D x
    H^T <- exp(s_last) H^T  +  B^T (dt x exp(s_last - s_j))

Backward: the same walk from the last chunk to the first with the state's
cotangent in VMEM; residuals are the inputs and the states entering each
chunk (float32, made by the differentiated forward only). It returns the
cotangents of x, dt and s (rows again: what the lane reductions give a
column a head is turned once a chunk), of B and C (summed over a group's
heads in the kernel) and D's partial sums a sequence and group; the running
sum's and A's gradients are XLA's, from `ds`.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Tests on the CPU flip this to run the kernels in the Pallas interpreter;
# `ssd.ssd` then also takes them off a TPU.
INTERPRET = False

LANES = 128
# grid (batch, group, chunk): the chunk axis carries the state
_COMPILER_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary"),
    vmem_limit_bytes=64 * 1024 * 1024)

_NT = (((1,), (1,)), ((), ()))      # a @ b^T
_TN = (((0,), (0,)), ((), ()))      # a^T @ b
_F32 = jnp.float32


def applies(x_shape, group_shape, chunk: int) -> bool:
    """Whether the kernels take `ssd`'s arguments: by shape alone. Chunks,
    a group's lanes and the state are whole tiles, a head divides one, and
    the split columns of a group's heads (`_columns`) fit one."""
    _, t, h, p = x_shape
    g, n = group_shape[2:]
    return (chunk % LANES == 0 and t % chunk == 0 and h % g == 0
            and n % LANES == 0 and LANES % p == 0 and p >= 8
            and (h // g * p) % LANES == 0 and 6 * (h // g) <= LANES)


def _dot(a, b, dims=(((1,), (0,)), ((), ()))):
    precision = None if a.dtype == jnp.bfloat16 else lax.Precision.HIGHEST
    return lax.dot_general(a, b, dims, preferred_element_type=_F32,
                           precision=precision)


def _columns(s_row, dt_row):
    """(r, q) rows of s and dt -> (q, 128) bf16 columns: lanes 0..3r-1 the
    three bf16 parts of s whose sum is s, lanes 3r..6r-1 those of dt, the
    rest zero. What one bf16 pass of the MXU carries without loss, turned
    once so that `_selectors`' matrices can move it into lanes."""
    parts = []
    for rows in (s_row, dt_row):
        for _ in range(3):
            part = rows.astype(jnp.bfloat16).astype(_F32)
            parts.append(part)
            rows = rows - part
    r, q = s_row.shape
    parts.append(jnp.zeros((LANES - 6 * r, q), _F32))
    return jnp.concatenate(parts, axis=0).astype(jnp.bfloat16).T


def _selectors(r, p, q):
    """0/1 matrices that move a head's column into lanes on the MXU, where
    a lane broadcast of a column is a shuffle a vreg: `columns @ repeat` is
    (q, r*q), head k's s in all of lanes k*q..k*q+q-1; `columns @ spread` is
    (q, r*p), head k's dt over its own p lanes. The three parts of a split
    add up in the float32 accumulator."""
    repeat = np.zeros((LANES, r * q), np.float32)
    spread = np.zeros((LANES, r * p), np.float32)
    for k in range(r):
        for part in range(3):
            repeat[part * r + k, k * q:(k + 1) * q] = 1
            spread[(3 + part) * r + k, k * p:(k + 1) * p] = 1
    return jnp.asarray(repeat, jnp.bfloat16), jnp.asarray(spread, jnp.bfloat16)


def _spread(s_rep, first, heads, p, q):
    """(q, 128) of a lane tile: head `first + u`'s s over the u-th run of p
    lanes, from the heads' repeated columns."""
    out = s_rep[:, first * q:first * q + LANES]
    lane = lax.broadcasted_iota(jnp.int32, out.shape, 1)
    for u in range(1, heads):
        at = (first + u) * q
        out = jnp.where(lane >= u * p, s_rep[:, at:at + LANES], out)
    return out


def _head_lanes(shape, u, p):
    lane = lax.broadcasted_iota(jnp.int32, shape, len(shape) - 1)
    return (lane >= u * p) & (lane < (u + 1) * p)


def _masked_decay(s_rep, s_row, k):
    """exp(s_i - s_j) for j <= i, else 0: (q, q) float32 of head k."""
    q = s_row.shape[1]
    seen = lax.broadcasted_iota(jnp.int32, (q, q), 0) \
        >= lax.broadcasted_iota(jnp.int32, (q, q), 1)
    return jnp.exp(jnp.where(
        seen, s_rep[:, k * q:(k + 1) * q] - s_row[k:k + 1, :], -jnp.inf))


def _chunk(b_ref, c_ref, dtrow_ref, srow_ref, repeat_ref, spread_ref):
    """What both kernels make of a chunk's small operands: B, C (q, n); s
    in rows (r, q) and repeated over lanes (q, r*q); dt spread over the
    heads' lanes (q, r*p); C B^T (q_i, q_j)."""
    bm, cm, s_row = b_ref[0], c_ref[0], srow_ref[...]
    columns = _columns(s_row, dtrow_ref[...])
    return (bm, cm, s_row, _dot(columns, repeat_ref[...]),
            _dot(columns, spread_ref[...]), _dot(cm, bm, _NT))


def _fwd_kernel(x_ref, b_ref, c_ref, dtrow_ref, srow_ref, d_ref, repeat_ref,
                spread_ref, y_ref, *rest, p, save_states):
    states_ref = rest[0] if save_states else None
    h_ref = rest[-1]                                   # (n, r*p) float32
    q = x_ref.shape[1]
    width = x_ref.shape[2]
    dtype = x_ref.dtype
    per_tile = LANES // p

    @pl.when(pl.program_id(2) == 0)
    def _():
        h_ref[...] = jnp.zeros_like(h_ref)

    if save_states:
        states_ref[...] = h_ref[...]
    bm, cm, s_row, s_rep, dt_sp, cb = _chunk(
        b_ref, c_ref, dtrow_ref, srow_ref, repeat_ref, spread_ref)
    b_t = bm.T                                         # (n, q)
    for tile in range(width // LANES):
        lanes = slice(tile * LANES, (tile + 1) * LANES)
        first = tile * per_tile
        xt = x_ref[0, :, lanes].astype(_F32)
        s_sp = _spread(s_rep, first, per_tile, p, q)
        x_dt = xt * dt_sp[:, lanes]
        x_dt_low = x_dt.astype(dtype)
        within = None
        for u in range(per_tile):
            m = (cb * _masked_decay(s_rep, s_row, first + u)).astype(dtype)
            one = _dot(m, x_dt_low)
            within = one if within is None else jnp.where(
                _head_lanes(one.shape, u, p), one, within)
        ht = h_ref[:, lanes]
        past = _dot(cm, ht.astype(dtype)) * jnp.exp(s_sp)
        y_ref[0, :, lanes] = within + past + xt * d_ref[:, lanes]
        last = s_sp[q - 1:q, :]                        # (1, 128)
        to_end = (x_dt * jnp.exp(last - s_sp)).astype(dtype)
        h_ref[:, lanes] = jnp.exp(last) * ht + _dot(b_t, to_end)


def _bwd_kernel(x_ref, b_ref, c_ref, dtrow_ref, srow_ref, d_ref, repeat_ref,
                spread_ref, states_ref, dy_ref,
                dx_ref, db_ref, dc_ref, ddt_ref, ds_ref, dd_ref, dh_ref,
                *, p):
    q = x_ref.shape[1]
    width = x_ref.shape[2]
    r = srow_ref.shape[0]
    dtype = x_ref.dtype
    per_tile = LANES // p

    @pl.when(pl.program_id(2) == 0)
    def _():
        dh_ref[...] = jnp.zeros_like(dh_ref)
        dd_ref[...] = jnp.zeros_like(dd_ref)

    bm, cm, s_row, s_rep, dt_all, cb = _chunk(
        b_ref, c_ref, dtrow_ref, srow_ref, repeat_ref, spread_ref)
    c_t = cm.T
    mine_of = [_head_lanes((q, LANES), u, p) for u in range(per_tile)]
    # what the lane reductions give, a column a head: d_dt in lanes 0..r-1,
    # ds in lanes r..2r-1, turned into rows once at the end
    head_col = lax.broadcasted_iota(jnp.int32, (q, LANES), 1)
    head_row = lax.broadcasted_iota(jnp.int32, (r, q), 0)
    at_last = lax.broadcasted_iota(jnp.int32, (1, q), 1) == q - 1
    d_cb = jnp.zeros((q, q), _F32)
    d_b = jnp.zeros(bm.shape, _F32)
    d_c = jnp.zeros(cm.shape, _F32)
    by_col = jnp.zeros((q, LANES), _F32)
    ds_row = jnp.zeros((r, q), _F32)
    for tile in range(width // LANES):
        lanes = slice(tile * LANES, (tile + 1) * LANES)
        first = tile * per_tile
        xt = x_ref[0, :, lanes].astype(_F32)
        dy = dy_ref[0, :, lanes]
        dy_low = dy.astype(dtype)
        s_sp = _spread(s_rep, first, per_tile, p, q)
        dt_sp = dt_all[:, lanes]
        x_dt = xt * dt_sp
        x_dt_low = x_dt.astype(dtype)
        last = s_sp[q - 1:q, :]
        to_end = jnp.exp(last - s_sp)
        keep = jnp.exp(last)                           # (1, 128)
        ht = states_ref[:, lanes]                      # entering, (n, 128)
        ht_low = ht.astype(dtype)
        d_ht = dh_ref[:, lanes]                        # of the leaving state
        d_ht_low = d_ht.astype(dtype)

        # from the past: y += exp(s_i) (C H^T)
        grow = jnp.exp(s_sp)
        past = _dot(cm, ht_low) * grow
        d_read = (dy * grow).astype(dtype)
        d_c += _dot(d_read, ht_low, _NT)
        d_enter = _dot(c_t, d_read)                    # (n, 128)
        from_past = dy * past                          # ds_i, by head below

        # the state's update: H' = exp(s_last) H + B^T (x dt to_end)
        handed = (x_dt * to_end).astype(dtype)
        d_b += _dot(handed, d_ht_low, _NT)
        d_handed = _dot(bm, d_ht_low)                  # (q, 128)
        d_to_end = d_handed * x_dt * to_end            # ds_last - ds_j
        kept = jnp.sum(d_ht * ht, axis=0, keepdims=True) * keep
        dh_ref[:, lanes] = keep * d_ht + d_enter

        # within the chunk, a head at a time
        d_x_dt = None
        for u in range(per_tile):
            k = first + u
            mine = mine_of[u]
            decay = _masked_decay(s_rep, s_row, k)
            weights = cb * decay
            d_m = _dot(jnp.where(mine, dy, 0.0).astype(dtype), x_dt_low, _NT)
            one = _dot(weights.astype(dtype), dy_low, _TN)
            d_x_dt = one if d_x_dt is None else jnp.where(mine, one, d_x_dt)
            d_cb += d_m * decay
            d_decay = d_m * weights                    # times the decay
            rows = jnp.sum(d_decay, axis=1, keepdims=True) + jnp.sum(
                jnp.where(mine, from_past - d_to_end, 0.0), axis=1,
                keepdims=True)
            by_col = jnp.where(head_col == r + k, rows, by_col)
            ending = jnp.sum(jnp.where(mine[:1], jnp.sum(
                d_to_end, axis=0, keepdims=True) + kept, 0.0),
                axis=1, keepdims=True)                 # (1, 1)
            cols = jnp.where(at_last, ending, 0.0) - jnp.sum(
                d_decay, axis=0, keepdims=True)
            ds_row = jnp.where(head_row == k, cols, ds_row)
        moved = d_x_dt + d_handed * to_end             # of x dt, (q, 128)
        dx_ref[0, :, lanes] = (moved * dt_sp + dy * d_ref[:, lanes]
                               ).astype(dx_ref.dtype)
        by_x = moved * xt
        for u in range(per_tile):
            by_col = jnp.where(head_col == first + u, jnp.sum(
                jnp.where(mine_of[u], by_x, 0.0), axis=1, keepdims=True),
                by_col)
        dd_ref[:, lanes] += jnp.sum(dy * xt, axis=0, keepdims=True)

    d_cb_low = d_cb.astype(dtype)
    dc_ref[0] = (d_c + _dot(d_cb_low, bm)).astype(dc_ref.dtype)
    db_ref[0] = (d_b + _dot(d_cb_low, cm, _TN)).astype(db_ref.dtype)
    by_row = by_col.T                                  # (128, q)
    ddt_ref[...] = by_row[:r]
    ds_ref[...] = ds_row + by_row[r:2 * r]


def _operands(x, b_in, c_in, dt_row, s_row, d_row, *, backward):
    """(arrays, their block specs, the specs by name) of what both kernels
    read, for a grid (batch, group, chunk), the chunk axis walked from the
    last to the first where `backward`."""
    _, c, g, r, q = dt_row.shape
    n, width = b_in.shape[2] // g, x.shape[2] // g
    at = (lambda ci: c - 1 - ci) if backward else (lambda ci: ci)
    whole = lambda a: pl.BlockSpec(a.shape, lambda bi, gi, ci: (0, 0))
    spec = {
        "wide": pl.BlockSpec((1, q, width),
                             lambda bi, gi, ci: (bi, at(ci), gi)),
        "narrow": pl.BlockSpec((1, q, n),
                               lambda bi, gi, ci: (bi, at(ci), gi)),
        "rows": pl.BlockSpec((None, None, None, r, q),
                             lambda bi, gi, ci: (bi, at(ci), gi, 0, 0)),
        "states": pl.BlockSpec((None, None, n, width),
                               lambda bi, gi, ci: (bi, at(ci), 0, gi))}
    repeat, spread = _selectors(r, width // r, q)
    arrays = (x, b_in, c_in, dt_row, s_row, d_row, repeat, spread)
    specs = [spec["wide"], spec["narrow"], spec["narrow"], spec["rows"],
             spec["rows"],
             pl.BlockSpec((None, 1, width), lambda bi, gi, ci: (gi, 0, 0)),
             whole(repeat), whole(spread)]
    return arrays, specs, spec


def _forward(x, b_in, c_in, dt_row, s_row, d_row, save_states):
    b, t, width_all = x.shape
    _, c, g, r, _ = dt_row.shape
    n, width = b_in.shape[2] // g, width_all // g
    arrays, specs, spec = _operands(x, b_in, c_in, dt_row, s_row, d_row,
                                    backward=False)
    out_shape = [jax.ShapeDtypeStruct((b, t, width_all), _F32)]
    out_specs = [spec["wide"]]
    if save_states:
        out_shape.append(jax.ShapeDtypeStruct((b, c, n, width_all), _F32))
        out_specs.append(spec["states"])
    out = pl.pallas_call(
        functools.partial(_fwd_kernel, p=width // r,
                          save_states=save_states),
        grid=(b, g, c), in_specs=specs,
        out_specs=out_specs, out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((n, width), _F32)],
        compiler_params=_COMPILER_PARAMS, interpret=INTERPRET,
    )(*arrays)
    return out if save_states else out[0]


@jax.custom_vjp
def _scan(x, b_in, c_in, dt_row, s_row, d_row):
    return _forward(x, b_in, c_in, dt_row, s_row, d_row, False)


def _scan_fwd(x, b_in, c_in, dt_row, s_row, d_row):
    y, states = _forward(x, b_in, c_in, dt_row, s_row, d_row, True)
    return y, (x, b_in, c_in, dt_row, s_row, d_row, states)


def _scan_bwd(residuals, dy):
    x, b_in, c_in, dt_row, s_row, d_row, states = residuals
    b, _, width_all = x.shape
    _, c, g, r, q = dt_row.shape
    n, width = b_in.shape[2] // g, width_all // g
    arrays, specs, spec = _operands(x, b_in, c_in, dt_row, s_row, d_row,
                                    backward=True)
    small = jax.ShapeDtypeStruct(dt_row.shape, _F32)
    # the name `scan` opens around the forward: this function is traced
    # outside it (PERF.md section 3: the row `ssm_scan` reads both passes)
    with jax.named_scope("ssm_scan"):
        dx, db, dc, d_dt, ds, dd = pl.pallas_call(
            functools.partial(_bwd_kernel, p=width // r),
            grid=(b, g, c),
            in_specs=specs + [spec["states"], spec["wide"]],
            out_specs=[spec["wide"], spec["narrow"], spec["narrow"],
                       spec["rows"], spec["rows"],
                       pl.BlockSpec((None, None, 1, width),
                                    lambda bi, gi, ci: (bi, gi, 0, 0))],
            out_shape=[
                jax.ShapeDtypeStruct(x.shape, x.dtype),
                jax.ShapeDtypeStruct(b_in.shape, b_in.dtype),
                jax.ShapeDtypeStruct(c_in.shape, c_in.dtype),
                small, small,
                jax.ShapeDtypeStruct((b, g, 1, width), _F32)],
            scratch_shapes=[pltpu.VMEM((n, width), _F32)],
            compiler_params=_COMPILER_PARAMS, interpret=INTERPRET,
        )(*arrays, states, dy)
        return dx, db, dc, d_dt, ds, jnp.sum(dd, axis=0)


_scan.defvjp(_scan_fwd, _scan_bwd)


@functools.partial(jax.jit, static_argnames="chunk")
def scan(x, dt, A, B, C, D, chunk: int):
    """`ssd.ssd`'s arguments, at sizes `applies` admits. One jitted function,
    so that the layers of a model share one trace and lowering of each
    kernel: traced at every call site, the kernels' unrolled bodies cost the
    benchmark's hybrid step 8 s of set-up in every process, compile cache
    or not (PERF.md section 6, PR 33). JAX lowers such a function once,
    apart from its call sites, so what is inside carries no name stack but
    its own: hence the scope, which the benchmark's readers go by."""
    b, t, h, p = x.shape
    g, n = B.shape[2:]
    c, r = t // chunk, h // g
    with jax.named_scope("ssm_scan"):
        dt_row = dt.astype(_F32).reshape(b, c, chunk, g, r).transpose(
            0, 1, 3, 4, 2)                             # (b, c, g, r, q)
        # the running sums inside a chunk as a product with a triangle of
        # ones, float32 to the last bits: XLA:TPU's own cumsum of these 4 MB
        # is a reduce-window of 1.6 ms, each way (PERF.md section 6, PR 33)
        upto = jnp.asarray(np.triu(np.ones((chunk, chunk), np.float32)))
        s_row = jnp.einsum("bcgrj,ji->bcgri",
                           dt_row * A.astype(_F32).reshape(g, r, 1), upto,
                           precision=lax.Precision.HIGHEST)
        d_row = jnp.repeat(D.astype(_F32), p).reshape(g, 1, r * p)
        y = _scan(x.reshape(b, t, h * p),
                  B.astype(x.dtype).reshape(b, t, g * n),
                  C.astype(x.dtype).reshape(b, t, g * n), dt_row, s_row,
                  d_row)
        return y.reshape(b, t, h, p)
