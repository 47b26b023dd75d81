"""Kimi Delta Attention's chunked WY form (ops/kda.py) as a pair of Pallas TPU
kernels in which nothing of size chunk x chunk a head, no decayed copy of q
or k and no per-chunk map of the state ever reaches HBM.

Why a kernel. The XLA form makes, for every chunk of 64 and head, two 64 x 64
score matrices in four sub-blocks, their inverse by doubling (some 10,000
small float32 products a layer), five decayed copies of q and k and the
chunk's affine map of the state, writes each to HBM and reads it back, three
times forward and once backward. The recurrence needs q, k, v, g, beta in and
o out, once. Here a grid step holds up to `CHUNKS` chunks of a group of up to
`HEADS` heads and walks the chunks in a loop; every head's state (float32,
kept transposed, `d_v x d_k`, so that a channel's decay multiplies lanes) is
carried in VMEM scratch from chunk to chunk and from step to step.

Layouts, all as the mixer has them (no heads-major copy on either side):

    q, k, v, g, o   (b, t, h*128)   a block is the chunks' rows of the
                    group's lanes; a head is one static lane tile of it
    beta, dbeta     (b, t, h)       a block is the chunks' rows of ALL heads
                    (32 heads are a quarter of a lane tile: a block of a
                    group's heads alone is not a legal block, and a copy
                    with positions in the lanes is an XLA transpose a call);
                    a head's column is selected by a lane mask
    states          (b, chunks, h, d_v, d_k) float32: what enters each chunk,
                    written by the differentiated forward only

The grid is (batch, block of chunks, group), the group innermost, so that
the block of beta (and of its cotangent, which the groups fill in turn)
stays where it is while a block's groups pass.

Per chunk and head, forward (G the running sum of g inside the chunk, S the
entering state, `lo` the rounding to q's dtype before a product):

    KK, QK   in sub-blocks of `SUB` = 16 rows against the sub-block's first
             position r: rows lo(k_i e^{G_i - G_r}), lo(q_i e^{G_i - G_r})
             against columns lo(k_j e^{min(G_r - G_j, CAP)}): no exponent is
             formed that can be large (ops/kda.py, "The exponents")
    M^-1     = (I + Diag(beta) strictly-lower(KK))^-1, float32: forward
             substitution on the vector unit, a column a step, two heads to
             a register (`_inverses`); no product, no power
    R        = V - lo(K e^G) lo(S)             (the delta rule's error)
    U        = lo(M^-1) lo(Diag(beta) R)       (= T V - W S of ops/kda.py)
    o        = lo(Q e^G) lo(S) + lo(masked QK) lo(U)
    S'       = Diag(e^{G_last}) S + lo(K e^{G_last - G})^T lo(U)

**The order of the program is part of the design.** The compiler's
scheduler keeps close to the order it is given, and a head's chunk is a
chain of a dozen products that each wait for the one before. So the kernels
take all heads of the group through one stage before the next (`_chunks`,
`_enter`, the lists in the kernels' bodies): the products of different heads
stand side by side and the MXU works on one while another's result is on its
way. Head by head the same operations took 2.3 times as long (PERF.md
section 6, PR 35).

Backward: the same walk from the last chunk to the first with the state's
cotangent in VMEM; a chunk's scores, inverse, R and U are made again from
the inputs and the entering state. With dM^-1 = dU (beta R)^T the solve's
cotangent -M^-T dM^-1 M^-T is -d(beta R) U^T: one product of what is there.
The reference point of a sub-block cancels in exp(G_i - G_r) exp(G_r - G_j),
so dG is what the decayed copies give: q dq + k (dk of rows and of K e^G,
less dk of columns and of K e^{G_last - G}), plus the chunk's last row's
share; dg is its reversed running sum. Cotangents are rounded like the
values they meet in a product.

The running sums are products with a triangle of ones, g split three ways
into bf16 parts that add up to the float32 value (exact: the ones are).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from distributed_vgg_f_tpu.ops.kda import SUB, _CAP

# Tests on the CPU flip this to run the kernels in the Pallas interpreter;
# `kda.kda` then also takes them off a TPU.
INTERPRET = False

LANES = 128
CHUNK = 64
#: the most heads a grid step holds (its body is unrolled over them, two to
#: a register in the solve): the more, the more products of different heads
#: stand side by side (PERF.md section 6, PR 35)
HEADS = 16


def _heads_a_step(heads: int) -> int:
    return max(n for n in range(2, HEADS + 1, 2) if heads % n == 0)


# grid (batch, block of chunks, group): the blocks carry the states, the
# group axis the block of beta's cotangent
_COMPILER_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "arbitrary", "arbitrary"),
    vmem_limit_bytes=64 * 1024 * 1024)

_NN = (((1,), (0,)), ((), ()))      # a @ b
_NT = (((1,), (1,)), ((), ()))      # a @ b^T
_TN = (((0,), (0,)), ((), ()))      # a^T @ b
_F32 = jnp.float32
_HIGHEST = lax.Precision.HIGHEST


def applies(k_shape, v_shape, chunk: int) -> bool:
    """Whether the kernels take `kda`'s arguments: by shape alone. Chunks of
    64, a head one lane tile on both sides, the heads in pairs."""
    _, t, h, dk = k_shape
    return (chunk == CHUNK and t % CHUNK == 0 and dk == LANES
            and v_shape[-1] == LANES and h % 2 == 0)


def _dot(a, b, dims=_NN):
    precision = None if a.dtype == jnp.bfloat16 else _HIGHEST
    return lax.dot_general(a, b, dims, preferred_element_type=_F32,
                           precision=precision)


def _parts(x):
    """A float32 x three ways into bf16 parts that add up to it."""
    parts = []
    for _ in range(3):
        parts.append(x.astype(jnp.bfloat16))
        x = x - parts[-1].astype(_F32)
    return parts


def _by_ones(ones, x):
    """`ones @ x` for a 0/1 matrix in bf16 and a float32 x, float32 to the
    last bits: x goes three ways into bf16 parts that add up to it, and the
    float32 accumulator adds the parts' products up again."""
    high, mid, low = (lax.dot_general(ones, part, _NN,
                                      preferred_element_type=_F32)
                      for part in _parts(x))
    return (low + mid) + high


#: rows of a float32 register: the inverse is held in pieces of it
_ROWS = 8


def _inverses(a_heads):
    """(I + a)^-1 for each `a` (C, C) float32, strictly lower triangular,
    by forward substitution on the vector unit, a column a step: row j of
    the inverse is final after step j - 1, and step j takes `a[i, j]` times
    it from every row i below (a register holds 8 rows: the registers that
    end at or above j have nothing left to take). Two heads share a
    register's 128 lanes, so a step's lane gather (column j of both) and
    its multiply-subtract serve both; the pairs go side by side, so that
    one's waits are another's work. Float32 throughout, no product on the
    MXU and no power of `a`. (With 16 x 16 diagonal blocks done so and the
    rest as two doublings of float32 products, six bf16 passes each, the
    doublings took 4.0 ms of a forward's 10.5 and the 15 steps 0.3:
    PERF.md section 6, PR 35.)"""
    n, per = CHUNK, CHUNK // _ROWS
    # (fresh iotas, not slices of one: Mosaic's compiler fails on a slice
    # of an iota that is constant along the sliced dimension)
    eye_row = lax.broadcasted_iota(jnp.int32, (_ROWS, 2 * n), 0)
    lane = lax.broadcasted_iota(jnp.int32, (_ROWS, 2 * n), 1)
    own, pair = lane & (n - 1), lane & n       # column in the head, its half
    pieces = []                        # [a's rows, the inverse's] a register
    for i in range(0, len(a_heads), 2):
        both = jnp.concatenate(a_heads[i:i + 2], axis=1)       # (C, 2C)
        pieces += [[both[at:at + _ROWS], (own == eye_row + at).astype(_F32)]
                   for at in range(0, n, _ROWS)]
    # (lax's own operations, and one index array a step: through jnp, with
    # `take_along_axis` for each of the 280 updates a pair, tracing the
    # three kernels took 15 s of every process's set-up)
    gather = functools.partial(
        lax.gather, dimension_numbers=lax.GatherDimensionNumbers(
            offset_dims=(), collapsed_slice_dims=(1,), start_index_map=(1,),
            operand_batching_dims=(0,), start_indices_batching_dims=(0,)),
        slice_sizes=(1, 1), mode=lax.GatherScatterMode.PROMISE_IN_BOUNDS)
    for j in range(n - 1):
        column = (pair + j)[..., None]             # column j of both heads
        for first in range(0, len(pieces), per):
            done = lax.broadcast_in_dim(lax.slice_in_dim(
                pieces[first + j // _ROWS][1], j % _ROWS, j % _ROWS + 1),
                (_ROWS, 2 * n), (0, 1))
            for piece in pieces[first + (j + 1) // _ROWS:first + per]:
                piece[1] = lax.sub(piece[1], lax.mul(
                    done, gather(piece[0], column)))
    out = []
    for first in range(0, len(pieces), per):
        both = jnp.concatenate(
            [piece[1] for piece in pieces[first:first + per]], axis=0)
        out += [both[:, :n], both[:, n:]]
    return out


class _Chunk:
    """What both kernels make of one head's chunk. Its methods are the
    stages of the work, and the kernels take the heads through each stage
    together (`_chunks`, `_enter`): products of different heads stand side
    by side in the program, so that the MXU works on one head's while
    another's result is on its way (a head at a time, the chain of a
    dozen dependent products was most of a grid step)."""

    def __init__(self, q, k, g, beta, lower, row, col):
        self.dtype = q.dtype
        self.q32, self.k32 = q.astype(_F32), k.astype(_F32)
        self.beta = beta                                   # (C, 1)
        self.strict, self.seen = row > col, row >= col
        self.G = _by_ones(lower, g)                        # (C, dk)

    def decays(self):
        lo = lambda x: x.astype(self.dtype)
        G = self.G
        last = G[CHUNK - 1:CHUNK]
        self.grow, self.to_end = jnp.exp(G), jnp.exp(last - G)
        self.keep = jnp.exp(last)                          # (1, dk)
        self.k_out = lo(self.k32 * self.to_end)
        # [K e^G; Q e^G], (2C, dk): one product with the state for both
        self.kq_in = jnp.concatenate(
            [lo(self.k32 * self.grow), lo(self.q32 * self.grow)], axis=0)
        # the scores' operands, `SUB` rows at a time against the
        # sub-block's first position: rows decayed from it (<= 0), columns
        # back to it (<= 0 before the sub-block, at most (SUB - 1) x 5
        # inside it; behind it the clamp, and the masks take the product)
        self.row_decay, self.col_decay, self.rows, self.cols = [], [], [], []
        for at in range(0, CHUNK, SUB):
            first = G[at:at + 1]
            self.row_decay.append(jnp.exp(G[at:at + SUB] - first))
            self.col_decay.append(jnp.exp(jnp.minimum(first - G, _CAP)))
            self.rows.append(jnp.concatenate(
                [lo(self.k32[at:at + SUB] * self.row_decay[-1]),
                 lo(self.q32[at:at + SUB] * self.row_decay[-1])], axis=0))
            self.cols.append(lo(self.k32 * self.col_decay[-1]))

    def scores(self):
        both = [_dot(rows, cols, _NT)                      # (2 SUB, C)
                for rows, cols in zip(self.rows, self.cols)]
        self.kk = jnp.where(self.strict, jnp.concatenate(
            [b[:SUB] for b in both], axis=0), 0.0)
        self.qk = jnp.where(self.seen, jnp.concatenate(
            [b[SUB:] for b in both], axis=0), 0.0).astype(self.dtype)
        self.a = self.beta * self.kk

    def read_state(self, inverse, state, v):
        self.inverse = inverse.astype(self.dtype)          # (C, C)
        self.state = state.astype(self.dtype)              # (dv, dk)
        ks = _dot(self.kq_in, self.state, _NT)             # (2C, dv)
        self.error = v.astype(_F32) - ks[:CHUNK]           # R
        self.read = ks[CHUNK:]
        self.weighed = (self.beta * self.error).astype(self.dtype)

    def solve(self):
        self.u = _dot(self.inverse, self.weighed).astype(self.dtype)


def _chunks(q_ref, k_ref, g_ref, rows, betas, first, lower, row, col):
    """The heads' chunks at `rows` of a grid step's block, as far as the
    entering states are not needed: scores and inverses. A stage for all
    heads, then the next."""
    heads = q_ref.shape[1] // LANES
    chunks = []
    for x in range(heads):
        lanes = slice(x * LANES, (x + 1) * LANES)
        chunks.append(_Chunk(q_ref[rows, lanes], k_ref[rows, lanes],
                             g_ref[rows, lanes],
                             _head_column(betas, first + x), lower, row, col))
    for c in chunks:
        c.decays()
    for c in chunks:
        c.scores()
    return chunks, _inverses([c.a for c in chunks])


def _enter(chunks, inverses, states, v_ref, rows):
    """R and U of every head from its entering state."""
    for x, (c, inverse, state) in enumerate(zip(chunks, inverses, states)):
        c.read_state(inverse, state,
                     v_ref[rows, x * LANES:(x + 1) * LANES])
    for c in chunks:
        c.solve()


def _rows(i):
    """The i-th chunk's rows of a block."""
    return pl.ds(pl.multiple_of(i * CHUNK, CHUNK), CHUNK)


def _head_column(block, head):
    """(C, 1): column `head` (a traced scalar) of `block` (C, h)."""
    lane = lax.broadcasted_iota(jnp.int32, block.shape, 1)
    return jnp.sum(jnp.where(lane == head, block, 0.0), axis=1, keepdims=True)


def _constants():
    """The triangle of ones (bf16) and a chunk's row and column indices."""
    row = lax.broadcasted_iota(jnp.int32, (CHUNK, CHUNK), 0)
    col = lax.broadcasted_iota(jnp.int32, (CHUNK, CHUNK), 1)
    return (row >= col).astype(jnp.bfloat16), row, col


def _fwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, o_ref, *rest,
                save_states):
    states_ref = rest[0] if save_states else None
    s_ref = rest[-1]                               # (h, dv, dk) float32
    heads = q_ref.shape[1] // LANES
    first = pl.program_id(2) * heads

    @pl.when(pl.program_id(1) == 0)
    def _():
        s_ref[pl.ds(first, heads)] = jnp.zeros((heads, LANES, LANES), _F32)

    lower, row, col = _constants()

    def chunk(i, carry):
        rows = _rows(i)
        chunks, inverses = _chunks(q_ref, k_ref, g_ref, rows,
                                   beta_ref[rows, :], first, lower, row, col)
        states = [s_ref[first + x] for x in range(heads)]
        if save_states:
            for x, state in enumerate(states):
                states_ref[i, x] = state
        _enter(chunks, inverses, states, v_ref, rows)
        outs = [c.read + _dot(c.qk, c.u) for c in chunks]
        nexts = [state * c.keep + _dot(c.u, c.k_out, _TN)
                 for c, state in zip(chunks, states)]
        for x, (out, state) in enumerate(zip(outs, nexts)):
            o_ref[rows, x * LANES:(x + 1) * LANES] = out
            s_ref[first + x] = state
        return carry

    lax.fori_loop(0, q_ref.shape[0] // CHUNK, chunk, None)


def _bwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, states_ref, do_ref,
                dq_ref, dk_ref, dv_ref, dg_ref, dbeta_ref, ds_ref):
    heads = q_ref.shape[1] // LANES
    group = pl.program_id(2)
    first = group * heads
    dtype = q_ref.dtype
    lo = lambda x: x.astype(dtype)

    @pl.when(pl.program_id(1) == 0)
    def _():
        ds_ref[pl.ds(first, heads)] = jnp.zeros((heads, LANES, LANES), _F32)

    @pl.when(group == 0)
    def _():
        dbeta_ref[...] = jnp.zeros_like(dbeta_ref)

    lower, row, col = _constants()
    upper = (row <= col).astype(jnp.bfloat16)
    head_lane = lax.broadcasted_iota(jnp.int32, (CHUNK, beta_ref.shape[1]), 1)
    chunks_here = q_ref.shape[0] // CHUNK

    def chunk(step, carry):
        i = chunks_here - 1 - step                 # the last chunk first
        rows = _rows(i)
        of = lambda x: slice(x * LANES, (x + 1) * LANES)
        chunks, inverses = _chunks(q_ref, k_ref, g_ref, rows,
                                   beta_ref[rows, :], first, lower, row, col)
        entering = [states_ref[i, x] for x in range(heads)]
        _enter(chunks, inverses, entering, v_ref, rows)
        d_o = [lo(do_ref[rows, of(x)]) for x in range(heads)]
        d_next = [ds_ref[first + x] for x in range(heads)]  # of the leaving
        d_next_lo = [lo(d) for d in d_next]
        # o = read + QK U;  S' = keep S + U^T K_out
        d_u = [lo(_dot(c.qk, d, _TN) + _dot(c.k_out, dn, _NT))
               for c, d, dn in zip(chunks, d_o, d_next_lo)]
        d_k_out = [_dot(c.u, dn) for c, dn in zip(chunks, d_next_lo)]
        d_qk = [jnp.where(c.seen, _dot(d, c.u, _NT), 0.0)
                for c, d in zip(chunks, d_o)]
        # U = M^-1 (beta R);  R = V - (K e^G) S
        d_weighed = [_dot(c.inverse, d, _TN) for c, d in zip(chunks, d_u)]
        d_error = [c.beta * d for c, d in zip(chunks, d_weighed)]
        d_beta = [jnp.sum(d * c.error, axis=1, keepdims=True)
                  for c, d in zip(chunks, d_weighed)]
        # [-dR; do] against [K e^G; Q e^G]: the decayed copies' cotangents
        # and the entering state's in one product each
        both = [jnp.concatenate([lo(-de), d], axis=0)      # (2C, dv)
                for de, d in zip(d_error, d_o)]
        d_kq_in = [_dot(b, c.state) for b, c in zip(both, chunks)]
        d_enter = [dn * c.keep + _dot(b, c.kq_in, _TN)
                   for dn, c, b in zip(d_next, chunks, both)]
        # M = I + Diag(beta) KK:  dM = -M^-T dM^-1 M^-T with dM^-1 =
        # dU (beta R)^T, which is -(M^-T dU) (M^-1 beta R)^T = -d(beta R) U^T
        d_a = [jnp.where(c.strict, -_dot(lo(d), c.u, _NT), 0.0)
               for c, d in zip(chunks, d_weighed)]
        d_kk = [c.beta * d for c, d in zip(chunks, d_a)]
        # the scores' sub-blocks
        d_both = [[jnp.concatenate([lo(dk[at:at + SUB]), lo(dq[at:at + SUB])],
                                   axis=0) for at in range(0, CHUNK, SUB)]
                  for dk, dq in zip(d_kk, d_qk)]
        d_rows = [[_dot(d, cols) for d, cols in zip(ds, c.cols)]
                  for ds, c in zip(d_both, chunks)]        # (2 SUB, dk) each
        d_cols = [[_dot(d, rows_, _TN) for d, rows_ in zip(ds, c.rows)]
                  for ds, c in zip(d_both, chunks)]        # (C, dk) each
        d_betas = dbeta_ref[rows, :]
        for x, c in enumerate(chunks):
            d_k_rows = jnp.concatenate(
                [d[:SUB] * decay for d, decay in zip(d_rows[x], c.row_decay)],
                axis=0) + d_kq_in[x][:CHUNK] * c.grow
            d_q = jnp.concatenate(
                [d[SUB:] * decay for d, decay in zip(d_rows[x], c.row_decay)],
                axis=0) + d_kq_in[x][CHUNK:] * c.grow
            d_k_cols = d_k_out[x] * c.to_end
            for d, decay in zip(d_cols[x], c.col_decay):
                d_k_cols += d * decay
            dq_ref[rows, of(x)] = d_q.astype(dq_ref.dtype)
            dk_ref[rows, of(x)] = (d_k_rows + d_k_cols).astype(dk_ref.dtype)
            dv_ref[rows, of(x)] = d_error[x].astype(dv_ref.dtype)
            ds_ref[first + x] = d_enter[x]
            # dG: what decays forward adds, what decays backward takes; the
            # chunk's last row also has exp(G_last)'s and K_out's
            d_G = c.q32 * d_q + c.k32 * (d_k_rows - d_k_cols)
            d_keep = jnp.sum(d_next[x] * entering[x], axis=0, keepdims=True)
            ending = jnp.sum(c.k32 * d_k_out[x] * c.to_end, axis=0,
                             keepdims=True) + c.keep * d_keep
            dg_ref[rows, of(x)] = _by_ones(upper, d_G) + ending
            d_betas = jnp.where(
                head_lane == first + x, d_beta[x] + jnp.sum(
                    d_a[x] * c.kk, axis=1, keepdims=True), d_betas)
        dbeta_ref[rows, :] = d_betas
        return carry

    lax.fori_loop(0, chunks_here, chunk, None)


#: the most chunks a grid step holds: with 16 heads the backward's blocks
#: and their second buffers take 33 MiB of VMEM (8 chunks: 66, over the 64
#: the kernels ask for); steps of one chunk ran no slower (PERF.md section
#: 6, PR 35)
CHUNKS = 4


def _chunks_a_step(chunks: int) -> int:
    return max(n for n in range(1, CHUNKS + 1) if chunks % n == 0)


def _specs(h, blocks, per, *, backward):
    heads = _heads_a_step(h)
    """Block specs by name for a grid (batch, block of `per` chunks,
    group), the blocks walked from the last to the first where
    `backward`."""
    at = (lambda ci: blocks - 1 - ci) if backward else (lambda ci: ci)
    return {
        "wide": pl.BlockSpec((None, per * CHUNK, heads * LANES),
                             lambda bi, ci, gi: (bi, at(ci), gi)),
        "heads": pl.BlockSpec((None, per * CHUNK, h),
                              lambda bi, ci, gi: (bi, at(ci), 0)),
        "states": pl.BlockSpec((None, per, heads, LANES, LANES),
                               lambda bi, ci, gi: (bi, at(ci), gi, 0, 0))}


def _forward(q, k, v, g, beta, save_states):
    b, t, width = q.shape
    h, c = width // LANES, t // CHUNK
    per = _chunks_a_step(c)
    spec = _specs(h, c // per, per, backward=False)
    out_shape = [jax.ShapeDtypeStruct((b, t, width), _F32)]
    out_specs = [spec["wide"]]
    if save_states:
        out_shape.append(
            jax.ShapeDtypeStruct((b, c, h, LANES, LANES), _F32))
        out_specs.append(spec["states"])
    out = pl.pallas_call(
        functools.partial(_fwd_kernel, save_states=save_states),
        grid=(b, c // per, h // _heads_a_step(h)),
        in_specs=[spec["wide"]] * 4 + [spec["heads"]],
        out_specs=out_specs, out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((h, LANES, LANES), _F32)],
        compiler_params=_COMPILER_PARAMS, interpret=INTERPRET,
    )(q, k, v, g, beta)
    return out if save_states else out[0]


@jax.custom_vjp
def _core(q, k, v, g, beta):
    return _forward(q, k, v, g, beta, False)


def _core_fwd(q, k, v, g, beta):
    o, states = _forward(q, k, v, g, beta, True)
    return o, (q, k, v, g, beta, states)


def _core_bwd(residuals, d_o):
    q, k, v, g, beta, states = residuals
    b, t, width = q.shape
    h, c = width // LANES, t // CHUNK
    per = _chunks_a_step(c)
    spec = _specs(h, c // per, per, backward=True)
    like = lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype)
    # the name `chunked` opens around the forward: this function is traced
    # outside it (the row `kda_core` reads both passes)
    with jax.named_scope("kda_core"):
        return tuple(pl.pallas_call(
            _bwd_kernel, grid=(b, c // per, h // _heads_a_step(h)),
            in_specs=[spec["wide"]] * 4 + [spec["heads"], spec["states"],
                                           spec["wide"]],
            out_specs=[spec["wide"]] * 4 + [spec["heads"]],
            out_shape=[like(q), like(k), like(v), like(g), like(beta)],
            scratch_shapes=[pltpu.VMEM((h, LANES, LANES), _F32)],
            compiler_params=_COMPILER_PARAMS, interpret=INTERPRET,
        )(q, k, v, g, beta, states, d_o))


_core.defvjp(_core_fwd, _core_bwd)


@jax.jit
def chunked(q, k, v, g, beta):
    """`kda.kda`'s arguments at sizes `applies` admits (chunks of 64). One
    jitted function, so that the layers of a model share one trace and
    lowering of each kernel (PERF.md section 6, PR 33 and PR 35). JAX
    lowers such a function once, apart from its call sites, so what is
    inside carries no name stack but its own: hence the scope, which the
    benchmark's readers go by."""
    b, t, h, _ = q.shape
    flat = lambda x: x.reshape(b, t, h * x.shape[-1])
    with jax.named_scope("kda_core"):
        o = _core(flat(q), flat(k), flat(v), flat(g.astype(_F32)),
                  beta.astype(_F32))
        return o.reshape(b, t, h, v.shape[-1])
