"""Kimi Delta Attention's recurrence (Kimi Linear, arXiv:2510.26692) by its
chunked WY form.

The recurrence, a decay a channel and a state of `d_k x d_v` a head:

    S_t = (I - beta_t k_t k_t^T) Diag(exp(g_t)) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t                                         S_0 = 0

is computed `chunk` positions at a time. With u_t = beta_t (v_t - (Diag(
exp(g_t)) S_{t-1})^T k_t) it reads S_t = Diag(exp(g_t)) S_{t-1} + k_t u_t^T,
so inside a chunk, with G the running sum of g from the chunk's start and S
the state that enters it:

    A_ij = beta_i (k_i * exp(G_i - G_j)) . k_j     for j < i, else 0
    T    = (I + A)^-1 Diag(beta)
    W    = T (K * exp(G));   U0 = T V;   U = U0 - W S
    o_i  = (q_i * exp(G_i)) S + sum_{j <= i} ((q_i * exp(G_i - G_j)) . k_j) u_j
    S'   = Diag(exp(G_last)) S + sum_j (k_j * exp(G_last - G_j)) u_j^T

Both are affine in S. With Q_in = Q * exp(G), K_out = K * exp(G_last - G)
and QK the read-out's masked scores:

    S'   = Diag(exp(G_last)) S - (K_out^T W) S + K_out^T U0
    o    = (Q_in - QK W) S + QK U0

so in the XLA form (`kda_xla`) everything but S itself (A, T, W, U0, the
scores, the decayed copies of q and k, and the four matrices above) is made
for many chunks at once (`_within`, a group of chunks at a time, each group
made again in the backward pass so that one group's intermediates are
alive); the scan over the chunks (`_across`) is one small product a chunk,
and the read-out is made for all chunks at once from the states the scan
kept.

**The exponents.** A chunk of 64 at a gate of -5 a position spans G = -320,
and exp(-G_j) alone overflows float32 after 17 positions. So no exponent is
formed that can be large: `exp(G_i)` and `exp(G_last - G_j)` are
differences that are <= 0; the pairwise `exp(G_i - G_j)` of A and of the
read-out's scores is split at the first position r of the row's sub-block
of `SUB` = 16 positions, `exp(G_i - G_r) exp(G_r - G_j)`: the first
factor's exponent is <= 0, and so is the second's for every j before the
sub-block. Inside the row's own sub-block (j in [r, i]) the two gates a
caller can state part ways (`kda`'s `bounded`, a static choice):

- **bounded** (Ling's `kda_safe_gate`, g >= `LOWER_BOUND`): the same split,
  whose second exponent is at most `(SUB - 1) x 5 = 75 < 88` there. Its
  factor is made for every column j of the chunk, behind the row's
  sub-block too, where the mask takes the product: there its exponent is
  clamped at `_CAP`. A g under the bound is clamped with them: finite, and
  no longer the recurrence, which is why this form is only for a caller
  whose gate cannot go under it;
- **unbounded** (Kimi Linear's `-exp(A_log) softplus(..)`, any g <= 0): the
  split only for columns before the sub-block (the second factor's exponent
  is then clamped at 0, which changes no column the mask keeps), and on the
  diagonal 16 x 16 sub-blocks each pair's `exp(G_i - G_j)` formed directly,
  an exponent <= 0 whatever the gate, as a product of float32 elements
  summed over the channels. A gate of -1e4 gives exp = 0, not a NaN. Its
  price is the diagonal's exponentials (a chunk's 4 x 16 x 16 x 128 of them
  a head: PERF.md, section 6).

Decays, their sums, the solve and the state are float32; the products take
operands in q's dtype and accumulate in float32, as ops/ssd.py `ssd_xla`
rounds. A beta in (0, 2) (`kda_allow_neg_eigval`: negative eigenvalues of
the transition) changes nothing here: the solve is exact algebra at any
beta.

The solve is forward substitution in blocks that double (the inverse of a
2 x 2 block-triangular matrix from its diagonal blocks' inverses, from
blocks of one position up to the chunk), in float32 at `highest`
precision: no power of A is ever formed, so keys that repeat do not cancel
catastrophically.

Two implementations behind `kda`, chosen by what the call can observe:

- the Pallas kernels of ops/kda_pallas.py, forward and hand-written
  backward, where the chunk is 64, a head is one lane tile on both sides,
  the heads come in pairs (`kda_pallas.applies`) and the backend is a TPU
  (or the Pallas interpreter a test switched on): the direct form above
  (U = T (V - (K * exp(G)) S), with S in VMEM along a sequential chunk
  axis), the same sub-blocks, reference points and either form of the
  diagonal sub-blocks, the solve by
  forward substitution on the vector unit in float32; a chunk's scores,
  their inverse and the decayed copies of q and k never reach HBM (PERF.md,
  section 6, PR 35);
- `kda_xla` everywhere else (the CPU, the tiny preset's heads of 16): the
  affine form below as plain XLA products over groups of chunks,
  differentiated by JAX. It is also the function the kernels are tested
  against.

`kda_recurrent` is the literal recurrence, one position at a time: what the
tests hold both against.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

#: the smallest g a position may have under the bounded form (the published
#: `kda_lower_bound`)
LOWER_BOUND = -5.0
#: positions that share one reference point of the pairwise decays
SUB = 16
#: where the second factor's exponent is clamped: above the (SUB - 1) x 5 =
#: 75 a gate at its bound reaches, under float32's 88 with room for a sum
#: over the channels
_CAP = SUB * -LOWER_BOUND
#: the XLA form's only (the kernels walk a grid step's chunks one by one):
#: chunks whose S-free parts are made (and made again) together: at the
#: benchmark's widths on a v5e a layer's forward and backward take 119 ms
#: with 32, 96 with 128 and 67 with 8 (PERF.md, PR 34)
GROUP_CHUNKS = 8

_HIGHEST = jax.lax.Precision.HIGHEST


def _unit_lower_inverse(a):
    """(I + a)^-1 for `a` (..., n, n) strictly lower triangular, float32,
    by doubling: the inverse of a block-triangular [[P, 0], [R, Q]] is
    [[P^-1, 0], [-Q^-1 R P^-1, Q^-1]], from blocks of one (whose inverse is
    1) up to n, every pair of a level at once. It is forward substitution
    in blocks: no power of `a` is formed."""
    n = a.shape[-1]
    size = 1 << (n - 1).bit_length()
    if size != n:                      # [[I + a, 0], [0, I]]
        a = jnp.pad(a, [(0, 0)] * (a.ndim - 2) + [(0, size - n)] * 2)
    lead = a.shape[:-2]
    inverse = jnp.ones((*lead, size, 1, 1), a.dtype)
    width = 1
    while width < size:
        pairs = size // (2 * width)
        # the pairs' own blocks of `a`, (..., pairs, 2 width, 2 width)
        own = jnp.einsum("...ipiq->...ipq", a.reshape(
            *lead, pairs, 2 * width, pairs, 2 * width))
        halves = inverse.reshape(*lead, pairs, 2, width, width)
        p, q = halves[..., 0, :, :], halves[..., 1, :, :]
        low = -jnp.matmul(jnp.matmul(q, own[..., width:, :width],
                                     precision=_HIGHEST), p,
                          precision=_HIGHEST)
        inverse = jnp.concatenate([
            jnp.concatenate([p, jnp.zeros_like(p)], -1),
            jnp.concatenate([low, q], -1)], -2)
        width *= 2
    return inverse[..., 0, :n, :n]


def _within(q, k, v, g, beta, bounded: bool = True):
    """What a chunk gives without the state S that enters it. `q`, `k`
    (n, C, h, dk), `v` (n, C, h, dv) in the compute dtype, `g` (n, C, h,
    dk) and `beta` (n, C, h) float32, n chunks of C positions; `bounded`
    the form of the diagonal sub-blocks (module docstring). Returns the
    chunk's map of the state, S' = keep * S - turn S + add, and its
    read-out, o = read S + own: `turn` (n, h, dk, dk) and `read` (n, h, C,
    dk) in the compute dtype, `add` (n, h, dk, dv), `own` (n, h, C, dv) and
    `keep` = exp(G_last) (n, h, dk) float32."""
    n, c, h, dk = k.shape
    dtype, f32 = q.dtype, jnp.float32
    sub = math.gcd(c, SUB)
    blocks = c // sub
    G = jnp.cumsum(g.astype(f32), axis=1)                    # (n, C, h, dk)
    k32, q32 = k.astype(f32), q.astype(f32)
    # rows: decayed from the first position of their own sub-block
    by_block = G.reshape(n, blocks, sub, h, dk)
    first = by_block[:, :, :1]                               # (n, B, 1, h, dk)
    from_first = jnp.exp(by_block - first)
    k_rows = (k32.reshape(by_block.shape) * from_first).astype(dtype)
    q_rows = (q32.reshape(by_block.shape) * from_first).astype(dtype)
    # columns: every position decayed back to each sub-block's first one:
    # <= 0 before that sub-block, at most (SUB - 1) x 5 inside it under the
    # bounded gate; behind it the mask below takes what the clamp leaves
    cols = (k32[:, None] * jnp.exp(jnp.minimum(
        first - G[:, None], _CAP if bounded else 0.0))
            ).astype(dtype)                                  # (n, B, C, h, dk)
    kk = jnp.einsum("nbihd,nbjhd->nhbij", k_rows, cols,
                    preferred_element_type=f32).reshape(n, h, c, c)
    qk = jnp.einsum("nbihd,nbjhd->nhbij", q_rows, cols,
                    preferred_element_type=f32).reshape(n, h, c, c)
    at = jnp.arange(c)
    if not bounded:
        # the diagonal sub-blocks pair by pair, exp(G_i - G_j) <= 1 for
        # j <= i, in place of the split's (what lies above the diagonal
        # the masks below take)
        pair = jnp.exp(jnp.minimum(
            by_block[:, :, :, None] - by_block[:, :, None], 0.0))
        k_b, q_b = (x.reshape(by_block.shape) for x in (k32, q32))
        diag = [jnp.einsum("nbihd,nbjhd,nbijhd->nhbij", x, k_b, pair,
                           precision=_HIGHEST) for x in (k_b, q_b)]
        own_block = (at[:, None] // sub) == (at[None, :] // sub)
        kk, qk = (jnp.where(own_block, jnp.einsum(
            "nhbij,bc->nhbicj", d, jnp.eye(blocks, dtype=f32)).reshape(
                n, h, c, c), split) for d, split in zip(diag, (kk, qk)))
    b_rows = beta.astype(f32).transpose(0, 2, 1)             # (n, h, C)
    A = jnp.where(at[:, None] > at[None, :], kk, 0.0) * b_rows[..., None]
    T = (_unit_lower_inverse(A) * b_rows[..., None, :]).astype(dtype)
    qk = jnp.where(at[:, None] >= at[None, :], qk, 0.0).astype(dtype)
    decay = jnp.exp(G)                                       # <= 1
    W = jnp.einsum("nhij,njhd->nhid", T, (k32 * decay).astype(dtype),
                   preferred_element_type=f32).astype(dtype)
    U0 = jnp.einsum("nhij,njhd->nhid", T, v.astype(dtype),
                    preferred_element_type=f32).astype(dtype)
    last = G[:, -1:]                                         # (n, 1, h, dk)
    q_in = (q32 * decay).astype(dtype).transpose(0, 2, 1, 3)
    k_out = (k32 * jnp.exp(last - G)).astype(dtype).transpose(0, 2, 1, 3)
    # with U = U0 - W S:  S' = exp(G_last) * S - (K_out^T W) S + K_out^T U0
    # and o = (Q_in - QK W) S + QK U0, so the scan over the chunks is one
    # product a chunk and the read-out is made for all chunks at once
    turn = jnp.einsum("nhjk,nhjd->nhkd", k_out, W,
                      preferred_element_type=f32).astype(dtype)
    add = jnp.einsum("nhjk,nhjv->nhkv", k_out, U0,
                     preferred_element_type=f32)
    read = (q_in.astype(f32) - jnp.einsum(
        "nhij,nhjd->nhid", qk, W, preferred_element_type=f32)).astype(dtype)
    own = jnp.einsum("nhij,nhjv->nhiv", qk, U0, preferred_element_type=f32)
    return turn, add, read, own, jnp.exp(last[:, 0])


def _across(parts, batch: int):
    """The scan over the chunks and the read-out: `parts` as `_within`
    gives them, n = batch x chunks a sequence. Returns o (batch, chunks,
    h, C, dv) float32."""
    turn, add, read, own, keep = parts
    chunks = turn.shape[0] // batch
    dtype, f32 = turn.dtype, jnp.float32
    # (chunks, batch, ...): the scan's axis first
    by_chunk = lambda p: p.reshape(batch, chunks, *p.shape[1:]).swapaxes(0, 1)

    def hand_on(S, chunk):
        turn, add, keep = chunk
        return keep[..., None] * S - jnp.einsum(
            "bhkd,bhdv->bhkv", turn, S.astype(dtype),
            preferred_element_type=f32) + add, S

    # a chunk's product is made again in the backward pass: what is kept of
    # the scan is the state that enters each chunk
    _, entering = jax.lax.scan(
        jax.checkpoint(hand_on), jnp.zeros((batch, *add.shape[1:]), f32),
        (by_chunk(turn), by_chunk(add), by_chunk(keep)))
    entering = entering.swapaxes(0, 1).reshape(turn.shape[0],
                                               *entering.shape[2:])
    o = own + jnp.einsum("nhik,nhkv->nhiv", read, entering.astype(dtype),
                         preferred_element_type=f32)
    return o.reshape(batch, chunks, *o.shape[1:])


def takes_kernels(k_shape, v_shape, chunk: int) -> bool:
    """Whether `kda` runs the Pallas kernels for arguments of these shapes
    (`k`'s and `v`'s) here: shapes and backend decide, nothing else."""
    from distributed_vgg_f_tpu.ops import kda_pallas
    return (jax.default_backend() == "tpu" or kda_pallas.INTERPRET) \
        and kda_pallas.applies(k_shape, v_shape, chunk)


def kda(q, k, v, g, beta, chunk: int = 64, *, bounded: bool = True):
    """`q`, `k` (b, t, h, dk) in the compute dtype (normalised, q scaled,
    by the caller), `v` (b, t, h, dv), `g` (b, t, h, dk) float32, `beta`
    (b, t, h) float32 (in (0, 1), or in (0, 2) where the transition may
    have negative eigenvalues). `bounded` is the caller's statement about
    its gate, read once when the program is traced: True, g in
    [`LOWER_BOUND`, 0] (the factored sub-blocks); False, any g <= 0 (the
    diagonal sub-blocks pair by pair: module docstring). Returns o (b, t,
    h, dv) float32. `t` is a whole number of chunks (or shorter than
    one)."""
    t = k.shape[1]
    if t % min(chunk, t):
        raise ValueError(f"{t} positions in chunks of {chunk}: a rest of a "
                         "chunk is left")
    if takes_kernels(k.shape, v.shape, chunk):
        from distributed_vgg_f_tpu.ops import kda_pallas
        return kda_pallas.chunked(q, k, v, g, beta, bounded=bounded)
    return kda_xla(q, k, v, g, beta, chunk, bounded=bounded)


def kda_xla(q, k, v, g, beta, chunk: int = 64, *, bounded: bool = True):
    """`kda` as plain XLA products, differentiated by JAX."""
    b, t, h, dk = k.shape
    dv = v.shape[-1]
    c = min(chunk, t)
    n = b * (t // c)
    group = math.gcd(n, GROUP_CHUNKS)
    split = lambda x: x.reshape(n // group, group, c, *x.shape[2:])
    within = jax.checkpoint(lambda *args: _within(*args, bounded=bounded))
    # the S-free parts a group of chunks at a time, each group made again
    # in the backward pass: one group's intermediates are alive
    parts = jax.lax.map(lambda args: within(*args),
                        tuple(split(x) for x in (q, k, v, g, beta)))
    parts = [p.reshape(n, *p.shape[2:]) for p in parts]
    o = _across(parts, b)                                # (b, chunks, h, C, dv)
    return o.transpose(0, 1, 3, 2, 4).reshape(b, t, h, dv)


def kda_recurrent(q, k, v, g, beta, *, reset_every: int | None = None):
    """The literal recurrence, one position at a time, float32: the same
    arguments and result as `kda`. With `reset_every` the state is zeroed
    before every position that is a multiple of it (a chunked form that
    forgets to hand its state on)."""
    b, t, h, dk = k.shape
    f32 = jnp.float32

    def position(S, inputs):
        q_t, k_t, v_t, g_t, beta_t, keep = inputs
        S = S * (keep * jnp.exp(g_t))[..., None]             # (b, h, dk, dv)
        u = beta_t[..., None] * (v_t - jnp.einsum(
            "bhk,bhkv->bhv", k_t, S, precision=_HIGHEST))
        S = S + k_t[..., None] * u[..., None, :]
        return S, jnp.einsum("bhk,bhkv->bhv", q_t, S, precision=_HIGHEST)

    at = jnp.arange(t)
    keep = jnp.ones(t, f32) if reset_every is None \
        else (at % reset_every != 0).astype(f32)
    first = lambda x: jnp.moveaxis(x.astype(f32), 1, 0)
    _, o = jax.lax.scan(
        position, jnp.zeros((b, h, dk, v.shape[-1]), f32),
        (first(q), first(k), first(v), first(g), first(beta), keep))
    return jnp.moveaxis(o, 0, 1)


def smallest_decay(g):
    """The smallest `exp(g)` of a batch: exp(LOWER_BOUND) where a bounded
    gate sits at its bound, 1 where nothing ever decays. Under an unbounded
    gate it has no floor: 0 where some channel forgets its state at once
    (g under -103, where float32's exp underflows)."""
    return jnp.exp(jnp.min(g.astype(jnp.float32)))


def share_below_bound(g):
    """The share of a batch's gate entries under `LOWER_BOUND`: those the
    bounded form would have clamped (0 under a bounded gate)."""
    return jnp.mean((g < LOWER_BOUND).astype(jnp.float32))
