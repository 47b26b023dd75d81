"""Ring attention — sequence-parallel self-attention over a mesh axis.

BEYOND-PARITY capability. The reference has no long-sequence workload
(SURVEY.md §5: the only attention in scope is ViT-S/16's 197 tokens under
plain DP, and SP/CP is recorded absent-by-design), but the mesh layer was
built to leave a sequence axis open — this module demonstrates that the
door actually opens: exact attention over a sequence SHARDED across
devices, with memory per device O(T_local·T_local) instead of O(T·T) and
the K/V blocks streamed around the ring.

TPU-native design:
- `shard_map` over the mesh axis; each device holds its (B, T_local, H, D)
  shard of Q/K/V.
- The K/V block circulates with `lax.ppermute` (neighbor exchange — rides
  ICI hops, never all-to-all), overlapping the next hop with the current
  block's matmuls when XLA schedules it.
- Numerically exact streaming softmax (the flash/online formulation): a
  running row max `m`, normalizer `l`, and un-normalized accumulator are
  corrected as each block arrives — fp32 accumulation regardless of the
  input dtype, bf16 matmuls on the MXU when inputs are bf16.
- The ring length is a trace-time constant (mesh axis size), so the loop
  unrolls into a fixed schedule — no dynamic control flow inside jit.

`ring_self_attention` is the sharded function (call inside your own
shard_map); `ring_attention` wraps it with jit+shard_map for direct use.
Equality with full (gathered) attention is tested to fp32 tolerance on the
8-device CPU mesh in tests/test_ring_attention.py, plus a bf16 dtype test
and a grad test.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.lax import axis_size
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def ring_self_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                        axis_name: str, *, causal: bool = False) -> jnp.ndarray:
    """Exact attention over a sequence sharded on `axis_name`.

    Args (PER-SHARD, inside shard_map): q, k, v of shape
    (B, T_local, H, D). Returns the (B, T_local, H, D) attention output for
    this device's query block, attending over the FULL sequence.

    `causal`: token i attends to j <= i in GLOBAL positions. K/V blocks
    travel the ring regardless (the permute schedule must be identical on
    every device), but a device contributes a block only when allowed:
    future source blocks are masked out entirely, the diagonal block gets
    the triangular mask, past blocks pass whole — so the masking costs a
    `where`, never a different collective schedule.
    """
    n = axis_size(axis_name)
    scale = 1.0 / math.sqrt(q.shape[-1])
    qf = q * scale

    b, t_q, h, d = q.shape
    acc = jnp.zeros((b, t_q, h, d), jnp.float32)        # un-normalized out
    row_max = jnp.full((b, h, t_q), -jnp.inf, jnp.float32)
    row_sum = jnp.zeros((b, h, t_q), jnp.float32)

    my_blk = lax.axis_index(axis_name)
    perm = [(i, (i + 1) % n) for i in range(n)]
    k_blk, v_blk = k, v
    for step in range(n):
        def _update(acc, row_max, row_sum, k_blk=k_blk, v_blk=v_blk,
                    step=step):
            # bf16 inputs keep the MXU GEMM in bf16; scores accumulate fp32
            scores = jnp.einsum("bqhd,bkhd->bhqk", qf, k_blk,
                                preferred_element_type=jnp.float32)
            if causal:
                # the block arriving at `step` hops started src = my - step
                src_blk = (my_blk - step) % n
                t_k = k.shape[1]
                q_pos = my_blk * t_q + jnp.arange(t_q)
                k_pos = src_blk * t_k + jnp.arange(t_k)
                allowed = q_pos[:, None] >= k_pos[None, :]    # (t_q, t_k)
                scores = jnp.where(allowed[None, None], scores, -jnp.inf)
            blk_max = jnp.max(scores, axis=-1)
            # new_max is finite from step 0 even under causal masking: step 0
            # is always the device's own DIAGONAL block (src = my - 0), where
            # every row's own position is allowed — so no -inf/-inf guard is
            # needed in the correction (code-review r3: an earlier isneginf
            # guard here was dead on every step of every device).
            new_max = jnp.maximum(row_max, blk_max)
            # correction folds previously-accumulated blocks under the new max
            correction = jnp.exp(row_max - new_max)
            probs = jnp.exp(scores - new_max[..., None])
            new_sum = row_sum * correction + jnp.sum(probs, axis=-1)
            ctx = jnp.einsum("bhqk,bkhd->bqhd", probs.astype(v_blk.dtype),
                             v_blk, preferred_element_type=jnp.float32)
            new_acc = acc * correction.transpose(0, 2, 1)[..., None] + ctx
            return new_acc, new_max, new_sum

        if causal and n > 1:
            # A fully-future visiting block (src > my: every key masked for
            # every local row) updates the state by EXACTLY the identity
            # (new_max = row_max, correction = 1, probs = 0 — state never
            # virgin here, step 0 is the self block). Skip both einsums
            # under lax.cond; the ppermute schedule below stays uniform, so
            # only dead local FLOPs disappear — on average half the causal
            # ring (mirrors ring_flash.py's kernel-call skip).
            # position-exact (not block-index) predicate: supports the
            # t_k != t_q shards the masking code above allows — fully
            # future ⟺ the block's FIRST key is past the LAST local query
            src_blk = (my_blk - step) % n
            acc, row_max, row_sum = lax.cond(
                src_blk * k.shape[1] > my_blk * t_q + t_q - 1,
                lambda a, m_, s: (a, m_, s), _update,
                acc, row_max, row_sum)
        else:
            acc, row_max, row_sum = _update(acc, row_max, row_sum)
        if step < n - 1:
            k_blk = lax.ppermute(k_blk, axis_name, perm)
            v_blk = lax.ppermute(v_blk, axis_name, perm)

    out = acc / row_sum.transpose(0, 2, 1)[..., None]
    return out.astype(q.dtype)


@functools.lru_cache(maxsize=8)
def _ring_fn(mesh: Mesh, axis_name: str, causal: bool):
    """The jit(shard_map(...)) executable, cached per (mesh, axis_name,
    causal) — a fresh closure per call would retrace and recompile every
    invocation (jit caches by function identity)."""
    seq_spec = P(None, axis_name)
    return jax.jit(shard_map(
        functools.partial(ring_self_attention, axis_name=axis_name,
                          causal=causal),
        mesh=mesh,
        in_specs=(seq_spec, seq_spec, seq_spec),
        out_specs=seq_spec,
        check_vma=False,
    ))


def ring_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                   mesh: Mesh, axis_name: str = "data",
                   causal: bool = False) -> jnp.ndarray:
    """Convenience wrapper: GLOBAL (B, T, H, D) inputs sharded on T over
    `axis_name`; jit + shard_map + ring. T must divide evenly by the axis
    size (pad upstream — attention over padding is the caller's masking
    decision, same contract as data/eval_pad.py)."""
    if q.shape[1] % mesh.shape[axis_name] != 0:
        raise ValueError(
            f"sequence length {q.shape[1]} not divisible by mesh axis "
            f"{axis_name} size {mesh.shape[axis_name]}")
    sh = NamedSharding(mesh, P(None, axis_name))
    return _ring_fn(mesh, axis_name, causal)(
        jax.device_put(q, sh), jax.device_put(k, sh), jax.device_put(v, sh))


def full_attention_reference(q: jnp.ndarray, k: jnp.ndarray,
                             v: jnp.ndarray,
                             causal: bool = False) -> jnp.ndarray:
    """The plain O(T²)-memory oracle the ring is tested against."""
    scale = 1.0 / math.sqrt(q.shape[-1])
    scores = jnp.einsum("bqhd,bkhd->bhqk", q * scale, k,
                        preferred_element_type=jnp.float32)
    if causal:
        t = q.shape[1]
        mask = jnp.tril(jnp.ones((t, t), bool))
        scores = jnp.where(mask[None, None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return out.astype(q.dtype)
