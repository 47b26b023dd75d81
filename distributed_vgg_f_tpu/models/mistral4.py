"""Mistral-Small-4's decoder block (`model_type: mistral4`) as a language
model the trainer can train: latent attention (MLA) and, in every layer, a
mixture of routed SwiGLU experts beside one shared expert.

Equations (the DeepSeek-V2 form the config's keys name; eps 1e-6, no bias):

    h = x + MLA(RMSNorm(x));   y = h + MoE(RMSNorm(h))
    MLA:  c_q = RMSNorm(x W_dq);  q = c_q W_uq -> heads x [q_nope | q_rope]
          [c_kv | k_r] = x W_dkv;  c_kv = RMSNorm(c_kv)
          c_kv W_ukv -> heads x [k_nope | v];  k = [k_nope | rope(k_r)]
          (one k_r shared by all heads; rope on interleaved pairs, YaRN
          frequencies); causal softmax(q k^T scale) v; concat heads; W_o
    MoE:  p = softmax(u W_r) in float32 over ALL experts; top-k;
          w_k = p_k / sum p_k;  out = sum_k w_k E_k(u) + S(u)
          E(u) = (silu(u W_g) * u W_u) W_d, S the same

**The share.** A layer is told which experts it holds, `[first_expert,
first_expert + experts_held)` of `n_routed_experts`. It routes over all of
them at the published width and top-k, normalises the weights over all the
chosen experts, held or not, and adds only its own experts' terms: what one
chip of an expert-parallel group computes between the exchanges. What the
absent experts would add is left out and the partial result goes on; no
code stands in for the absent chips. With every expert held it is the whole
layer. Nothing is dropped, and the routed buffers are sized to the rows
that are live: the held assignments, sorted by expert, go through the
grouped products (`jax.lax.ragged_dot`, which the TPU compiler turns into
its grouped-matmul kernel) `routed_capacity` rows at a time, in as many
passes as the batch's load needs (`routed_experts`): one where the router
is anywhere near balanced, `tokens x top-k / capacity` where every
assignment falls on this share.

Training only: serving (a latent cache), checkpoint re-topology and ZeRO's
flat vector for this model are out of scope.

**Shared with `models/nemotron_h.py` and `models/ling3.py`**, which build
other stacks on the same pieces (the latter also on `LatentAttention`,
without a query compression and with a plain rotary table): `RMSNorm`, `_dense`, `Head` and `chunked_next_token_loss`; and
the whole expert share (`route`, `routed_capacity`, `routed_passes`,
`_window`, `routed_experts` with its hand-written backward pass,
`ExpertShare`), which takes the scoring (`softmax` | `sigmoid`, the latter
with a selection bias and a scale) and the expert's function (`swiglu`:
gate, up, down | `relu2`: up, down) as static arguments. The windows, the
sort, the grouped products and the counters are one code path for both; a
window's rows go back to their tokens through ops/routed_combine.py (a
Pallas kernel on the chip at whole tiles, XLA's scatter-add elsewhere).
"""

from __future__ import annotations

import functools
import math
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from distributed_vgg_f_tpu.ops import routed_combine


# ---- rotary position embedding ---------------------------------------------

def yarn_inv_freq(dim: int, base: float, factor: float, original_len: int,
                  beta_fast: float, beta_slow: float) -> np.ndarray:
    """YaRN's `dim // 2` inverse frequencies: the plain ones where a pair
    turns more than `beta_fast` times over the original length, the plain
    ones over `factor` where it turns fewer than `beta_slow` times, a
    linear ramp between."""
    def correction(turns):
        return dim * math.log(original_len / (turns * 2 * math.pi)) \
            / (2 * math.log(base))
    low = max(math.floor(correction(beta_fast)), 0)
    high = min(math.ceil(correction(beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low)
                   / (high - low), 0.0, 1.0)
    extrap = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    return (extrap * (1.0 - ramp) + extrap / factor * ramp).astype(np.float32)


def plain_inv_freq(dim: int, base: float) -> np.ndarray:
    """The `dim // 2` inverse frequencies of a rotary table with no
    scaling."""
    return (1.0 / base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
            ).astype(np.float32)


def rotate_interleaved(x, positions, inv_freq):
    """Rope on interleaved pairs: (x[2i], x[2i+1]) turned by
    positions * inv_freq[i]. `x` is (B, T, heads, d) or (..., T, d) with
    `positions` (T,); computed in float32, returned in x's dtype."""
    angles = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    if x.ndim == 4:
        cos, sin = cos[:, None, :], sin[:, None, :]
    pairs = x.astype(jnp.float32).reshape(*x.shape[:-1], -1, 2)
    a, b = pairs[..., 0], pairs[..., 1]
    out = jnp.stack([a * cos - b * sin, b * cos + a * sin], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


def yarn_attention_scale(qk_head_dim: int, factor: float,
                         mscale_all_dim: float) -> float:
    """qk_head_dim^-0.5 * m^2, m = 0.1 * mscale_all_dim * ln(factor) + 1
    (the DeepSeek-V3 reading of `mscale_all_dim`)."""
    m = 0.1 * mscale_all_dim * math.log(factor) + 1.0 if factor > 1 else 1.0
    return qk_head_dim ** -0.5 * m * m


def llama4_query_scale(positions, original_len: int, beta: float):
    """1 + beta * ln(1 + floor(pos / original_len)): 1 below the original
    length."""
    return 1.0 + beta * jnp.log1p(jnp.floor(
        positions.astype(jnp.float32) / original_len))


# ---- layers -----------------------------------------------------------------

class RMSNorm(nn.Module):
    eps: float = 1e-6

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],),
                           jnp.float32)
        x32 = x.astype(jnp.float32)
        x32 = x32 * jax.lax.rsqrt(
            jnp.mean(x32 * x32, axis=-1, keepdims=True) + self.eps)
        return (x32 * scale).astype(x.dtype)


def _dense(features: int, dtype, name: str) -> nn.Dense:
    return nn.Dense(features, use_bias=False, dtype=dtype,
                    param_dtype=jnp.float32, name=name)


class LatentAttention(nn.Module):
    """MLA, causal over the whole sequence. The core is the Pallas kernel
    of ops/flash_attention.py where that can run (a TPU, or the Pallas
    interpreter that tests switch on) and explicit scores elsewhere (a CPU
    run of the tiny preset). `q_lora_rank=None`: no query compression, one
    `q_proj` and no `q_a_norm` (DeepSeek-V2-Lite's form, models/ling3.py).
    `rope` without `rope_type: yarn` is a plain table by its `rope_theta`:
    no frequency ramp, no attention or query scale. Query and key heads
    (nope + rope) and value heads may differ in size."""
    num_heads: int
    q_lora_rank: int | None
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    rope: Any                     # the config's `rope_parameters`, a dict
    compute_dtype: Any
    eps: float = 1e-6

    @nn.compact
    def __call__(self, x):
        from distributed_vgg_f_tpu.ops import flash_attention
        b, t, d_model = x.shape
        h, dn, dr, dv = (self.num_heads, self.qk_nope_head_dim,
                         self.qk_rope_head_dim, self.v_head_dim)
        rope = dict(self.rope)
        yarn = rope.get("rope_type") == "yarn"
        inv_freq = jnp.asarray(yarn_inv_freq(
            dr, rope["rope_theta"], rope["factor"],
            rope["original_max_position_embeddings"], rope["beta_fast"],
            rope["beta_slow"]) if yarn
            else plain_inv_freq(dr, rope["rope_theta"]))
        positions = jnp.arange(t)
        with jax.named_scope("mla_q"):
            if self.q_lora_rank is None:
                q = _dense(h * (dn + dr), self.compute_dtype, "q_proj")(x)
            else:
                c_q = RMSNorm(self.eps, name="q_a_norm")(_dense(
                    self.q_lora_rank, self.compute_dtype, "q_a_proj")(x))
                q = _dense(h * (dn + dr), self.compute_dtype, "q_b_proj")(c_q)
            q = q.reshape(b, t, h, dn + dr)
            q = jnp.concatenate(
                [q[..., :dn],
                 rotate_interleaved(q[..., dn:], positions, inv_freq)], -1)
            if yarn:
                # the kernel applies d^-0.5 itself: what is left goes on q
                q_scale = yarn_attention_scale(dn + dr, rope["factor"],
                                               rope["mscale_all_dim"]) \
                    * math.sqrt(dn + dr)
                q = (q.astype(jnp.float32) * (q_scale * llama4_query_scale(
                    positions, rope["original_max_position_embeddings"],
                    rope["llama_4_scaling_beta"]))[None, :, None, None]
                     ).astype(self.compute_dtype)
        with jax.named_scope("mla_kv"):
            kv_a = _dense(self.kv_lora_rank + dr, self.compute_dtype,
                          "kv_a_proj")(x)
            c_kv = RMSNorm(self.eps, name="kv_a_norm")(
                kv_a[..., :self.kv_lora_rank])
            k_rope = rotate_interleaved(kv_a[..., self.kv_lora_rank:],
                                        positions, inv_freq)
            kv = _dense(h * (dn + dv), self.compute_dtype, "kv_b_proj")(c_kv)
            kv = kv.reshape(b, t, h, dn + dv)
            k = jnp.concatenate(
                [kv[..., :dn],
                 jnp.broadcast_to(k_rope[:, :, None, :], (b, t, h, dr))], -1)
            v = kv[..., dn:]
        with jax.named_scope("mla_core"):
            if jax.default_backend() == "tpu" or flash_attention.INTERPRET:
                # the kernel's own default is blocks of at most 128: at
                # 4096 tokens and 32 heads that is 17 k grid steps of one
                # small product each, 30 ms forward and backward on a v5e
                # against 5.8 ms with blocks of 1024 (PERF.md, PR 28)
                block = next((n for n in (1024, 512, 256) if t % n == 0),
                             None)
                ctx = flash_attention.flash_self_attention(
                    q, k, v, causal=True, block_q=block, block_k=block)
            else:
                scores = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                                    preferred_element_type=jnp.float32) \
                    * (dn + dr) ** -0.5
                scores = jnp.where(jnp.tril(jnp.ones((t, t), bool)), scores,
                                   -jnp.inf)
                probs = jax.nn.softmax(scores, axis=-1)
                ctx = jnp.einsum("bhqk,bkhd->bqhd",
                                 probs.astype(self.compute_dtype), v)
        with jax.named_scope("mla_out"):
            return _dense(d_model, self.compute_dtype, "o_proj")(
                ctx.reshape(b, t, h * dv))


def kept_groups(choice, n_group: int, topk_group: int):
    """DeepSeek-V3's group limit: the experts in `n_group` equal runs, a
    group's score the sum of its two largest `choice` (tokens, experts),
    the `topk_group` best groups kept (the lower index wins a tie).
    Returns (tokens, n_group) bool."""
    groups = choice.reshape(choice.shape[0], n_group, -1)
    best_two, _ = jax.lax.top_k(groups, 2)
    _, kept = jax.lax.top_k(jnp.sum(best_two, axis=-1), topk_group)
    return jnp.any(kept[..., None] == jnp.arange(n_group), axis=-2)


def route(scores, top_k: int, first_expert: int, experts_held: int, *,
          bias=None, scale: float = 1.0, n_group: int = 1,
          topk_group: int = 1):
    """Top-k of `scores` (tokens, experts) and this share's view of it:
    `(weights, local)` of shape (tokens, top_k), weights normalised over
    all chosen experts, `local` the held experts' index in
    [0, experts_held) and `experts_held` for one that lives elsewhere.
    With a selection `bias` (experts,) the choice is by `scores + bias`,
    the weights are the chosen scores themselves over their sum, times
    `scale`, and no gradient reaches the bias (the sigmoid scoring of
    DeepSeek-V3's router, which models/nemotron_h.py takes); with
    `n_group` > 1 the choice is among the experts of the `topk_group`
    groups that `kept_groups` keeps (models/ling3.py)."""
    if bias is None:
        top_p, top_e = jax.lax.top_k(scores, top_k)
        weights = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
    else:
        choice = scores + jax.lax.stop_gradient(bias)
        if n_group > 1:
            kept = kept_groups(choice, n_group, topk_group)
            choice = jnp.where(
                jnp.repeat(kept, choice.shape[-1] // n_group, axis=-1),
                choice, -jnp.inf)
        _, top_e = jax.lax.top_k(choice, top_k)
        top_p = jnp.take_along_axis(scores, top_e, axis=-1)
        weights = scale * top_p / (jnp.sum(top_p, axis=-1, keepdims=True)
                                   + 1e-20)
    local = top_e - first_expert
    held = (local >= 0) & (local < experts_held)
    return weights, jnp.where(held, local, experts_held)


#: rows the routed buffers hold over the rows a balanced router sends this
#: share. One expert layer of the benchmark's cell, forward and backward on
#: a v5e (PERF.md, PR 31): 13.55 ms with buffers of 1,536 rows, 13.66 with
#: 2,048, 13.78 with 3,072, 14.27 with 4,096, 19.55 with all 16,384; a
#: second pass costs 4.9 ms. So room for twice the balanced load costs
#: 0.11 ms a layer, a forty-fifth of the pass it saves.
ROUTED_HEADROOM = 2.0
#: the row tile of XLA:TPU's grouped-product kernel; a buffer is whole tiles
ROUTED_ROW_TILE = 512
#: and the tile of its other two widths: a product whose inner and outer
#: widths are whole tiles runs three times as fast as one whose are not
#: (12,288 live rows in 16 groups on a v5e, forward: 2688 x 1856 5.43 ms,
#: 2688 x 2048 1.95, 3072 x 2048 1.50; 1856 x 2688 4.24, 2048 x 3072 1.58;
#: the gradients alike: PERF.md, PR 32). So widths over one tile that are
#: not whole tiles are padded with zeros on their way into the routed path
#: (`ExpertShare`): rows of zeros in, columns of zeros out, the same sums.
ROUTED_WIDTH_TILE = 512


def _to_whole_tiles(width: int) -> int:
    """Zeros to append to a width of the grouped products."""
    return -width % ROUTED_WIDTH_TILE if width > ROUTED_WIDTH_TILE else 0


def routed_capacity(assignments: int, experts_held: int,
                    n_routed_experts: int) -> int:
    """Rows of the routed buffers, from shapes alone: the share's part of
    the batch's `assignments` (tokens x top-k) with headroom, in whole
    tiles, and never more than there are assignments. A share that holds
    every expert gets them all and makes one pass."""
    balanced = assignments * experts_held / n_routed_experts
    tiles = math.ceil(ROUTED_HEADROOM * balanced / ROUTED_ROW_TILE)
    return min(assignments, tiles * ROUTED_ROW_TILE)


def routed_passes(load, capacity: int):
    """Passes of `capacity` rows that take every held assignment of `load`
    (experts_held,); the first is made whatever the load."""
    held = jnp.sum(load, dtype=jnp.int32)
    return jax.lax.max(jax.lax.div(held + (capacity - 1), jnp.int32(capacity)),
                       jnp.int32(1))


def _window(x, weights, order, load, start, capacity):
    """The held assignments `order[start:start + capacity]`: (their
    indices, each one's token, the experts' group sizes clipped to this
    window, which rows are a held assignment at all, the tokens' rows of
    `x` with zeros behind the last held one, each row's weight)."""
    with jax.named_scope("moe_dispatch"):
        picked = jax.lax.dynamic_slice(order, (start,), (capacity,))
        token = jax.lax.div(picked, jnp.int32(weights.shape[1]))
        ends = jnp.cumsum(load)
        sizes = jnp.clip(ends, start, start + capacity) \
            - jnp.clip(ends - load, start, start + capacity)
        live = (start + jnp.arange(capacity) < ends[-1])[:, None]
        rows = jnp.where(live, x.at[token].get(mode="promise_in_bounds"), 0)
    with jax.named_scope("moe_combine"):
        weight = weights.reshape(-1).at[picked].get(
            mode="promise_in_bounds")[:, None]
    return picked, token, sizes, live, rows, weight


#: the experts' functions: what stands before the last matrix
EXPERTS = ("swiglu", "relu2")


def _hidden(rows, *ins, sizes, expert: str = "swiglu"):
    """A row's hidden vector, each row through its own expert: `swiglu`
    silu(rows W_g) * rows W_u of `ins` = (gate, up); `relu2`
    relu(rows W_u)^2 of `ins` = (up,)."""
    if expert == "swiglu":
        gate, up = ins
        return nn.silu(jax.lax.ragged_dot(rows, gate, sizes)) \
            * jax.lax.ragged_dot(rows, up, sizes)
    up, = ins
    return jnp.square(nn.relu(jax.lax.ragged_dot(rows, up, sizes)))


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def routed_experts(x, mats, weights, order, load, capacity: int,
                   expert: str = "swiglu"):
    """sum_k w_k E_k(x) over the held experts, (tokens, hidden) float32.

    `x` (tokens, hidden) and the held experts' matrices `mats` in the
    compute dtype (`swiglu`: gate, up, down; `relu2`: up, down),
    `weights` (tokens, top-k) float32, `order` the batch's
    assignments (token x top-k + choice) sorted by held expert, those of
    absent experts last, padded to whole windows, `load` (experts_held,).
    `routed_passes` windows of `capacity` rows each, a loop whose length
    the device reads from `load`. JAX never differentiates that choice:
    the backward pass below is a second such loop, and nothing of the
    forward pass is kept but its inputs. Rows behind the last held
    assignment are zeros going in and coming out: what the grouped
    products leave unwritten there reaches nothing."""
    *ins, down = mats

    def one(c, routed):
        _, token, sizes, live, rows, weight = _window(
            x, weights, order, load, c * capacity, capacity)
        with jax.named_scope("moe_experts"):
            outs = jax.lax.ragged_dot(
                _hidden(rows, *ins, sizes=sizes, expert=expert), down, sizes)
        return routed_combine.add_weighted(routed, outs, weight, token, live,
                                           first=c == 0)
    return jax.lax.fori_loop(0, routed_passes(load, capacity), one,
                             routed_combine.empty_sum(x, capacity))


def _routed_forward(x, mats, weights, order, load, capacity, expert):
    return (routed_experts(x, mats, weights, order, load, capacity, expert),
            (x, mats, weights, order, load))


def _routed_backward(capacity, expert, inputs, d_routed):
    x, (*ins, down), weights, order, load = inputs
    dtype = x.dtype

    def window_gradients(c):
        """Window `c`: its hidden rows again, then the gradients of x,
        the matrices and weights, each in its own dtype. With g a row's
        cotangent, w its weight and h its hidden row, the row's output
        h W_d is not needed again: q = g W_d^T gives dh = w q and
        dw = q . h, and dW_d takes (w h)^T g."""
        picked, token, sizes, live, rows, weight = _window(
            x, weights, order, load, c * capacity, capacity)
        with jax.named_scope("moe_combine"):
            g = jnp.where(live, d_routed.at[token].get(
                mode="promise_in_bounds"), 0).astype(dtype)
        with jax.named_scope("moe_experts"):
            h, pull = jax.vjp(functools.partial(
                _hidden, sizes=sizes, expert=expert), rows, *ins)
            by_down = lambda lhs, rhs: jax.lax.ragged_dot(lhs, rhs, sizes)
            q, = jax.linear_transpose(lambda h: by_down(h, down), h)(g)
            q = jnp.where(live, q, 0).astype(jnp.float32)
            weighted = (h.astype(jnp.float32) * weight).astype(dtype)
            d_down, = jax.linear_transpose(
                lambda down: by_down(weighted, down), down)(g)
            d_rows, *d_ins = pull((q * weight).astype(dtype))
        with jax.named_scope("moe_combine"):
            d_weight = jnp.sum(
                jnp.where(live, q * h.astype(jnp.float32), 0), axis=-1)
            d_weights = jnp.zeros(weights.size, weights.dtype).at[picked].add(
                d_weight, mode="promise_in_bounds").reshape(weights.shape)
        d_x = routed_combine.to_tokens(d_rows, token, live, x)
        return (d_x, *d_ins, d_down, d_weights)

    def one_more(c, so_far):
        # the cell never comes here; where a skewed batch does, the sums
        # are float32 and the running totals keep the gradients' dtypes
        scopes = ("moe_dispatch", *["moe_experts"] * (len(ins) + 1),
                  "moe_combine")
        out = []
        for scope, a, b in zip(scopes, so_far, window_gradients(c)):
            with jax.named_scope(scope):
                out.append((a.astype(jnp.float32)
                            + b.astype(jnp.float32)).astype(a.dtype))
        return tuple(out)

    # the first window outside the loop: its gradients are the totals, with
    # nothing to zero and nothing to add (0.4 GB of expert gradients)
    d_x, *d_mats, d_weights = jax.lax.fori_loop(
        1, routed_passes(load, capacity), one_more, window_gradients(0))
    return d_x, tuple(d_mats), d_weights, None, None


routed_experts.defvjp(_routed_forward, _routed_backward)


class ExpertShare(nn.Module):
    """The routed experts this chip holds, and the shared expert.
    `scoring` "softmax" (over all experts) or "sigmoid" (each expert's own,
    chosen with the selection bias `router_bias`, the weights times
    `routed_scaling_factor`, the choice under `route`'s group limit where
    `n_group` > 1); `expert` one of `EXPERTS`, for the routed experts and
    the shared one alike; the shared expert is `shared_intermediate_size`
    wide (default: `moe_intermediate_size` x `n_shared_experts`)."""
    n_routed_experts: int
    num_experts_per_tok: int
    moe_intermediate_size: int
    n_shared_experts: int
    first_expert: int
    experts_held: int
    compute_dtype: Any
    scoring: str = "softmax"
    expert: str = "swiglu"
    routed_scaling_factor: float = 1.0
    shared_intermediate_size: int | None = None
    n_group: int = 1
    topk_group: int = 1

    @nn.compact
    def __call__(self, u):
        b, t, d = u.shape
        tokens, k, held = b * t, self.num_experts_per_tok, self.experts_held
        width, dtype = self.moe_intermediate_size, self.compute_dtype
        capacity = routed_capacity(tokens * k, held, self.n_routed_experts)
        x = u.reshape(tokens, d)
        with jax.named_scope("moe_router"):
            router = self.param(
                "router", nn.initializers.lecun_normal(),
                (d, self.n_routed_experts), jnp.float32)
            # float32 all the way: top-k is discontinuous, and a bf16 pass
            # over these logits flips near-ties
            logits = jnp.dot(x.astype(jnp.float32), router,
                             precision=jax.lax.Precision.HIGHEST)
            if self.scoring == "softmax":
                weights, local = route(jax.nn.softmax(logits, axis=-1), k,
                                       self.first_expert, held)
            else:
                bias = self.param("router_bias", nn.initializers.normal(0.01),
                                  (self.n_routed_experts,), jnp.float32)
                scores = jax.nn.sigmoid(logits)
                weights, local = route(
                    scores, k, self.first_expert, held, bias=bias,
                    scale=self.routed_scaling_factor, n_group=self.n_group,
                    topk_group=self.topk_group)
                if self.n_group > 1:
                    # the share of the batch's tokens whose kept groups
                    # include the one this share's first expert is in: what
                    # the group limit lets reach this chip at all
                    mine = self.first_expert * self.n_group \
                        // self.n_routed_experts
                    self.sow("counters", "group_share", jnp.mean(kept_groups(
                        scores + bias, self.n_group, self.topk_group
                    )[:, mine].astype(jnp.float32)))
        with jax.named_scope("moe_dispatch"):
            # every assignment of the batch, sorted by held expert; those
            # of experts that live elsewhere sort behind the last group,
            # where no window goes
            flat = local.reshape(tokens * k)
            order = jnp.argsort(flat, stable=True)
            load = jnp.bincount(flat, length=held + 1)[:held].astype(
                jnp.int32)
            # assignments to a held expert that no window took: 0 by
            # construction (the passes go on until the last of them)
            dropped = jnp.sum(flat < held).astype(jnp.int32) - jnp.sum(load)
            # whole windows, so that the last one's slice is never shifted
            order = jnp.pad(order, (0, -(tokens * k) % capacity))
        init = nn.initializers.variance_scaling(
            1.0, "fan_in", "normal", in_axis=-2, out_axis=-1, batch_axis=0)
        if self.expert not in EXPERTS:
            raise ValueError(f"expert {self.expert!r} is none of {EXPERTS}")
        gated = self.expert == "swiglu"
        mats = [self.param(f"experts_{name}_proj", init, (held, d, width),
                           jnp.float32)
                for name in (("gate", "up") if gated else ("up",))]
        mats.append(self.param("experts_down_proj", init, (held, width, d),
                               jnp.float32))
        with jax.named_scope("moe_experts"):
            # cast before the routed path: the experts' gradients leave it
            # in the compute dtype
            mats = tuple(w.astype(dtype) for w in mats)
        rows_in, more_d, more_w = x, _to_whole_tiles(d), _to_whole_tiles(width)
        if more_d or more_w:
            with jax.named_scope("moe_experts"):
                *ins, down = mats
                mats = (*(jnp.pad(w, ((0, 0), (0, more_d), (0, more_w)))
                          for w in ins),
                        jnp.pad(down, ((0, 0), (0, more_w), (0, more_d))))
            with jax.named_scope("moe_dispatch"):
                rows_in = jnp.pad(x, ((0, 0), (0, more_d)))
        routed = routed_experts(rows_in, mats, weights, order, load, capacity,
                                self.expert)
        if more_d:
            routed = routed[:, :d]
        # the routed path's own receipts, for a caller that asks for them
        # (`mutable=["counters"]`): 1 pass = the compact buffers held all
        self.sow("counters", "passes", routed_passes(load, capacity))
        self.sow("counters", "capacity", capacity)
        # 1 = both directions handed the rows back through the kernel of
        # ops/routed_combine_pallas.py, 0 = through XLA's scatter-add
        self.sow("counters", "combine_kernel", int(routed_combine.takes_kernel(
            rows_in.shape, rows_in.dtype, capacity)))
        with jax.named_scope("moe_shared"):
            shared_width = self.shared_intermediate_size \
                or width * self.n_shared_experts
            if gated:
                mid = nn.silu(
                    _dense(shared_width, dtype, "shared_gate_proj")(x)) \
                    * _dense(shared_width, dtype, "shared_up_proj")(x)
            else:
                mid = jnp.square(nn.relu(
                    _dense(shared_width, dtype, "shared_up_proj")(x)))
            shared = _dense(d, dtype, "shared_down_proj")(mid)
        out = (routed + shared.astype(jnp.float32)).astype(u.dtype)
        return out.reshape(b, t, d), jnp.append(load, dropped)


class Block(nn.Module):
    attention: dict
    experts: dict
    compute_dtype: Any
    eps: float = 1e-6

    @nn.compact
    def __call__(self, x):
        h = x + LatentAttention(
            **self.attention, compute_dtype=self.compute_dtype, eps=self.eps,
            name="attn")(RMSNorm(self.eps, name="input_norm")(x))
        y, counts = ExpertShare(
            **self.experts, compute_dtype=self.compute_dtype,
            name="moe")(RMSNorm(self.eps, name="post_attention_norm")(h))
        return h + y, counts


def _logits(h, kernel, dtype):
    return jnp.dot(h.astype(dtype), kernel.astype(dtype),
                   preferred_element_type=jnp.float32)


@jax.checkpoint
def _chunk_loss(kernel, h_rows, ids):
    """Summed cross-entropy of one chunk of rows; its float32 logits are
    made again in the backward pass and never outlive the chunk."""
    logits = _logits(h_rows, kernel, h_rows.dtype)
    picked = jnp.take_along_axis(logits, ids[:, None], axis=-1)[:, 0]
    return jnp.sum(jax.nn.logsumexp(logits, axis=-1) - picked)


def chunked_next_token_loss(kernel, h, targets, chunk_rows: int, dtype):
    """Mean cross-entropy of `targets` (B, T) under the head `kernel` at the
    final hidden states `h` (B, T, hidden): float32 logits, `chunk_rows`
    rows at a time, each chunk's logits made again in the backward pass."""
    rows = h.reshape(-1, h.shape[-1])
    wanted = targets.reshape(-1)
    chunk = math.gcd(rows.shape[0], chunk_rows)
    with jax.named_scope("lm_head"):
        total = 0.0
        for start in range(0, rows.shape[0], chunk):
            total = total + _chunk_loss(
                kernel, rows[start:start + chunk].astype(dtype),
                wanted[start:start + chunk])
    return total / rows.shape[0]


class Head(nn.Module):
    """The untied output head, `hidden x vocabulary`."""
    hidden_size: int
    vocab_size: int
    compute_dtype: Any

    def setup(self):
        self.kernel = self.param(
            "kernel", nn.initializers.lecun_normal(),
            (self.hidden_size, self.vocab_size), jnp.float32)

    def __call__(self, h):
        return _logits(h, self.kernel, self.compute_dtype)


class Mistral4LM(nn.Module):
    """Token ids (B, T) -> float32 logits (B, T, vocabulary held).
    `next_token_loss` is what the train step calls: it never holds the
    logits of a whole sequence."""
    vocab_size: int
    hidden_size: int
    num_hidden_layers: int
    attention: dict
    experts: dict
    compute_dtype: Any = jnp.bfloat16
    rms_norm_eps: float = 1e-6
    loss_chunk_rows: int = 1024

    @property
    def expert_layers(self) -> tuple:
        """The layers the rows of `hidden`'s counts stand for: all."""
        return tuple(range(self.num_hidden_layers))

    def setup(self):
        self.embed = nn.Embed(self.vocab_size, self.hidden_size,
                              dtype=self.compute_dtype,
                              param_dtype=jnp.float32, name="embed")
        # recomputation per block: only a block's input outlives its forward
        # pass, and the bf16 casts of its weights are made again inside it
        self.blocks = [nn.remat(Block)(self.attention, self.experts,
                                       self.compute_dtype, self.rms_norm_eps,
                                       name=f"layer_{i}")
                       for i in range(self.num_hidden_layers)]
        self.norm = RMSNorm(self.rms_norm_eps, name="norm")
        self.lm_head = Head(self.hidden_size, self.vocab_size,
                            self.compute_dtype, name="lm_head")

    def hidden(self, tokens):
        """Final-norm hidden states (B, T, hidden) and every layer's
        counts (layers, experts_held + 1): the assignments each held
        expert took, then the dropped ones (0 by construction)."""
        with jax.named_scope("embed_tokens"):
            x = self.embed(tokens)
        counts = []
        for block in self.blocks:
            x, count = block(x)
            counts.append(count)
        return self.norm(x), jnp.stack(counts)

    def __call__(self, tokens, *, train: bool = False):
        h, _ = self.hidden(tokens)
        with jax.named_scope("lm_head"):
            return self.lm_head(h)

    def next_token_loss(self, tokens, targets):
        """(mean cross-entropy of `targets` under the logits at `tokens`,
        the layers' counts as `hidden` gives them): float32 logits,
        `loss_chunk_rows` rows at a time, each chunk's logits made again in
        the backward pass."""
        h, counts = self.hidden(tokens)
        return chunked_next_token_loss(
            self.lm_head.kernel, h, targets, self.loss_chunk_rows,
            self.compute_dtype), counts


#: keys of `ModelConfig.extra` (the preset's own) and of the published
#: config alike; `build` splits them between the layers
_ATTENTION = ("num_attention_heads", "q_lora_rank", "kv_lora_rank",
              "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim")
_EXPERTS = ("n_routed_experts", "num_experts_per_tok",
            "moe_intermediate_size", "n_shared_experts")


def build(vocab_size: int, compute_dtype, extra: dict) -> Mistral4LM:
    """`extra`: the published keys (widths, heads, experts, rope) plus the
    share: `first_expert`, `experts_held` (default: all), and
    `num_hidden_layers`; `seq_len` is the data source's and is not read
    here."""
    extra = dict(extra)
    attention = {k: extra[k] for k in _ATTENTION}
    attention["num_heads"] = attention.pop("num_attention_heads")
    attention["rope"] = dict(extra["rope_parameters"])
    experts = {k: extra[k] for k in _EXPERTS}
    experts["first_expert"] = extra.get("first_expert", 0)
    experts["experts_held"] = extra.get("experts_held",
                                        extra["n_routed_experts"])
    return Mistral4LM(
        vocab_size=vocab_size, hidden_size=extra["hidden_size"],
        num_hidden_layers=extra["num_hidden_layers"], attention=attention,
        experts=experts, compute_dtype=compute_dtype,
        rms_norm_eps=extra.get("rms_norm_eps", 1e-6))
