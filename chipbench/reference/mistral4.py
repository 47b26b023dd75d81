"""The plain reference of Mistral-Small-4's block (`model_type: mistral4`),
as a language model and as one chip's share of its experts.

Float32 `jax.numpy` at `highest` matmul precision (`ops.Ops`), nothing of
the program: no kernel, no sort, no grouped product. Attention is an
explicit causal softmax over `[rows, S]` scores per head, `block_rows`
query rows at a time (each block made again in the backward pass, so that
only one is alive); the routed experts are a plain loop over the held
experts, each computed on every token and weighted by a mask.

`arch` is the configuration file's published keys; `share` =
(first_expert, experts_held). The parameter tree is named as the program
names its own (`layer_0/attn/q_a_proj/kernel`, `layer_0/moe/router`, ...).

    block   h = x + MLA(rms(x));  y = h + MoE(rms(h));  eps from `arch`
    MLA     c_q = rms(x W_dq); q = c_q W_uq -> heads x [nope | rope]
            [c_kv | k_r] = x W_dkv; c_kv = rms(c_kv);
            c_kv W_ukv -> heads x [k_nope | v]; k = [k_nope | rope(k_r)]
            rope on interleaved pairs, YaRN frequencies, cos/sin factor
            mscale / mscale_all_dim = 1; softmax(q k^T scale) v; W_o
    MoE     p = softmax(u W_r) over all experts; top-k by repeated argmax;
            w_k = p_k / sum_k p_k; out = sum_{k held} w_k E_k(u) + S(u)

Departures from the published description, all listed under `assumed` in
the configuration file: softmax scoring (the config has no
`scoring_func`); attention scale `qk_head_dim^-0.5 * m^2` with
`m = 0.1 * mscale_all_dim * ln(factor) + 1`; no document mask; what the
experts held elsewhere would add is left out (the share), so the block's
output is a partial sum unless every expert is held.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from .ops import Ops


def inv_freq(rope: dict, dim: int) -> np.ndarray:
    base, factor = rope["rope_theta"], rope["factor"]
    length = rope["original_max_position_embeddings"]
    bound = lambda turns: dim * math.log(length / (turns * 2 * math.pi)) \
        / (2 * math.log(base))
    low = max(math.floor(bound(rope["beta_fast"])), 0)
    high = min(math.ceil(bound(rope["beta_slow"])), dim - 1)
    high = high + 0.001 if high == low else high
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0.0, 1.0)
    plain = base ** (-np.arange(0, dim, 2) / dim)
    return (plain * (1 - ramp) + plain / factor * ramp).astype(np.float32)


def rope_pairs(x, freqs):
    """x (S, ..., d): pair (x[2i], x[2i+1]) turned by position * freqs[i]."""
    angle = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * freqs
    angle = angle.reshape(x.shape[0], *([1] * (x.ndim - 2)), -1)
    even, odd = x[..., 0::2], x[..., 1::2]
    turned = jnp.stack([even * jnp.cos(angle) - odd * jnp.sin(angle),
                        odd * jnp.cos(angle) + even * jnp.sin(angle)], -1)
    return turned.reshape(x.shape)


def rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def attention(p, x, arch: dict, ops: Ops, block_rows: int):
    """MLA on one sequence x (S, hidden)."""
    heads, dn, dr, dv = (arch["num_attention_heads"],
                         arch["qk_nope_head_dim"], arch["qk_rope_head_dim"],
                         arch["v_head_dim"])
    rope, eps, seq = arch["rope_parameters"], arch["rms_norm_eps"], x.shape[0]
    freqs = inv_freq(rope, dr)
    c_q = rms(ops.dense(x, p["q_a_proj"]["kernel"]), p["q_a_norm"]["scale"],
              eps)
    q = ops.dense(c_q, p["q_b_proj"]["kernel"]).reshape(seq, heads, dn + dr)
    q = jnp.concatenate([q[..., :dn], rope_pairs(q[..., dn:], freqs)], -1)
    q = q * (1.0 + rope["llama_4_scaling_beta"] * jnp.log1p(jnp.floor(
        jnp.arange(seq) / rope["original_max_position_embeddings"]))
             )[:, None, None]
    kv_a = ops.dense(x, p["kv_a_proj"]["kernel"])
    rank = arch["kv_lora_rank"]
    c_kv = rms(kv_a[:, :rank], p["kv_a_norm"]["scale"], eps)
    k_rope = rope_pairs(kv_a[:, rank:], freqs)
    kv = ops.dense(c_kv, p["kv_b_proj"]["kernel"]).reshape(seq, heads,
                                                           dn + dv)
    k = jnp.concatenate([kv[..., :dn], jnp.broadcast_to(
        k_rope[:, None, :], (seq, heads, dr))], -1)
    v = kv[..., dn:]
    m = 0.1 * rope["mscale_all_dim"] * math.log(rope["factor"]) + 1.0 \
        if rope["factor"] > 1 else 1.0
    scale = (dn + dr) ** -0.5 * m * m

    @jax.checkpoint
    def rows(q_rows, first):
        scores = ops.dense(q_rows.transpose(1, 0, 2),
                           k.transpose(1, 2, 0)) * scale     # (h, rows, S)
        seen = (first + jnp.arange(q_rows.shape[0]))[:, None] \
            >= jnp.arange(seq)[None, :]
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), -1)
        return ops.dense(probs, v.transpose(1, 0, 2)).transpose(1, 0, 2)

    block = math.gcd(seq, block_rows)
    ctx = jnp.concatenate([rows(q[i:i + block], i)
                           for i in range(0, seq, block)])
    return ops.dense(ctx.reshape(seq, heads * dv), p["o_proj"]["kernel"])


def top_k(probs, k: int):
    """(values, indices) of the k largest of each row, by repeated argmax
    (the lowest index wins a tie)."""
    values, indices, left = [], [], probs
    for _ in range(k):
        i = jnp.argmax(left, -1)
        values.append(jnp.take_along_axis(left, i[:, None], -1)[:, 0])
        indices.append(i)
        left = jnp.where(jax.nn.one_hot(i, probs.shape[-1], dtype=bool),
                         -jnp.inf, left)
    return jnp.stack(values, -1), jnp.stack(indices, -1)


def routing(p, u, arch: dict):
    """(weights (S, k) normalised over the k chosen, experts (S, k))."""
    logits = jnp.matmul(u, p["router"], precision=jax.lax.Precision.HIGHEST)
    top_p, top_e = top_k(jax.nn.softmax(logits, -1),
                         arch["num_experts_per_tok"])
    return top_p / jnp.sum(top_p, -1, keepdims=True), top_e


def swiglu(x, gate, up, down, ops: Ops):
    return ops.dense(jax.nn.silu(ops.dense(x, gate)) * ops.dense(x, up), down)


def experts(p, u, arch: dict, share, ops: Ops, *, shared: bool = True):
    """(the share's part of the expert layer on u (S, hidden), the held
    experts' loads). `shared=False` leaves the shared expert out."""
    first, held = share
    weights, chosen = routing(p, u, arch)
    out = jnp.zeros_like(u)
    loads = []
    for local in range(held):
        mine = chosen == first + local                       # (S, k)
        out = out + jnp.sum(jnp.where(mine, weights, 0.0), -1)[:, None] \
            * swiglu(u, p["experts_gate_proj"][local],
                     p["experts_up_proj"][local],
                     p["experts_down_proj"][local], ops)
        loads.append(jnp.sum(mine))
    if shared:
        out = out + swiglu(u, p["shared_gate_proj"]["kernel"],
                           p["shared_up_proj"]["kernel"],
                           p["shared_down_proj"]["kernel"], ops)
    return out, jnp.asarray(loads, jnp.int32).reshape(held)


def block(p, x, arch: dict, share, ops: Ops, block_rows: int = 512):
    """One decoder block on one sequence: (y, held experts' loads)."""
    eps = arch["rms_norm_eps"]
    h = x + attention(p["attn"], rms(x, p["input_norm"]["scale"], eps), arch,
                      ops, block_rows)
    y, loads = experts(p["moe"], rms(h, p["post_attention_norm"]["scale"],
                                     eps), arch, share, ops)
    return h + y, loads


def head_loss(p_norm, p_head, x, targets, arch: dict, ops: Ops):
    """(summed next-token cross-entropy of one sequence, its logits)."""
    logits = ops.dense(rms(x, p_norm["scale"], arch["rms_norm_eps"]),
                       p_head["kernel"])
    picked = jnp.take_along_axis(logits, targets[:, None], -1)[:, 0]
    return jnp.sum(jax.nn.logsumexp(logits, -1) - picked), logits


def forward(params, tokens, arch: dict, share, ops: Ops | None = None,
            block_rows: int = 512):
    """tokens (B, S) -> (logits (B, S, V), loads (B, layers, held))."""
    ops = ops or Ops("float32")
    layers = sorted((k for k in params if k.startswith("layer_")),
                    key=lambda k: int(k[6:]))

    def one(row):
        x, loads = params["embed"]["embedding"][row], []
        for name in layers:
            x, load = block(params[name], x, arch, share, ops, block_rows)
            loads.append(load)
        zeros = jnp.zeros(row.shape, jnp.int32)
        return head_loss(params["norm"], params["lm_head"], x, zeros, arch,
                         ops)[1], jnp.stack(loads)

    outs = [one(row) for row in tokens]
    return (jnp.stack([o[0] for o in outs]),
            jnp.stack([o[1] for o in outs]))


def loss(params, tokens, arch: dict, share, ops: Ops | None = None,
         block_rows: int = 512):
    """Mean next-token cross-entropy of tokens (B, S + 1): inputs
    [:, :-1], targets [:, 1:]."""
    logits, _ = forward(params, tokens[:, :-1], arch, share, ops, block_rows)
    logp = jax.nn.log_softmax(logits, -1)
    return -jnp.mean(jnp.take_along_axis(
        logp, tokens[:, 1:, None], -1))
