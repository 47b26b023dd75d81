"""The plain reference of Ling-3.0-flash's language stack (`model_type:
bailing_hybrid`), as a language model and as one chip's share of its
experts.

Float32 `jax.numpy` at `highest` matmul precision (`ops.Ops`), nothing of
the program: no kernel, no sort, no grouped product, no chunked form. The
linear layers are **the literal recurrence** of Kimi Delta Attention, one
position at a time (`jax.lax.scan`), checkpointed in stretches of
`SCAN_STRETCH` positions so that the backward pass keeps one stretch of
states and not all of them (a head's state is 128 x 128 floats: 2 MB over
32 heads, 17 GB at 8,192 positions). Latent attention is an explicit causal
softmax over `[rows, S]` scores per head, `block_rows` query rows at a time
(one block after the other, each made again in the backward pass); the
router's group limit and its top-k are repeated argmax; the routed experts
are a plain loop over the held experts (`jax.lax.scan`), each computed on
every token and weighted by a mask, each made again in the backward pass.

`arch` is the configuration file's published keys with the pattern as it
is run, one letter a layer: `D` KDA + dense feed-forward, `K` KDA +
experts, `L` latent attention + experts, `A` latent + dense. `share` =
(first_expert, experts_held). The parameter tree is named as the program
names its own (`layer_0/attn/f_proj/kernel`, `layer_1/moe/router`, ...).

    layer i:   h = x + Attn(rms(x));   y = h + FFN(rms(h))        eps 1e-6
    KDA     q, k, v = silu(conv4(u W_q)), silu(conv4(u W_k)), silu(conv4(
            u W_v)): causal, depthwise, zeros before the start;
            q, k <- q / sqrt(|q|^2 + 1e-6), k / sqrt(|k|^2 + 1e-6) a head;
            q <- q d_k^-0.5;
            g = lower_bound * sigmoid(exp(A_log_h) (u W_f + dt_bias));
            beta = sigmoid(u W_b);
            S_t = (I - beta_t k_t k_t^T) Diag(exp(g_t)) S_{t-1}
                  + beta_t k_t v_t^T;   o_t = S_t^T q_t;   S_0 = 0
            y = [rms_head(o) * w * sigmoid(u W_g)_h] W_o
    latent  q = u W_q -> heads x [nope | rope]; [c | k_r] = u W_kva;
            c = rms(c); c W_kvb -> heads x [k_nope | v];
            k = [k_nope | rope(k_r)]; rope on interleaved pairs, plain
            frequencies theta^(-2i/d); softmax(q k^T / sqrt(nope + rope)) v;
            W_o
    experts s = sigmoid(u W_r); c = s + b; a group's score the sum of its
            two largest c; the topk_group best groups stay; top-k of c in
            them by repeated argmax; w_k = scale * s_k / (sum_k s_k +
            1e-20); out = sum_{k held} w_k E_k(u) + S(u);
            E(u) = (silu(u W_g) * u W_u) W_d, S the same
    dense   (silu(u W_g) * u W_u) W_d

`fault="chunk_reset"` plants this model's own fault: the state set to zero
at every multiple of `CHUNK` positions, which is what a chunked form gives
that forgets to hand its state on.

Departures from the published description are listed under `assumed` in
the configuration file: the safe gate's formula, a fixed selection bias,
no document mask, no swiglu clamp in the layers held, no vision tower, no
multi-token prediction, and what the experts held elsewhere would add is
left out (the share).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from .ops import Ops

SCAN_STRETCH = 128
#: positions of one chunk of the program's chunked form (`chunk_reset`)
CHUNK = 64
KINDS = {"D": ("kda", "dense"), "K": ("kda", "experts"),
         "L": ("latent", "experts"), "A": ("latent", "dense")}


def rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def rope_pairs(x, freqs):
    """x (S, ..., d): pair (x[2i], x[2i+1]) turned by position * freqs[i]."""
    angle = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * freqs
    angle = angle.reshape(x.shape[0], *([1] * (x.ndim - 2)), -1)
    even, odd = x[..., 0::2], x[..., 1::2]
    turned = jnp.stack([even * jnp.cos(angle) - odd * jnp.sin(angle),
                        odd * jnp.cos(angle) + even * jnp.sin(angle)], -1)
    return turned.reshape(x.shape)


def delta_rule(q, k, v, g, beta, *, reset_every: int | None = None):
    """o_t = S_t^T q_t with S_t = (I - beta_t k_t k_t^T) Diag(exp(g_t))
    S_{t-1} + beta_t k_t v_t^T, S_0 = 0, position by position. `q`, `k`,
    `g` (S, heads, dk), `v` (S, heads, dv), `beta` (S, heads). With
    `reset_every` the state is zeroed before every position that is a
    multiple of it."""
    seq, heads, dk = k.shape
    stretch = math.gcd(seq, SCAN_STRETCH)

    def position(S, inputs):
        q_t, k_t, v_t, g_t, beta_t, keep = inputs
        S = S * (keep * jnp.exp(g_t))[:, :, None]            # (heads, dk, dv)
        seen = jnp.sum(S * k_t[:, :, None], 1)               # S^T k
        S = S + k_t[:, :, None] * (beta_t[:, None] * (v_t - seen))[:, None, :]
        return S, jnp.sum(S * q_t[:, :, None], 1)

    @jax.checkpoint
    def run(S, inputs):
        return jax.lax.scan(position, S, inputs)

    at = jnp.arange(seq)
    keep = jnp.ones(seq) if reset_every is None \
        else (at % reset_every != 0).astype(jnp.float32)
    split = lambda x: x.reshape(seq // stretch, stretch, *x.shape[1:])
    _, o = jax.lax.scan(run, jnp.zeros((heads, dk, v.shape[-1]), jnp.float32),
                        tuple(split(x) for x in (q, k, v, g, beta, keep)))
    return o.reshape(seq, heads, v.shape[-1])


def kda(p, u, arch: dict, ops: Ops, *, fault: str | None = None):
    """Kimi Delta Attention on one sequence u (S, hidden)."""
    heads, dk, width = (arch["num_attention_heads"], arch["head_dim"],
                        arch["short_conv_kernel_size"])
    seq = u.shape[0]

    def conv_silu(x, kernel):
        # kernel[width - 1] multiplies the position itself
        padded = jnp.concatenate([jnp.zeros((width - 1, x.shape[1])), x])
        return jax.nn.silu(sum(padded[j:j + seq] * kernel[j]
                               for j in range(width)))

    q, k, v = (conv_silu(ops.dense(u, p[f"{name}_proj"]["kernel"]),
                         p[f"{name}_conv"]["kernel"]).reshape(seq, heads, dk)
               for name in "qkv")
    unit = lambda x: x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True)
                                       + 1e-6)
    q, k = unit(q) * dk ** -0.5, unit(k)
    f = ops.dense(u, p["f_proj"]["kernel"]) + p["dt_bias"]
    g = arch["kda_lower_bound"] * jax.nn.sigmoid(
        jnp.exp(p["A_log"])[:, None] * f.reshape(seq, heads, dk))
    beta = jax.nn.sigmoid(ops.dense(u, p["b_proj"]["kernel"]))
    gate = jax.nn.sigmoid(ops.dense(u, p["g_proj"]["kernel"]))
    o = delta_rule(q, k, v, g, beta,
                   reset_every=CHUNK if fault == "chunk_reset" else None)
    o = rms(o, p["o_norm"]["scale"], arch["rms_norm_eps"]) * gate[:, :, None]
    return ops.dense(o.reshape(seq, heads * dk), p["o_proj"]["kernel"])


def latent(p, u, arch: dict, ops: Ops, block_rows: int):
    """Latent attention with no query compression on one sequence u
    (S, hidden)."""
    heads, dn, dr, dv = (arch["num_attention_heads"],
                         arch["qk_nope_head_dim"], arch["qk_rope_head_dim"],
                         arch["v_head_dim"])
    rank, eps, seq = arch["kv_lora_rank"], arch["rms_norm_eps"], u.shape[0]
    freqs = (arch["rope_theta"] ** (-np.arange(0, dr, 2) / dr)
             ).astype(np.float32)
    q = ops.dense(u, p["q_proj"]["kernel"]).reshape(seq, heads, dn + dr)
    q = jnp.concatenate([q[..., :dn], rope_pairs(q[..., dn:], freqs)], -1)
    kv_a = ops.dense(u, p["kv_a_proj"]["kernel"])
    c_kv = rms(kv_a[:, :rank], p["kv_a_norm"]["scale"], eps)
    k_rope = rope_pairs(kv_a[:, rank:], freqs)
    kv = ops.dense(c_kv, p["kv_b_proj"]["kernel"]).reshape(seq, heads,
                                                           dn + dv)
    k = jnp.concatenate([kv[..., :dn], jnp.broadcast_to(
        k_rope[:, None, :], (seq, heads, dr))], -1)
    v = kv[..., dn:]

    @jax.checkpoint
    def rows(block):
        q_rows, first = block
        scores = ops.dense(q_rows.transpose(1, 0, 2), k.transpose(1, 2, 0)) \
            * (dn + dr) ** -0.5                              # (h, rows, S)
        seen = (first + jnp.arange(q_rows.shape[0]))[:, None] \
            >= jnp.arange(seq)[None, :]
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), -1)
        return ops.dense(probs, v.transpose(1, 0, 2)).transpose(1, 0, 2)

    # one block after the other (`lax.map`), so that one block's scores
    # are alive at a time, forward and backward
    block = math.gcd(seq, block_rows)
    ctx = jax.lax.map(rows, (q.reshape(seq // block, block, heads, dn + dr),
                             jnp.arange(0, seq, block)))
    return ops.dense(ctx.reshape(seq, heads * dv), p["o_proj"]["kernel"])


def _largest(left, k: int):
    """The indices of the k largest of each row by repeated argmax (the
    lowest index wins a tie)."""
    chosen = []
    for _ in range(k):
        i = jnp.argmax(left, -1)
        chosen.append(i)
        left = jnp.where(jax.nn.one_hot(i, left.shape[-1], dtype=bool),
                         -jnp.inf, left)
    return jnp.stack(chosen, -1)


def routing(p, u, arch: dict):
    """(weights (S, k), experts (S, k)): sigmoid scores; the choice by
    score + bias, limited to the `topk_group` groups whose two largest
    sum highest, then the k largest; weighted by the scores themselves
    over their sum, times the scale."""
    scores = jax.nn.sigmoid(jnp.matmul(u, p["router"],
                                       precision=jax.lax.Precision.HIGHEST))
    choice = scores + jax.lax.stop_gradient(p["router_bias"])
    groups = arch["n_group"]
    if groups > 1:
        by_group = choice.reshape(choice.shape[0], groups, -1)
        two = jnp.take_along_axis(by_group, _largest(by_group, 2), -1)
        kept = jnp.any(jax.nn.one_hot(
            _largest(jnp.sum(two, -1), arch["topk_group"]), groups,
            dtype=bool), -2)                                 # (S, groups)
        choice = jnp.where(kept[:, :, None], by_group, -jnp.inf
                           ).reshape(choice.shape)
    chosen = _largest(choice, arch["num_experts_per_tok"])
    top = jnp.take_along_axis(scores, chosen, -1)
    return arch["routed_scaling_factor"] * top \
        / (jnp.sum(top, -1, keepdims=True) + 1e-20), chosen


def swiglu(x, gate, up, down, ops: Ops):
    return ops.dense(jax.nn.silu(ops.dense(x, gate)) * ops.dense(x, up), down)


def experts(p, u, arch: dict, share, ops: Ops, *, shared: bool = True):
    """(the share's part of the expert layer on u (S, hidden), the held
    experts' loads). `shared=False` leaves the shared expert out."""
    first, held = share
    weights, chosen = routing(p, u, arch)

    @jax.checkpoint
    def one(out, expert):
        gate, up, down, index = expert
        mine = chosen == index                               # (S, k)
        out = out + jnp.sum(jnp.where(mine, weights, 0.0), -1)[:, None] \
            * swiglu(u, gate, up, down, ops)
        return out, jnp.sum(mine).astype(jnp.int32)

    # one held expert after the other (`lax.scan`), each on every token and
    # made again in the backward pass: one expert's hidden rows at a time
    out, loads = jnp.zeros_like(u), jnp.zeros((0,), jnp.int32)
    if held:
        out, loads = jax.lax.scan(one, out, (
            p["experts_gate_proj"], p["experts_up_proj"],
            p["experts_down_proj"], first + jnp.arange(held)))
    if shared:
        out = out + swiglu(u, p["shared_gate_proj"]["kernel"],
                           p["shared_up_proj"]["kernel"],
                           p["shared_down_proj"]["kernel"], ops)
    return out, loads


def kinds_of(arch: dict, layer: int) -> tuple:
    return KINDS[arch["hybrid_override_pattern"][layer]]


def block(p, x, arch: dict, share, ops: Ops, block_rows: int = 512, *,
          layer: int, fault: str | None = None):
    """Layer `layer` on one sequence: (y, the held experts' loads, zeros
    for a layer without experts)."""
    attention, ffn = kinds_of(arch, layer)
    eps = arch["rms_norm_eps"]
    u = rms(x, p["input_norm"]["scale"], eps)
    h = x + (kda(p["attn"], u, arch, ops, fault=fault) if attention == "kda"
             else latent(p["attn"], u, arch, ops, block_rows))
    u = rms(h, p["post_attention_norm"]["scale"], eps)
    if ffn == "dense":
        return h + swiglu(u, *(p["mlp"][f"{name}_proj"]["kernel"]
                               for name in ("gate", "up", "down")), ops), \
            jnp.zeros(share[1], jnp.int32)
    out, loads = experts(p["moe"], u, arch, share, ops)
    return h + out, loads


def expert_layers(arch: dict) -> list:
    return [i for i, letter in enumerate(arch["hybrid_override_pattern"])
            if KINDS[letter][1] == "experts"]


def head_loss(p_norm, p_head, x, targets, arch: dict, ops: Ops):
    """(summed next-token cross-entropy of one sequence, its logits)."""
    logits = ops.dense(rms(x, p_norm["scale"], arch["rms_norm_eps"]),
                       p_head["kernel"])
    picked = jnp.take_along_axis(logits, targets[:, None], -1)[:, 0]
    return jnp.sum(jax.nn.logsumexp(logits, -1) - picked), logits


def forward(params, tokens, arch: dict, share, ops: Ops | None = None,
            block_rows: int = 512, fault: str | None = None):
    """tokens (B, S) -> (logits (B, S, V), loads (B, expert layers, held))."""
    ops = ops or Ops("float32")
    with_experts = expert_layers(arch)

    def one(row):
        x, loads = params["embed"]["embedding"][row], []
        for i in range(len(arch["hybrid_override_pattern"])):
            x, load = block(params[f"layer_{i}"], x, arch, share, ops,
                            block_rows, layer=i, fault=fault)
            if i in with_experts:
                loads.append(load)
        zeros = jnp.zeros(row.shape, jnp.int32)
        return head_loss(params["norm"], params["lm_head"], x, zeros, arch,
                         ops)[1], jnp.stack(loads)

    outs = [one(row) for row in tokens]
    return (jnp.stack([o[0] for o in outs]),
            jnp.stack([o[1] for o in outs]))


def loss(params, tokens, arch: dict, share, ops: Ops | None = None,
         block_rows: int = 512, fault: str | None = None):
    """Mean next-token cross-entropy of tokens (B, S + 1): inputs
    [:, :-1], targets [:, 1:]."""
    logits, _ = forward(params, tokens[:, :-1], arch, share, ops, block_rows,
                        fault)
    logp = jax.nn.log_softmax(logits, -1)
    return -jnp.mean(jnp.take_along_axis(
        logp, tokens[:, 1:, None], -1))
