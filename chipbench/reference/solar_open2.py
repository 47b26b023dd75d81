"""The plain reference of Solar-Open2-250B's stack (`model_type:
solar_open2`), as a language model and as one chip's share of its experts.

Float32 `jax.numpy` at `highest` matmul precision (`ops.Ops`), nothing of
the program: no kernel, no sort, no grouped product, no chunked form. The
linear layers are **the literal recurrence** of Kimi Delta Attention, one
position at a time (`ling3.delta_rule`: every decay exp(g_t) formed from
its own position's gate, whatever the gate); the softmax layers an
explicit causal softmax over `[rows, S]` scores per head, `block_rows`
query rows at a time; the router's top-k is repeated argmax and the routed
experts a plain loop over the held experts, each on every token and
weighted by a mask (`ling3.routing`, `ling3.experts`, with no group
limit).

`arch` is the configuration file's published keys with the pattern as it
is run, one letter a layer: `G` gated grouped-query attention + experts,
`K` KDA + experts. `share` = (first_expert, experts_held). The parameter
tree is named as the program names its own (`layer_1/attn/f_a_proj/
kernel`, `layer_0/attn/gate_proj/kernel`, `layer_2/moe/router`, ...).

    layer i:   h = x + Attn(rms(x));   y = h + MoE(rms(h))          eps 1e-5
    KDA     q, k, v = silu(conv4(u W_q)), silu(conv4(u W_k)), silu(conv4(
            u W_v)): causal, depthwise, zeros before the start;
            q, k <- q / sqrt(|q|^2 + 1e-6), k / sqrt(|k|^2 + 1e-6) a head;
            q <- q d^-0.5;
            g = -exp(A_log_h) softplus(u F_a F_b + dt_bias);
            beta = 2 sigmoid(u W_b)      (1 x where negative eigenvalues
                                          are not allowed);
            S_t = (I - beta_t k_t k_t^T) Diag(exp(g_t)) S_{t-1}
                  + beta_t k_t v_t^T;   o_t = S_t^T q_t;   S_0 = 0
            y = [rms_head(o) * w * sigmoid(u G_a G_b)] W_o
    GQA     q = u W_q (64 heads), k, v = u W_k, u W_v (8 heads, each read
            by 8 query heads); softmax(q k^T / sqrt(d)) v, causal, no
            position embedding; times sigmoid(u W_gate); W_o
    experts s = sigmoid(u W_r); top-k of s + b by repeated argmax;
            w_k = scale * s_k / (sum_k s_k + 1e-20);
            out = sum_{k held} w_k E_k(u) + S(u), E and S swiglu

`fault="chunk_reset"` plants this model's own fault: the delta rule's
state set to zero at every multiple of `CHUNK` positions, which is what a
chunked form gives that forgets to hand its state on.

Departures from the published description are listed under `assumed` in
the configuration file; what the experts held elsewhere would add is left
out (the share).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from . import ling3
from .ops import Ops

#: positions of one chunk of the program's chunked form (`chunk_reset`)
CHUNK = ling3.CHUNK
KINDS = {"G": "gqa", "K": "kda"}
rms = ling3.rms


def _projection(p, u, name: str, arch: dict, ops: Ops):
    """u through `name`'s projection: whole, or its two low-rank halves."""
    if arch["kda_use_full_proj"]:
        return ops.dense(u, p[f"{name}_proj"]["kernel"])
    return ops.dense(ops.dense(u, p[f"{name}_a_proj"]["kernel"]),
                     p[f"{name}_b_proj"]["kernel"])


def kda(p, u, arch: dict, ops: Ops, *, fault: str | None = None):
    """Kimi Delta Attention on one sequence u (S, hidden)."""
    linear = arch["linear_attn_config"]
    heads, dk, width = (linear["num_heads"], linear["head_dim"],
                        linear["short_conv_kernel_size"])
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
    f = _projection(p, u, "f", arch, ops) + p["dt_bias"]
    g = -jnp.exp(p["A_log"])[:, None] * jax.nn.softplus(
        f.reshape(seq, heads, dk))
    beta = jax.nn.sigmoid(ops.dense(u, p["b_proj"]["kernel"]))
    if arch["kda_allow_neg_eigval"]:
        beta = 2.0 * beta
    gate = jax.nn.sigmoid(_projection(p, u, "g", arch, ops)).reshape(
        seq, heads, dk)
    o = ling3.delta_rule(q, k, v, g, beta,
                         reset_every=CHUNK if fault == "chunk_reset" else None)
    o = rms(o, p["o_norm"]["scale"], arch["rms_norm_eps"]) * gate
    return ops.dense(o.reshape(seq, heads * dk), p["o_proj"]["kernel"])


def gqa(p, u, arch: dict, ops: Ops, block_rows: int):
    """Gated grouped-query attention on one sequence u (S, hidden)."""
    heads, kv, d = (arch["num_attention_heads"], arch["num_key_value_heads"],
                    arch["head_dim"])
    seq = u.shape[0]
    q = ops.dense(u, p["q_proj"]["kernel"]).reshape(seq, heads, d)
    k, v = (jnp.repeat(ops.dense(u, p[f"{name}_proj"]["kernel"]).reshape(
        seq, kv, d), heads // kv, axis=1) for name in "kv")

    @jax.checkpoint
    def rows(block):
        q_rows, first = block
        scores = ops.dense(q_rows.transpose(1, 0, 2),
                           k.transpose(1, 2, 0)) * d ** -0.5   # (h, rows, S)
        seen = (first + jnp.arange(q_rows.shape[0]))[:, None] \
            >= jnp.arange(seq)[None, :]
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), -1)
        return ops.dense(probs, v.transpose(1, 0, 2)).transpose(1, 0, 2)

    # one block after the other (`lax.map`), so that one block's scores
    # are alive at a time, forward and backward
    block = math.gcd(seq, block_rows)
    ctx = jax.lax.map(rows, (q.reshape(seq // block, block, heads, d),
                             jnp.arange(0, seq, block))).reshape(seq, heads * d)
    if arch["use_gqa_gate"]:
        ctx = ctx * jax.nn.sigmoid(ops.dense(u, p["gate_proj"]["kernel"]))
    return ops.dense(ctx, p["o_proj"]["kernel"])


def experts(p, u, arch: dict, share, ops: Ops, *, shared: bool = True):
    """(the share's part of the expert layer on u (S, hidden), the held
    experts' loads): `ling3.experts` with no group limit."""
    return ling3.experts(p, u, {**arch, "n_group": 1}, share, ops,
                         shared=shared)


def block(p, x, arch: dict, share, ops: Ops, block_rows: int = 512, *,
          layer: int, fault: str | None = None):
    """Layer `layer` on one sequence: (y, the held experts' loads)."""
    eps = arch["rms_norm_eps"]
    u = rms(x, p["input_norm"]["scale"], eps)
    kind = KINDS[arch["hybrid_override_pattern"][layer]]
    h = x + (kda(p["attn"], u, arch, ops, fault=fault) if kind == "kda"
             else gqa(p["attn"], u, arch, ops, block_rows))
    out, loads = experts(p["moe"], rms(h, p["post_attention_norm"]["scale"],
                                       eps), arch, share, ops)
    return h + out, loads


def expert_layers(arch: dict) -> list:
    return list(range(len(arch["hybrid_override_pattern"])))


def head_loss(p_norm, p_head, x, targets, arch: dict, ops: Ops):
    """(summed next-token cross-entropy of one sequence, its logits)."""
    return ling3.head_loss(p_norm, p_head, x, targets, arch, ops)


def forward(params, tokens, arch: dict, share, ops: Ops | None = None,
            block_rows: int = 512, fault: str | None = None):
    """tokens (B, S) -> (logits (B, S, V), loads (B, layers, held))."""
    ops = ops or Ops("float32")

    def one(row):
        x, loads = params["embed"]["embedding"][row], []
        for i in range(len(arch["hybrid_override_pattern"])):
            x, load = block(params[f"layer_{i}"], x, arch, share, ops,
                            block_rows, layer=i, fault=fault)
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
