"""The plain reference of NVIDIA-Nemotron-3-Nano's hybrid stack
(`model_type: nemotron_h`), as a language model and as one chip's share of
its experts.

Float32 `jax.numpy` at `highest` matmul precision (`ops.Ops`), nothing of
the program: no kernel, no sort, no grouped product, no chunked scan. The
state-space layer is **the literal recurrence**, one position at a time
(`jax.lax.scan`), checkpointed in stretches of `SCAN_STRETCH` positions so
that its backward pass keeps one stretch of states and not all of them (a
head's state is p x n floats: 2 MB over 64 heads, 17 GB at 8,192
positions). Attention is an explicit causal softmax over `[rows, S]`
scores per head, `block_rows` query rows at a time (one block after the
other, each made again in the backward pass); the routed experts are a
plain loop over the held experts (`jax.lax.scan`: one after the other),
each computed on every token and weighted by a mask, each made again in
the backward pass.

`arch` is the configuration file's published keys with the pattern as it
is run; `share` = (first_expert, experts_held). The parameter tree is named
as the program names its own (`layer_0/mixer/in_proj/kernel`,
`layer_1/mixer/router`, ...).

    layer i of kind t_i in the pattern:   x <- x + Mixer_{t_i}(rms(x))
    M   [z | xBC | dt] = u W_in;  xBC = silu(conv4(xBC) + bias), causal,
        depthwise;  [x | B | C] = xBC;  dt = softplus(dt + dt_bias);
        A = -exp(A_log);  h_t = exp(dt_t A) h_{t-1} + dt_t x_t (x) B_t;
        y_t = h_t C_t + D x_t;  y = rms_grouped(y * silu(z)) * w;  y W_out
    E   s = sigmoid(u W_r); top-k of s + b by repeated argmax;
        w_k = scale * s_k / (sum_k s_k + 1e-20);
        out = sum_{k held} w_k E_k(u) + S(u);  E(u) = relu(u W_up)^2 W_down
    *   q, k, v = u W_q, u W_k, u W_v; query head j on key head
        j // (heads / kv heads); causal softmax(q k^T / sqrt(d)) v; W_o

`fault="chunk_reset"` plants this model's own fault: the state set to zero
at every multiple of `chunk_size`, which is what a chunked scan gives that
forgets to hand its state on.

Departures from the published description are listed under `assumed` in
the configuration file: no rotary embedding, a fixed selection bias, no
document mask, and what the experts held elsewhere would add is left out
(the share).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from .ops import Ops

SCAN_STRETCH = 128
KINDS = {"M": "mamba", "E": "experts", "*": "attention"}


def rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def recurrence(x, dt, a, b_in, c_out, *, reset_every: int | None = None):
    """y_t = h_t C_t with h_t = exp(dt_t a) h_{t-1} + dt_t x_t (x) B_t,
    h_0 = 0, position by position. `x` (S, heads, p), `dt` (S, heads), `a`
    (heads,), `b_in`, `c_out` (S, heads, n) (each head's group's). With
    `reset_every` the state is zeroed before every position that is a
    multiple of it."""
    seq, heads, p = x.shape
    n = b_in.shape[-1]
    stretch = math.gcd(seq, SCAN_STRETCH)

    def position(h, inputs):
        x_t, dt_t, b_t, c_t, keep = inputs
        h = h * (keep * jnp.exp(dt_t * a))[:, None, None] \
            + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :]
        return h, jnp.sum(h * c_t[:, None, :], -1)

    @jax.checkpoint
    def run(h, inputs):
        return jax.lax.scan(position, h, inputs)

    at = jnp.arange(seq)
    keep = jnp.ones(seq) if reset_every is None \
        else (at % reset_every != 0).astype(jnp.float32)
    split = lambda v: v.reshape(seq // stretch, stretch, *v.shape[1:])
    _, y = jax.lax.scan(run, jnp.zeros((heads, p, n), jnp.float32),
                        tuple(split(v) for v in (x, dt, b_in, c_out, keep)))
    return y.reshape(seq, heads, p)


def mamba(p, u, arch: dict, ops: Ops, *, fault: str | None = None):
    """The Mamba-2 mixer on one sequence u (S, hidden)."""
    heads, dim, groups, n = (arch["mamba_num_heads"], arch["mamba_head_dim"],
                             arch["n_groups"], arch["ssm_state_size"])
    inner, seq, width = heads * dim, u.shape[0], arch["conv_kernel"]
    zxbcdt = ops.dense(u, p["in_proj"]["kernel"])
    z, xbc, dt = (zxbcdt[:, :inner], zxbcdt[:, inner:-heads],
                  zxbcdt[:, -heads:])
    # kernel[width - 1] multiplies the position itself
    padded = jnp.concatenate([jnp.zeros((width - 1, xbc.shape[1])), xbc])
    xbc = jax.nn.silu(p["conv1d"]["bias"] + sum(
        padded[k:k + seq] * p["conv1d"]["kernel"][k] for k in range(width)))
    x = xbc[:, :inner].reshape(seq, heads, dim)
    per_head = lambda v: jnp.repeat(v.reshape(seq, groups, n),
                                    heads // groups, axis=1)
    b_in = per_head(xbc[:, inner:inner + groups * n])
    c_out = per_head(xbc[:, inner + groups * n:])
    dt = jax.nn.softplus(dt + p["dt_bias"])
    y = recurrence(x, dt, -jnp.exp(p["A_log"]), b_in, c_out,
                   reset_every=arch["chunk_size"]
                   if fault == "chunk_reset" else None)
    y = (y + p["D"][:, None] * x).reshape(seq, inner) * jax.nn.silu(z)
    runs = y.reshape(seq, groups, inner // groups)
    runs = runs * jax.lax.rsqrt(
        jnp.mean(runs * runs, -1, keepdims=True) + arch["layer_norm_epsilon"])
    return ops.dense(runs.reshape(seq, inner) * p["norm"]["scale"],
                     p["out_proj"]["kernel"])


def attention(p, u, arch: dict, ops: Ops, block_rows: int):
    """Grouped-query attention on one sequence u (S, hidden)."""
    heads, kv, d = (arch["num_attention_heads"], arch["num_key_value_heads"],
                    arch["head_dim"])
    seq = u.shape[0]
    q = ops.dense(u, p["q_proj"]["kernel"]).reshape(seq, heads, d)
    k = ops.dense(u, p["k_proj"]["kernel"]).reshape(seq, kv, d)
    v = ops.dense(u, p["v_proj"]["kernel"]).reshape(seq, kv, d)
    k, v = (jnp.repeat(m, heads // kv, axis=1) for m in (k, v))

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
                             jnp.arange(0, seq, block)))
    return ops.dense(ctx.reshape(seq, heads * d), p["o_proj"]["kernel"])


def routing(p, u, arch: dict):
    """(weights (S, k), experts (S, k)): the k largest of sigmoid(u W_r) +
    bias by repeated argmax (the lowest index wins a tie), weighted by the
    scores themselves over their sum, times the scale."""
    scores = jax.nn.sigmoid(jnp.matmul(u, p["router"],
                                       precision=jax.lax.Precision.HIGHEST))
    left = scores + jax.lax.stop_gradient(p["router_bias"])
    chosen = []
    for _ in range(arch["num_experts_per_tok"]):
        i = jnp.argmax(left, -1)
        chosen.append(i)
        left = jnp.where(jax.nn.one_hot(i, left.shape[-1], dtype=bool),
                         -jnp.inf, left)
    chosen = jnp.stack(chosen, -1)
    top = jnp.take_along_axis(scores, chosen, -1)
    return arch["routed_scaling_factor"] * top \
        / (jnp.sum(top, -1, keepdims=True) + 1e-20), chosen


def relu2(x, up, down, ops: Ops):
    return ops.dense(jnp.square(jax.nn.relu(ops.dense(x, up))), down)


def experts(p, u, arch: dict, share, ops: Ops, *, shared: bool = True):
    """(the share's part of the expert layer on u (S, hidden), the held
    experts' loads). `shared=False` leaves the shared expert out."""
    first, held = share
    weights, chosen = routing(p, u, arch)

    @jax.checkpoint
    def one(out, expert):
        up, down, index = expert
        mine = chosen == index                               # (S, k)
        out = out + jnp.sum(jnp.where(mine, weights, 0.0), -1)[:, None] \
            * relu2(u, up, down, ops)
        return out, jnp.sum(mine).astype(jnp.int32)

    # one held expert after the other (`lax.scan`), each on every token and
    # made again in the backward pass: one expert's hidden rows at a time
    out, loads = jnp.zeros_like(u), jnp.zeros((0,), jnp.int32)
    if held:
        out, loads = jax.lax.scan(one, out, (
            p["experts_up_proj"], p["experts_down_proj"],
            first + jnp.arange(held)))
    if shared:
        out = out + relu2(u, p["shared_up_proj"]["kernel"],
                          p["shared_down_proj"]["kernel"], ops)
    return out, loads


def kind_of(arch: dict, layer: int) -> str:
    return KINDS[arch["hybrid_override_pattern"][layer]]


def block(p, x, arch: dict, share, ops: Ops, block_rows: int = 512, *,
          layer: int, fault: str | None = None):
    """Layer `layer` on one sequence: (y, the held experts' loads, zeros
    for a layer without experts)."""
    kind = kind_of(arch, layer)
    u = rms(x, p["norm"]["scale"], arch["layer_norm_epsilon"])
    loads = jnp.zeros(share[1], jnp.int32)
    if kind == "mamba":
        out = mamba(p["mixer"], u, arch, ops, fault=fault)
    elif kind == "attention":
        out = attention(p["mixer"], u, arch, ops, block_rows)
    else:
        out, loads = experts(p["mixer"], u, arch, share, ops)
    return x + out, loads


def expert_layers(arch: dict) -> list:
    return [i for i, letter in enumerate(arch["hybrid_override_pattern"])
            if letter == "E"]


def head_loss(p_norm, p_head, x, targets, arch: dict, ops: Ops):
    """(summed next-token cross-entropy of one sequence, its logits)."""
    logits = ops.dense(rms(x, p_norm["scale"], arch["layer_norm_epsilon"]),
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
