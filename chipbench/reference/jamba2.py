"""The plain reference of AI21-Jamba2-3B's stack (`model_type: jamba`), as a
language model: one pipeline stage's layers and the tied head over the
vocabulary rows held.

Float32 `jax.numpy` at `highest` matmul precision (`ops.Ops`), nothing of
the program: no kernel, no chunked scan. The Mamba-1 layers are **the
literal recurrence**, one position at a time (`jax.lax.scan` over t),
checkpointed in stretches of `STRETCH` positions so that one layer's
backward pass keeps one stretch of states and not all of them (a state is
5,120 x 16 floats: 1.3 GB over 4,096 positions). The attention layer is an
explicit causal softmax over `[rows, S]` scores per head, `block_rows`
query rows at a time (`nemotron_h.attention`).

`arch` is the configuration file's published keys with the pattern as it
is run, one letter a layer: `M` Mamba-1 + MLP, `A` attention + MLP.
`share` = (0, 0): the stack has no experts, and `block` gives an empty
load. The parameter tree is named as the program names its own
(`layer_0/mixer/A_log`, `layer_1/mixer/dt_proj/bias`,
`layer_7/mixer/q_proj/kernel`, `layer_0/mlp/gate_proj/kernel`, ...); the
head is `embed/embedding` transposed (`reference/hybrid_lm_step.py`).

    layer i:  h = x + Mixer_i(rms(x));  y = h + MLP(rms(h))         eps 1e-6
    M   [x | z] = u W_in;  x = silu(conv4(x) + bias), causal, depthwise;
        [delta | B | C] = x W_x;  delta, B, C = rms(delta), rms(B), rms(C);
        dt = softplus(delta W_dt + b_dt);  A = -exp(A_log)   (d_inner, n);
        h_t = exp(dt_t A) * h_{t-1} + (dt_t x_t) (x) B_t;  h_0 = 0;
        y_t = h_t C_t + D x_t;  out = (y * silu(z)) W_out
    A   q = u W_q (20 heads), k, v = u W_k, u W_v (1 head, read by all 20);
        softmax(q k^T / sqrt(128)) v, causal, no position embedding; W_o
    MLP (silu(u W_g) * u W_u) W_d

`fault="chunk_reset"` plants this model's own fault: the recurrence's
state set to zero at every multiple of `CHUNK` positions, which is what a
chunked scan gives that forgets to hand its state on.

Departures from the published description are listed under `assumed` in
the configuration file: head_dim = hidden / heads (the config gives none),
no document mask, and what the second pipeline stage and the other
vocabulary slices would add is left out.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from . import ling3, nemotron_h
from .ops import Ops

#: positions of one chunk of the program's recurrence (`chunk_reset`)
CHUNK = 128
#: positions a checkpointed stretch of the literal recurrence
STRETCH = 256
KINDS = {"M": "mamba", "A": "attention"}
rms = ling3.rms


def recurrence(x, dt, a, b_in, c_out, *, reset_every: int | None = None):
    """y_t = h_t C_t with h_t = exp(dt_t a) * h_{t-1} + (dt_t x_t) (x) B_t,
    h_0 = 0, position by position. `x`, `dt` (S, d), `a` (d, n), `b_in`,
    `c_out` (S, n). With `reset_every` the state is zeroed before every
    position that is a multiple of it."""
    seq, d = x.shape
    stretch = math.gcd(seq, STRETCH)

    def position(h, inputs):
        x_t, dt_t, b_t, c_t, keep = inputs
        h = keep * jnp.exp(dt_t[:, None] * a) * h \
            + (dt_t * x_t)[:, None] * b_t[None, :]
        return h, jnp.sum(h * c_t[None, :], -1)

    @jax.checkpoint
    def run(h, inputs):
        return jax.lax.scan(position, h, inputs)

    at = jnp.arange(seq)
    keep = jnp.ones(seq) if reset_every is None \
        else (at % reset_every != 0).astype(jnp.float32)
    split = lambda v: v.reshape(seq // stretch, stretch, *v.shape[1:])
    _, y = jax.lax.scan(run, jnp.zeros((d, a.shape[1]), jnp.float32),
                        tuple(split(v) for v in (x, dt, b_in, c_out, keep)))
    return y.reshape(seq, d)


def mamba(p, u, arch: dict, ops: Ops, *, fault: str | None = None):
    """The Mamba-1 mixer on one sequence u (S, hidden)."""
    inner = arch["mamba_expand"] * arch["hidden_size"]
    n, r, width = (arch["mamba_d_state"], arch["mamba_dt_rank"],
                   arch["mamba_d_conv"])
    eps, seq = arch["rms_norm_eps"], u.shape[0]
    xz = ops.dense(u, p["in_proj"]["kernel"])
    x, z = xz[:, :inner], xz[:, inner:]
    # kernel[width - 1] multiplies the position itself
    padded = jnp.concatenate([jnp.zeros((width - 1, inner)), x])
    x = jax.nn.silu(p["conv1d"]["bias"] + sum(
        padded[k:k + seq] * p["conv1d"]["kernel"][k] for k in range(width)))
    dbc = ops.dense(x, p["x_proj"]["kernel"])
    delta = rms(dbc[:, :r], p["dt_norm"]["scale"], eps)
    b_in = rms(dbc[:, r:r + n], p["b_norm"]["scale"], eps)
    c_out = rms(dbc[:, r + n:], p["c_norm"]["scale"], eps)
    dt = jax.nn.softplus(ops.dense(delta, p["dt_proj"]["kernel"])
                         + p["dt_proj"]["bias"])
    y = recurrence(x, dt, -jnp.exp(p["A_log"]), b_in, c_out,
                   reset_every=CHUNK if fault == "chunk_reset" else None)
    y = y + p["D"] * x
    return ops.dense(y * jax.nn.silu(z), p["out_proj"]["kernel"])


def attention(p, u, arch: dict, ops: Ops, block_rows: int):
    """Grouped-query attention on one sequence u (S, hidden), heads of
    hidden / heads."""
    head_dim = arch["hidden_size"] // arch["num_attention_heads"]
    return nemotron_h.attention(p, u, {**arch, "head_dim": head_dim}, ops,
                                block_rows)


def block(p, x, arch: dict, share, ops: Ops, block_rows: int = 512, *,
          layer: int, fault: str | None = None):
    """Layer `layer` on one sequence: (y, the empty load of no experts)."""
    eps = arch["rms_norm_eps"]
    u = rms(x, p["input_norm"]["scale"], eps)
    if KINDS[arch["hybrid_override_pattern"][layer]] == "mamba":
        h = x + mamba(p["mixer"], u, arch, ops, fault=fault)
    else:
        h = x + attention(p["mixer"], u, arch, ops, block_rows)
    mlp = p["mlp"]
    out = ling3.swiglu(rms(h, p["pre_ff_norm"]["scale"], eps),
                       mlp["gate_proj"]["kernel"], mlp["up_proj"]["kernel"],
                       mlp["down_proj"]["kernel"], ops)
    return h + out, jnp.zeros((share[1],), jnp.int32)


def expert_layers(arch: dict) -> list:
    return []


def head_loss(p_norm, p_head, x, targets, arch: dict, ops: Ops):
    """(summed next-token cross-entropy of one sequence, its logits)."""
    return ling3.head_loss(p_norm, p_head, x, targets, arch, ops)


def forward(params, tokens, arch: dict, ops: Ops | None = None,
            block_rows: int = 512, fault: str | None = None):
    """tokens (B, S) -> logits (B, S, V) through the tied head."""
    ops = ops or Ops("float32")
    head = {"kernel": params["embed"]["embedding"].T}

    def one(row):
        x = params["embed"]["embedding"][row]
        for i in range(len(arch["hybrid_override_pattern"])):
            x, _ = block(params[f"layer_{i}"], x, arch, (0, 0), ops,
                         block_rows, layer=i, fault=fault)
        return head_loss(params["norm"], head, x,
                         jnp.zeros(row.shape, jnp.int32), arch, ops)[1]

    return jnp.stack([one(row) for row in tokens])


def loss(params, tokens, arch: dict, ops: Ops | None = None,
         block_rows: int = 512, fault: str | None = None):
    """Mean next-token cross-entropy of tokens (B, S + 1): inputs
    [:, :-1], targets [:, 1:]."""
    logits = forward(params, tokens[:, :-1], arch, ops, block_rows, fault)
    logp = jax.nn.log_softmax(logits, -1)
    return -jnp.mean(jnp.take_along_axis(logp, tokens[:, 1:, None], -1))
