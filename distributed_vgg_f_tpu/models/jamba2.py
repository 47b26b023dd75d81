"""AI21-Jamba2-3B's stack (`model_type: jamba`) as a language model the
trainer can train: pre-norm residual blocks, a mixer and a SwiGLU MLP in
every layer, the mixer Mamba-1 but in one layer of every fourteen, which
is attention; the head is the embedding's transpose.

    x <- x + Mixer_i(RMSNorm(x));   x <- x + MLP(RMSNorm(x))      eps 1e-6
    Mixer_i   attention where i % attn_layer_period == attn_layer_offset
              (7 of 0..13), else Mamba-1
    MLP       (silu(u W_g) * u W_u) W_d, intermediate_size wide: every
              layer's (`num_experts: 1`, so no router)

    Mamba-1 (d_inner = mamba_expand x hidden, N = mamba_d_state, R =
    mamba_dt_rank; `mamba_proj_bias: false`, `mamba_conv_bias: true`):
        [x | z] = u W_in
        x = silu(causal depthwise conv over the sequence, 4 taps, + bias)
        [delta | B | C] = x W_x                 widths R | N | N
        delta, B, C = RMSNorm(delta), RMSNorm(B), RMSNorm(C)
        dt = softplus(delta W_dt + b_dt)        one a channel
        A = -exp(A_log)                         (d_inner, N)
        h_t = exp(dt_t A) * h_{t-1} + (dt_t x_t) (x) B_t
        y_t = h_t C_t + D x_t                   (ops/selective_scan.py)
        out = (y * silu(z)) W_out
    attention  20 query heads on 1 key/value head of hidden / heads = 128,
               no position embedding: causal softmax(q k^T / sqrt(128)) v
               (`nemotron_h.GroupedQueryAttention`)
    head       logits = RMSNorm(x) E^T, E the embedding (tied)

The kinds are spelled one letter a layer (`pattern_of`): `M` Mamba-1, `A`
attention, each with its MLP; the published 28 layers read `MMMMMMMA
MMMMMM` twice. The state is zero at the start of every sequence; the
decays, the state and dt are float32, the products take operands in the
compute dtype. Departures from the published modelling code are listed
under `assumed` in the benchmark's configuration file (`chipbench/
configs/jamba2_3b_v8.json`). The stack, the blocks' recomputation and the
loss are models/ling3.py's (`LingLM`), with this file's letters and block
and the head tied.
"""

from __future__ import annotations

from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from distributed_vgg_f_tpu.models.ling3 import DT_INIT, DenseMLP, LingLM
from distributed_vgg_f_tpu.models.mistral4 import RMSNorm, _dense
from distributed_vgg_f_tpu.models.nemotron_h import (
    CausalConv, GroupedQueryAttention, _log_uniform_dt_bias)
from distributed_vgg_f_tpu.ops import selective_scan

#: a letter of the pattern -> (mixer, feed-forward)
KINDS = {"M": ("mamba", "dense"), "A": ("attention", "dense")}


def pattern_of(num_hidden_layers: int, period: int, offset: int) -> str:
    """The published rule, one letter of `KINDS` a layer."""
    return "".join("A" if i % period == offset else "M"
                   for i in range(num_hidden_layers))


def _a_log(key, shape, dtype=jnp.float32):
    """log(1 .. N) for every channel: A = -(1 .. N), the published start."""
    del key
    return jnp.broadcast_to(jnp.log(jnp.arange(1, shape[1] + 1, dtype=dtype)),
                            shape)


class TimeStep(nn.Module):
    """dt = softplus(delta W_dt + b_dt): the product in the compute dtype,
    accumulated, biased and softplus'd in float32."""
    features: int
    compute_dtype: Any
    time_step: tuple               # (min, max, floor) of the initial dt

    @nn.compact
    def __call__(self, delta):
        kernel = self.param("kernel", nn.initializers.lecun_normal(),
                            (delta.shape[-1], self.features), jnp.float32)
        bias = self.param("bias", _log_uniform_dt_bias(*self.time_step),
                          (self.features,), jnp.float32)
        dtype = self.compute_dtype
        return jax.nn.softplus(jnp.dot(
            delta.astype(dtype), kernel.astype(dtype),
            preferred_element_type=jnp.float32) + bias)


class Mamba1Mixer(nn.Module):
    inner: int                     # d_inner
    state_size: int
    dt_rank: int
    conv_kernel: int
    chunk_size: int
    time_step: tuple
    compute_dtype: Any
    eps: float = 1e-6

    @nn.compact
    def __call__(self, u):
        b, t, d_model = u.shape
        inner, n, r, dtype = (self.inner, self.state_size, self.dt_rank,
                              self.compute_dtype)
        with jax.named_scope("mamba1_in"):
            xz = _dense(2 * inner, dtype, "in_proj")(u)
        with jax.named_scope("mamba1_conv"):
            x = nn.silu(CausalConv(self.conv_kernel, name="conv1d")(
                xz[..., :inner])).astype(dtype)
        with jax.named_scope("mamba1_xdt"):
            dbc = _dense(r + 2 * n, dtype, "x_proj")(x)
            delta = RMSNorm(self.eps, name="dt_norm")(dbc[..., :r])
            b_in = RMSNorm(self.eps, name="b_norm")(dbc[..., r:r + n])
            c_out = RMSNorm(self.eps, name="c_norm")(dbc[..., r + n:])
            dt = TimeStep(inner, dtype, self.time_step, name="dt_proj")(delta)
        a_log = self.param("A_log", _a_log, (inner, n), jnp.float32)
        skip = self.param("D", nn.initializers.ones, (inner,), jnp.float32)
        with jax.named_scope("mamba1_scan"):
            y = selective_scan.selective_scan(
                x, dt, -jnp.exp(a_log), b_in, c_out, skip,
                chunk=self.chunk_size)
            # whether the recurrence took the Pallas kernels (1) or the XLA
            # form (0), for a caller that asks (`mutable=["counters"]`)
            self.sow("counters", "mamba1_kernel", int(
                selective_scan.takes_kernels((b, t, inner), n,
                                             self.chunk_size)))
        with jax.named_scope("mamba1_gate_out"):
            gated = (y * nn.silu(xz[..., inner:].astype(jnp.float32))
                     ).astype(dtype)
            return _dense(d_model, dtype, "out_proj")(gated)


class JambaBlock(nn.Module):
    """One layer of kinds `KINDS[letter]`; no expert counts (None)."""
    letter: str
    layers: dict                  # kind -> that layer's arguments
    compute_dtype: Any
    eps: float = 1e-6

    @nn.compact
    def __call__(self, x):
        mixer, _ = KINDS[self.letter]
        if mixer == "mamba":
            mix = Mamba1Mixer(**self.layers["mamba"],
                              compute_dtype=self.compute_dtype, eps=self.eps,
                              name="mixer")
        else:
            mix = GroupedQueryAttention(**self.layers["attention"],
                                        compute_dtype=self.compute_dtype,
                                        name="mixer")
        h = x + mix(RMSNorm(self.eps, name="input_norm")(x))
        return h + DenseMLP(**self.layers["dense"],
                            compute_dtype=self.compute_dtype, name="mlp")(
            RMSNorm(self.eps, name="pre_ff_norm")(h)), None


class JambaLM(LingLM):
    """Token ids (B, T) -> float32 logits (B, T, vocabulary held) through
    the tied head; `next_token_loss` as `LingLM`'s, with the empty counts
    table of a stack without experts."""
    kinds = KINDS
    block = JambaBlock
    tied = True


#: positions a chunk of the recurrence: the states entering the chunks are
#: what its backward pass keeps
CHUNK = 128


def build(vocab_size: int, compute_dtype, extra: dict) -> JambaLM:
    """`extra`: the published keys plus the cut in depth
    (`num_hidden_layers`); the kinds follow from the published rule, and a
    `hybrid_override_pattern` that spells them otherwise is an error.
    `seq_len` is the data source's and is not read here."""
    e = dict(extra)
    pattern = pattern_of(e["num_hidden_layers"], e["attn_layer_period"],
                         e["attn_layer_offset"])
    if e.get("hybrid_override_pattern", pattern) != pattern:
        raise ValueError(
            f"hybrid_override_pattern {e['hybrid_override_pattern']!r} is "
            f"not the published rule's {pattern!r}")
    if e["num_experts"] != 1 or not e["tie_word_embeddings"] \
            or e["mamba_proj_bias"] or not e["mamba_conv_bias"] \
            or e["hidden_act"] != "silu" or e["sliding_window"] is not None:
        raise NotImplementedError(
            "routed experts, an untied head, projection biases, a "
            "convolution without bias, another activation or a sliding "
            "window are no part of this stack")
    hidden = e["hidden_size"]
    layers = {
        "mamba": dict(
            inner=e["mamba_expand"] * hidden, state_size=e["mamba_d_state"],
            dt_rank=e["mamba_dt_rank"], conv_kernel=e["mamba_d_conv"],
            chunk_size=CHUNK, time_step=DT_INIT),   # Mamba's published dt
        "attention": dict(
            num_heads=e["num_attention_heads"],
            num_kv_heads=e["num_key_value_heads"],
            head_dim=hidden // e["num_attention_heads"]),
        "dense": dict(intermediate_size=e["intermediate_size"]),
    }
    return JambaLM(
        vocab_size=vocab_size, hidden_size=hidden, pattern=pattern,
        layers=layers, compute_dtype=compute_dtype, eps=e["rms_norm_eps"])
