"""Ling-3.0-flash's language stack (`model_type: bailing_hybrid`) as a
language model the trainer can train: pre-norm residual blocks whose
attention and whose feed-forward both vary by depth.

    x <- x + Attn_i(RMSNorm(x));   x <- x + FFN_i(RMSNorm(x))     eps 1e-6
    Attn_i  latent attention where (i + 1) % layer_group_size == 0, else
            Kimi Delta Attention (KDA): five linear layers to one latent
    FFN_i   dense swiglu where i < first_k_dense_replace, else the experts

    KDA (H heads, d_k = d_v = head_dim; arXiv:2510.26692):
        q, k, v = silu(conv4(u W_q)), silu(conv4(u W_k)), silu(conv4(u W_v))
                  causal depthwise convolutions of 4 taps, zero history
        q, k <- q / |q|, k / |k| a head (eps 1e-6);  q <- q d_k^-0.5
        g = lower_bound * sigmoid(exp(A_log_h) (u W_f + dt_bias))
                  a channel, in (lower_bound, 0): the safe gate
        beta = sigmoid(u W_b)                           a head
        S_t = (I - beta_t k_t k_t^T) Diag(exp(g_t)) S_{t-1} + beta_t k_t v_t^T
        o_t = S_t^T q_t                                 (ops/kda.py)
        y = [RMSNorm_head(o) * sigmoid(u W_g)_h] W_o    one gate a head
        (`KimiDeltaAttention` also takes Kimi Linear's own form, which
        models/solar_open2.py runs: an unbounded softplus gate, low-rank
        gates, one output gate a channel, beta in (0, 2))
    latent  q = u W_q (H x [nope | rope]);  [c | k_r] = u W_kva;
            c <- RMSNorm(c);  [k_nope | v] = c W_kvb;  plain rotary table on
            q's rope part and k_r (shared by the heads); causal softmax of
            q k^T / sqrt(nope + rope);  W_o     (`mistral4.LatentAttention`
            with no query compression; 192-wide keys on 128-wide values)
    experts s = sigmoid(u W_r), float32, over ALL experts; the choice by
            s + bias among the `topk_group` best of `n_group` groups (a
            group's score: its two largest), top-k of those; weights
            scale * s_k / (sum + 1e-20); swiglu experts beside one shared
            expert (`mistral4.ExpertShare`: this chip's share)
    dense   (silu(u W_g) * u W_u) W_d

The kinds are spelled one letter a layer (`pattern_of`): `D` KDA + dense,
`K` KDA + experts, `L` latent + experts, `A` latent + dense; the published
42 layers read `DDKKKLKKKKKL...`.

Departures from the published description are listed under `assumed` in
the benchmark's configuration file (`chipbench/configs/
ling3_flash_ep64.json`): the safe gate's formula, a fixed selection bias,
no document mask, no swiglu clamp (0 in every layer a cut of the first 34
holds), no vision tower and no multi-token-prediction module.

Training only, as models/mistral4.py, whose `RMSNorm`, `Head`, chunked
loss, latent attention and whole expert share this file builds on.
"""

from __future__ import annotations

import functools
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from distributed_vgg_f_tpu.models.mistral4 import (
    ExpertShare, Head, LatentAttention, RMSNorm, _dense,
    chunked_next_token_loss)
from distributed_vgg_f_tpu.models.nemotron_h import (
    _a_log, _log_uniform_dt_bias)
from distributed_vgg_f_tpu.ops import kda, short_conv

#: a letter of the pattern -> (attention, feed-forward)
KINDS = {"D": ("kda", "dense"), "K": ("kda", "experts"),
         "L": ("latent", "experts"), "A": ("latent", "dense")}


#: `dt_bias` starts as the inverse softplus of log-uniform [0.001, 0.1],
#: floored at 1e-4, and `A_log` as the log of uniform [1, 16]: Kimi Linear's
#: initialisers, which are models/nemotron_h.py's Mamba-2 ones
DT_INIT = (0.001, 0.1, 0.0001)


def pattern_of(num_hidden_layers: int, layer_group_size: int,
               first_k_dense_replace: int) -> str:
    """The published rule, one letter of `KINDS` a layer."""
    letters = {v: k for k, v in KINDS.items()}
    return "".join(letters[
        "latent" if (i + 1) % layer_group_size == 0 else "kda",
        "dense" if i < first_k_dense_replace else "experts"]
        for i in range(num_hidden_layers))


class ConvTaps(nn.Module):
    """The kernel of a causal depthwise convolution over the sequence,
    (taps, channels): `kernel[-1]` is on the position itself."""
    width: int
    channels: int

    @nn.compact
    def __call__(self):
        return self.param(
            "kernel", nn.initializers.variance_scaling(
                1.0, "fan_in", "normal", in_axis=0, out_axis=1),
            (self.width, self.channels), jnp.float32)


# The elementwise stretches of the layer are made again in the backward
# pass from what enters them (`jax.checkpoint`; the short convolutions' are
# ops/short_conv.py's): kept, their float32 intermediates of tokens x 4096 (a
# quarter of a GiB each in the cell, a dozen of them) would stand beside the
# recurrence's own.

@functools.partial(jax.checkpoint, static_argnums=(3,))
def _safe_gate(f, a_log, dt_bias, lower_bound: float):
    """g = lower_bound * sigmoid(exp(A_log_h) (f + dt_bias)), float32,
    (b, t, heads, channels / heads)."""
    b, t, channels = f.shape
    heads = a_log.shape[0]
    return lower_bound * jax.nn.sigmoid(
        jnp.exp(a_log)[:, None] * (f.astype(jnp.float32) + dt_bias).reshape(
            b, t, heads, channels // heads))


@jax.checkpoint
def _kimi_gate(f, a_log, dt_bias):
    """g = -exp(A_log_h) softplus(f + dt_bias), float32, (b, t, heads,
    channels / heads): Kimi Linear's own gate, any value <= 0."""
    b, t, channels = f.shape
    heads = a_log.shape[0]
    return -jnp.exp(a_log)[:, None] * jax.nn.softplus(
        (f.astype(jnp.float32) + dt_bias).reshape(
            b, t, heads, channels // heads))


@functools.partial(jax.checkpoint, static_argnums=(3, 4))
def _norm_gate(o, scale, gate, eps: float, dtype):
    """RMSNorm over each head's channels of o (b, t, heads, dv) float32,
    times the learned scale and the gate: the head's (b, t, heads) or the
    channel's (b, t, heads, dv)."""
    o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + eps)
    if gate.ndim < o.ndim:
        return (o * scale * gate[..., None]).astype(dtype)
    return (o * scale * gate).astype(dtype)


class Scale(nn.Module):
    """A norm's learned scale, (channels,), ones at the start."""
    channels: int

    @nn.compact
    def __call__(self):
        return self.param("scale", nn.initializers.ones, (self.channels,),
                          jnp.float32)


class KimiDeltaAttention(nn.Module):
    """The delta-rule layer of the module docstring, in the two forms the
    published configurations spell: Ling's (`lower_bound` the safe gate's
    bound; full `f_proj`; one output gate a head) and Kimi Linear's as
    Solar-Open2 takes it (`lower_bound` None: g = -exp(A_log_h)
    softplus(u F_a F_b + dt_bias), unbounded below; `gate_rank`: the decay
    and output gates through a rank of that many channels, `f_a_proj` then
    `f_b_proj`, `g_a_proj` then `g_b_proj`; `channel_gate`: one output gate
    a channel; `beta_scale` 2: beta in (0, 2), negative eigenvalues of the
    transition)."""
    num_heads: int
    head_dim: int
    conv_kernel: int
    lower_bound: float | None
    compute_dtype: Any
    eps: float = 1e-6
    chunk_size: int = 64
    gate_rank: int | None = None
    channel_gate: bool = False
    beta_scale: float = 1.0

    def _projection(self, u, features: int, name: str):
        """u -> features, whole or through `gate_rank`."""
        dtype = self.compute_dtype
        if self.gate_rank is None:
            return _dense(features, dtype, f"{name}_proj")(u)
        return _dense(features, dtype, f"{name}_b_proj")(
            _dense(self.gate_rank, dtype, f"{name}_a_proj")(u))

    @nn.compact
    def __call__(self, u):
        b, t, d_model = u.shape
        h, dk, dtype = self.num_heads, self.head_dim, self.compute_dtype
        inner = h * dk
        with jax.named_scope("kda_qkv"):
            q, k, v = (_dense(inner, dtype, f"{name}_proj")(u)
                       for name in "qkv")
        with jax.named_scope("kda_conv"):
            taps = {name: ConvTaps(self.conv_kernel, inner,
                                   name=f"{name}_conv")() for name in "qkv"}
            # whether the convolutions took the Pallas kernels (1) or the
            # XLA form (0), for a caller that asks (`mutable=["counters"]`)
            self.sow("counters", "kda_conv_kernel", int(
                short_conv.takes_kernels(q.shape, taps["q"].shape, h)))
            q, k, v = (short_conv.conv_silu_heads(x, taps[name], h, scale)
                       for name, x, scale in (("q", q, dk ** -0.5),
                                              ("k", k, 1.0), ("v", v, None)))
        with jax.named_scope("kda_gates"):
            a_log = self.param("A_log", _a_log, (h,), jnp.float32)
            dt_bias = self.param("dt_bias", _log_uniform_dt_bias(*DT_INIT),
                                 (inner,), jnp.float32)
            f = self._projection(u, inner, "f")
            bounded = self.lower_bound is not None
            g = _safe_gate(f, a_log, dt_bias, float(self.lower_bound)) \
                if bounded else _kimi_gate(f, a_log, dt_bias)
            beta = jax.nn.sigmoid(
                _dense(h, dtype, "b_proj")(u).astype(jnp.float32))
            if self.beta_scale != 1.0:
                beta = self.beta_scale * beta
            gate = jax.nn.sigmoid(self._projection(
                u, inner if self.channel_gate else h, "g").astype(jnp.float32))
            if self.channel_gate:
                gate = gate.reshape(b, t, h, dk)
        with jax.named_scope("kda_core"):
            o = kda.kda(q, k, v, g, beta, chunk=self.chunk_size,
                        bounded=bounded)
            # receipts for a caller that asks (`mutable=["counters"]`): a
            # gate stuck at its bound shows as exp(lower_bound); and whether
            # the recurrence took the Pallas kernels (1) or the XLA form (0)
            self.sow("counters", "kda_chunks", b * -(-t // self.chunk_size))
            self.sow("counters", "kda_kernel", int(kda.takes_kernels(
                k.shape, v.shape, self.chunk_size)))
            self.sow("counters", "kda_decay_min", kda.smallest_decay(g))
            # and where the form in use reaches past Ling's: the share of
            # gate entries the bounded form would have clamped, and the
            # largest beta (over 1: a negative eigenvalue)
            if not bounded:
                self.sow("counters", "kda_gate_below_bound",
                         kda.share_below_bound(g))
            if self.beta_scale != 1.0:
                self.sow("counters", "kda_beta_max", jnp.max(beta))
        with jax.named_scope("kda_out"):
            # one norm over each head's channels, one scale for all heads
            scale = Scale(dk, name="o_norm")()
            return _dense(d_model, dtype, "o_proj")(_norm_gate(
                o, scale, gate, self.eps, dtype).reshape(b, t, inner))


class DenseMLP(nn.Module):
    intermediate_size: int
    compute_dtype: Any

    @nn.compact
    def __call__(self, u):
        dtype = self.compute_dtype
        with jax.named_scope("mlp_dense"):
            mid = nn.silu(_dense(self.intermediate_size, dtype,
                                 "gate_proj")(u)) \
                * _dense(self.intermediate_size, dtype, "up_proj")(u)
            return _dense(u.shape[-1], dtype, "down_proj")(mid)


class LingBlock(nn.Module):
    """One layer of kinds `KINDS[letter]`; for an expert layer also its
    counts, as `mistral4.Block` gives them, else None."""
    letter: str
    layers: dict                  # kind -> that layer's arguments
    compute_dtype: Any
    eps: float = 1e-6

    @nn.compact
    def __call__(self, x):
        attention, ffn = KINDS[self.letter]
        attend = {"kda": KimiDeltaAttention, "latent": LatentAttention
                  }[attention]
        h = x + attend(**self.layers[attention],
                       compute_dtype=self.compute_dtype, eps=self.eps,
                       name="attn")(RMSNorm(self.eps, name="input_norm")(x))
        u = RMSNorm(self.eps, name="post_attention_norm")(h)
        if ffn == "dense":
            return h + DenseMLP(**self.layers["dense"],
                                compute_dtype=self.compute_dtype,
                                name="mlp")(u), None
        y, counts = ExpertShare(**self.layers["experts"],
                                compute_dtype=self.compute_dtype,
                                name="moe")(u)
        return h + y, counts


class LingLM(nn.Module):
    """Token ids (B, T) -> float32 logits (B, T, vocabulary held);
    `next_token_loss` is what the train step calls. `kinds` (the letters)
    and `block` (a layer) are the class's: models/solar_open2.py gives its
    own."""
    vocab_size: int
    hidden_size: int
    pattern: str                   # one letter of `KINDS` a layer
    layers: dict                   # kind -> that layer's arguments
    compute_dtype: Any = jnp.bfloat16
    eps: float = 1e-6
    loss_chunk_rows: int = 1024

    kinds = KINDS
    block = LingBlock

    @property
    def expert_layers(self) -> tuple:
        """The layers the rows of `hidden`'s counts stand for."""
        return tuple(i for i, letter in enumerate(self.pattern)
                     if self.kinds[letter][1] == "experts")

    def setup(self):
        self.embed = nn.Embed(self.vocab_size, self.hidden_size,
                              dtype=self.compute_dtype,
                              param_dtype=jnp.float32, name="embed")
        # recomputation per block, as `Mistral4LM`
        self.blocks = [nn.remat(self.block)(
            letter, self.layers, self.compute_dtype, self.eps,
            name=f"layer_{i}") for i, letter in enumerate(self.pattern)]
        self.norm = RMSNorm(self.eps, name="norm")
        self.lm_head = Head(self.hidden_size, self.vocab_size,
                            self.compute_dtype, name="lm_head")

    def hidden(self, tokens):
        """Final-norm hidden states (B, T, hidden) and the expert layers'
        counts (expert layers, experts_held + 1)."""
        with jax.named_scope("embed_tokens"):
            x = self.embed(tokens)
        counts = []
        for block in self.blocks:
            x, count = block(x)
            if count is not None:
                counts.append(count)
        return self.norm(x), jnp.stack(counts)

    def __call__(self, tokens, *, train: bool = False):
        h, _ = self.hidden(tokens)
        with jax.named_scope("lm_head"):
            return self.lm_head(h)

    def next_token_loss(self, tokens, targets):
        h, counts = self.hidden(tokens)
        return chunked_next_token_loss(
            self.lm_head.kernel, h, targets, self.loss_chunk_rows,
            self.compute_dtype), counts


def build(vocab_size: int, compute_dtype, extra: dict) -> LingLM:
    """`extra`: the published keys plus the share (`first_expert`,
    `experts_held`, default all; `n_routed_experts` the router's width,
    the source's `num_experts`) and the cut in depth
    (`num_hidden_layers`, `first_k_dense_replace`); the kinds follow from
    the published rule, and a `hybrid_override_pattern` that spells them
    otherwise is an error. `seq_len` is the data source's and is not read
    here."""
    e = dict(extra)
    pattern = pattern_of(e["num_hidden_layers"], e["layer_group_size"],
                         e["first_k_dense_replace"])
    if e.get("hybrid_override_pattern", pattern) != pattern:
        raise ValueError(
            f"hybrid_override_pattern {e['hybrid_override_pattern']!r} is "
            f"not the published rule's {pattern!r}")
    layers = {
        "kda": dict(
            num_heads=e["num_attention_heads"], head_dim=e["head_dim"],
            conv_kernel=e["short_conv_kernel_size"],
            lower_bound=e["kda_lower_bound"]),
        "latent": dict(
            num_heads=e["num_attention_heads"], q_lora_rank=e["q_lora_rank"],
            kv_lora_rank=e["kv_lora_rank"],
            qk_nope_head_dim=e["qk_nope_head_dim"],
            qk_rope_head_dim=e["qk_rope_head_dim"],
            v_head_dim=e["v_head_dim"],
            rope={"rope_theta": e["rope_theta"]}),
        "dense": dict(intermediate_size=e["intermediate_size"]),
        "experts": dict(
            n_routed_experts=e["n_routed_experts"],
            num_experts_per_tok=e["num_experts_per_tok"],
            moe_intermediate_size=e["moe_intermediate_size"],
            n_shared_experts=1,
            shared_intermediate_size=e["moe_shared_expert_intermediate_size"],
            routed_scaling_factor=e["routed_scaling_factor"],
            n_group=e["n_group"], topk_group=e["topk_group"],
            first_expert=e.get("first_expert", 0),
            experts_held=e.get("experts_held", e["n_routed_experts"]),
            scoring="sigmoid", expert="swiglu"),
    }
    return LingLM(
        vocab_size=vocab_size, hidden_size=e["hidden_size"], pattern=pattern,
        layers=layers, compute_dtype=compute_dtype,
        eps=e.get("rms_norm_eps", 1e-6))
