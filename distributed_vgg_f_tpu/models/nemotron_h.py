"""NVIDIA-Nemotron-3-Nano's hybrid stack (`model_type: nemotron_h`) as a
language model the trainer can train: layers of three kinds, one mixer
each, in the order the config's `hybrid_override_pattern` spells (`M` a
Mamba-2 state-space mixer, `E` a mixture of routed relu^2 experts beside a
shared one, `*` grouped-query attention).

Equations (eps 1e-5; no bias but the convolution's):

    layer i of kind t_i:   x <- x + Mixer_{t_i}(RMSNorm(x))
    after the last layer: RMSNorm, untied head, mean next-token cross-entropy

    M   [z | xBC | dt] = u W_in       widths d_inner | d_inner + 2 g n | heads
        xBC = silu(causal depthwise conv over the sequence, kernel 4, + bias)
        [x | B | C] = xBC    x: heads x head_dim;  B, C: g groups x n, a
                             group serving heads / g consecutive heads
        dt = softplus(dt + dt_bias);  A = -exp(A_log)     one scalar a head
        h_t = exp(dt_t A) h_{t-1} + dt_t x_t (x) B_t      h: head x p x n
        y_t = h_t C_t + D x_t          (ops/ssd.py: Pallas kernels where
                                       the sizes are whole tiles of a TPU)
        y = RMSNorm_grouped(y * silu(z)) * w    groups of d_inner / g channels
        out = y W_out
    E   s = sigmoid(u W_r), float32, over ALL experts
        choose the top-k of (s + b), b the selection bias (no gradient)
        w_k = scale * s_k / (sum of the chosen s + 1e-20)
        out = sum_k w_k E_k(u) + S(u);  E(u) = relu(u W_up)^2 W_down, S the
        same at the shared width    (models/mistral4.py `ExpertShare`: the
        share of the experts this chip holds, as that file sets out)
    *   q = u W_q (heads x d);  k, v = u W_k, u W_v (kv heads x d each)
        query head j reads key head j // (heads / kv heads)
        causal softmax(q k^T / sqrt(d)) v; concat; W_o

The state is zero at the start of every sequence and runs over the whole
packed sequence. The decay, its running sums and the state are float32; the
products take operands in the compute dtype and accumulate in float32.

Departures from the published modelling code, each listed under `assumed`
in the benchmark's configuration file: no rotary embedding in the attention
layers (the published `nemotron_h` code applies none: the Mamba layers
carry position; the config's `rope_theta` and `partial_rotary_factor` are
not read by it); the selection bias is a seeded constant (the published
recipe moves it by a balancing rule outside the gradient); `d_inner` is
`mamba_num_heads x mamba_head_dim` (the config's `expand` would give
another width and is not what the modelling code uses); no document mask;
`n_group = topk_group = 1`, so the router has no group limit.

Training only, as models/mistral4.py, whose `RMSNorm`, `Head`, chunked loss
and whole expert share this file builds on.
"""

from __future__ import annotations

import math
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from distributed_vgg_f_tpu.models.mistral4 import (
    ExpertShare, Head, RMSNorm, _dense, chunked_next_token_loss)
from distributed_vgg_f_tpu.ops import ssd

#: the letters of `hybrid_override_pattern`
KINDS = {"M": "mamba", "E": "experts", "*": "attention"}


def _log_uniform_dt_bias(low: float, high: float, floor: float):
    """`dt_bias` such that softplus(dt_bias) is log-uniform over
    [low, high], not under `floor` (the published initialiser)."""
    def init(key, shape, dtype=jnp.float32):
        dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32)
                     * (math.log(high) - math.log(low)) + math.log(low))
        dt = jnp.maximum(dt, floor)
        return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)
    return init


def _a_log(key, shape, dtype=jnp.float32):
    """log of uniform [1, 16]: A = -exp(A_log) in [-16, -1]."""
    return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0)
                   ).astype(dtype)


class CausalConv(nn.Module):
    """Depthwise convolution over the sequence, `kernel[-1]` on the
    position itself and `kernel[0]` on the one `len(kernel) - 1` before;
    zeros before the sequence's start."""
    width: int

    @nn.compact
    def __call__(self, x):
        kernel = self.param(
            "kernel", nn.initializers.variance_scaling(
                1.0, "fan_in", "normal", in_axis=0, out_axis=1),
            (self.width, x.shape[-1]), jnp.float32)
        bias = self.param("bias", nn.initializers.zeros, (x.shape[-1],),
                          jnp.float32)
        t = x.shape[1]
        padded = jnp.pad(x.astype(jnp.float32),
                         ((0, 0), (self.width - 1, 0), (0, 0)))
        return bias + sum(padded[:, k:k + t] * kernel[k]
                          for k in range(self.width))


class GroupedRMSNorm(nn.Module):
    """RMSNorm over each of `groups` equal runs of the channels, then one
    learned scale a channel; float32 in, `dtype` out."""
    groups: int
    dtype: Any
    eps: float = 1e-5

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],),
                           jnp.float32)
        runs = x.reshape(*x.shape[:-1], self.groups, -1)
        runs = runs * jax.lax.rsqrt(
            jnp.mean(runs * runs, axis=-1, keepdims=True) + self.eps)
        return (runs.reshape(x.shape) * scale).astype(self.dtype)


class Mamba2Mixer(nn.Module):
    num_heads: int
    head_dim: int
    n_groups: int
    state_size: int
    conv_kernel: int
    chunk_size: int
    time_step: tuple               # (min, max, floor) of the initial dt
    compute_dtype: Any
    eps: float = 1e-5

    @nn.compact
    def __call__(self, u):
        b, t, d_model = u.shape
        h, p, g, n = (self.num_heads, self.head_dim, self.n_groups,
                      self.state_size)
        inner, dtype = h * p, self.compute_dtype
        with jax.named_scope("ssm_in"):
            zxbcdt = _dense(2 * inner + 2 * g * n + h, dtype, "in_proj")(u)
            z = zxbcdt[..., :inner]
            dt = zxbcdt[..., -h:]
        with jax.named_scope("ssm_conv"):
            xbc = nn.silu(CausalConv(self.conv_kernel, name="conv1d")(
                zxbcdt[..., inner:-h])).astype(dtype)
        dt_bias = self.param("dt_bias", _log_uniform_dt_bias(*self.time_step),
                             (h,), jnp.float32)
        a_log = self.param("A_log", _a_log, (h,), jnp.float32)
        skip = self.param("D", nn.initializers.ones, (h,), jnp.float32)
        with jax.named_scope("ssm_scan"):
            dt = jax.nn.softplus(dt.astype(jnp.float32) + dt_bias)
            a = -jnp.exp(a_log)
            y = ssd.ssd(xbc[..., :inner].reshape(b, t, h, p), dt, a,
                        xbc[..., inner:inner + g * n].reshape(b, t, g, n),
                        xbc[..., inner + g * n:].reshape(b, t, g, n), skip,
                        chunk=self.chunk_size)
            # receipts for a caller that asks (`mutable=["counters"]`): a
            # state that has died or never decays is the first thing to
            # go wrong in a run; and whether the recurrence took the Pallas
            # kernels (1) or the XLA form (0)
            self.sow("counters", "ssm_chunks",
                     b * -(-t // self.chunk_size))
            self.sow("counters", "ssm_kernel", int(ssd.takes_kernels(
                (b, t, h, p), (b, t, g, n), self.chunk_size)))
            self.sow("counters", "ssm_decay_min", ssd.smallest_decay(dt, a))
        with jax.named_scope("ssm_gate_out"):
            gated = y.reshape(b, t, inner) * nn.silu(z.astype(jnp.float32))
            return _dense(d_model, dtype, "out_proj")(
                GroupedRMSNorm(g, dtype, self.eps, name="norm")(gated))


class GroupedQueryAttention(nn.Module):
    """Causal attention over the whole sequence, `num_heads` query heads on
    `num_kv_heads` key/value heads, no position embedding. The core is the
    Pallas kernel of ops/flash_attention.py where that can run (a TPU, or
    the Pallas interpreter that tests switch on) and explicit scores
    elsewhere, as `mistral4.LatentAttention`. With `output_gate` (a
    config's `use_gqa_gate`) the heads' output is gated element by element
    before the output projection, ctx * sigmoid(u W_gate) (Qiu et al.,
    arXiv:2505.06708)."""
    num_heads: int
    num_kv_heads: int
    head_dim: int
    compute_dtype: Any
    output_gate: bool = False

    @nn.compact
    def __call__(self, u):
        from distributed_vgg_f_tpu.ops import flash_attention
        b, t, d_model = u.shape
        h, kv, d, dtype = (self.num_heads, self.num_kv_heads, self.head_dim,
                           self.compute_dtype)
        with jax.named_scope("gqa_qkv"):
            q = _dense(h * d, dtype, "q_proj")(u).reshape(b, t, h, d)
            k = _dense(kv * d, dtype, "k_proj")(u).reshape(b, t, kv, d)
            v = _dense(kv * d, dtype, "v_proj")(u).reshape(b, t, kv, d)
        with jax.named_scope("gqa_core"):
            if jax.default_backend() == "tpu" or flash_attention.INTERPRET:
                # blocks of 1024 for the reason mistral4.LatentAttention
                # gives (PERF.md, PR 28)
                block = next((n for n in (1024, 512, 256) if t % n == 0),
                             None)
                ctx = flash_attention.flash_self_attention(
                    q, k, v, causal=True, block_q=block, block_k=block)
            else:
                grouped = q.reshape(b, t, kv, h // kv, d)
                scores = jnp.einsum("bqgrd,bkgd->bgrqk", grouped, k,
                                    preferred_element_type=jnp.float32) \
                    * d ** -0.5
                scores = jnp.where(jnp.tril(jnp.ones((t, t), bool)), scores,
                                   -jnp.inf)
                probs = jax.nn.softmax(scores, axis=-1)
                ctx = jnp.einsum("bgrqk,bkgd->bqgrd", probs.astype(dtype), v)
        if self.output_gate:
            with jax.named_scope("gqa_gate"):
                gate = jax.nn.sigmoid(_dense(h * d, dtype, "gate_proj")(
                    u).astype(jnp.float32))
                ctx = (ctx.reshape(b, t, h * d).astype(jnp.float32)
                       * gate).astype(dtype)
        with jax.named_scope("gqa_out"):
            return _dense(d_model, dtype, "o_proj")(ctx.reshape(b, t, h * d))


class HybridBlock(nn.Module):
    """x + Mixer(RMSNorm(x)); for an expert layer also its counts, as
    `mistral4.Block` gives them, else None."""
    kind: str
    mixer: dict
    compute_dtype: Any
    eps: float = 1e-5

    @nn.compact
    def __call__(self, x):
        u = RMSNorm(self.eps, name="norm")(x)
        layer = {"mamba": Mamba2Mixer, "experts": ExpertShare,
                 "attention": GroupedQueryAttention}[self.kind]
        out = layer(**self.mixer, compute_dtype=self.compute_dtype,
                    name="mixer")(u)
        if self.kind == "experts":
            out, counts = out
            return x + out, counts
        return x + out, None


class NemotronHLM(nn.Module):
    """Token ids (B, T) -> float32 logits (B, T, vocabulary held);
    `next_token_loss` is what the train step calls."""
    vocab_size: int
    hidden_size: int
    pattern: str                   # one letter of `KINDS` a layer
    mixers: dict                   # kind -> that mixer's arguments
    compute_dtype: Any = jnp.bfloat16
    eps: float = 1e-5
    loss_chunk_rows: int = 1024

    @property
    def expert_layers(self) -> tuple:
        """The layers the rows of `hidden`'s counts stand for."""
        return tuple(i for i, letter in enumerate(self.pattern)
                     if letter == "E")

    def setup(self):
        self.embed = nn.Embed(self.vocab_size, self.hidden_size,
                              dtype=self.compute_dtype,
                              param_dtype=jnp.float32, name="embed")
        # recomputation per block, as `Mistral4LM`
        self.blocks = [nn.remat(HybridBlock)(
            KINDS[letter], self.mixers[KINDS[letter]], self.compute_dtype,
            self.eps, name=f"layer_{i}")
            for i, letter in enumerate(self.pattern)]
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


def build(vocab_size: int, compute_dtype, extra: dict) -> NemotronHLM:
    """`extra`: the published keys plus the share (`first_expert`,
    `experts_held`, default all) and the pattern as it is run; `seq_len`
    is the data source's and is not read here."""
    e = dict(extra)
    eps = e.get("layer_norm_epsilon", 1e-5)
    mixers = {
        "mamba": dict(
            num_heads=e["mamba_num_heads"], head_dim=e["mamba_head_dim"],
            n_groups=e["n_groups"], state_size=e["ssm_state_size"],
            conv_kernel=e["conv_kernel"], chunk_size=e["chunk_size"],
            time_step=(e["time_step_min"], e["time_step_max"],
                       e["time_step_floor"]), eps=eps),
        "experts": dict(
            n_routed_experts=e["n_routed_experts"],
            num_experts_per_tok=e["num_experts_per_tok"],
            moe_intermediate_size=e["moe_intermediate_size"],
            n_shared_experts=e["n_shared_experts"],
            shared_intermediate_size=e["moe_shared_expert_intermediate_size"],
            routed_scaling_factor=e["routed_scaling_factor"],
            first_expert=e.get("first_expert", 0),
            experts_held=e.get("experts_held", e["n_routed_experts"]),
            scoring="sigmoid", expert="relu2"),
        "attention": dict(
            num_heads=e["num_attention_heads"],
            num_kv_heads=e["num_key_value_heads"], head_dim=e["head_dim"]),
    }
    return NemotronHLM(
        vocab_size=vocab_size, hidden_size=e["hidden_size"],
        pattern=e["hybrid_override_pattern"], mixers=mixers,
        compute_dtype=compute_dtype, eps=eps)
