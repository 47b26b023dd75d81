"""Solar-Open2-250B's stack (`model_type: solar_open2`) as a language model
the trainer can train: pre-norm residual blocks whose attention varies by
depth and whose feed-forward is a mixture of experts in every layer.

    x <- x + Attn_i(RMSNorm(x));   x <- x + MoE(RMSNorm(x))      eps 1e-5
    Attn_i  gated grouped-query attention where i is in `gqa_layers`
            (0, 4, 8, ...), else Kimi Delta Attention: three linear layers
            to one softmax layer

    KDA (H = 64 heads, d = 128; `linear_attn_config`; arXiv:2510.26692):
        q, k, v = silu(conv4(u W_{q,k,v}))    causal depthwise, zero history
        q, k <- q / |q|, k / |k| a head (eps 1e-6);  q <- q d^-0.5
        g = -exp(A_log_h) softplus(u F_a F_b + dt_bias)   a channel, <= 0,
                  unbounded below: Kimi Linear's own gate (no
                  `kda_lower_bound`); F_a hidden x d, F_b d x H d
                  (`kda_use_full_proj: false`)
        beta = 2 sigmoid(u W_b)             a head, in (0, 2)
                  (`kda_allow_neg_eigval`: negative eigenvalues)
        S_t = (I - beta_t k_t k_t^T) Diag(exp(g_t)) S_{t-1} + beta_t k_t v_t^T
        o_t = S_t^T q_t                     (ops/kda.py, the unbounded form)
        y = [RMSNorm_head(o) * sigmoid(u G_a G_b)] W_o   a gate a channel,
                  one norm scale of d for all heads
    GQA     64 query heads on 8 key/value heads of 128, no rotary embedding
            (`use_rope: false`): causal softmax(q k^T / sqrt(d)) v, times
            sigmoid(u W_gate) element by element (`use_gqa_gate`), W_o
            (`nemotron_h.GroupedQueryAttention`)
    experts s = sigmoid(u W_r), float32, over ALL experts; the top-k of
            s + bias, no group limit; weights scale * s_k / (sum + 1e-20)
            (`norm_topk_prob`); swiglu experts beside one shared expert of
            the same width, added unweighted (`mistral4.ExpertShare`: this
            chip's share)

The kinds are spelled one letter a layer (`pattern_of`): `G` gated GQA,
`K` KDA, each with experts; the published 48 layers read `GKKKGKKK...`.
Departures from the published description are listed under `assumed` in
the benchmark's configuration file (`chipbench/configs/
solar_open2_250b_ep40.json`). The block, the language model and the loss
are models/ling3.py's (`LingLM`), with this file's letters and block.
"""

from __future__ import annotations

from typing import Any

import flax.linen as nn

from distributed_vgg_f_tpu.models.ling3 import KimiDeltaAttention, LingLM
from distributed_vgg_f_tpu.models.mistral4 import ExpertShare, RMSNorm
from distributed_vgg_f_tpu.models.nemotron_h import GroupedQueryAttention

#: a letter of the pattern -> (attention, feed-forward)
KINDS = {"G": ("gqa", "experts"), "K": ("kda", "experts")}


def pattern_of(num_hidden_layers: int, gqa_layers) -> str:
    """The published rule, one letter of `KINDS` a layer."""
    return "".join("G" if i in gqa_layers else "K"
                   for i in range(num_hidden_layers))


class SolarBlock(nn.Module):
    """One layer of kinds `KINDS[letter]`, and its expert share's counts."""
    letter: str
    layers: dict                  # kind -> that layer's arguments
    compute_dtype: Any
    eps: float = 1e-5

    @nn.compact
    def __call__(self, x):
        attention, _ = KINDS[self.letter]
        if attention == "kda":
            attend = KimiDeltaAttention(
                **self.layers["kda"], compute_dtype=self.compute_dtype,
                eps=self.eps, name="attn")
        else:
            attend = GroupedQueryAttention(
                **self.layers["gqa"], compute_dtype=self.compute_dtype,
                name="attn")
        h = x + attend(RMSNorm(self.eps, name="input_norm")(x))
        y, counts = ExpertShare(**self.layers["experts"],
                                compute_dtype=self.compute_dtype,
                                name="moe")(
            RMSNorm(self.eps, name="post_attention_norm")(h))
        return h + y, counts


class SolarOpen2LM(LingLM):
    """Token ids (B, T) -> float32 logits (B, T, vocabulary held);
    `next_token_loss` and `hidden`'s counts as `LingLM`'s, every layer an
    expert layer."""
    kinds = KINDS
    block = SolarBlock


def build(vocab_size: int, compute_dtype, extra: dict) -> SolarOpen2LM:
    """`extra`: the published keys plus the share (`first_expert`,
    `experts_held`, default all; `n_routed_experts` the router's width) and
    the cut in depth (`num_hidden_layers`); the kinds follow from the
    published `gqa_layers`, and a `hybrid_override_pattern` that spells
    them otherwise is an error. `seq_len` is the data source's and is not
    read here."""
    e = dict(extra)
    pattern = pattern_of(e["num_hidden_layers"], e["gqa_layers"])
    if e.get("hybrid_override_pattern", pattern) != pattern:
        raise ValueError(
            f"hybrid_override_pattern {e['hybrid_override_pattern']!r} is "
            f"not the published rule's {pattern!r}")
    if e["first_k_dense_replace"] or e["use_rope"] \
            or not e["norm_topk_prob"]:
        raise NotImplementedError(
            "a leading dense layer, rotary attention or unnormalised expert "
            "weights are no part of this stack")
    linear = e["linear_attn_config"]
    layers = {
        "kda": dict(
            num_heads=linear["num_heads"], head_dim=linear["head_dim"],
            conv_kernel=linear["short_conv_kernel_size"],
            lower_bound=e.get("kda_lower_bound"),
            gate_rank=None if e["kda_use_full_proj"] else linear["head_dim"],
            channel_gate=True,
            beta_scale=2.0 if e["kda_allow_neg_eigval"] else 1.0),
        "gqa": dict(
            num_heads=e["num_attention_heads"],
            num_kv_heads=e["num_key_value_heads"], head_dim=e["head_dim"],
            output_gate=e["use_gqa_gate"]),
        "experts": dict(
            n_routed_experts=e["n_routed_experts"],
            num_experts_per_tok=e["num_experts_per_tok"],
            moe_intermediate_size=e["moe_intermediate_size"],
            n_shared_experts=e["n_shared_experts"],
            routed_scaling_factor=e["routed_scaling_factor"],
            first_expert=e.get("first_expert", 0),
            experts_held=e.get("experts_held", e["n_routed_experts"]),
            scoring="sigmoid", expert="swiglu"),
    }
    return SolarOpen2LM(
        vocab_size=vocab_size, hidden_size=e["hidden_size"], pattern=pattern,
        layers=layers, compute_dtype=compute_dtype, eps=e["rms_norm_eps"])
