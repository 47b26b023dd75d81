"""`chipbench/lm_counts.py`: pinned to a hand count at the published
widths, and at a tiny size to what `counts.jaxpr_ops` finds in the loss
gradient of a sparse reference (each expert on the tokens routed to it,
gathered; each query row against the keys it may see)."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import counts, lm_counts
from chipbench.reference import mistral4 as ref
from chipbench.reference.ops import Ops
from distributed_vgg_f_tpu.config import MISTRAL_SMALL4_PUBLISHED, get_config
from distributed_vgg_f_tpu.models.registry import build_model

PUBLISHED = dict(MISTRAL_SMALL4_PUBLISHED)


def test_the_cell_s_step_by_hand():
    """A token of a layer: projections 2 x 28,049,408; the causal core
    4 x 4096 x 32 x 128 / 2; router 2 x 4096 x 128; shared expert
    6 x 4096 x 2048; routed experts a quarter of that (1024 of 4096
    assignments held). 153.6 MFLOP a layer, 134.2 the head, three passes:
    2.246 GFLOP a token, 9.2 TFLOP a step of 4096."""
    layer = 2 * 28_049_408 + 33_554_432 + 1_048_576 + 50_331_648 \
        + 12_582_912
    assert layer == 153_616_384
    token = 3 * (4 * layer + 2 * 4096 * 16384)
    assert token == 2_246_049_792
    ops = lm_counts.step_ops(arch=PUBLISHED, layers=4, vocab_rows=16384,
                             experts_held=8, seq_len=4096, rows=1,
                             assignments_held=[1024] * 4)
    assert sum(op["flops"] for op in ops) == 4096 * token
    # every product three times; 14 products a layer and the head
    assert len(ops) == 3 * (4 * 14 + 1)
    grouped = [op for op in ops if op["kind"] == "grouped"]
    assert len(grouped) == 3 * 4 * 3
    assert grouped[0]["elements"] == 8 * 4096 * 2048 + 1024 * (4096 + 2048)


@pytest.mark.parametrize("held", [[0, 0, 0, 0], [4096 * 4] * 4])
def test_routed_work_follows_the_assignments_held(held):
    """None held: only the experts' weights are left to read. All held
    (every token to four held experts): four times the shared expert."""
    ops = lm_counts.expert_ops(PUBLISHED, 8, held[0])
    assert sum(op["flops"] for op in ops) == held[0] * 6 * 4096 * 2048
    assert all(op["elements"] >= 8 * 4096 * 2048 for op in ops)


def _sparse_loss(params, tokens, arch, share, chosen_by_layer):
    """The reference's loss with each expert on its own tokens only
    (`chosen_by_layer`: every layer's concrete top-k choices) and each
    query row against the keys up to its own position."""
    ops, eps = Ops("float32"), arch["rms_norm_eps"]
    heads, dn, dr, dv = (arch["num_attention_heads"],
                         arch["qk_nope_head_dim"], arch["qk_rope_head_dim"],
                         arch["v_head_dim"])
    first, held = share
    rope = arch["rope_parameters"]
    freqs = ref.inv_freq(rope, dr)
    m = 0.1 * rope["mscale_all_dim"] * math.log(rope["factor"]) + 1.0
    scale = (dn + dr) ** -0.5 * m * m
    total = 0.0
    for row, chosen_layers in zip(tokens, chosen_by_layer):
        x = params["embed"]["embedding"][row[:-1]]
        seq = x.shape[0]
        for name, chosen in zip(sorted(k for k in params
                                       if k.startswith("layer_")),
                                chosen_layers):
            p = params[name]
            a, u = p["attn"], ref.rms(x, p["input_norm"]["scale"], eps)
            q = ops.dense(ref.rms(ops.dense(u, a["q_a_proj"]["kernel"]),
                                  a["q_a_norm"]["scale"], eps),
                          a["q_b_proj"]["kernel"]).reshape(seq, heads, -1)
            q = jnp.concatenate([q[..., :dn], ref.rope_pairs(q[..., dn:],
                                                             freqs)], -1)
            kv_a = ops.dense(u, a["kv_a_proj"]["kernel"])
            kv = ops.dense(ref.rms(kv_a[:, :arch["kv_lora_rank"]],
                                   a["kv_a_norm"]["scale"], eps),
                           a["kv_b_proj"]["kernel"]).reshape(seq, heads, -1)
            k = jnp.concatenate([kv[..., :dn], jnp.broadcast_to(
                ref.rope_pairs(kv_a[:, -dr:], freqs)[:, None],
                (seq, heads, dr))], -1)
            ctx = jnp.stack([jnp.einsum(
                "hk,khd->hd", jax.nn.softmax(jnp.einsum(
                    "hd,khd->hk", q[i], k[:i + 1]) * scale, -1),
                kv[:i + 1, :, dn:])
                for i in range(seq)])
            h = x + ops.dense(ctx.reshape(seq, heads * dv),
                              a["o_proj"]["kernel"])
            u, m = ref.rms(h, p["post_attention_norm"]["scale"], eps), p["moe"]
            weights, _ = ref.routing(m, u, arch)
            y = ref.swiglu(u, m["shared_gate_proj"]["kernel"],
                           m["shared_up_proj"]["kernel"],
                           m["shared_down_proj"]["kernel"], ops)
            for local in range(held):
                mine = np.asarray(chosen) == first + local
                rows = np.nonzero(mine.any(-1))[0]
                if len(rows):
                    w = jnp.sum(jnp.where(mine[rows], weights[rows], 0), -1)
                    y = y.at[rows].add(w[:, None] * ref.swiglu(
                        u[rows], m["experts_gate_proj"][local],
                        m["experts_up_proj"][local],
                        m["experts_down_proj"][local], ops))
            x = h + y
        total = total + ref.head_loss(params["norm"], params["lm_head"], x,
                                      row[1:], arch, ops)[0]
    return total / (tokens.shape[0] * (tokens.shape[1] - 1))


def test_tiny_step_against_the_jaxpr_of_a_sparse_reference():
    cfg = get_config("mistral_small4_tiny")
    arch, share = dict(cfg.model.extra), (2, 4)
    model = build_model(cfg.model)
    tokens = jax.random.randint(jax.random.key(0), (2, arch["seq_len"] + 1),
                                0, cfg.model.num_classes)
    params = model.init({"params": jax.random.key(1)},
                        tokens[:, :-1])["params"]
    params = jax.tree.map(lambda x: x, params)
    cut = lambda p: {k: (v[2:6] if k.startswith("experts_") else v)
                     for k, v in p.items()}
    params = {k: ({**v, "moe": cut(v["moe"])} if k.startswith("layer_")
                  else v) for k, v in params.items()}

    # each layer's concrete routing, from the dense reference run eagerly
    chosen_by_layer, held = [], np.zeros(2)
    for row in tokens:
        x, chosen_layers = params["embed"]["embedding"][row[:-1]], []
        for i, name in enumerate(("layer_0", "layer_1")):
            p = params[name]
            h = x + ref.attention(p["attn"], ref.rms(
                x, p["input_norm"]["scale"], 1e-6), arch, Ops("float32"), 16)
            u = ref.rms(h, p["post_attention_norm"]["scale"], 1e-6)
            chosen = np.asarray(ref.routing(p["moe"], u, arch)[1])
            chosen_layers.append(chosen)
            held[i] += ((chosen >= 2) & (chosen < 6)).sum()
            x = ref.block(p, x, arch, share, Ops("float32"), 16)[0]
        chosen_by_layer.append(chosen_layers)

    sparse = lambda p: _sparse_loss(p, tokens, arch, share, chosen_by_layer)
    dense = ref.loss(params, tokens, arch, share)
    assert abs(float(sparse(params)) - float(dense)) < 1e-5 * float(dense)
    traced = counts.jaxpr_ops(jax.grad(sparse), params)
    mine = lm_counts.step_ops(arch=arch, layers=2, vocab_rows=256,
                              experts_held=4, seq_len=arch["seq_len"],
                              rows=2, assignments_held=list(held))
    got = sum(op["flops"] for op in mine)
    want = sum(op["flops"] for op in traced)
    assert abs(got - want) < 0.01 * want, (got, want)
