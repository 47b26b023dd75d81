"""Solar-Open2's stack (models/solar_open2.py) against its plain reference
(chipbench/reference/solar_open2.py) on the CPU at the tiny preset's size,
float32, seeded weights: each kind of layer with every gradient (the delta
rule under Kimi's unbounded gate with beta in (0, 2), through the XLA form
and through the Pallas kernels in the interpreter; the gated grouped-query
attention, explicit and through the flash kernel; the expert share), the
whole model's loss, first logits and every leaf's gradient, the kinds from
the published `gqa_layers`, the expert shares adding up to the uncut layer,
and the counters the layers sow."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.reference import solar_open2 as ref
from chipbench.reference.ops import Ops
from distributed_vgg_f_tpu.config import ModelConfig, get_config
from distributed_vgg_f_tpu.models import solar_open2
from distributed_vgg_f_tpu.models.ling3 import KimiDeltaAttention
from distributed_vgg_f_tpu.models.mistral4 import ExpertShare
from distributed_vgg_f_tpu.models.nemotron_h import GroupedQueryAttention
from distributed_vgg_f_tpu.models.registry import build_model

TINY = get_config("solar_open2_tiny")
SEQ = TINY.model.extra["seq_len"]
#: float32 on both sides: what is left is the order of the sums
TOLERANCE = 1e-4


def _rel(a, b):
    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    return float(jnp.linalg.norm(a - b) / (jnp.linalg.norm(b) + 1e-30))


def _model(**extra):
    cfg = ModelConfig(name="solar_open2", num_classes=TINY.model.num_classes,
                      compute_dtype="float32",
                      extra={**TINY.model.extra, **extra})
    return build_model(cfg), dict(cfg.extra)


def _seeded(model, seed=1):
    """The model's own initial weights, with the selection bias and the
    norm scales moved off their constants."""
    tokens = jax.random.randint(jax.random.key(seed), (2, SEQ + 1), 0,
                                TINY.model.num_classes)
    params = model.init({"params": jax.random.key(seed + 1)},
                        tokens[:, :-1])["params"]
    keys = iter(jax.random.split(jax.random.key(seed + 2), 128))

    def shake(path, leaf):
        if str(path[-1].key) in ("scale", "router_bias"):
            return leaf + 0.1 * jax.random.normal(next(keys), leaf.shape)
        return leaf
    return jax.tree_util.tree_map_with_path(shake, params), tokens


#: case -> (layer index, module name, class); `kda_kernels` at two
#: heads of 128 (the fewest the kernels take), interpreted
LAYERS = {"kda": (1, "attn", KimiDeltaAttention),
          "kda_kernels": (1, "attn", KimiDeltaAttention),
          "gqa": (0, "attn", GroupedQueryAttention),
          "gqa_flash": (0, "attn", GroupedQueryAttention),
          "experts": (2, "moe", ExpertShare)}


@pytest.mark.parametrize("case", sorted(LAYERS))
def test_layer_matches_the_reference(case, monkeypatch):
    from distributed_vgg_f_tpu.ops import (flash_attention, kda_pallas,
                                           short_conv_pallas)
    kind = case.split("_")[0]
    extra = {}
    if case == "kda_kernels":
        for module in (kda_pallas, short_conv_pallas):
            monkeypatch.setattr(module, "INTERPRET", True)
        monkeypatch.setattr(kda_pallas, "HEADS", 2)
        extra = {"linear_attn_config": {
            **TINY.model.extra["linear_attn_config"], "num_heads": 2,
            "head_dim": kda_pallas.LANES}}
    if case == "gqa_flash":
        monkeypatch.setattr(flash_attention, "INTERPRET", True)
    index, name, layer = LAYERS[case]
    model, arch = _model(**extra)
    params, _ = _seeded(model)
    p = params[f"layer_{index}"][name]
    u = jax.random.normal(jax.random.key(4), (2, SEQ, arch["hidden_size"]))
    weigh = jax.random.normal(jax.random.key(5), u.shape)
    kwargs = dict(model.layers[kind], compute_dtype=jnp.float32)
    if kind == "kda":
        kwargs["eps"] = arch["rms_norm_eps"]
        _, sown = layer(**kwargs).apply({"params": p}, u,
                                        mutable=["counters"])
        assert sown["counters"]["kda_kernel"][0] == (case == "kda_kernels")
    share = (0, arch["n_routed_experts"])

    def program(p, u):
        out = layer(**kwargs).apply({"params": p}, u)
        return jnp.sum((out[0] if kind == "experts" else out) * weigh)

    def reference(p, u):
        f32 = Ops("float32")
        one = {"kda": lambda row: ref.kda(p, row, arch, f32),
               "gqa": lambda row: ref.gqa(p, row, arch, f32, 32),
               "experts": lambda row: ref.experts(p, row, arch, share,
                                                  f32)[0]}[kind]
        return jnp.sum(jnp.stack([one(row) for row in u]) * weigh)

    got, (d_p, d_u) = jax.jit(jax.value_and_grad(
        program, argnums=(0, 1)))(p, u)
    want, (want_p, want_u) = jax.jit(jax.value_and_grad(
        reference, argnums=(0, 1)))(p, u)
    # the interpreted flash kernel rounds its probabilities as the chip's
    limit = 2e-3 if case == "gqa_flash" else TOLERANCE
    assert abs(float(got) - float(want)) < limit * abs(float(want)) + 1e-4
    assert _rel(d_u, want_u) < limit
    gaps = jax.tree.map(_rel, d_p, want_p)
    if kind == "experts":
        # no gradient reaches the selection bias, on either side
        assert not np.asarray(d_p["router_bias"]).any()
        gaps = {**gaps, "router_bias": 0.0}
    assert max(jax.tree.leaves(gaps)) < limit, gaps


def test_the_kinds_follow_the_published_rule():
    published = get_config("solar_open2_250b_ep40").model.extra
    assert solar_open2.pattern_of(48, published["gqa_layers"]) == "GKKK" * 12
    assert solar_open2.pattern_of(4, published["gqa_layers"]) == "GKKK"
    model, _ = _model()
    assert model.pattern == "GKKK"
    assert model.expert_layers == (0, 1, 2, 3)
    with pytest.raises(ValueError, match="published rule"):
        _model(hybrid_override_pattern="KGKK")
    with pytest.raises(ValueError, match="published rule"):
        _model(hybrid_override_pattern="GKKKG")


# ---- the whole model --------------------------------------------------------

def _without_bias(tree):
    return {k: ({**v, "moe": {n: x for n, x in v["moe"].items()
                              if n != "router_bias"}}
                if "moe" in v else v) for k, v in tree.items()}


@pytest.mark.parametrize("case", ["full", "share"])
def test_model_matches_the_reference(case):
    extra = {"first_expert": 4, "experts_held": 4} if case == "share" else {}
    model, arch = _model(**extra)
    share = (arch.get("first_expert", 0),
             arch.get("experts_held", arch["n_routed_experts"]))
    params, tokens = _seeded(model)
    inputs, targets = tokens[:, :-1], tokens[:, 1:]

    logits = jax.jit(model.apply)({"params": params}, inputs)
    want_logits, want_loads = jax.jit(
        lambda p: ref.forward(p, inputs, arch, share, block_rows=32))(params)
    assert float(jnp.max(jnp.abs(logits[:, :4] - want_logits[:, :4]))) < 2e-4

    program = lambda p: model.apply({"params": p}, inputs, targets,
                                    method="next_token_loss")
    (loss, counts), grads = jax.jit(jax.value_and_grad(
        program, has_aux=True))(params)
    want_loss, want_grads = jax.jit(jax.value_and_grad(
        lambda p: ref.loss(p, tokens, arch, share, block_rows=32)))(params)
    assert abs(float(loss) - float(want_loss)) < 1e-5 * float(want_loss)
    assert counts.shape == (4, share[1] + 1)       # every layer has experts
    assert np.array_equal(counts[:, :-1], want_loads.sum(0))
    assert not counts[:, -1].any()                 # nothing dropped
    gaps = jax.tree.map(_rel, _without_bias(grads), _without_bias(want_grads))
    assert max(jax.tree.leaves(gaps)) < TOLERANCE, gaps
    # the planted fault is seen by the same comparison
    broken = jax.jit(lambda p: ref.loss(
        p, tokens, arch, share, block_rows=32, fault="chunk_reset"))(params)
    assert abs(float(broken) - float(want_loss)) > 1e-4 * float(want_loss)


# ---- the shares add up ------------------------------------------------------

def test_four_shares_and_the_shared_expert_once_equal_the_uncut_layer():
    """The routed parts of all 4 shares (4 experts each, as 40 chips hold 8
    of 320) plus the shared expert once, in the program and in the
    reference, equal the uncut layer; the loads add up to tokens x top-k."""
    model, arch = _model()
    params, _ = _seeded(model, seed=5)
    p = params["layer_1"]["moe"]
    u = jax.random.normal(jax.random.key(5), (SEQ, arch["hidden_size"]))
    kwargs = dict(model.layers["experts"], compute_dtype=jnp.float32)
    cut = lambda first: {k: (v[first:first + 4]
                             if k.startswith("experts_") else v)
                         for k, v in p.items()}

    whole, counts = ExpertShare(**kwargs).apply({"params": p}, u[None])
    want_whole, want_loads = ref.experts(p, u, arch, (0, 16), Ops("float32"))
    assert _rel(whole[0], want_whole) < TOLERANCE
    assert int(counts[:-1].sum()) == SEQ * arch["num_experts_per_tok"]

    shared = ref.experts(p, u, arch, (0, 0), Ops("float32"))[0]
    routed, routed_ref, loads = 0.0, 0.0, []
    for first in range(0, 16, 4):
        out, count = ExpertShare(
            **{**kwargs, "first_expert": first, "experts_held": 4}).apply(
            {"params": cut(first)}, u[None])
        routed = routed + (out[0] - shared)
        part, load = ref.experts(cut(first), u, arch, (first, 4),
                                 Ops("float32"), shared=False)
        routed_ref = routed_ref + part
        loads.extend(int(x) for x in count[:-1])
        assert np.array_equal(load, count[:-1]) and int(count[-1]) == 0
    assert _rel(routed + shared, whole[0]) < TOLERANCE
    assert _rel(routed_ref + shared, want_whole) < TOLERANCE
    assert loads == [int(x) for x in want_loads]


def test_blocks_sow_their_counters():
    """Every layer's expert share; the delta-rule layers' chunks, form and
    decay, the share of gate entries under -5 and the largest beta (over 1:
    the transition's negative eigenvalues); nothing of KDA's in the
    attention layer."""
    model, _ = _model()
    params, tokens = _seeded(model)
    _, sown = jax.jit(lambda p: model.apply(
        {"params": p}, tokens[:, :-1], mutable=["counters"]))(params)
    counters = sown["counters"]
    assert "attn" not in counters["layer_0"]
    for i in (1, 2, 3):
        attn = {k: float(v[0])
                for k, v in counters[f"layer_{i}"]["attn"].items()}
        assert attn["kda_chunks"] == 2 * 2 and attn["kda_kernel"] == 0
        assert 0 <= attn["kda_gate_below_bound"] < 0.1
        assert 1 < attn["kda_beta_max"] < 2
        assert 0 <= attn["kda_decay_min"] < 1
    for i in range(4):
        assert int(counters[f"layer_{i}"]["moe"]["passes"][0]) == 1
