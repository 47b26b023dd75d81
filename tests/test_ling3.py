"""Ling-3.0-flash's language stack (models/ling3.py) at a small size on the
CPU (`ling3_flash_tiny`: `DKKKKLK`, hidden 64, 4 heads of 16, latent
attention at 24-wide keys on 16-wide values, 16 swiglu experts in 4 groups
top-4 of the best 2 beside a shared one, sequences of 128 = two chunks of
the delta rule): the chunked form (ops/kda.py) against the literal
recurrence, at the gate's bound too; the group-limited routing against a
plain one; the flax model against the plain reference
(chipbench/reference/ling3.py) on seeded weights, each layer kind alone and
the whole loss and gradient, whole and as a share; the shares add up to the
uncut layer; and three `Trainer` steps against the reference's follower.
(Both sides run jitted: eagerly the reference's nested scans take minutes.)"""

import io
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.reference import hybrid_lm_step, ling3 as ref
from chipbench.reference.ops import Ops
from distributed_vgg_f_tpu.config import ModelConfig, get_config
from distributed_vgg_f_tpu.models import ling3, mistral4
from distributed_vgg_f_tpu.models.mistral4 import ExpertShare
from distributed_vgg_f_tpu.models.registry import build_model
from distributed_vgg_f_tpu.ops import kda
from distributed_vgg_f_tpu.parallel.mesh import MeshSpec, build_mesh
from distributed_vgg_f_tpu.train.trainer import Trainer
from distributed_vgg_f_tpu.utils.logging import MetricLogger

TINY = get_config("ling3_flash_tiny")
SEQ = TINY.model.extra["seq_len"]

#: float32 on both sides: what is left is the order of the sums (the
#: chunked form adds a chunk's products where the recurrence adds positions)
TOLERANCE = 1e-4


def _rel(a, b):
    return float(jnp.linalg.norm(a - b) / (jnp.linalg.norm(b) + 1e-30))


# ---- the chunked form against the literal recurrence ------------------------

def _kda_inputs(seed=0, seq=192, heads=2, dk=16, dv=24, dtype=jnp.float32,
                g=None, beta=None):
    keys = jax.random.split(jax.random.key(seed), 5)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    shape = (2, seq, heads, dk)
    if g is None:
        g = kda.LOWER_BOUND * jax.nn.sigmoid(
            2 * jax.random.normal(keys[3], shape))
    if beta is None:
        beta = jax.nn.sigmoid(jax.random.normal(keys[4], shape[:3]))
    return ((unit(jax.random.normal(keys[0], shape)) * dk ** -0.5
             ).astype(dtype),
            unit(jax.random.normal(keys[1], shape)).astype(dtype),
            jax.random.normal(keys[2], (2, seq, heads, dv)).astype(dtype),
            jnp.broadcast_to(g, shape).astype(jnp.float32),
            jnp.broadcast_to(beta, shape[:3]).astype(jnp.float32))


def _value_and_grads(fn, args, weigh):
    return jax.jit(jax.value_and_grad(
        lambda *a: jnp.sum(fn(*a).astype(jnp.float32) * weigh),
        argnums=(0, 1, 2, 3, 4)))(*args)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("chunks", [1, 3])
def test_chunked_kda_equals_the_literal_recurrence(chunks, dtype):
    """Values and the gradients of q, k, v, g and beta."""
    args = _kda_inputs(seq=64 * chunks, dtype=jnp.dtype(dtype))
    weigh = jax.random.normal(jax.random.key(9), args[2].shape)
    got, d_got = _value_and_grads(kda.kda, args, weigh)
    want, d_want = _value_and_grads(kda.kda_recurrent, args, weigh)
    # bf16: the chunked form rounds its operands where the recurrence
    # (float32 inside) rounds none
    limit = TOLERANCE if dtype == "float32" else 5e-2
    assert _rel(jax.jit(kda.kda)(*args),
                jax.jit(kda.kda_recurrent)(*args)) < limit
    assert abs(float(got) - float(want)) < limit * (abs(float(want)) + 1)
    for name, a, b in zip("qkvgb", d_got, d_want):
        assert _rel(a.astype(jnp.float32), b.astype(jnp.float32)) < limit, name


def test_a_gate_at_its_bound_over_three_chunks_stays_finite_and_agrees():
    """exp(-G_j) alone would overflow float32 after 17 positions."""
    args = _kda_inputs(seq=192, g=jnp.float32(kda.LOWER_BOUND))
    weigh = jax.random.normal(jax.random.key(9), args[2].shape)
    got, d_got = _value_and_grads(kda.kda, args, weigh)
    want, d_want = _value_and_grads(kda.kda_recurrent, args, weigh)
    assert np.isfinite(float(got))
    assert all(bool(jnp.all(jnp.isfinite(d))) for d in d_got)
    assert _rel(jax.jit(kda.kda)(*args),
                jax.jit(kda.kda_recurrent)(*args)) < TOLERANCE
    for name, a, b in zip("qkvgb", d_got, d_want):
        # g's gradient is a difference of large terms at the bound
        assert _rel(a, b) < (1e-3 if name == "g" else TOLERANCE), name
    assert abs(float(kda.smallest_decay(args[3])) - np.exp(-5.0)) < 1e-6


@pytest.mark.parametrize("case", ["nothing_written", "decayed_sum",
                                  "plain_delta_rule"])
def test_kda_reduces_to_its_special_cases(case):
    if case == "nothing_written":
        # beta = 0: the state stays zero
        args = _kda_inputs(seq=128, beta=jnp.float32(0.0))
        assert float(jnp.max(jnp.abs(jax.jit(kda.kda)(*args)))) == 0.0
        return
    if case == "decayed_sum":
        # orthonormal keys under one decay for all channels: the delta
        # term finds nothing to take back, and what is left is
        # o_t = sum_{j<=t} exp(G_t - G_j) beta_j (q_t . k_j) v_j
        q, _, v, g, beta = _kda_inputs(seq=64, heads=1, dk=64)
        k = jnp.linalg.qr(jax.random.normal(jax.random.key(7), (2, 64, 64))
                          )[0][:, :, None, :]
        g = jnp.broadcast_to(g[..., :1], g.shape)
        G = jnp.cumsum(g[..., 0], axis=1)                    # (b, t, h)
        decay = jnp.where(jnp.tril(jnp.ones((64, 64), bool))[None, :, :, None],
                          jnp.exp(G[:, :, None] - G[:, None, :]), 0.0)
        want = jnp.einsum("bthd,bjhd,btjh,bjh,bjhv->bthv", q, k, decay, beta,
                          v, precision="highest")
        assert _rel(jax.jit(kda.kda)(q, k, v, g, beta), want) < TOLERANCE
        return
    # alpha = 1, beta = 1: S_t = (I - k k^T) S_{t-1} + k v^T, by hand
    q, k, v, g, beta = _kda_inputs(seq=128, g=jnp.float32(0.0),
                                   beta=jnp.float32(1.0))
    S = np.zeros((2, 2, 16, 24))
    want = []
    for t in range(128):
        k_t, v_t = np.asarray(k[:, t], np.float64), np.asarray(v[:, t])
        S = S - k_t[..., None] * np.einsum("bhk,bhkv->bhv", k_t, S
                                           )[:, :, None] \
            + k_t[..., None] * v_t[:, :, None]
        want.append(np.einsum("bhk,bhkv->bhv", np.asarray(q[:, t]), S))
    got = jax.jit(kda.kda)(q, k, v, g, beta)
    assert _rel(got, jnp.asarray(np.stack(want, 1), jnp.float32)) < TOLERANCE


def test_a_form_that_forgets_its_state_fails_by_a_wide_margin():
    args = _kda_inputs(seq=192)
    forgetful = jax.jit(lambda *a: kda.kda_recurrent(*a, reset_every=64))
    assert _rel(forgetful(*args), jax.jit(kda.kda)(*args)) > 100 * TOLERANCE


def test_a_rest_of_a_chunk_raises():
    with pytest.raises(ValueError, match="rest of a chunk"):
        kda.kda(*_kda_inputs(seq=96))


# ---- the group-limited routing ----------------------------------------------

def _plain_route(scores, bias, top_k, n_group, topk_group, scale):
    """numpy, a token at a time."""
    weights, chosen = [], []
    for s in np.asarray(scores, np.float64):
        c = s + np.asarray(bias, np.float64)
        groups = c.reshape(n_group, -1)
        score = np.sort(groups, -1)[:, -2:].sum(-1)
        kept = sorted(range(n_group), key=lambda i: (-score[i], i)
                      )[:topk_group]
        allowed = np.full(c.shape, -np.inf).reshape(n_group, -1)
        allowed[kept] = groups[kept]
        order = sorted(range(c.size),
                       key=lambda i: (-allowed.reshape(-1)[i], i))[:top_k]
        chosen.append(order)
        weights.append(scale * s[order] / (s[order].sum() + 1e-20))
    return np.asarray(weights), np.asarray(chosen)


@pytest.mark.parametrize("case", ["random", "ties", "biased_group"])
def test_group_limited_route_equals_a_plain_routing(case):
    scores = jax.nn.sigmoid(jax.random.normal(jax.random.key(0), (64, 16)))
    bias = 0.01 * jax.random.normal(jax.random.key(1), (16,))
    if case == "ties":
        # whole groups equal, and equal experts inside them
        scores = jnp.round(scores * 4) / 4
        bias = jnp.zeros(16)
    if case == "biased_group":
        bias = bias.at[8:12].add(1.0)
    weights, local = jax.jit(lambda s, b: mistral4.route(
        s, 4, 0, 16, bias=b, scale=2.5, n_group=4, topk_group=2))(
        scores, bias)
    want_w, want_e = _plain_route(scores, bias, 4, 4, 2, 2.5)
    assert np.array_equal(np.asarray(local), want_e)
    assert np.allclose(np.asarray(weights), want_w, rtol=1e-6)
    if case == "biased_group":
        assert (np.asarray(local) // 4 == 2).sum(-1).min() >= 1
    # and the reference's routing is the same choice
    arch = {"n_group": 4, "topk_group": 2, "num_experts_per_tok": 4,
            "routed_scaling_factor": 2.5}
    logits = jnp.log(scores / (1 - scores + 1e-9) + 1e-9)
    if case != "ties":
        ref_w, ref_e = ref.routing(
            {"router": jnp.eye(16), "router_bias": bias}, logits, arch)
        again = _plain_route(jax.nn.sigmoid(logits), bias, 4, 4, 2, 2.5)
        assert np.array_equal(np.asarray(ref_e), again[1])
        assert np.allclose(np.asarray(ref_w), again[0], rtol=1e-5)


def test_one_group_is_today_s_route():
    scores = jax.nn.sigmoid(jax.random.normal(jax.random.key(0), (64, 16)))
    bias = 0.01 * jax.random.normal(jax.random.key(1), (16,))
    plain = mistral4.route(scores, 4, 4, 8, bias=bias, scale=2.5)
    one = mistral4.route(scores, 4, 4, 8, bias=bias, scale=2.5, n_group=1,
                         topk_group=1)
    assert all(np.array_equal(a, b) for a, b in zip(plain, one))
    lowered = lambda **kw: jax.jit(lambda s, b: mistral4.route(
        s, 4, 4, 8, bias=b, scale=2.5, **kw)).lower(scores, bias).as_text()
    assert lowered() == lowered(n_group=1, topk_group=1)


# ---- each kind of layer against the plain reference -------------------------

def _model(**extra):
    cfg = ModelConfig(name="ling3", num_classes=TINY.model.num_classes,
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


LAYERS = {"kda": (0, "attn", ling3.KimiDeltaAttention),
          "latent": (5, "attn", mistral4.LatentAttention),
          # the Pallas kernel at 24-wide keys on 16-wide values, interpreted
          "latent_flash": (5, "attn", mistral4.LatentAttention),
          "experts": (1, "moe", ExpertShare),
          "dense": (0, "mlp", ling3.DenseMLP)}


@pytest.mark.parametrize("case", sorted(LAYERS))
def test_layer_matches_the_reference(case, monkeypatch):
    if case == "latent_flash":
        from distributed_vgg_f_tpu.ops import flash_attention
        monkeypatch.setattr(flash_attention, "INTERPRET", True)
    kind = case.split("_")[0]
    index, name, layer = LAYERS[case]
    model, arch = _model()
    params, _ = _seeded(model)
    p = params[f"layer_{index}"][name]
    u = jax.random.normal(jax.random.key(4), (2, SEQ, arch["hidden_size"]))
    weigh = jax.random.normal(jax.random.key(5), u.shape)
    kwargs = dict(model.layers[kind], compute_dtype=jnp.float32)
    share = (0, arch["n_routed_experts"])

    def program(p, u):
        out = layer(**kwargs).apply({"params": p}, u)
        return jnp.sum((out[0] if kind == "experts" else out) * weigh)

    def reference(p, u):
        f32 = Ops("float32")
        one = {"kda": lambda row: ref.kda(p, row, arch, f32),
               "latent": lambda row: ref.latent(p, row, arch, f32, 32),
               "experts": lambda row: ref.experts(p, row, arch, share,
                                                  f32)[0],
               "dense": lambda row: ref.swiglu(
                   row, *(p[f"{n}_proj"]["kernel"]
                          for n in ("gate", "up", "down")), f32)}[kind]
        return jnp.sum(jnp.stack([one(row) for row in u]) * weigh)

    got, (d_p, d_u) = jax.jit(jax.value_and_grad(
        program, argnums=(0, 1)))(p, u)
    want, (want_p, want_u) = jax.jit(jax.value_and_grad(
        reference, argnums=(0, 1)))(p, u)
    # the interpreted kernel rounds its probabilities as the chip's does
    limit = 2e-3 if case == "latent_flash" else TOLERANCE
    assert abs(float(got) - float(want)) < limit * abs(float(want)) + 1e-4
    assert _rel(d_u, want_u) < limit
    gaps = jax.tree.map(_rel, d_p, want_p)
    if kind == "experts":
        # no gradient reaches the selection bias, on either side
        assert not np.asarray(d_p["router_bias"]).any()
        assert not np.asarray(want_p["router_bias"]).any()
        gaps = {**gaps, "router_bias": 0.0}
    assert max(jax.tree.leaves(gaps)) < limit, gaps


def test_the_kinds_follow_the_published_rule():
    assert ling3.pattern_of(42, 6, 2) == "DD" + "KKKLKK" * 6 + "KKKL"
    assert ling3.pattern_of(7, 6, 1) == "DKKKKLK"
    model, _ = _model()
    assert model.pattern == "DKKKKLK"
    assert model.expert_layers == (1, 2, 3, 4, 5, 6)
    with pytest.raises(ValueError, match="published rule"):
        _model(hybrid_override_pattern="DKKKKKL")


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
        lambda p: ref.forward(p, inputs, arch, share))(params)
    assert float(jnp.max(jnp.abs(logits[:, :4] - want_logits[:, :4]))) < 2e-4

    program = lambda p: model.apply({"params": p}, inputs, targets,
                                    method="next_token_loss")
    (loss, counts), grads = jax.jit(jax.value_and_grad(
        program, has_aux=True))(params)
    want_loss, want_grads = jax.jit(jax.value_and_grad(
        lambda p: ref.loss(p, tokens, arch, share)))(params)
    assert abs(float(loss) - float(want_loss)) < 1e-5 * float(want_loss)
    assert counts.shape == (6, share[1] + 1)       # the expert layers' rows
    assert np.array_equal(counts[:, :-1], want_loads.sum(0))
    assert not counts[:, -1].any()                 # nothing dropped
    worst = max(jax.tree.leaves(jax.tree.map(
        _rel, _without_bias(grads), _without_bias(want_grads))))
    assert worst < TOLERANCE, worst
    # the planted fault is seen by the same comparison
    broken = jax.jit(lambda p: ref.loss(p, tokens, arch, share,
                                        fault="chunk_reset"))(params)
    assert abs(float(broken) - float(want_loss)) > 1e-4 * float(want_loss)


# ---- the shares add up ------------------------------------------------------

def test_four_shares_and_the_shared_expert_once_equal_the_uncut_layer():
    """The routed parts of all 4 shares (a group of 4 experts each) plus
    the shared expert once, in the program and in the reference, equal the
    uncut layer; the loads add up to tokens x top-k; and a share's
    `group_share` is the part of the tokens whose kept groups include its
    own, which add up to `topk_group`."""
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
    routed, routed_ref, loads, group_shares = 0.0, 0.0, [], []
    for first in range(0, 16, 4):
        (out, count), sown = ExpertShare(
            **{**kwargs, "first_expert": first, "experts_held": 4}).apply(
            {"params": cut(first)}, u[None], mutable=["counters"])
        routed = routed + (out[0] - shared)
        part, load = ref.experts(cut(first), u, arch, (first, 4),
                                 Ops("float32"), shared=False)
        routed_ref = routed_ref + part
        loads.extend(int(x) for x in count[:-1])
        group_shares.append(float(sown["counters"]["group_share"][0]))
        assert np.array_equal(load, count[:-1]) and int(count[-1]) == 0
        # no token outside the kept groups reaches the share
        assert int(count[:-1].sum()) <= round(
            group_shares[-1] * SEQ) * arch["num_experts_per_tok"]
    assert _rel(routed + shared, whole[0]) < TOLERANCE
    assert _rel(routed_ref + shared, want_whole) < TOLERANCE
    assert loads == [int(x) for x in want_loads]
    assert abs(sum(group_shares) - arch["topk_group"]) < 1e-6


# ---- through the trainer ----------------------------------------------------

def _recipe(cfg) -> dict:
    return {"base_lr": cfg.optim.base_lr, "momentum": cfg.optim.momentum,
            "reference_batch": cfg.optim.reference_batch_size,
            "global_batch": cfg.data.global_batch_size,
            "weight_decay": cfg.optim.weight_decay,
            "schedule": cfg.optim.schedule}


def _trainer(stream=None):
    mesh = build_mesh(MeshSpec((TINY.mesh.data_axis,), (1,)),
                      jax.devices()[:1])
    return Trainer(TINY, mesh=mesh,
                   logger=MetricLogger(stream=stream or io.StringIO()))


def test_block_sows_its_counters():
    model, arch = _model()
    params, tokens = _seeded(model)
    _, sown = jax.jit(lambda p: model.apply(
        {"params": p}, tokens[:, :-1], mutable=["counters"]))(params)
    counters = sown["counters"]
    assert int(counters["layer_0"]["attn"]["kda_chunks"][0]) == 2 * 2
    assert int(counters["layer_0"]["attn"]["kda_kernel"][0]) == 0
    assert int(counters["layer_0"]["attn"]["kda_conv_kernel"][0]) == 0
    assert 0 < float(counters["layer_4"]["attn"]["kda_decay_min"][0]) < 1
    assert "attn" not in counters["layer_5"]            # the latent layer
    assert 0 < float(counters["layer_5"]["moe"]["group_share"][0]) <= 1
    assert "moe" not in counters["layer_0"]             # the dense layer


@pytest.mark.parametrize("case", ["tile_sizes", "tiny_preset"])
def test_layer_sows_whether_it_took_the_kernels(case, monkeypatch):
    """`kda_kernel` and `kda_conv_kernel` beside `kda_chunks`: 1 at tile
    sizes (two heads of 128), 0 for the tiny preset's heads of 16,
    interpreters on in both."""
    from distributed_vgg_f_tpu.ops import kda_pallas, short_conv_pallas
    monkeypatch.setattr(kda_pallas, "INTERPRET", True)
    monkeypatch.setattr(short_conv_pallas, "INTERPRET", True)
    tiles = {"num_attention_heads": 2, "head_dim": kda_pallas.LANES}
    model, arch = _model(**(tiles if case == "tile_sizes" else {}))
    layer = ling3.KimiDeltaAttention(**model.layers["kda"],
                                     compute_dtype=jnp.float32)
    u = jax.random.normal(jax.random.key(4), (2, SEQ, arch["hidden_size"]))
    params = layer.init(jax.random.key(1), u)["params"]
    _, sown = layer.apply({"params": params}, u, mutable=["counters"])
    counters = {k: v[0] for k, v in sown["counters"].items()}
    assert counters["kda_kernel"] == (1 if case == "tile_sizes" else 0)
    assert counters["kda_conv_kernel"] == counters["kda_kernel"]
    assert counters["kda_chunks"] == 2 * SEQ // 64


def test_fit_with_the_tiny_preset_logs_every_layer_under_its_own_index():
    """`Trainer.fit` on the seeded token source, telemetry on: three steps,
    the first loss the reference's on the source's first batch, the expert
    layers' counters under 1 to 6, the delta-rule layers' under 0 to 4 and
    6, and the trainer's three new gauges."""
    from distributed_vgg_f_tpu import telemetry
    from distributed_vgg_f_tpu.data.synthetic_tokens import SyntheticTokens
    stream = io.StringIO()
    trainer = _trainer(stream)
    extra = dict(TINY.model.extra)
    source = SyntheticTokens(TINY.data.global_batch_size, extra["seq_len"],
                             TINY.model.num_classes, seed=TINY.train.seed)
    start = trainer.init_state().params
    want = hybrid_lm_step.follow(
        ref, extra, (0, extra["n_routed_experts"]), _recipe(TINY),
        lambda group: jax.tree.map(jnp.copy, start[group]), list(start),
        jnp.asarray(next(source)["tokens"]), steps=1, block_rows=32)
    telemetry.reset()
    telemetry.configure(enabled=True)
    try:
        trainer.fit()
        gauges = telemetry.get_registry().snapshot_split()["gauges"]
    finally:
        telemetry.reset()
        telemetry.configure(enabled=True)
    assert gauges["kda/chunks"] == 6 * 4              # six delta-rule layers
    assert gauges["kda/kernel_layers"] == 0           # heads of 16: XLA's
    assert gauges["kda/conv_kernel_layers"] == 0
    assert 0 < gauges["kda/decay_min"] < 1
    assert 0 < gauges["moe/group_share"] <= 1
    assert gauges["moe/assignments_held"] == 6 * 1024  # six expert layers
    assert gauges["moe/dropped_assignments"] == 0
    assert "ssm/chunks" not in gauges
    log = stream.getvalue()
    lines = re.findall(r"^\[train\] step=(\d) .*? loss=(\S+) .*", log, re.M)
    assert [int(step) for step, _ in lines] == [1, 2, 3], log
    first = float(want["losses"][0])
    assert abs(float(lines[0][1]) - first) < 2e-5 * first
    for name in ("moe_held/layer_1", "moe_dropped/layer_6=0",
                 "moe_passes/layer_5=1", "moe_group_share/layer_5",
                 "kda_chunks/layer_0=4", "kda_chunks/layer_6=4",
                 "kda_kernel/layer_0=0", "kda_kernel/layer_6=0",
                 "kda_conv_kernel/layer_0=0", "kda_conv_kernel/layer_6=0",
                 "kda_decay_min/layer_2"):
        assert name in log, name
    assert "moe_held/layer_0" not in log and "kda_chunks/layer_5" not in log
    assert "kda_kernel/layer_5" not in log
    assert "kda_conv_kernel/layer_5" not in log
    assert "moe_kda" not in log


def test_three_steps_on_one_batch_move_the_weights_as_the_reference_s():
    """The trainer's compiled step three times on one batch, against the
    follower's three steps on it: losses, first gradient by way of the
    change, and every leaf's change."""
    trainer, extra = _trainer(), dict(TINY.model.extra)
    tokens = jax.random.randint(jax.random.key(3), (2, SEQ + 1), 0,
                                TINY.model.num_classes)
    state = trainer.init_state()
    start = jax.tree.map(jnp.copy, state.params)
    want = hybrid_lm_step.follow(
        ref, extra, (0, extra["n_routed_experts"]), _recipe(TINY),
        lambda group: jax.tree.map(jnp.copy, start[group]), list(start),
        tokens, steps=3, block_rows=32)
    batch, rng = trainer.shard({"tokens": np.asarray(tokens)}), \
        trainer.base_rng()
    # float32 on both sides, and the first step agrees to the last digits.
    # But some 10,000 router choices a step lie 0.02 apart, and after one
    # update the two sides' inputs 1e-6: about one choice a step falls the
    # other way, and from the model's own unscaled initial weights (the
    # benchmark's seeding scales every projection into the residual stream
    # by the depth, tests/chipbench/test_ling_lm_harness.py) that grows: a
    # few assignments at step 2, a hundred of 6,144 at step 3. So the
    # later steps are held loosely, and the leaves' changes by their median.
    for step, (ref_loss, ref_load) in enumerate(zip(want["losses"],
                                                    want["loads"])):
        state, metrics = trainer.train_step(state, batch, rng)
        moved_rows = np.abs(np.asarray(metrics["moe_load"]) - ref_load).sum()
        assert abs(float(metrics["loss"]) - float(ref_loss)) \
            < (2e-5 if step == 0 else 5e-3) * float(ref_loss)
        assert moved_rows <= (0 if step == 0 else 0.03 * ref_load.sum())
    moved = jax.tree.map(lambda a, b: float(jnp.linalg.norm(a - b)),
                         state.params, start)
    gaps = jax.tree.leaves(jax.tree.map(
        lambda a, b: abs(a - float(b)) / max(float(b), 1e-6), moved,
        want["change_norms"]))
    assert np.median(gaps) < 1e-2 and max(gaps) < 0.5, sorted(gaps)[-5:]
