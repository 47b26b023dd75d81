"""AI21-Jamba2-3B's stack (models/jamba2.py) against its plain reference
(chipbench/reference/jamba2.py) on the CPU at the tiny preset's size,
float32, seeded weights: the Mamba-1 mixer with every gradient (through the
selective scan's XLA form and through its Pallas kernels in the
interpreter), the attention layer, the whole model's loss, logits and every
leaf's gradient, the tied matrix's gradient as the lookup's plus the
head's, the kinds from the published rule, what the layers sow, and the
empty counts table of the other hybrid stacks without experts."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.reference import jamba2 as ref
from chipbench.reference.ops import Ops
from distributed_vgg_f_tpu.config import ModelConfig, get_config
from distributed_vgg_f_tpu.models import jamba2
from distributed_vgg_f_tpu.models.nemotron_h import GroupedQueryAttention
from distributed_vgg_f_tpu.models.registry import build_model

TINY = get_config("jamba2_3b_tiny")
SEQ = TINY.model.extra["seq_len"]
#: float32 on both sides: what is left is the order of the sums (the
#: recurrence's 256 positions among them)
TOLERANCE = 1e-4


def _rel(a, b):
    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    return float(jnp.linalg.norm(a - b) / (jnp.linalg.norm(b) + 1e-30))


def _model(**extra):
    cfg = ModelConfig(name="jamba", num_classes=TINY.model.num_classes,
                      compute_dtype="float32",
                      extra={**TINY.model.extra, **extra})
    return build_model(cfg), dict(cfg.extra)


def _seeded(model, seed=1):
    """The model's own initial weights, with the norm scales, D and A_log
    moved off their constants, and the embedding at the published std (the
    tied head reads it)."""
    tokens = jax.random.randint(jax.random.key(seed), (2, SEQ + 1), 0,
                                TINY.model.num_classes)
    params = model.init({"params": jax.random.key(seed + 1)},
                        tokens[:, :-1])["params"]
    keys = iter(jax.random.split(jax.random.key(seed + 2), 128))

    def shake(path, leaf):
        name = str(path[-1].key)
        if name in ("scale", "D", "A_log"):
            return leaf + 0.1 * jax.random.normal(next(keys), leaf.shape)
        if name == "embedding":
            return 0.02 * jax.random.normal(next(keys), leaf.shape)
        return leaf
    return jax.tree_util.tree_map_with_path(shake, params), tokens


@pytest.mark.parametrize("case", ["mamba", "mamba_kernels", "attention"])
def test_mixer_matches_the_reference(case, monkeypatch):
    """One mixer on two sequences under a weighted sum: value and every
    gradient. `mamba_kernels` at 128 Mamba channels (one lane tile, the
    fewest the kernels take), interpreted."""
    from distributed_vgg_f_tpu.ops import selective_scan_pallas
    kind = case.split("_")[0]
    extra = {}
    if case == "mamba_kernels":
        monkeypatch.setattr(selective_scan_pallas, "INTERPRET", True)
        extra = {"hidden_size": 64}
    model, arch = _model(**extra)
    params, _ = _seeded(model)
    index = arch["hybrid_override_pattern"].index(
        "M" if kind == "mamba" else "A")
    p = params[f"layer_{index}"]["mixer"]
    u = jax.random.normal(jax.random.key(4), (2, SEQ, arch["hidden_size"]))
    weigh = jax.random.normal(jax.random.key(5), u.shape)
    kwargs = dict(model.layers[kind], compute_dtype=jnp.float32)
    if kind == "mamba":
        layer = jamba2.Mamba1Mixer(**kwargs, eps=arch["rms_norm_eps"])
        _, sown = layer.apply({"params": p}, u, mutable=["counters"])
        assert sown["counters"]["mamba1_kernel"][0] == (
            case == "mamba_kernels")
    else:
        layer = GroupedQueryAttention(**kwargs)

    def program(p, u):
        return jnp.sum(layer.apply({"params": p}, u) * weigh)

    def reference(p, u):
        f32 = Ops("float32")
        one = {"mamba": lambda row: ref.mamba(p, row, arch, f32),
               "attention": lambda row: ref.attention(p, row, arch, f32,
                                                      64)}[kind]
        return jnp.sum(jnp.stack([one(row) for row in u]) * weigh)

    got, (d_p, d_u) = jax.jit(jax.value_and_grad(
        program, argnums=(0, 1)))(p, u)
    want, (want_p, want_u) = jax.jit(jax.value_and_grad(
        reference, argnums=(0, 1)))(p, u)
    assert abs(float(got) - float(want)) \
        < TOLERANCE * abs(float(want)) + 1e-4
    assert _rel(d_u, want_u) < TOLERANCE
    gaps = jax.tree.map(_rel, d_p, want_p)
    assert max(jax.tree.leaves(gaps)) < TOLERANCE, gaps


def test_the_kinds_follow_the_published_rule():
    published = get_config("jamba2_3b_v8").model.extra
    rule = (published["attn_layer_period"], published["attn_layer_offset"])
    assert jamba2.pattern_of(28, *rule) == "MMMMMMMAMMMMMM" * 2
    assert jamba2.pattern_of(14, *rule) \
        == published["hybrid_override_pattern"]
    model, _ = _model()
    assert model.pattern == "MMAM" and model.expert_layers == ()
    with pytest.raises(ValueError, match="published rule"):
        _model(hybrid_override_pattern="MAMM")
    with pytest.raises(NotImplementedError):
        _model(num_experts=16)
    with pytest.raises(NotImplementedError):
        _model(tie_word_embeddings=False)


# ---- the whole model --------------------------------------------------------

def _reference_loss_apart(lookup, head, params, tokens, arch):
    """The reference's loss with the embedding's two uses apart: `lookup`
    for the ids, `head` transposed for the logits."""
    f32 = Ops("float32")
    total = 0.0
    for row in tokens:
        x = lookup[row[:-1]]
        for i in range(len(arch["hybrid_override_pattern"])):
            x, _ = ref.block(params[f"layer_{i}"], x, arch, (0, 0), f32,
                             64, layer=i)
        total = total + ref.head_loss(params["norm"], {"kernel": head.T}, x,
                                      row[1:], arch, f32)[0]
    return total / (tokens.shape[0] * (tokens.shape[1] - 1))


def test_model_matches_the_reference():
    model, arch = _model()
    params, tokens = _seeded(model)
    inputs, targets = tokens[:, :-1], tokens[:, 1:]
    assert "lm_head" not in params                 # the head is tied

    logits = jax.jit(model.apply)({"params": params}, inputs)
    want_logits = jax.jit(
        lambda p: ref.forward(p, inputs, arch, block_rows=64))(params)
    assert _rel(logits, want_logits) < TOLERANCE

    program = lambda p: model.apply({"params": p}, inputs, targets,
                                    method="next_token_loss")
    (loss, counts), grads = jax.jit(jax.value_and_grad(
        program, has_aux=True))(params)
    want_loss, want_grads = jax.jit(jax.value_and_grad(
        lambda p: ref.loss(p, tokens, arch, block_rows=64)))(params)
    assert abs(float(loss) - float(want_loss)) < 1e-5 * float(want_loss)
    assert counts.shape == (0, 1)                  # no layer has experts
    gaps = jax.tree.map(_rel, grads, want_grads)
    assert max(jax.tree.leaves(gaps)) < TOLERANCE, gaps
    # the planted fault is seen by the same comparison, in the gradients
    broken = jax.jit(jax.grad(lambda p: ref.loss(
        p, tokens, arch, block_rows=64, fault="chunk_reset")))(params)
    gaps = jax.tree.map(_rel, broken, want_grads)
    assert max(jax.tree.leaves(gaps)) > 100 * TOLERANCE, gaps


def test_the_tied_matrix_s_gradient_is_the_lookup_s_plus_the_head_s():
    """The program's gradient of the one matrix against the reference's two
    uses taken apart: it is their sum, and far from either alone."""
    model, arch = _model()
    params, tokens = _seeded(model, seed=7)
    inputs, targets = tokens[:, :-1], tokens[:, 1:]
    grads = jax.jit(jax.grad(lambda p: model.apply(
        {"params": p}, inputs, targets, method="next_token_loss")[0]))(params)
    got = grads["embed"]["embedding"]
    matrix = params["embed"]["embedding"]
    by_lookup, by_head = jax.jit(jax.grad(
        lambda a, b: _reference_loss_apart(a, b, params, tokens, arch),
        argnums=(0, 1)))(matrix, matrix)
    assert _rel(got, by_lookup + by_head) < TOLERANCE
    assert _rel(got, by_lookup) > 0.1 and _rel(got, by_head) > 0.1


def test_blocks_sow_the_form_of_the_recurrence():
    model, _ = _model()
    params, tokens = _seeded(model)
    _, sown = jax.jit(lambda p: model.apply(
        {"params": p}, tokens[:, :-1], mutable=["counters"]))(params)
    counters = sown["counters"]
    assert set(counters) == {"layer_0", "layer_1", "layer_3"}
    for layer in counters.values():
        assert int(layer["mixer"]["mamba1_kernel"][0]) == 0


# ---- stacks without experts -------------------------------------------------

@pytest.mark.parametrize("preset, extra, held", [
    ("nemotron3_nano_tiny", {"hybrid_override_pattern": "M*M"}, 8),
    ("ling3_flash_tiny", {"first_k_dense_replace": 7,
                          "hybrid_override_pattern": "DDDDDAD"}, 16),
])
def test_an_expert_free_pattern_gives_an_empty_table(preset, extra, held):
    """`hidden` of the other hybrid stacks with no expert layer: a table of
    no rows and the share's columns, which the train step's routing
    metrics take, not a stack of nothing."""
    cfg = get_config(preset)
    model = build_model(ModelConfig(
        name=cfg.model.name, num_classes=cfg.model.num_classes,
        compute_dtype="float32", extra={**cfg.model.extra, **extra}))
    assert model.expert_layers == ()
    tokens = jnp.zeros((1, cfg.model.extra["seq_len"]), jnp.int32)
    params = jax.eval_shape(model.init, jax.random.key(0), tokens)
    _, counts = jax.eval_shape(lambda p: model.apply(
        p, tokens, method="hidden"), params)
    assert counts.shape == (0, held + 1)
    loss, counts = jax.eval_shape(lambda p: model.apply(
        p, tokens, tokens, method="next_token_loss"), params)
    assert loss.shape == () and counts.shape == (0, held + 1)
    assert np.dtype(counts.dtype) == np.int32
