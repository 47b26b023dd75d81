"""Mistral-Small-4's block (models/mistral4.py) at a small size on the CPU
(hidden 64, 8 experts top-2, 2 layers, vocabulary 256, sequences of 32):
the flax model against the plain reference (chipbench/reference/mistral4.py)
on seeded weights, whole and as a share of the experts; the shares add up
to the uncut layer; routing under skew drops nothing; the rotary
frequencies, the interleaved rotation and the llama-4 query scale against
hand values; `python train.py` with the tiny preset through `Trainer`
against the reference's steps; the routed path at loads that take one pass
over its buffers and more; and the image step left as it was."""

import io
import math
import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.reference import lm_step, mistral4 as ref
from chipbench.reference.ops import Ops
from distributed_vgg_f_tpu.config import (ModelConfig, apply_overrides,
                                          get_config)
from distributed_vgg_f_tpu.models import mistral4
from distributed_vgg_f_tpu.models.registry import build_model
from distributed_vgg_f_tpu.parallel.mesh import MeshSpec, build_mesh
from distributed_vgg_f_tpu.train.trainer import Trainer
from distributed_vgg_f_tpu.utils.logging import MetricLogger

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TINY = get_config("mistral_small4_tiny")
SEQ = TINY.model.extra["seq_len"]
PUBLISHED_ROPE = dict(TINY.model.extra["rope_parameters"])


def _model(**extra):
    cfg = ModelConfig(name="mistral4", num_classes=TINY.model.num_classes,
                      compute_dtype="float32",
                      extra={**TINY.model.extra, **extra})
    return build_model(cfg), dict(cfg.extra)


def _seeded(model, seed=1):
    tokens = jax.random.randint(jax.random.key(seed), (2, SEQ + 1), 0,
                                TINY.model.num_classes)
    params = model.init({"params": jax.random.key(seed + 1)},
                        tokens[:, :-1])["params"]
    return params, tokens


def _rel(a, b):
    return float(jnp.linalg.norm(a - b) / (jnp.linalg.norm(b) + 1e-30))


# ---- the model against the plain reference ---------------------------------

CASES = {
    "full": {},
    "share": {"first_expert": 2, "experts_held": 4},
    # an original length the sequence passes, so that the YaRN ramp and the
    # llama-4 query scale are not 1 everywhere
    "short_original": {"rope_parameters": {
        **PUBLISHED_ROPE, "original_max_position_embeddings": 16}},
    # the Pallas kernel under its interpreter (switched on below)
    "flash": {},
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_model_matches_the_reference(case, monkeypatch):
    if case == "flash":
        from distributed_vgg_f_tpu.ops import flash_attention
        monkeypatch.setattr(flash_attention, "INTERPRET", True)
    model, arch = _model(**CASES[case])
    share = (arch.get("first_expert", 0),
             arch.get("experts_held", arch["n_routed_experts"]))
    params, tokens = _seeded(model)
    inputs, targets = tokens[:, :-1], tokens[:, 1:]

    logits = model.apply({"params": params}, inputs)
    want_logits, want_loads = ref.forward(params, inputs, arch, share)
    assert float(jnp.max(jnp.abs(logits[:, :4] - want_logits[:, :4]))) < 2e-4

    program = lambda p: model.apply({"params": p}, inputs, targets,
                                    method="next_token_loss")
    (loss, counts), grads = jax.value_and_grad(program, has_aux=True)(params)
    want_loss, want_grads = jax.value_and_grad(
        lambda p: ref.loss(p, tokens, arch, share))(params)
    assert abs(float(loss) - float(want_loss)) < 1e-5 * float(want_loss)
    assert np.array_equal(counts[:, :-1], want_loads.sum(0))
    assert not counts[:, -1].any()                     # nothing dropped
    worst = max(jax.tree.leaves(jax.tree.map(_rel, grads, want_grads)))
    assert worst < (2e-3 if case == "flash" else 1e-4), worst


# ---- the shares add up ------------------------------------------------------

def _layer(seed=5):
    """One expert layer's weights with every expert held, and an input."""
    model, arch = _model()
    params, _ = _seeded(model, seed)
    u = jax.random.normal(jax.random.key(seed), (SEQ, arch["hidden_size"]))
    return params["layer_0"]["moe"], u, arch


def _cut(p, first, held):
    """The share's weights: its experts' slices, everything else whole."""
    return {k: (v[first:first + held] if k.startswith("experts_") else v)
            for k, v in p.items()}


def _program_share(p, u, arch, first, held):
    layer = mistral4.ExpertShare(
        **{k: arch[k] for k in mistral4._EXPERTS}, first_expert=first,
        experts_held=held, compute_dtype=jnp.float32)
    out, counts = layer.apply({"params": _cut(p, first, held)}, u[None])
    return out[0], counts


def _reference_share(p, u, arch, first, held):
    return ref.experts(_cut(p, first, held), u, arch, (first, held),
                       Ops("float32"))


@pytest.mark.parametrize("side", ["program", "reference"])
def test_the_shares_add_up_to_the_uncut_layer(side):
    """Four shares of two experts each, the shared expert counted once:
    their sum is what the uncut reference layer gives."""
    p, u, arch = _layer()
    whole, whole_loads = ref.experts(p, u, arch, (0, 8), Ops("float32"))
    shared = ref.experts(p, u, arch, (0, 0), Ops("float32"))[0]
    one = _program_share if side == "program" else _reference_share
    parts = [one(p, u, arch, first, 2) for first in (0, 2, 4, 6)]
    total = sum(out - shared for out, _ in parts) + shared
    assert float(jnp.max(jnp.abs(total - whole))) < 1e-5
    loads = np.concatenate([np.asarray(c)[:2] for _, c in parts])
    assert np.array_equal(loads, whole_loads)
    assert loads.sum() == SEQ * arch["num_experts_per_tok"]


@pytest.mark.parametrize("favoured, load", [((3, 5), [0, SEQ]),
                                            ((2, 3), [SEQ, SEQ]),
                                            ((0, 6), [0, 0])])
def test_routing_under_skew_drops_nothing(favoured, load):
    """Every token to the same two experts, of which this share (experts 2
    and 3) holds one, both or none: the loads say so, nothing is dropped,
    and program and reference agree."""
    p, u, arch = _layer()
    u = jnp.abs(u) + 1.0               # every logit then grows with its column
    column = jnp.zeros(8).at[jnp.asarray(favoured)].set(
        jnp.asarray([0.02, 0.01]))
    p = {**p, "router": jnp.ones_like(p["router"]) * column}
    out, counts = _program_share(p, u, arch, 2, 2)
    want, want_loads = _reference_share(p, u, arch, 2, 2)
    assert list(np.asarray(counts)) == load + [0]
    assert list(np.asarray(want_loads)) == load
    assert float(jnp.max(jnp.abs(out - want))) < 1e-5
    if not any(load):                  # to none: the shared expert alone
        shared = ref.experts(p, u, arch, (0, 0), Ops("float32"))[0]
        assert float(jnp.max(jnp.abs(out - shared))) < 1e-5


# ---- the routed path in passes ----------------------------------------------

#: tokens of the passes test: 2 of 8 experts held and top-2 make 1024
#: assignments and buffers of 512 rows, so a load can pass them
ROWS = 512
#: (tokens with both choices held, tokens with one choice held) -> passes;
#: the rest of the batch goes to two absent experts. `None`: a random router
PASSES = {
    "none_held": ((0, 0), 1),
    "usual": (None, 1),
    "exactly_the_buffers": ((256, 0), 1),
    "one_more_row": ((256, 1), 2),
    "every_assignment": ((512, 0), 2),
}


def _steered(p, u, kinds):
    """Input and router under which the first `kinds[0]` tokens choose
    experts (2, 3), the next `kinds[1]` experts (3, 5), the others (0, 6):
    three indicator features that only the router's first rows read."""
    both, one = kinds
    kind = jnp.where(jnp.arange(ROWS) < both, 0,
                     jnp.where(jnp.arange(ROWS) < both + one, 1, 2))
    u = u.at[:, :3].set(jax.nn.one_hot(kind, 3))
    router = jnp.zeros_like(p["router"])
    for row, (first, second) in enumerate([(2, 3), (3, 5), (0, 6)]):
        router = router.at[row, first].set(2.0).at[row, second].set(1.0)
    return {**p, "router": router}, u


@pytest.mark.parametrize("case", list(PASSES))
def test_routed_path_takes_every_assignment_in_as_many_passes_as_it_needs(
        case):
    """The share (experts 2 and 3 of 8) against the plain reference in
    value, input gradient and the three expert-weight gradients, at loads
    below, at and above what its buffers hold; the passes it counted, and
    nothing dropped."""
    model, arch = _model()
    p = _cut(_seeded(model, 5)[0]["layer_0"]["moe"], 2, 2)
    u = jax.random.normal(jax.random.key(7), (ROWS, arch["hidden_size"]))
    kinds, want_passes = PASSES[case]
    if kinds is not None:
        p, u = _steered(p, u, kinds)
    layer = mistral4.ExpertShare(
        **{k: arch[k] for k in mistral4._EXPERTS}, first_expert=2,
        experts_held=2, compute_dtype=jnp.float32)
    cotangent = jax.random.normal(jax.random.key(8), u.shape)

    def program(p, u):
        (out, counts), sown = layer.apply({"params": p}, u[None],
                                          mutable=["counters"])
        return jnp.sum(out[0] * cotangent), (out[0], counts,
                                             sown["counters"])

    def reference(p, u):
        out, loads = ref.experts(p, u, arch, (2, 2), Ops("float32"))
        return jnp.sum(out * cotangent), (out, loads)

    (_, (out, counts, counters)), grads = jax.value_and_grad(
        program, argnums=(0, 1), has_aux=True)(p, u)
    (_, (want, loads)), want_grads = jax.value_and_grad(
        reference, argnums=(0, 1), has_aux=True)(p, u)
    held = int(np.asarray(loads).sum())
    if kinds is not None:
        assert held == 2 * kinds[0] + kinds[1]
    assert counters["capacity"] == (512,)
    assert int(counters["passes"][0]) == want_passes == max(
        1, -(-held // 512))
    assert list(np.asarray(counts)) == list(np.asarray(loads)) + [0]
    assert float(jnp.max(jnp.abs(out - want))) < 1e-5
    assert _rel(grads[1], want_grads[1]) < 1e-5
    for leaf in ("experts_gate_proj", "experts_up_proj",
                 "experts_down_proj"):
        assert _rel(grads[0][leaf], want_grads[0][leaf]) < 1e-5, leaf


def test_routed_capacity_follows_the_share():
    """Rows of the routed buffers from shapes alone: twice the balanced
    load in whole tiles, everything where every expert is held."""
    assert mistral4.routed_capacity(4096 * 4, 8, 128) == 2048    # the cell
    assert mistral4.routed_capacity(4096 * 4, 128, 128) == 4096 * 4
    assert mistral4.routed_capacity(1024, 2, 8) == 512
    assert mistral4.routed_capacity(64, 2, 8) == 64   # under one tile: all
    assert mistral4.routed_capacity(4096 * 4, 9, 128) == 2560


def test_a_step_through_the_trainer_publishes_the_routed_passes():
    """One logged step of the tiny preset with telemetry on: the step's
    metrics carry each layer's passes and the trainer's gauges their
    largest, beside the rows the routed buffers hold (every expert is held
    there: all 128 assignments, one pass)."""
    from distributed_vgg_f_tpu import telemetry
    cfg = apply_overrides(TINY, {"train.steps": 1, "train.log_every": 1})
    mesh = build_mesh(MeshSpec((cfg.mesh.data_axis,), (1,)),
                      jax.devices()[:1])
    stream = io.StringIO()
    telemetry.reset()
    telemetry.configure(enabled=True)
    try:
        Trainer(cfg, mesh=mesh, logger=MetricLogger(stream=stream)).fit()
        gauges = telemetry.get_registry().snapshot_split()["gauges"]
    finally:
        telemetry.reset()
        telemetry.configure(enabled=True)
    assignments = cfg.data.global_batch_size * SEQ \
        * cfg.model.extra["num_experts_per_tok"]
    assert gauges["moe/routed_passes"] == 1
    assert gauges["moe/routed_capacity"] == assignments
    assert gauges["moe/assignments_held"] \
        == assignments * cfg.model.extra["num_hidden_layers"]
    assert gauges["moe/dropped_assignments"] == 0
    assert "moe_passes/layer_1=1" in stream.getvalue()


# ---- rotary embedding against hand values ----------------------------------

def test_yarn_frequencies_by_hand():
    """64 rope dims, base 10000, factor 128, original length 8192, betas
    32 and 1: low = floor(64 ln(8192 / 64 pi) / (2 ln 10000)) = 12,
    high = ceil(64 ln(8192 / 2 pi) / (2 ln 10000)) = 25."""
    freqs = mistral4.yarn_inv_freq(64, 10000, 128, 8192, 32, 1)
    plain = lambda i: 10000.0 ** (-2 * i / 64)
    assert freqs.shape == (32,)
    for i in (0, 7, 12):
        assert math.isclose(freqs[i], plain(i), rel_tol=1e-6)
    for i in (25, 31):
        assert math.isclose(freqs[i], plain(i) / 128, rel_tol=1e-6)
    ramp = (18 - 12) / (25 - 12)
    assert math.isclose(freqs[18], plain(18) * (1 - ramp + ramp / 128),
                        rel_tol=1e-6)
    np.testing.assert_allclose(freqs, ref.inv_freq(PUBLISHED_ROPE, 64),
                               rtol=1e-6)
    m = 0.1 * math.log(128) + 1
    assert math.isclose(mistral4.yarn_attention_scale(128, 128, 1),
                        128 ** -0.5 * m * m, rel_tol=1e-12)


def test_interleaved_rotation_by_hand():
    """Position 1, two pairs: (1, 0) turns to (cos a, sin a), (0, 1) to
    (-sin b, cos b); position 0 stays."""
    freqs = jnp.asarray([0.5, 0.25])
    x = jnp.asarray([[1.0, 0.0, 0.0, 1.0]] * 2)
    out = np.asarray(mistral4.rotate_interleaved(x, jnp.arange(2), freqs))
    np.testing.assert_allclose(out[0], [1, 0, 0, 1], atol=1e-7)
    np.testing.assert_allclose(out[1], [math.cos(0.5), math.sin(0.5),
                                        -math.sin(0.25), math.cos(0.25)],
                               atol=1e-6)
    heads = jnp.broadcast_to(x[None, :, None, :], (1, 2, 3, 4))
    np.testing.assert_allclose(
        np.asarray(mistral4.rotate_interleaved(heads, jnp.arange(2), freqs)
                   )[0, :, 1], out, atol=1e-7)
    np.testing.assert_allclose(np.asarray(ref.rope_pairs(x, freqs)), out,
                               atol=1e-7)


def test_llama4_query_scale_at_original_length_16():
    scale = np.asarray(mistral4.llama4_query_scale(jnp.arange(48), 16, 0.1))
    assert scale[0] == scale[15] == 1.0
    np.testing.assert_allclose(scale[[16, 31, 32]],
                               [1 + 0.1 * math.log(2)] * 2
                               + [1 + 0.1 * math.log(3)], rtol=1e-6)


# ---- through the trainer ----------------------------------------------------

def _reference_steps(trainer, cfg, steps=3):
    """The plain reference's steps on the seeded token source's own
    batches, from the trainer's own initial weights."""
    from distributed_vgg_f_tpu.data.synthetic_tokens import SyntheticTokens
    extra = dict(cfg.model.extra)
    source = SyntheticTokens(cfg.data.global_batch_size, extra["seq_len"],
                             cfg.model.num_classes, seed=cfg.train.seed)
    batches = [jnp.asarray(next(source)["tokens"]) for _ in range(steps)]
    params = trainer.init_state().params
    recipe = {"base_lr": cfg.optim.base_lr, "momentum": cfg.optim.momentum,
              "reference_batch": cfg.optim.reference_batch_size,
              "global_batch": cfg.data.global_batch_size,
              "weight_decay": cfg.optim.weight_decay,
              "schedule": cfg.optim.schedule}
    return lm_step.follow(extra, (0, extra["n_routed_experts"]), recipe,
                          # a copy: the follower donates what it updates
                          lambda group: jax.tree.map(jnp.copy, params[group]),
                          list(params), batches,
                          steps=steps, block_rows=16), params


def test_train_py_with_the_tiny_preset_equals_the_reference(tmp_path):
    """`python train.py --config mistral_small4_tiny`: three steps through
    `Trainer.fit` on the seeded token source give the reference's three
    losses; in process, the weights also move as the reference's do."""
    done = subprocess.run(
        [sys.executable, os.path.join(ROOT, "train.py"), "--config",
         "mistral_small4_tiny"],
        # one CPU device, as a user's shell has (the suite's eight virtual
        # ones would make a mesh of eight for a batch of two)
        env={**os.environ, "JAX_PLATFORMS": "cpu", "XLA_FLAGS": ""},
        capture_output=True, text=True, timeout=600, cwd=str(tmp_path))
    assert done.returncode == 0, done.stderr[-2000:]
    lines = re.findall(r"^\[train\] step=(\d) .*? loss=(\S+) .*"
                       r"moe_dropped/layer_0=(\S+)", done.stdout, re.M)
    assert [int(step) for step, _, _ in lines] == [1, 2, 3], done.stdout

    mesh = build_mesh(MeshSpec((TINY.mesh.data_axis,), (1,)),
                      jax.devices()[:1])
    trainer = Trainer(TINY, mesh=mesh,
                      logger=MetricLogger(stream=io.StringIO()))
    want, start = _reference_steps(trainer, TINY)
    for (_, loss, dropped), ref_loss in zip(lines, want["losses"]):
        assert abs(float(loss) - float(ref_loss)) < 2e-5 * float(ref_loss)
        assert float(dropped) == 0
    state = trainer.fit()
    moved = jax.tree.map(lambda a, b: float(jnp.linalg.norm(a - b)),
                         state.params, start)
    gaps = jax.tree.map(lambda a, b: abs(a - float(b)) / float(b), moved,
                        want["change_norms"])
    assert max(jax.tree.leaves(gaps)) < 1e-3


# ---- the image step is left as it was --------------------------------------

@pytest.mark.parametrize("preset", ["vggf_imagenet_dp", "resnet50_imagenet"])
def test_an_image_batch_traces_to_the_step_without_the_token_path(preset):
    """The batch's kind comes from the model's ingest descriptor; an image
    model's step holds nothing of the language model's path, and a token
    batch handed to it is refused at trace time."""
    cfg = apply_overrides(get_config(preset), {
        "data.image_size": 32, "model.num_classes": 10,
        "data.global_batch_size": 8, "mesh.num_data": 1,
        **({"model.extra": {"stage_sizes": (1, 1, 1, 1)}}
           if preset.startswith("resnet") else {})})
    mesh = build_mesh(MeshSpec((cfg.mesh.data_axis,), (1,)),
                      jax.devices()[:1])
    trainer = Trainer(cfg, mesh=mesh,
                      logger=MetricLogger(stream=io.StringIO()))
    assert trainer.batch_kind == "image"
    state = jax.eval_shape(trainer.init_state)
    batch = {"image": jax.ShapeDtypeStruct((8, 32, 32, 3), jnp.uint8),
             "label": jax.ShapeDtypeStruct((8,), jnp.int32)}
    text = trainer.train_step.lower(state, batch, trainer.base_rng()) \
        .as_text(debug_info=True)
    for name in ("moe_", "mla_", "lm_head", "next_token"):
        assert name not in text
    with pytest.raises(KeyError):
        trainer.train_step.lower(
            state, {"tokens": jax.ShapeDtypeStruct((8, 33), jnp.int32)},
            trainer.base_rng())
