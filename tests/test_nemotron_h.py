"""Nemotron-3-Nano's hybrid stack (models/nemotron_h.py) at a small size on
the CPU (`nemotron3_nano_tiny`: `MEM*E`, hidden 64, 4 Mamba heads of 8 in 2
groups, state 16, chunks of 8 over sequences of 32, 4 query heads on 2 key
heads, 8 relu^2 experts top-2 beside a shared one): the flax model against
the plain reference (chipbench/reference/nemotron_h.py) on seeded weights,
each mixer alone and the whole loss and gradient, whole and as a share; the
chunked scan (ops/ssd.py) against the literal recurrence, which a scan that
forgets its state between chunks fails; the shares add up to the uncut
layer; and three `Trainer` steps against the reference's follower.
(Both sides run jitted: eagerly the reference's nested scans take minutes.)"""

import io
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.reference import hybrid_lm_step, nemotron_h as ref
from chipbench.reference.ops import Ops
from distributed_vgg_f_tpu.config import ModelConfig, get_config
from distributed_vgg_f_tpu.models import nemotron_h
from distributed_vgg_f_tpu.models.mistral4 import ExpertShare
from distributed_vgg_f_tpu.models.registry import build_model
from distributed_vgg_f_tpu.ops import ssd, ssd_pallas
from distributed_vgg_f_tpu.parallel.mesh import MeshSpec, build_mesh
from distributed_vgg_f_tpu.train.trainer import Trainer
from distributed_vgg_f_tpu.utils.logging import MetricLogger

TINY = get_config("nemotron3_nano_tiny")
SEQ = TINY.model.extra["seq_len"]

#: float32 on both sides: what is left is the order of the sums (the
#: chunked scan adds a chunk's products where the recurrence adds positions)
TOLERANCE = 1e-4


def _model(**extra):
    cfg = ModelConfig(name="nemotron_h", num_classes=TINY.model.num_classes,
                      compute_dtype="float32",
                      extra={**TINY.model.extra, **extra})
    return build_model(cfg), dict(cfg.extra)


def _seeded(model, seed=1):
    """The model's own initial weights, with the selection bias, the
    convolution's bias and the norm scales moved off their constants."""
    tokens = jax.random.randint(jax.random.key(seed), (2, SEQ + 1), 0,
                                TINY.model.num_classes)
    params = model.init({"params": jax.random.key(seed + 1)},
                        tokens[:, :-1])["params"]
    keys = iter(jax.random.split(jax.random.key(seed + 2), 64))

    def shake(path, leaf):
        kind = str(path[-1].key)
        if kind in ("bias", "D") or kind == "scale":
            return leaf + 0.1 * jax.random.normal(next(keys), leaf.shape)
        return leaf
    return jax.tree_util.tree_map_with_path(shake, params), tokens


def _rel(a, b):
    return float(jnp.linalg.norm(a - b) / (jnp.linalg.norm(b) + 1e-30))


# ---- the chunked scan against the literal recurrence -----------------------

def _scan_inputs(seed=0, seq=40, heads=4, dim=8, groups=2, state=16):
    keys = jax.random.split(jax.random.key(seed), 6)
    return dict(
        x=jax.random.normal(keys[0], (2, seq, heads, dim)),
        dt=jax.nn.softplus(jax.random.normal(keys[1], (2, seq, heads)) - 2),
        A=-jnp.exp(jax.random.uniform(keys[2], (heads,), minval=0.0,
                                      maxval=2.5)),
        B=jax.random.normal(keys[3], (2, seq, groups, state)),
        C=jax.random.normal(keys[4], (2, seq, groups, state)),
        D=jax.random.normal(keys[5], (heads,)))


def _literal(x, dt, A, B, C, D, reset_every=None):
    per_head = lambda v: jnp.repeat(v, x.shape[2] // v.shape[2], axis=2)
    y = jnp.stack([ref.recurrence(x[i], dt[i], A, per_head(B)[i],
                                  per_head(C)[i], reset_every=reset_every)
                   for i in range(x.shape[0])])
    return y + D[:, None] * x


def _forgetful(x, dt, A, B, C, D, chunk):
    """The chunked scan with the state reset at every chunk boundary: each
    chunk as a sequence of its own."""
    b, t = x.shape[:2]
    cut = lambda v: v.reshape(b * t // chunk, chunk, *v.shape[2:])
    return ssd.ssd(cut(x), cut(dt), A, cut(B), cut(C), D,
                   chunk=chunk).reshape(x.shape)


@pytest.mark.parametrize("chunk", [8, 40, 128])
def test_chunked_scan_equals_the_literal_recurrence(chunk):
    """Five chunks of 8, one chunk of 40, and a chunk longer than the
    sequence: values and every input's gradient."""
    inputs = _scan_inputs()
    weigh = jax.random.normal(jax.random.key(9), inputs["x"].shape)
    ours = lambda kw: jnp.sum(ssd.ssd(**kw, chunk=chunk) * weigh)
    theirs = lambda kw: jnp.sum(_literal(**kw) * weigh)
    assert _rel(ssd.ssd(**inputs, chunk=chunk),
                jax.jit(_literal)(**inputs)) < TOLERANCE
    got, want = jax.jit(jax.grad(ours))(inputs), \
        jax.jit(jax.grad(theirs))(inputs)
    for name in inputs:
        assert _rel(got[name], want[name]) < TOLERANCE, name


def test_a_scan_that_forgets_its_state_fails_the_same_tolerance():
    inputs = _scan_inputs()
    want = _literal(**inputs)
    assert _rel(ssd.ssd(**inputs, chunk=8), want) < TOLERANCE
    forgetful = _forgetful(**inputs, chunk=8)
    assert _rel(forgetful, want) > 100 * TOLERANCE
    # and it is the fault the reference plants under that name
    assert _rel(forgetful, _literal(**inputs, reset_every=8)) < TOLERANCE


def test_smallest_decay_is_the_scan_s_own():
    inputs = _scan_inputs()
    want = float(jnp.min(jnp.exp(inputs["dt"] * inputs["A"])))
    assert abs(float(ssd.smallest_decay(inputs["dt"], inputs["A"])) - want) \
        < 1e-6
    assert 0 < want < 1


# ---- the Pallas kernels (ops/ssd_pallas.py), interpreted -------------------

@pytest.fixture
def interpreted(monkeypatch):
    """`ssd.ssd` takes the kernels off a TPU, in the Pallas interpreter."""
    monkeypatch.setattr(ssd_pallas, "INTERPRET", True)


def _tile_inputs(chunks, groups, dtype, seed=0):
    """Two sequences at the smallest sizes the kernels take: chunks of 128,
    two heads of 64 a group, state 128."""
    inputs = _scan_inputs(seed, seq=128 * chunks, heads=2 * groups, dim=64,
                          groups=groups, state=128)
    return {k: v.astype(dtype) if k in "xBC" else v
            for k, v in inputs.items()}


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("groups", [1, 2])
@pytest.mark.parametrize("chunks", [1, 3])
def test_kernels_equal_the_xla_form_and_the_literal_recurrence(
        interpreted, chunks, groups, dtype):
    """Values and every input's gradient (x, dt, A, B, C, D). In float32
    the kernels, the XLA form and the recurrence differ by the order of
    their sums; in bf16 the kernels round what the XLA form rounds (its own
    backward rounds the cotangents of its products once more, which is the
    0.5 % between the two gradients) and the float32 recurrence on the
    same rounded inputs stands a bf16 product's rounding away."""
    inputs = _tile_inputs(chunks, groups, jnp.dtype(dtype))
    assert ssd.takes_kernels(inputs["x"].shape, inputs["B"].shape, 128)
    weigh = jax.random.normal(jax.random.key(9), inputs["x"].shape)
    as_f32 = lambda kw: {k: v.astype(jnp.float32) for k, v in kw.items()}
    forms = {"kernels": lambda kw: ssd.ssd(**kw, chunk=128),
             "xla": lambda kw: ssd.ssd_xla(**kw, chunk=128),
             "literal": lambda kw: _literal(**as_f32(kw))}
    assert "pallas_call" in str(jax.make_jaxpr(forms["kernels"])(inputs))
    assert "pallas_call" not in str(jax.make_jaxpr(forms["xla"])(inputs))
    value, grad = {}, {}
    for name, form in forms.items():
        value[name], grad[name] = jax.jit(jax.value_and_grad(
            lambda kw, form=form: jnp.sum(form(kw) * weigh)))(inputs)
    y = jax.jit(forms["kernels"])(inputs)
    assert y.dtype == jnp.float32 and y.shape == inputs["x"].shape
    limits = {"xla": TOLERANCE, "literal": TOLERANCE} \
        if dtype == "float32" else {"xla": 1e-2, "literal": 2e-2}
    for other, limit in limits.items():
        assert _rel(y, jax.jit(forms[other])(inputs)) < limit, other
        for name in inputs:
            assert grad["kernels"][name].dtype == inputs[name].dtype
            assert _rel(grad["kernels"][name].astype(jnp.float32),
                        grad[other][name].astype(jnp.float32)) < limit, \
                (other, name)


@pytest.mark.parametrize("shape", [
    dict(seq=512, heads=2, dim=64, groups=1, state=128, chunk=256),
    dict(seq=256, heads=4, dim=32, groups=1, state=256, chunk=128),
    dict(seq=256, heads=2, dim=128, groups=2, state=128, chunk=128)],
    ids=["chunks_of_256", "heads_of_32_state_256", "heads_of_128"])
def test_kernels_at_the_other_sizes_they_admit(interpreted, shape):
    """A chunk of two lane tiles, four heads to a lane tile, one head to a
    lane tile: float32 values and gradients against the XLA form."""
    chunk = shape.pop("chunk")
    inputs = _scan_inputs(**shape)
    assert ssd.takes_kernels(inputs["x"].shape, inputs["B"].shape, chunk)
    weigh = jax.random.normal(jax.random.key(9), inputs["x"].shape)
    got, want = (jax.jit(jax.value_and_grad(
        lambda kw, form=form: jnp.sum(form(**kw, chunk=chunk) * weigh)))(
            inputs) for form in (ssd.ssd, ssd.ssd_xla))
    assert abs(float(got[0]) - float(want[0])) \
        < TOLERANCE * abs(float(want[0])) + 1e-3
    for name in inputs:
        assert _rel(got[1][name], want[1][name]) < TOLERANCE, name


def test_a_forgetful_scan_fails_the_same_tolerance_against_the_kernels(
        interpreted):
    """`chunk_reset`, the fault the reference plants, is a hundred
    tolerances away from what the kernels carry from chunk to chunk."""
    inputs = _tile_inputs(3, 1, jnp.float32)
    got = ssd.ssd(**inputs, chunk=128)
    assert _rel(got, jax.jit(_literal)(**inputs)) < TOLERANCE
    forgetful = _forgetful(**inputs, chunk=128)
    assert _rel(forgetful, got) > 100 * TOLERANCE
    assert _rel(forgetful, jax.jit(_literal, static_argnames="reset_every")(
        **inputs, reset_every=128)) < TOLERANCE


@pytest.mark.parametrize("form", ["kernels", "xla"])
def test_a_rest_of_a_chunk_still_raises(form, monkeypatch):
    monkeypatch.setattr(ssd_pallas, "INTERPRET", form == "kernels")
    inputs = _scan_inputs(seq=200, heads=2, dim=64, groups=1, state=128)
    with pytest.raises(ValueError, match="neither may leave a rest"):
        ssd.ssd(**inputs, chunk=128)
    with pytest.raises(ValueError, match="neither may leave a rest"):
        ssd.ssd(**_scan_inputs(heads=3, groups=2), chunk=8)


@pytest.mark.parametrize("shape, taken", [
    # the benchmark's cell, and the smallest the kernels take
    (dict(t=8192, h=64, p=64, g=8, n=128, chunk=128), True),
    (dict(t=128, h=2, p=64, g=1, n=128, chunk=128), True),
    # the tiny preset; a state, a chunk, a group's lanes that are no tile
    (dict(t=32, h=4, p=8, g=2, n=16, chunk=8), False),
    (dict(t=256, h=2, p=64, g=1, n=64, chunk=128), False),
    (dict(t=256, h=2, p=64, g=1, n=128, chunk=64), False),
    (dict(t=256, h=1, p=64, g=1, n=128, chunk=128), False),
    # a sequence shorter than its chunk is one chunk of its own length
    (dict(t=64, h=2, p=64, g=1, n=128, chunk=128), False)])
def test_shapes_and_backend_choose_the_kernels(shape, taken, monkeypatch):
    x = (2, shape["t"], shape["h"], shape["p"])
    group = (2, shape["t"], shape["g"], shape["n"])
    assert ssd_pallas.applies(x, group, shape["chunk"]) == taken
    assert not ssd.takes_kernels(x, group, shape["chunk"])   # the CPU
    monkeypatch.setattr(ssd_pallas, "INTERPRET", True)
    assert ssd.takes_kernels(x, group, shape["chunk"]) == taken


#: a Mamba mixer at the smallest sizes the kernels take
TILE_MAMBA = {"mamba_num_heads": 2, "mamba_head_dim": 64, "n_groups": 1,
              "ssm_state_size": 128, "chunk_size": 128}


@pytest.mark.parametrize("case", ["tile_sizes", "tiny_preset"])
def test_mixer_sows_whether_it_took_the_kernels(case, interpreted):
    """`ssm_kernel` beside `ssm_chunks`: 1 at tile sizes, 0 for the tiny
    preset, interpreter on in both."""
    model, arch = _model(**(TILE_MAMBA if case == "tile_sizes" else {}))
    seq = 256 if case == "tile_sizes" else SEQ
    mixer = nemotron_h.Mamba2Mixer(**model.mixers["mamba"],
                                   compute_dtype=jnp.float32)
    u = jax.random.normal(jax.random.key(4), (2, seq, arch["hidden_size"]))
    params = mixer.init(jax.random.key(1), u)["params"]
    _, sown = mixer.apply({"params": params}, u, mutable=["counters"])
    counters = {k: v[0] for k, v in sown["counters"].items()}
    assert counters["ssm_kernel"] == (1 if case == "tile_sizes" else 0)
    assert counters["ssm_chunks"] == 2 * seq // arch["chunk_size"]


# ---- each mixer against the plain reference --------------------------------

MIXERS = {"mamba": (0, nemotron_h.Mamba2Mixer),
          "experts": (1, ExpertShare),
          "attention": (3, nemotron_h.GroupedQueryAttention),
          # the Pallas kernel with 2 query heads a key head, interpreted
          "attention_flash": (3, nemotron_h.GroupedQueryAttention),
          # the recurrence through the Pallas kernels, interpreted, at the
          # smallest sizes they take (`TILE_MAMBA`, two chunks)
          "mamba_kernels": (0, nemotron_h.Mamba2Mixer),
          # the routed path's widths padded to whole tiles (of 24 here:
          # hidden 64 -> 72, width 32 -> 48), as the cell's 2688 and 1856 are
          "experts_padded": (1, ExpertShare)}


@pytest.mark.parametrize("case", sorted(MIXERS))
def test_mixer_matches_the_reference(case, monkeypatch):
    if case == "attention_flash":
        from distributed_vgg_f_tpu.ops import flash_attention
        monkeypatch.setattr(flash_attention, "INTERPRET", True)
    if case == "experts_padded":
        from distributed_vgg_f_tpu.models import mistral4
        monkeypatch.setattr(mistral4, "ROUTED_WIDTH_TILE", 24)
        assert (mistral4._to_whole_tiles(64), mistral4._to_whole_tiles(32),
                mistral4._to_whole_tiles(24), mistral4._to_whole_tiles(16)) \
            == (8, 16, 0, 0)
    seq, extra = SEQ, {}
    if case == "mamba_kernels":
        monkeypatch.setattr(ssd_pallas, "INTERPRET", True)
        seq, extra = 256, TILE_MAMBA
    kind = case.split("_")[0]
    index, layer = MIXERS[case]
    model, arch = _model(**extra)
    params, _ = _seeded(model)
    p = params[f"layer_{index}"]["mixer"]
    u = jax.random.normal(jax.random.key(4), (2, seq, arch["hidden_size"]))
    weigh = jax.random.normal(jax.random.key(5), u.shape)
    kwargs = dict(model.mixers[kind], compute_dtype=jnp.float32)
    share = (0, arch["n_routed_experts"])

    def program(p, u):
        out = layer(**kwargs).apply({"params": p}, u)
        return jnp.sum((out[0] if kind == "experts" else out) * weigh)

    def reference(p, u):
        one = {"mamba": lambda row: ref.mamba(p, row, arch, Ops("float32")),
               "attention": lambda row: ref.attention(
                   p, row, arch, Ops("float32"), 16),
               "experts": lambda row: ref.experts(
                   p, row, arch, share, Ops("float32"))[0]}[kind]
        return jnp.sum(jnp.stack([one(row) for row in u]) * weigh)

    got, (d_p, d_u) = jax.jit(jax.value_and_grad(
        program, argnums=(0, 1)))(p, u)
    want, (want_p, want_u) = jax.jit(jax.value_and_grad(
        reference, argnums=(0, 1)))(p, u)
    # the interpreted kernel rounds its probabilities as the chip's does
    limit = 2e-3 if case == "attention_flash" else TOLERANCE
    assert abs(float(got) - float(want)) < limit * abs(float(want)) + 1e-4
    assert _rel(d_u, want_u) < limit
    gaps = jax.tree.map(_rel, d_p, want_p)
    if kind == "experts":
        # no gradient reaches the selection bias, on either side
        assert not np.asarray(d_p["router_bias"]).any()
        assert not np.asarray(want_p["router_bias"]).any()
        gaps = {**gaps, "router_bias": 0.0}
    assert max(jax.tree.leaves(gaps)) < limit, gaps


# ---- the whole model --------------------------------------------------------

@pytest.mark.parametrize("case", ["full", "share"])
def test_model_matches_the_reference(case):
    extra = {"first_expert": 2, "experts_held": 4} if case == "share" else {}
    model, arch = _model(**extra)
    share = (arch.get("first_expert", 0),
             arch.get("experts_held", arch["n_routed_experts"]))
    params, tokens = _seeded(model)
    inputs, targets = tokens[:, :-1], tokens[:, 1:]
    assert model.expert_layers == (1, 4)

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
    assert counts.shape == (2, share[1] + 1)       # the expert layers' rows
    assert np.array_equal(counts[:, :-1], want_loads.sum(0))
    assert not counts[:, -1].any()                 # nothing dropped
    grads, want_grads = (
        {k: ({**v, "mixer": {n: x for n, x in v["mixer"].items()
                             if n != "router_bias"}}
             if k.startswith("layer_") else v) for k, v in tree.items()}
        for tree in (grads, want_grads))
    worst = max(jax.tree.leaves(jax.tree.map(_rel, grads, want_grads)))
    assert worst < TOLERANCE, worst
    # the planted fault is seen by the same comparison
    broken = jax.jit(lambda p: ref.loss(p, tokens, arch, share,
                                        fault="chunk_reset"))(params)
    assert abs(float(broken) - float(want_loss)) > 1e-4 * float(want_loss)


# ---- the shares add up ------------------------------------------------------

def test_eight_shares_and_the_shared_expert_once_equal_the_uncut_layer():
    """The routed parts of all 8 shares (one expert each) plus the shared
    expert once, in the program and in the reference, equal the uncut
    layer; the loads add up to tokens x top-k."""
    model, arch = _model()
    params, _ = _seeded(model, seed=5)
    p = params["layer_1"]["mixer"]
    u = jax.random.normal(jax.random.key(5), (SEQ, arch["hidden_size"]))
    kwargs = dict(model.mixers["experts"], compute_dtype=jnp.float32)
    cut = lambda first: {k: (v[first:first + 1]
                             if k.startswith("experts_") else v)
                         for k, v in p.items()}

    whole, counts = ExpertShare(**kwargs).apply({"params": p}, u[None])
    want_whole, want_loads = ref.experts(p, u, arch, (0, 8), Ops("float32"))
    assert _rel(whole[0], want_whole) < TOLERANCE
    assert int(counts[:-1].sum()) == SEQ * arch["num_experts_per_tok"]

    shared = ref.experts(p, u, arch, (0, 0), Ops("float32"))[0]
    routed, routed_ref, loads = 0.0, 0.0, []
    for first in range(8):
        out, count = ExpertShare(**{**kwargs, "first_expert": first,
                                    "experts_held": 1}).apply(
            {"params": cut(first)}, u[None])
        routed = routed + (out[0] - shared)
        part, load = ref.experts(cut(first), u, arch, (first, 1),
                                 Ops("float32"), shared=False)
        routed_ref = routed_ref + part
        loads.append(int(count[0]))
        assert int(load[0]) == int(count[0]) and int(count[-1]) == 0
    assert _rel(routed + shared, whole[0]) < TOLERANCE
    assert _rel(routed_ref + shared, want_whole) < TOLERANCE
    assert loads == [int(x) for x in want_loads]


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


def test_fit_with_the_tiny_preset_logs_every_layer_under_its_own_index():
    """`Trainer.fit` on the seeded token source, telemetry on: three steps,
    the first loss the reference's on the source's first batch, the expert
    layers' counters under 1 and 4, the Mamba layers' under 0 and 2, and
    the trainer's gauges over both kinds."""
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
        jnp.asarray(next(source)["tokens"]), steps=1, block_rows=16)
    telemetry.reset()
    telemetry.configure(enabled=True)
    try:
        trainer.fit()
        gauges = telemetry.get_registry().snapshot_split()["gauges"]
    finally:
        telemetry.reset()
        telemetry.configure(enabled=True)
    assert gauges["ssm/chunks"] == 2 * 8              # two Mamba layers
    assert gauges["ssm/kernel_layers"] == 0           # chunks of 8: XLA's
    assert 0 < gauges["ssm/decay_min"] < 1
    assert gauges["moe/assignments_held"] == 2 * 128  # two expert layers
    assert gauges["moe/dropped_assignments"] == 0
    log = stream.getvalue()
    lines = re.findall(r"^\[train\] step=(\d) .*? loss=(\S+) .*", log, re.M)
    assert [int(step) for step, _ in lines] == [1, 2, 3], log
    first = float(want["losses"][0])
    assert abs(float(lines[0][1]) - first) < 2e-5 * first
    for name in ("moe_held/layer_1", "moe_dropped/layer_4=0",
                 "moe_passes/layer_4=1", "ssm_chunks/layer_0=8",
                 "ssm_kernel/layer_0=0", "ssm_kernel/layer_2=0",
                 "ssm_decay_min/layer_2"):
        assert name in log, name
    assert "moe_held/layer_0" not in log and "ssm_chunks/layer_1" not in log


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
        tokens, steps=3, block_rows=16)
    batch, rng = trainer.shard({"tokens": np.asarray(tokens)}), \
        trainer.base_rng()
    for ref_loss, ref_load in zip(want["losses"], want["loads"]):
        state, metrics = trainer.train_step(state, batch, rng)
        assert abs(float(metrics["loss"]) - float(ref_loss)) \
            < 2e-5 * float(ref_loss)
        assert np.array_equal(np.asarray(metrics["moe_load"]), ref_load)
    moved = jax.tree.map(lambda a, b: float(jnp.linalg.norm(a - b)),
                         state.params, start)
    gaps = jax.tree.map(
        lambda a, b: abs(a - float(b)) / max(float(b), 1e-6), moved,
        want["change_norms"])
    assert max(jax.tree.leaves(gaps)) < 1e-3, gaps
