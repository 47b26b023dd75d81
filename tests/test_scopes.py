"""The names the program gives its device work (distributed_vgg_f_tpu/
scopes.py): every declared name has a `jax.named_scope` call site and
reaches the lowered program's debug info, in the forward or the backward
pass as it should; nothing outside the lists is named; and the two jitted
steps are called what they are."""

import io
import os
import re

import jax
import jax.numpy as jnp
import pytest

from distributed_vgg_f_tpu import scopes
from distributed_vgg_f_tpu.config import apply_overrides, get_config
from distributed_vgg_f_tpu.parallel.mesh import MeshSpec, build_mesh
from distributed_vgg_f_tpu.train.trainer import Trainer
from distributed_vgg_f_tpu.utils.logging import MetricLogger

PACKAGE = os.path.dirname(scopes.__file__)
SIZE, ROWS = 32, 8


def _lowered_steps(preset: str, chips: int, **overrides):
    """(train step, eval step) of a shrunk preset as lowered text with
    debug info; nothing is compiled."""
    cfg = apply_overrides(get_config(preset), {
        "data.image_size": SIZE, "model.num_classes": 10,
        "data.global_batch_size": ROWS, "mesh.num_data": chips, **overrides})
    mesh = build_mesh(MeshSpec((cfg.mesh.data_axis,), (chips,)),
                      jax.devices()[:chips])
    trainer = Trainer(cfg, mesh=mesh, logger=MetricLogger(stream=io.StringIO()))
    state = jax.eval_shape(trainer.init_state)
    batch = {"image": jax.ShapeDtypeStruct((ROWS, SIZE, SIZE, 3), jnp.uint8),
             "label": jax.ShapeDtypeStruct((ROWS,), jnp.int32)}
    train = trainer.train_step.lower(state, batch, trainer.base_rng())
    evaluate = trainer.eval_step.lower(state, batch)
    return (train.as_text(debug_info=True), evaluate.as_text(debug_info=True))


def _lowered_token_step(preset: str = "mistral_small4_tiny") -> str:
    """A language model's train step (a tiny preset) as lowered text."""
    cfg = get_config(preset)
    mesh = build_mesh(MeshSpec((cfg.mesh.data_axis,), (1,)),
                      jax.devices()[:1])
    trainer = Trainer(cfg, mesh=mesh, logger=MetricLogger(stream=io.StringIO()))
    batch = {"tokens": jax.ShapeDtypeStruct(
        (cfg.data.global_batch_size, cfg.model.extra["seq_len"] + 1),
        jnp.int32)}
    return trainer.train_step.lower(
        jax.eval_shape(trainer.init_state), batch,
        trainer.base_rng()).as_text(debug_info=True)


def _lowered_forward(name: str, **extra) -> str:
    from distributed_vgg_f_tpu.config import ModelConfig
    from distributed_vgg_f_tpu.models.registry import build_model
    model = build_model(ModelConfig(name=name, num_classes=10, extra=extra))
    x = jax.ShapeDtypeStruct((2, SIZE, SIZE, 3), jnp.float32)
    variables = jax.eval_shape(model.init, jax.random.key(0), x)
    return jax.jit(model.apply).lower(variables, x).as_text(debug_info=True)


@pytest.fixture(scope="module")
def lowered():
    """Two devices for VGG-F, so that its ZeRO-2 flags stand and the
    exchange has something to exchange; every stage of the augmentation
    switched on."""
    vggf, vggf_eval = _lowered_steps(
        "vggf_imagenet_dp", 2, **{"data.augment.crop_jitter": 2,
                                  "data.augment.rand_ops": 1})
    resnet, _ = _lowered_steps("resnet50_imagenet", 1,
                               **{"model.extra": {"stage_sizes": (1, 1, 1, 1)}})
    return {"vggf": vggf, "vggf_eval": vggf_eval, "resnet50": resnet,
            "vgg16": _lowered_forward("vgg16"),
            "vit_s16": _lowered_forward("vit_s16", depth=1),
            "mistral4": _lowered_token_step(),
            "nemotron_h": _lowered_token_step("nemotron3_nano_tiny"),
            "ling3": _lowered_token_step("ling3_flash_tiny"),
            "solar_open2": _lowered_token_step("solar_open2_tiny"),
            "jamba2": _lowered_token_step("jamba2_3b_tiny")}


def _stacks(text: str) -> set:
    """Every name stack of a lowered text, less its primitive."""
    return {loc.rpartition("/")[0]
            for loc in re.findall(r'loc\("([^"]*)"', text)}


def _holds(stacks: set, name: str) -> bool:
    """`name` is one scope of some stack, bare or inside `jvp(...)`."""
    scope = re.compile(rf"(^|[/(]){re.escape(name)}($|[/)])")
    return any(scope.search(stack) for stack in stacks)


#: where each declared name has to arrive
HOME = {**{name: "vggf" for name in scopes.PHASES},
        "lrn1": "vggf", "lrn2": "vggf", "pool1": "vggf", "pool2": "vggf",
        "pool5": "vggf", "pool3": "vgg16", "pool4": "vgg16",
        "pool_init": "resnet50", "gap": "resnet50",
        "embed_tokens": "vit_s16",
        **{name: "mistral4" for name in scopes.LM_LAYERS},
        **{name: "nemotron_h" for name in scopes.HYBRID_LM_LAYERS},
        **{name: "ling3" for name in scopes.LING_LM_LAYERS},
        **{name: "solar_open2" for name in scopes.SOLAR_LM_LAYERS},
        **{name: "jamba2" for name in scopes.JAMBA_LM_LAYERS}}


def test_every_declared_name_has_a_home():
    assert set(HOME) == set(scopes.PHASES) | set(scopes.LAYERS) \
        | set(scopes.LM_LAYERS) | set(scopes.HYBRID_LM_LAYERS) \
        | set(scopes.LING_LM_LAYERS) | set(scopes.SOLAR_LM_LAYERS) \
        | set(scopes.JAMBA_LM_LAYERS)
    assert not (set(scopes.PHASES) | set(scopes.LAYERS)
                | set(scopes.LM_LAYERS) | set(scopes.HYBRID_LM_LAYERS)
                | set(scopes.LING_LM_LAYERS) | set(scopes.SOLAR_LM_LAYERS)) \
        & set(scopes.JAMBA_LM_LAYERS)
    assert not (set(scopes.PHASES) | set(scopes.LAYERS)
                | set(scopes.LM_LAYERS) | set(scopes.HYBRID_LM_LAYERS)) \
        & set(scopes.LING_LM_LAYERS)
    assert not (set(scopes.PHASES) | set(scopes.LAYERS)
                | set(scopes.LM_LAYERS) | set(scopes.HYBRID_LM_LAYERS)
                | set(scopes.LING_LM_LAYERS)) & set(scopes.SOLAR_LM_LAYERS)
    assert not set(scopes.PHASES) & set(scopes.LAYERS)
    assert not (set(scopes.PHASES) | set(scopes.LAYERS)) \
        & set(scopes.LM_LAYERS)
    assert not (set(scopes.PHASES) | set(scopes.LAYERS)
                | set(scopes.LM_LAYERS)) & set(scopes.HYBRID_LM_LAYERS)


@pytest.mark.parametrize("name", scopes.PHASES + scopes.LAYERS
                         + scopes.LM_LAYERS + scopes.HYBRID_LM_LAYERS
                         + scopes.LING_LM_LAYERS + scopes.SOLAR_LM_LAYERS
                         + scopes.JAMBA_LM_LAYERS)
def test_declared_name_reaches_the_lowered_program(lowered, name):
    assert _holds(_stacks(lowered[HOME[name]]), name)


def test_the_hybrid_model_s_names_file_equals_the_declared_lists(lowered):
    """`chipbench/hybrid_lm_scopes.json`, the benchmark's own copy: its
    layers are the hybrid model's own names and the ones it shares (the
    expert share's, the embedding, the head), each of which reaches that
    model's lowered step, forward and backward; its phases are the first
    names file's."""
    import json
    from chipbench import scope_reduce
    here = os.path.dirname(scope_reduce.__file__)
    with open(os.path.join(here, "hybrid_lm_scopes.json")) as f:
        names = json.load(f)
    shared = {n for n in scopes.LM_LAYERS if not n.startswith("mla_")}
    assert set(names["layers"]) == set(scopes.HYBRID_LM_LAYERS) | shared \
        | {"embed_tokens"}
    assert names["phases"] == scope_reduce.declared()["phases"]
    assert set(names["ssm"]) | set(names["gqa"]) \
        == set(scopes.HYBRID_LM_LAYERS)
    assert set(names["moe"]) == {n for n in shared if n.startswith("moe_")}
    for group in ("ssm_scan", "gqa_core", "moe_experts"):
        assert names[group] == [group]
    stacks = _stacks(lowered["nemotron_h"])
    for name in names["layers"]:
        assert _holds(stacks, name), name
    for name in ("ssm_scan", "ssm_conv", "gqa_core", "moe_experts"):
        assert any(s.endswith(name) and "transpose(" not in s
                   for s in stacks), name
        assert any(s.endswith(name) and "transpose(" in s
                   for s in stacks), name
    # a stack of that model reduces to its layer by the file's own rules
    loss = "NemotronHLM.next_token_loss"
    assert scope_reduce.scope_of(
        f"jit(train_step)/transpose(jvp({loss}))/NemotronHLM.hidden/"
        f"jvp({loss})/NemotronHLM.hidden/checkpoint/rematted_computation/"
        "layer_0/mixer/ssm_scan/dot_general:", names) == ("ssm_scan", True)
    assert scope_reduce.scope_of(
        f"jit(train_step)/jvp({loss})/NemotronHLM.hidden/checkpoint/layer_0/"
        "norm/mul:", names) == ("checkpoint/layer_0/norm", False)


def test_the_solar_model_s_names_file_equals_the_declared_lists(lowered):
    """`chipbench/solar_lm_scopes.json`, the benchmark's own copy: its
    layers are Solar-Open2's own name (`gqa_gate`) and the ones it shares
    (Kimi Delta Attention's, grouped-query attention's, the expert
    share's, the embedding, the head), each of which reaches that model's
    lowered step, forward and backward; its phases are the first names
    file's."""
    import json
    from chipbench import scope_reduce
    here = os.path.dirname(scope_reduce.__file__)
    with open(os.path.join(here, "solar_lm_scopes.json")) as f:
        names = json.load(f)
    kda = {n for n in scopes.LING_LM_LAYERS if n.startswith("kda_")}
    moe = {n for n in scopes.LM_LAYERS if n.startswith("moe_")}
    assert set(names["layers"]) == set(scopes.SOLAR_LM_LAYERS) | kda | moe \
        | {n for n in scopes.HYBRID_LM_LAYERS if n.startswith("gqa_")} \
        | {"embed_tokens", "lm_head"}
    assert names["phases"] == scope_reduce.declared()["phases"]
    assert set(names["kda"]) == kda and set(names["moe"]) == moe
    assert set(scopes.SOLAR_LM_LAYERS) <= set(names["gqa"])
    for group in ("kda_core", "gqa_core", "moe_experts"):
        assert names[group] == [group]
    stacks = _stacks(lowered["solar_open2"])
    for name in names["layers"]:
        assert _holds(stacks, name), name
    for name in ("kda_core", "kda_gates", "gqa_core", "gqa_gate",
                 "moe_experts"):
        assert any(s.endswith(name) and "transpose(" not in s
                   for s in stacks), name
        assert any(s.endswith(name) and "transpose(" in s
                   for s in stacks), name


def test_the_jamba_model_s_names_file_equals_the_declared_lists(lowered):
    """`chipbench/jamba_lm_scopes.json`, the benchmark's own copy: its
    layers are AI21-Jamba2-3B's own names (`mamba1_*`) and the ones it
    shares (grouped-query attention's, the dense MLP's, the embedding, the
    head), each of which reaches that model's lowered step, forward and
    backward; its phases are the first names file's."""
    import json
    from chipbench import scope_reduce
    here = os.path.dirname(scope_reduce.__file__)
    with open(os.path.join(here, "jamba_lm_scopes.json")) as f:
        names = json.load(f)
    gqa = {n for n in scopes.HYBRID_LM_LAYERS if n.startswith("gqa_")}
    assert set(names["layers"]) == set(scopes.JAMBA_LM_LAYERS) | gqa \
        | {"mlp_dense", "embed_tokens", "lm_head"}
    assert names["phases"] == scope_reduce.declared()["phases"]
    assert set(names["mamba1"]) == set(scopes.JAMBA_LM_LAYERS)
    assert set(names["gqa"]) == gqa and names["mlp"] == ["mlp_dense"]
    for group in ("mamba1_scan", "gqa_core"):
        assert names[group] == [group]
    stacks = _stacks(lowered["jamba2"])
    for name in names["layers"]:
        assert _holds(stacks, name), name
    for name in (*scopes.JAMBA_LM_LAYERS, "gqa_core", "mlp_dense"):
        assert any(name in s and "transpose(" not in s
                   for s in stacks), name
        assert any(name in s and "transpose(" in s for s in stacks), name
    # the tied head's products sit under `lm_head`, both ways
    assert any(s.endswith("lm_head") and "transpose(" in s for s in stacks)


def test_selective_scan_kernels_keep_the_name_mamba1_scan(monkeypatch):
    """Jamba's train step at the smallest width the selective scan's Pallas
    kernels take (128 Mamba channels), lowered for a TPU. As `ssd_pallas.
    scan`, the op is one jitted function lowered once for the three Mamba
    layers: two kernels (the forward that saves the chunks' states, which
    the blocks' recomputation runs; the hand-written backward; the plain
    forward is not traced, as every Mamba layer is recomputed), each under
    the `mamba1_scan` the function opens itself, so the benchmark's reader
    gives them to that row."""
    import json
    from chipbench import scope_reduce
    cfg = apply_overrides(get_config("jamba2_3b_tiny"),
                          {"model.extra.hidden_size": 64})
    mesh = build_mesh(MeshSpec((cfg.mesh.data_axis,), (1,)),
                      jax.devices()[:1])
    trainer = Trainer(cfg, mesh=mesh, logger=MetricLogger(stream=io.StringIO()))
    batch = {"tokens": jax.ShapeDtypeStruct(
        (cfg.data.global_batch_size, cfg.model.extra["seq_len"] + 1),
        jnp.int32)}
    state, rng = jax.eval_shape(trainer.init_state), trainer.base_rng()
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    text = trainer.train_step.__wrapped__.trace(state, batch, rng).lower(
        lowering_platforms=("tpu",)).as_text(debug_info=True)
    monkeypatch.undo()
    with open(os.path.join(os.path.dirname(scope_reduce.__file__),
                           "jamba_lm_scopes.json")) as f:
        names = json.load(f)
    named = dict(re.findall(r'^(#loc\d+) = loc\("([^"]*)"', text, re.M))
    calls = [named[loc] for loc in re.findall(
        r"custom_call @tpu_custom_call.*loc\((#loc\d+)\)", text)]
    scan = sorted(call for call in calls if "gqa_core" not in call)
    assert scan and all("mamba1_scan" in call for call in scan), calls
    assert {scope_reduce.scope_of(call + ":", names)[0]
            for call in scan} == {"mamba1_scan"}
    assert any(stack.endswith("layer_0/mixer/mamba1_scan/jit(scan)")
               for stack in re.findall(r'loc\("([^"]*)"', text))


def test_the_ling_model_s_names_file_equals_the_declared_lists(lowered):
    """`chipbench/ling_lm_scopes.json`, the benchmark's own copy: its
    layers are Ling-3.0-flash's own names and the ones it shares (latent
    attention's, the expert share's, the embedding, the head), each of
    which reaches that model's lowered step, forward and backward; its
    phases are the first names file's."""
    import json
    from chipbench import scope_reduce
    here = os.path.dirname(scope_reduce.__file__)
    with open(os.path.join(here, "ling_lm_scopes.json")) as f:
        names = json.load(f)
    assert set(names["layers"]) == set(scopes.LING_LM_LAYERS) \
        | set(scopes.LM_LAYERS) | {"embed_tokens"}
    assert names["phases"] == scope_reduce.declared()["phases"]
    assert set(names["kda"]) | {"mlp_dense"} == set(scopes.LING_LM_LAYERS)
    assert set(names["mla"]) | set(names["moe"]) | {"lm_head"} \
        == set(scopes.LM_LAYERS)
    for group in ("kda_core", "mla_core", "moe_experts"):
        assert names[group] == [group]
    stacks = _stacks(lowered["ling3"])
    for name in names["layers"]:
        assert _holds(stacks, name), name
    for name in ("kda_core", "kda_conv", "kda_gates", "kda_out", "mla_core",
                 "mlp_dense", "moe_experts"):
        assert any(s.endswith(name) and "transpose(" not in s
                   for s in stacks), name
        assert any(name in s and "transpose(" in s for s in stacks), name
    # a stack of that model reduces to its layer by the file's own rules:
    # the recurrence's scan and its groups made again sit under `kda_core`
    loss = "LingLM.next_token_loss"
    assert scope_reduce.scope_of(
        f"jit(train_step)/transpose(jvp({loss}))/LingLM.hidden/"
        f"jvp({loss})/LingLM.hidden/checkpoint/rematted_computation/"
        "layer_0/attn/kda_core/while/body/checkpoint/dot_general:",
        names) == ("kda_core", True)
    assert scope_reduce.scope_of(
        f"jit(train_step)/jvp({loss})/LingLM.hidden/checkpoint/layer_0/"
        "input_norm/mul:", names) == ("checkpoint/layer_0/input_norm", False)


@pytest.mark.parametrize("model", ["resnet50", "vgg16", "vit_s16"])
def test_every_model_names_its_input_cast(lowered, model):
    assert _holds(_stacks(lowered[model]), "cast_in")


def test_forward_and_backward_carry_the_names_they_should(lowered):
    """(With one device the stacks start at `jit(train_step)/`, with more
    they start inside the `shard_map` body: compared by their ends.)"""
    stacks = _stacks(lowered["vggf"])
    ends = lambda stacks, end: any(s.endswith(end) for s in stacks)
    # the LRN's hand-written backward (a custom_vjp) keeps its layer's name
    assert ends(stacks, "transpose(jvp(VGGF))/lrn1")
    assert ends(stacks, "/jvp(VGGF)/lrn1") or "jvp(VGGF)/lrn1" in stacks
    assert ends(stacks, "transpose(jvp(VGGF))/pool5")
    assert ends(stacks, "jvp(loss)") and ends(stacks, "transpose(jvp(loss))")
    # the prologue runs before the gradient is taken: forward only; the
    # augmentation stage owns the train step's finish (data/augment.py)
    for phase in ("augment/finish_u8", "augment/flip", "augment/mix"):
        assert ends(stacks, phase)
        assert not any("transpose(" in s and phase in s for s in stacks)
    resnet = _stacks(lowered["resnet50"])
    assert ends(resnet, "transpose(jvp(ResNet))/pool_init")
    assert ends(resnet, "transpose(jvp(ResNet))/stage1_block1/bn1")
    # the language model's names survive recomputation per block: forward,
    # and again (with the backward pass) below the transposed stack
    tokens = _stacks(lowered["mistral4"])
    assert _holds(tokens, "embed_tokens")
    for name in ("mla_core", "moe_experts", "moe_combine"):
        assert any(s.endswith(name) and "transpose(" not in s
                   for s in tokens), name
        assert any(s.endswith(name) and "transpose(" in s
                   for s in tokens), name


def test_fused_lrn_kernels_keep_their_layers_names():
    """VGG-F's train step with the LRN kernel pair (ops/lrn_pallas.py),
    lowered for a TPU: each of the four custom calls carries its layer's
    name, forward and backward, and the benchmark's reader
    (`chipbench/scope_reduce.py`) gives it to `lrn1` / `lrn2`."""
    from chipbench import scope_reduce
    from distributed_vgg_f_tpu.ops.lrn import set_lrn_impl
    rows = 128                  # a batch that fills the lanes
    cfg = apply_overrides(get_config("vggf_imagenet_dp"), {
        "data.image_size": SIZE, "model.num_classes": 10,
        "data.global_batch_size": rows, "mesh.num_data": 1})
    mesh = build_mesh(MeshSpec((cfg.mesh.data_axis,), (1,)),
                      jax.devices()[:1])
    trainer = Trainer(cfg, mesh=mesh, logger=MetricLogger(stream=io.StringIO()))
    state = jax.eval_shape(trainer.init_state)
    batch = {"image": jax.ShapeDtypeStruct((rows, SIZE, SIZE, 3), jnp.uint8),
             "label": jax.ShapeDtypeStruct((rows,), jnp.int32)}
    set_lrn_impl("pallas")      # `lrn()` sees the CPU's backend here
    try:
        text = trainer.train_step.__wrapped__.trace(
            state, batch, trainer.base_rng()).lower(
                lowering_platforms=("tpu",)).as_text(debug_info=True)
    finally:
        set_lrn_impl(None)
    assert trainer.train_step.lrn_sites == {"fused": 2, "fallback": 0}
    named = dict(re.findall(r'^(#loc\d+) = loc\("([^"]*)"', text, re.M))
    calls = [named[loc] for loc in re.findall(
        r"custom_call @tpu_custom_call.*loc\((#loc\d+)\)", text)]
    names = scope_reduce.declared()
    found = sorted(scope_reduce.scope_of("jit(train_step)/" + call + ":",
                                         names) for call in calls)
    assert found == [("lrn1", False), ("lrn1", True),
                     ("lrn2", False), ("lrn2", True)], calls


def test_routed_experts_backward_keeps_the_three_names():
    """The routed path's hand-written backward (models/mistral4.py, a
    custom_vjp over two loops), lowered for a TPU: the benchmark's reader
    gives its gathers, grouped products and scatter-adds, inside the loop
    and outside it, to `moe_dispatch`, `moe_experts` and `moe_combine`,
    backward."""
    import json
    from chipbench import scope_reduce
    cfg = get_config("mistral_small4_tiny")
    mesh = build_mesh(MeshSpec((cfg.mesh.data_axis,), (1,)),
                      jax.devices()[:1])
    trainer = Trainer(cfg, mesh=mesh, logger=MetricLogger(stream=io.StringIO()))
    batch = {"tokens": jax.ShapeDtypeStruct(
        (cfg.data.global_batch_size, cfg.model.extra["seq_len"] + 1),
        jnp.int32)}
    text = trainer.train_step.__wrapped__.trace(
        jax.eval_shape(trainer.init_state), batch, trainer.base_rng()).lower(
            lowering_platforms=("tpu",)).as_text(debug_info=True)
    with open(os.path.join(os.path.dirname(scope_reduce.__file__),
                           "lm_scopes.json")) as f:
        names = json.load(f)
    found = {}
    for stack in set(re.findall(r'loc\("([^"]*)"', text)):
        primitive = stack.rpartition("/")[2]
        if "/layer_0/moe/" in stack and "transpose(" in stack.split(
                "/layer_0/")[0] and primitive in (
                    "gather", "ragged_dot_general", "scatter-add"):
            scope, backward = scope_reduce.scope_of(
                "jit(train_step)/" + stack + ":", names)
            assert backward, stack
            found.setdefault(primitive, set()).add(
                (scope, "/while/body/" in stack))
    both = lambda *scopes: {(s, loop) for s in scopes for loop in (0, 1)}
    found["scatter-add"].discard(("moe_router", False))    # top-k's own
    assert found == {
        "gather": both("moe_dispatch", "moe_combine"),
        "ragged_dot_general": both("moe_experts"),
        "scatter-add": both("moe_dispatch", "moe_combine")}, found


def test_scan_kernels_keep_the_name_ssm_scan(monkeypatch):
    """The hybrid model's train step at the smallest sizes the recurrence's
    Pallas kernels take (ops/ssd_pallas.py), lowered for a TPU. The op is
    one jitted function, which JAX lowers once for both Mamba layers and
    apart from its call sites: three kernels in all (the forward, the
    block's recomputed forward that saves the entering states, the
    hand-written backward), under no name stack but what the function
    opens itself. So it opens `ssm_scan`, in the forward and in the
    backward, and the benchmark's reader gives all three to that row:
    `ssm_pct` and `ssm_scan_roofline_pct` keep reading it."""
    import json
    from chipbench import scope_reduce
    cfg = apply_overrides(get_config("nemotron3_nano_tiny"), {
        "model.extra.mamba_num_heads": 2, "model.extra.mamba_head_dim": 64,
        "model.extra.n_groups": 1, "model.extra.ssm_state_size": 128,
        "model.extra.chunk_size": 128, "model.extra.seq_len": 256})
    mesh = build_mesh(MeshSpec((cfg.mesh.data_axis,), (1,)),
                      jax.devices()[:1])
    trainer = Trainer(cfg, mesh=mesh, logger=MetricLogger(stream=io.StringIO()))
    batch = {"tokens": jax.ShapeDtypeStruct(
        (cfg.data.global_batch_size, 257), jnp.int32)}
    state, rng = jax.eval_shape(trainer.init_state), trainer.base_rng()
    # `ssd.ssd` and the attention core ask the backend: a TPU's trace
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    text = trainer.train_step.__wrapped__.trace(state, batch, rng).lower(
        lowering_platforms=("tpu",)).as_text(debug_info=True)
    monkeypatch.undo()
    with open(os.path.join(os.path.dirname(scope_reduce.__file__),
                           "hybrid_lm_scopes.json")) as f:
        names = json.load(f)
    named = dict(re.findall(r'^(#loc\d+) = loc\("([^"]*)"', text, re.M))
    calls = [named[loc] for loc in re.findall(
        r"custom_call @tpu_custom_call.*loc\((#loc\d+)\)", text)]
    scan = sorted(call for call in calls if "gqa_core" not in call)
    assert scan == ["ssm_scan/pallas_call", "ssm_scan/pallas_call",
                    "ssm_scan/ssm_scan/pallas_call"], calls
    assert {scope_reduce.scope_of(call + ":", names)[0]
            for call in scan} == {"ssm_scan"}
    # the function is called under the layer's own `ssm_scan` too
    assert any(stack.endswith("layer_0/mixer/ssm_scan/jit(scan)")
               for stack in re.findall(r'loc\("([^"]*)"', text))


@pytest.fixture(scope="module")
def tile_sized_ling_step():
    """Ling's train step at the smallest sizes its Pallas kernels take
    (heads of 128 in pairs, two chunks of 64), lowered for a TPU: the text
    with debug info, the name stacks of its kernel calls but the latent
    layer's, and the benchmark's names for that model."""
    import json
    from chipbench import scope_reduce
    cfg = apply_overrides(get_config("ling3_flash_tiny"), {
        "model.extra.num_attention_heads": 4, "model.extra.head_dim": 128})
    seq = cfg.model.extra["seq_len"]
    mesh = build_mesh(MeshSpec((cfg.mesh.data_axis,), (1,)),
                      jax.devices()[:1])
    trainer = Trainer(cfg, mesh=mesh, logger=MetricLogger(stream=io.StringIO()))
    batch = {"tokens": jax.ShapeDtypeStruct(
        (cfg.data.global_batch_size, seq + 1), jnp.int32)}
    state, rng = jax.eval_shape(trainer.init_state), trainer.base_rng()
    # `kda.kda`, `short_conv.conv_silu_heads` and the attention core ask
    # the backend: a TPU's trace
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(jax, "default_backend", lambda: "tpu")
        text = trainer.train_step.__wrapped__.trace(state, batch, rng).lower(
            lowering_platforms=("tpu",)).as_text(debug_info=True)
    with open(os.path.join(os.path.dirname(scope_reduce.__file__),
                           "ling_lm_scopes.json")) as f:
        names = json.load(f)
    named = dict(re.findall(r'^(#loc\d+) = loc\("([^"]*)"', text, re.M))
    calls = [named[loc] for loc in re.findall(
        r"custom_call @tpu_custom_call.*loc\((#loc\d+)\)", text)]
    return text, sorted(call for call in calls if "mla_core" not in call), \
        names


def test_delta_rule_kernels_keep_the_name_kda_core(tile_sized_ling_step):
    """As `ssd_pallas.scan`, the op of ops/kda_pallas.py is one jitted
    function that JAX lowers once for the six KDA layers: three kernels in
    all (the forward, the block's recomputed forward that saves the
    entering states, the hand-written backward), each under the `kda_core`
    the function opens itself, so the benchmark's reader gives all three to
    that row: `kda_pct` and `kda_core_roofline_pct` keep reading it."""
    from chipbench import scope_reduce
    text, calls, names = tile_sized_ling_step
    core = [call for call in calls if "kda_core" in call]
    assert core == ["kda_core/kda_core/pallas_call", "kda_core/pallas_call",
                    "kda_core/pallas_call"], calls
    assert {scope_reduce.scope_of(call + ":", names)[0]
            for call in core} == {"kda_core"}
    # the function is called under the layer's own `kda_core` too
    assert any(stack.endswith("layer_0/attn/kda_core/jit(chunked)")
               for stack in re.findall(r'loc\("([^"]*)"', text))


def test_short_conv_kernels_keep_the_name_kda_conv(tile_sized_ling_step):
    """ops/short_conv_pallas.py likewise: one jitted function, traced with
    and without the head norm (q's and k's scales are operands), lowered
    once a variant for the six layers' 18 call sites: six kernels in all
    (a variant's forward, its forward as the differentiated rule calls it
    in the block's recomputation, its hand-written backward), each under
    the `kda_conv` the function opens itself, forward and backward, so the
    benchmark's reader gives all six to that row; no other kernel joins the
    step."""
    from chipbench import scope_reduce
    text, calls, names = tile_sized_ling_step
    conv = [call for call in calls if "kda_core" not in call]
    assert conv == ["kda_conv/kda_conv/pallas_call"] * 2 \
        + ["kda_conv/pallas_call"] * 4, calls
    assert {scope_reduce.scope_of(call + ":", names)[0]
            for call in conv} == {"kda_conv"}
    stacks = re.findall(r'loc\("([^"]*)"', text)
    # the function is called under the layer's own `kda_conv`, in the pass,
    # in the block's recomputation and in the backward pass
    at = "layer_0/attn/kda_conv/jit(convolved)"
    assert any(s.endswith(at) and "transpose(" not in s for s in stacks)
    assert any(s.endswith("rematted_computation/" + at) for s in stacks)
    assert any(s.endswith("checkpoint/" + at) and "transpose(" in s
               for s in stacks)


def test_jitted_steps_are_named_for_what_they_are(lowered):
    """The module's name is what a trace's `XLA Modules` line shows and,
    unlike the scopes, part of the persistent compile cache's key."""
    module = lambda text: re.search(r"module @(\S+)", text).group(1)
    assert module(lowered["vggf"]) == "jit_train_step"
    assert module(lowered["resnet50"]) == "jit_train_step"
    assert module(lowered["vggf_eval"]) == "jit_eval_step"
    assert module(lowered["mistral4"]) == "jit_train_step"
    assert _holds(_stacks(lowered["vggf_eval"]), "finish_u8")


def test_call_sites_and_declared_lists_agree():
    """`grep named_scope` over the package: every name of the lists has a
    call site, and no call site names anything else."""
    found = set()
    for folder, _, files in os.walk(PACKAGE):
        for file in files:
            if file.endswith(".py"):
                with open(os.path.join(folder, file)) as f:
                    found |= set(re.findall(
                        r'named_scope\(f?"([^"]+)"\)', f.read()))
    assert "pool{b}" in found          # models/vgg16.py, one for each block
    found = (found - {"pool{b}"}) | {f"pool{b}" for b in range(1, 6)}
    assert found == set(scopes.PHASES) | set(scopes.LAYERS) \
        | set(scopes.LM_LAYERS) | set(scopes.HYBRID_LM_LAYERS) \
        | set(scopes.LING_LM_LAYERS) | set(scopes.SOLAR_LM_LAYERS) \
        | set(scopes.JAMBA_LM_LAYERS)


def test_host_span_call_sites_and_declared_lists_agree():
    """Set-up's host spans (PR 36), held as the device names are: every
    `startup` span of the lists has a `span(...)` or `record(...)` call
    site and no call site opens another, and the stages the compile
    listener names its spans by are `COMPILE_SPANS`."""
    startup, stages = set(), set()
    for folder, _, files in os.walk(PACKAGE):
        for file in files:
            if file.endswith(".py"):
                with open(os.path.join(folder, file)) as f:
                    text = f.read()
                startup |= set(re.findall(
                    r'(?:span|record)\("([a-z_]+)", "startup"', text))
                stages |= set(re.findall(
                    r'record\(f"([a-z_]+):\{fun_name\}", "compile"', text))
    assert startup == set(scopes.STARTUP_SPANS)
    assert stages == set(scopes.COMPILE_SPANS)
    assert set(scopes.TRAINER_INIT_CHILDREN) < set(scopes.STARTUP_SPANS)
