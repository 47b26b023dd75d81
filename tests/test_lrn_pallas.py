"""The fused LRN kernel pair (ops/lrn_pallas.py) against the float32 oracle
and against the XLA banded form it replaces, value and gradient; what falls
back; and what the step's builder counts.

Runs in the Pallas interpreter on the 8-virtual-CPU test platform (SURVEY.md
§4: all TPU-kernel logic must be testable without hardware); what only the
chip's compiler can refuse is in tests/test_chip_compile.py.
"""

import io

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import distributed_vgg_f_tpu.ops.lrn_pallas as lrn_pallas
from distributed_vgg_f_tpu.ops import lrn as lrn_mod
from distributed_vgg_f_tpu.ops.lrn import (
    local_response_norm,
    local_response_norm_matmul_vjp,
    lrn,
    lrn_site_counts,
    set_lrn_impl,
)
from distributed_vgg_f_tpu.ops.lrn_pallas import (
    fused_view,
    local_response_norm_pallas,
)


@pytest.fixture(autouse=True)
def _interpret_mode():
    prev = lrn_pallas.INTERPRET
    lrn_pallas.INTERPRET = jax.default_backend() != "tpu"
    yield
    lrn_pallas.INTERPRET = prev


def _value_and_grad(fn, x, cot):
    """fn(x), and the gradient of <fn(x), cot> by x."""
    y, vjp = jax.vjp(fn, x)
    return y, vjp(cot.astype(y.dtype))[0]


# One shape a view; "tail" shrinks the blocks until neither axis of the
# grid divides (rows: 1152 = 2 x 512 + 128; sublanes: 576 rows = 2 x 256 +
# 64, a product's 128 rows cut in half, and 384 lanes = 256 + 128).
_SHAPES = {"rows": (128, 3, 3, 128), "sublanes": (384, 3, 3, 64)}
_TAIL = {"BLOCK_ELEMENTS": 1 << 16, "LANES_BLOCK": 256}


@pytest.mark.parametrize("kind", ["float32", "bfloat16", "tail"])
@pytest.mark.parametrize("relu", [False, True], ids=["plain", "relu_input"])
@pytest.mark.parametrize("alpha_scaled", [False, True],
                         ids=["alpha", "alpha_over_n"])
@pytest.mark.parametrize("view", ["rows", "sublanes"])
def test_fused_pair_matches_oracle_and_matmul_vjp(monkeypatch, view,
                                                  alpha_scaled, relu, kind):
    if kind == "tail":
        for name, value in _TAIL.items():
            monkeypatch.setattr(lrn_pallas, name, value)
    dtype = jnp.float32 if kind == "float32" else jnp.bfloat16
    shape = _SHAPES[view]
    x = (jax.random.normal(jax.random.key(0), shape, jnp.float32) * 3.0
         ).astype(dtype)
    cot = jax.random.normal(jax.random.key(1), shape, jnp.float32)
    kw = dict(alpha=3e-2, alpha_scaled=alpha_scaled)   # a normaliser that bites
    assert fused_view(shape, jnp.bfloat16) == view

    act = jax.nn.relu if relu else (lambda v: v)
    got, got_g = _value_and_grad(
        lambda v: local_response_norm_pallas(v, view=view, relu_input=relu,
                                             **kw), x, cot)
    # the oracle on the same (rounded) inputs, in float32
    want, want_g = _value_and_grad(
        lambda v: local_response_norm(act(v), **kw), x.astype(jnp.float32),
        cot)
    same, same_g = _value_and_grad(
        lambda v: local_response_norm_matmul_vjp(act(v), **kw), x, cot)
    assert got_g.dtype == x.dtype
    f32 = lambda a: np.asarray(a, np.float32)
    if dtype == jnp.float32:
        np.testing.assert_allclose(f32(got_g), f32(want_g), rtol=3e-4,
                                   atol=3e-6)
        np.testing.assert_allclose(f32(got), f32(want), rtol=2e-5, atol=2e-6)
        np.testing.assert_allclose(f32(got_g), f32(same_g), rtol=3e-4,
                                   atol=3e-6)
    else:
        # bf16 in and out bounds the distance to the oracle at bf16's step;
        # the XLA form rounds at the same places, so the pair may differ
        # from it by a rounding of the last bit here and there, no more
        np.testing.assert_allclose(f32(got_g), f32(want_g), rtol=2e-2,
                                   atol=2e-2)
        np.testing.assert_allclose(f32(got), f32(want), rtol=2e-2, atol=2e-2)
        np.testing.assert_allclose(f32(got_g), f32(same_g), rtol=1e-2,
                                   atol=1e-2)
        assert float(jnp.mean(f32(got_g) != f32(same_g))) < 0.01
        assert float(jnp.mean(f32(got) != f32(same))) < 0.01


@pytest.mark.parametrize("shape, dtype", [
    ((128, 3, 3, 64), jnp.float32),     # the fp32 serving tier
    ((96, 3, 3, 64), jnp.bfloat16),     # a server bucket: batch off the lanes
    ((128, 3, 3, 48), jnp.bfloat16),    # channels no divisor of 128
], ids=["float32", "batch96", "c48"])
def test_shapes_that_fall_back(shape, dtype):
    """No view, so `lrn()` takes the XLA banded form, bit for bit, and counts
    the site as a fallback; asking for the pair by name is an error."""
    assert fused_view(shape, dtype) is None
    x = jax.random.normal(jax.random.key(2), shape, jnp.float32).astype(dtype)
    before = lrn_site_counts()
    got = lrn(x, relu_input=True)
    after = lrn_site_counts()
    assert (after["fused"] - before["fused"],
            after["fallback"] - before["fallback"]) == (0, 1)
    np.testing.assert_array_equal(
        np.asarray(got, np.float32),
        np.asarray(local_response_norm_matmul_vjp(jax.nn.relu(x)),
                   np.float32))
    with pytest.raises(ValueError, match="no fused LRN view"):
        local_response_norm_pallas(x)


def test_dispatcher_takes_the_pair_where_it_applies():
    """bf16, batch on the lanes: the pair where a TPU (here: the interpreter)
    can run it, the XLA form on any other backend. Decided per call, from
    what the call observes."""
    x = jax.random.normal(jax.random.key(3), (128, 2, 2, 64),
                          jnp.float32).astype(jnp.bfloat16)
    before = lrn_site_counts()
    fused = lrn(x)
    lrn_pallas.INTERPRET = False
    plain = lrn(x) if jax.default_backend() != "tpu" else fused
    after = lrn_site_counts()
    assert after["fused"] - before["fused"] >= 1
    if jax.default_backend() != "tpu":
        assert after["fallback"] - before["fallback"] == 1
    np.testing.assert_allclose(np.asarray(fused, np.float32),
                               np.asarray(plain, np.float32),
                               rtol=1e-2, atol=1e-2)


def test_matmul_forward_matches_oracle():
    x = jax.random.normal(jax.random.key(1), (2, 5, 5, 64), jnp.float32) * 2.0
    want = local_response_norm(x)
    got = local_response_norm_matmul_vjp(x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-6)


def test_matmul_gradient_matches_oracle():
    """The matmul form's hand-written VJP equals autodiff of the
    reduce_window oracle."""
    x = jax.random.normal(jax.random.key(2), (2, 4, 4, 64), jnp.float32)
    cot = jax.random.normal(jax.random.key(3), x.shape, jnp.float32)
    _, want = _value_and_grad(local_response_norm, x, cot)
    _, got = _value_and_grad(local_response_norm_matmul_vjp, x, cot)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=3e-4, atol=3e-6)


def test_dispatcher_override():
    x = jax.random.normal(jax.random.key(6), (1, 2, 2, 8), jnp.float32)
    try:
        set_lrn_impl("reduce_window")
        a = lrn(x)
        set_lrn_impl("matmul_vjp")
        b = lrn(x)
    finally:
        set_lrn_impl(None)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=2e-5, atol=2e-6)


def test_set_lrn_impl_names_are_what_they_were():
    try:
        for name in ("matmul_vjp", "pallas", "reduce_window", None):
            set_lrn_impl(name)
        # "matmul" and "shift_vjp" went with their code (PR 30)
        for name in ("matmul", "shift_vjp", "fused", "rows", "sublanes",
                     "auto", ""):
            with pytest.raises(ValueError):
                set_lrn_impl(name)
    finally:
        set_lrn_impl(None)
    assert lrn_mod._IMPL_OVERRIDE is None


# ---- what the step's builder counts ----------------------------------------

@pytest.mark.parametrize("preset, want", [
    ("vggf_imagenet_dp", {"fused": 2, "fallback": 0}),      # bf16
    ("vggf_cifar10_smoke", {"fused": 0, "fallback": 2}),    # float32
])
def test_train_step_publishes_its_lrn_sites(preset, want):
    """A built step of a shrunk preset, one step run with telemetry on: the
    gauges say what the traced step's two LRN sites lowered to."""
    from distributed_vgg_f_tpu import telemetry
    from distributed_vgg_f_tpu.config import apply_overrides, get_config
    from distributed_vgg_f_tpu.parallel.mesh import MeshSpec, build_mesh
    from distributed_vgg_f_tpu.train.trainer import Trainer
    from distributed_vgg_f_tpu.utils.logging import MetricLogger
    size, rows = 32, 128
    cfg = apply_overrides(get_config(preset), {
        "data.image_size": size, "model.num_classes": 10,
        "data.global_batch_size": rows, "mesh.num_data": 1})
    mesh = build_mesh(MeshSpec((cfg.mesh.data_axis,), (1,)),
                      jax.devices()[:1])
    telemetry.reset()
    telemetry.configure(enabled=True)
    try:
        trainer = Trainer(cfg, mesh=mesh,
                          logger=MetricLogger(stream=io.StringIO()))
        assert trainer.train_step.lrn_sites == {}       # not traced yet
        rng = np.random.default_rng(0)
        u8 = trainer.device_finish is not None \
            or trainer.device_augment is not None
        image = rng.integers(0, 256, (rows, size, size, 3)).astype(
            np.uint8 if u8 else np.float32)
        batch = trainer.shard({
            "image": image,
            "label": rng.integers(0, 10, (rows,)).astype(np.int32)})
        _, metrics = trainer.train_step(trainer.init_state(), batch,
                                        trainer.base_rng())
        assert np.isfinite(float(metrics["loss"]))
        assert trainer.train_step.lrn_sites == want
        gauges = telemetry.get_registry().snapshot_split()["gauges"]
        assert gauges.get("lrn/fused_sites") == want["fused"]
        assert gauges.get("lrn/fallback_sites") == want["fallback"]
    finally:
        telemetry.reset()
        telemetry.configure(enabled=True)
