"""The benchmark's copy of the FLOP counter starts from the program's
pinned arithmetic, and an unknown chip is an error."""

import jax
import jax.numpy as jnp
import pytest

from chipbench import counts
from distributed_vgg_f_tpu.config import ModelConfig
from distributed_vgg_f_tpu.models.registry import build_model
from distributed_vgg_f_tpu.utils import flops as program_flops


@pytest.mark.parametrize("model_name", ["vggf", "resnet50"])
def test_counter_equals_the_programs(model_name):
    model = build_model(ModelConfig(name=model_name, num_classes=1000,
                                    dropout_rate=0.0))
    x = jnp.zeros((2, 224, 224, 3), jnp.float32)
    variables = jax.eval_shape(
        lambda: model.init({"params": jax.random.key(0)}, x, train=False))
    forward = lambda v, x: model.apply(v, x, train=False)
    ours = counts.jaxpr_flops(forward, variables, x)
    assert ours == program_flops.jaxpr_flops(forward, variables, x)
    assert ours > 1e9


@pytest.mark.parametrize("model_name, config, rows, stem, xla_tflop", [
    ("vggf", "vggf_imagenet", 1024, (54, 54, 64, 11 * 11 * 3), 4.29),
    ("resnet50", "resnet50_imagenet", 256, (112, 112, 64, 7 * 7 * 3), 6.17)])
def test_a_steps_operations_are_three_forwards_less_the_stems_input_gradient(
        model_name, config, rows, stem, xla_tflop):
    """What `step_mfu_pct` and `mxu_ops_roofline_pct` count at the cells'
    own batch: every convolution and product once forward and twice
    backward, but for the stem, whose input needs no gradient; nothing
    rematerialised, no product with a dilation's zeros. Within 1 % of what
    XLA's cost analysis gave the program's whole step (ISSUE 25's
    rehearsal: 4.29 and 6.17 TFLOP)."""
    import json
    import os

    from chipbench.layer_metrics import _step_ops
    from chipbench.reference import step as ref_step
    from chipbench.reference.ops import Ops

    model = build_model(ModelConfig(name=model_name, num_classes=1000,
                                    dropout_rate=0.0))
    variables = jax.eval_shape(lambda: model.init(
        {"params": jax.random.key(0)}, jnp.zeros((2, 224, 224, 3)),
        train=False))
    shapes = {"params": variables["params"],
              "stats": variables.get("batch_stats", {})}
    with open(os.path.join(os.path.dirname(counts.__file__), "configs",
                           f"{config}.json")) as f:
        recipe = json.load(f)["recipe"]
    step = sum(op["flops"] for op in _step_ops.of({
        "model": model_name, "recipe": recipe, "rows_per_step": rows,
        "shapes": shapes}))
    plain = ref_step.load_model(model_name)
    forward = counts.jaxpr_flops(
        lambda p, s, x: plain.forward(p, s, x, ops=Ops("float32"),
                                      train=True, masks=None)[0],
        shapes["params"], shapes["stats"],
        jax.ShapeDtypeStruct((rows, 224, 224, 3), jnp.float32))
    h, w, c, k = stem
    assert step == 3 * forward - 2.0 * rows * h * w * c * k
    assert step / 1e12 == pytest.approx(xla_tflop, rel=0.01)


def test_roofline_takes_the_larger_bound_per_op():
    peak = counts.peaks("TPU v5 lite")
    ops = [{"kind": "dot", "flops": 197e12, "elements": 1.0},
           {"kind": "conv", "flops": 1.0, "elements": 819e9 / 2}]
    least = counts.roofline_seconds(ops, peak)
    assert least["seconds"] == pytest.approx(2.0)
    assert least["compute_bound_s"] == pytest.approx(1.0)
    assert least["memory_bound_s"] == pytest.approx(1.0)


def test_an_unknown_device_kind_is_an_error():
    assert counts.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        counts.peaks("TPU v99")
