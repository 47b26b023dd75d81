"""End-to-end train-step tests on the virtual 8-device mesh (SURVEY.md §4):
mesh construction, pmean gradient sync, loss decrease, DP-vs-single-device
gradient equivalence."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from distributed_vgg_f_tpu.config import (
    DataConfig,
    ExperimentConfig,
    MeshConfig,
    ModelConfig,
    OptimConfig,
    TrainConfig,
)
from distributed_vgg_f_tpu.data.synthetic import SyntheticDataset
from distributed_vgg_f_tpu.parallel.mesh import MeshSpec, build_mesh
from distributed_vgg_f_tpu.train.trainer import Trainer
from distributed_vgg_f_tpu.utils.logging import MetricLogger


def _tiny_cfg(batch=16, dropout=0.5, num_data=0):
    return ExperimentConfig(
        name="tiny",
        model=ModelConfig(name="vggf", num_classes=10, dropout_rate=dropout,
                          compute_dtype="float32"),
        optim=OptimConfig(base_lr=0.01, reference_batch_size=batch,
                          weight_decay=1e-4, decay_epochs=(1000.0,)),
        data=DataConfig(name="synthetic", image_size=32, global_batch_size=batch,
                        num_train_examples=batch * 4),
        mesh=MeshConfig(num_data=num_data),
        train=TrainConfig(steps=5, log_every=100, seed=0),
    )


def _quiet():
    import io
    return MetricLogger(stream=io.StringIO())


def test_mesh_uses_all_8_devices(devices8):
    mesh = build_mesh(MeshSpec(("data",), (0,)))
    assert mesh.devices.size == 8
    assert mesh.axis_names == ("data",)


@pytest.mark.slow
def test_loss_decreases_on_fixed_batch(devices8):
    cfg = _tiny_cfg(batch=16, dropout=0.0)
    cfg = dataclasses.replace(cfg, optim=dataclasses.replace(cfg.optim,
                                                             base_lr=0.1))
    tr = Trainer(cfg, logger=_quiet())
    state = tr.init_state()
    rng = tr.base_rng()
    ds = SyntheticDataset(batch_size=16, image_size=32, num_classes=10, seed=0,
                          fixed=True)
    batch = tr.shard(next(ds))
    losses = []
    for _ in range(12):
        state, metrics = tr.train_step(state, batch, rng)
        losses.append(float(jax.device_get(metrics["loss"])))
    assert losses[-1] < losses[0] * 0.9, losses


def test_dp_matches_single_device(devices8):
    """Gradients pmean'd over 8 shards of a batch == gradients on the full batch
    on 1 device — the defining property of synchronous DP (SURVEY.md §4)."""
    batch_np = SyntheticDataset(batch_size=16, image_size=32, num_classes=10,
                                seed=3, fixed=True)._fixed_batch

    results = {}
    for label, num in (("dp8", 0), ("single", 1)):
        cfg = _tiny_cfg(batch=16, dropout=0.0, num_data=num)
        devices = None if num == 0 else jax.devices()[:1]
        mesh = build_mesh(MeshSpec(("data",), (num,)), devices=devices)
        tr = Trainer(cfg, mesh=mesh, logger=_quiet())
        state = tr.init_state()
        rng = tr.base_rng()
        batch = tr.shard(batch_np)
        for _ in range(3):
            state, metrics = tr.train_step(state, batch, rng)
        results[label] = (jax.device_get(state.params),
                          float(jax.device_get(metrics["loss"])))

    p8, loss8 = results["dp8"]
    p1, loss1 = results["single"]
    assert abs(loss8 - loss1) < 1e-4, (loss8, loss1)
    flat8 = jax.tree_util.tree_leaves(p8)
    flat1 = jax.tree_util.tree_leaves(p1)
    for a, b in zip(flat8, flat1):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-5)


@pytest.mark.slow
def test_bf16_reduce_tracks_fp32_reduce(devices8):
    """mesh.reduce_dtype='bfloat16' halves gradient wire bytes (the scaling
    model's fp32 worst case is VGG-16's 553 MB all-reduce); the update must
    track the fp32-reduce update to bf16 rounding — and ONLY the gradient
    sync may differ: momentum/params stay fp32, metrics are exact."""
    batch_np = SyntheticDataset(batch_size=16, image_size=32, num_classes=10,
                                seed=5, fixed=True)._fixed_batch
    results = {}
    for dtype in ("float32", "bfloat16"):
        cfg = _tiny_cfg(batch=16, dropout=0.0)
        cfg = dataclasses.replace(
            cfg, mesh=dataclasses.replace(cfg.mesh, reduce_dtype=dtype))
        tr = Trainer(cfg, logger=_quiet())
        state = tr.init_state()
        rng = tr.base_rng()
        batch = tr.shard(batch_np)
        for _ in range(3):
            state, metrics = tr.train_step(state, batch, rng)
        results[dtype] = (jax.device_get(state.params),
                          float(jax.device_get(metrics["loss"])))
    p32, loss32 = results["float32"]
    pbf, lossbf = results["bfloat16"]
    # metrics come from the fp32 forward, independent of the wire dtype of
    # the same-step gradient sync; 3 steps of bf16-perturbed updates shift
    # the step-3 loss by at most rounding-noise scale
    assert abs(loss32 - lossbf) < 1e-2, (loss32, lossbf)
    total = diff = 0.0
    for a, b in zip(jax.tree_util.tree_leaves(p32),
                    jax.tree_util.tree_leaves(pbf)):
        a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
        total += float(np.sum(a * a))
        diff += float(np.sum((a - b) ** 2))
        # per-leaf: the update difference is O(lr · bf16_eps · |grad|) per
        # step — far below the weights themselves
        np.testing.assert_allclose(a, b, rtol=0, atol=5e-4)
    # params must ACTUALLY differ (the bf16 cast really happened) yet stay
    # tiny relative to the weights
    assert 0 < diff < 1e-6 * total, (diff, total)


@pytest.mark.slow
def test_bf16_reduce_zero1_composition(devices8):
    """bf16 wire under ZeRO-1: ONLY the gradient reduce-scatter narrows.
    Checked against the replicated bf16-reduce run on the same data: the
    two layouts' updates may differ only by reduction-order rounding of the
    same bf16-cast gradients — a bf16 param all-gather (the regression this
    test guards) would show up as a ~1e-2-relative param divergence and as
    non-fp32 leaves (code-review r4: 'loss decreases' guarded nothing)."""
    batch_np = SyntheticDataset(batch_size=16, image_size=32, num_classes=10,
                                seed=6, fixed=True)._fixed_batch
    results = {}
    for label, zero1 in (("replicated", False), ("zero1", True)):
        cfg = _tiny_cfg(batch=16, dropout=0.0)
        cfg = dataclasses.replace(
            cfg, mesh=dataclasses.replace(cfg.mesh, shard_opt_state=zero1,
                                          reduce_dtype="bfloat16"))
        tr = Trainer(cfg, logger=_quiet())
        state = tr.init_state()
        batch = tr.shard(batch_np)
        for _ in range(3):
            state, metrics = tr.train_step(state, batch, tr.base_rng())
        assert np.isfinite(float(jax.device_get(metrics["loss"])))
        results[label] = jax.device_get(state.params)
    for a, b in zip(jax.tree_util.tree_leaves(results["replicated"]),
                    jax.tree_util.tree_leaves(results["zero1"])):
        assert np.asarray(b).dtype == np.float32     # fp32 gather preserved
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=0, atol=5e-5)


def test_reduce_dtype_validated():
    import pytest

    with pytest.raises(ValueError, match="reduce_dtype"):
        MeshConfig(reduce_dtype="float16")


def test_dropout_differs_across_replicas(devices8):
    """Per-replica RNG folding (SURVEY.md §7): identical inputs on every replica
    must produce *different* dropout masks per replica."""
    from jax.sharding import Mesh
    from jax import shard_map

    from distributed_vgg_f_tpu.parallel.collectives import fold_rng_per_replica

    mesh = build_mesh(MeshSpec(("data",), (0,)))

    def per_replica_mask(key):
        key = fold_rng_per_replica(key, "data")
        return jax.random.bernoulli(key, 0.5, (1, 16)).astype(jnp.float32)

    f = shard_map(per_replica_mask, mesh=mesh, in_specs=P(),
                  out_specs=P("data"), check_vma=False)
    masks = np.asarray(jax.jit(f)(jax.random.key(0)))
    assert masks.shape == (8, 16)
    # at least two replicas must differ
    assert len({m.tobytes() for m in masks}) > 1


@pytest.mark.parametrize("seed", [0, 7, 2 ** 31 - 2, 2 ** 31 + 5,
                                  2 ** 40 + 7, -4])
def test_base_rng_is_the_seeds_key_whatever_the_seed(devices8, seed):
    """The dropout key's program takes the seed as an argument (one program
    for every seed: a constant made each new seed a compile and a cache
    entry), and the key is still `jax.random.key(seed + 1)`, for a seed of
    any size."""
    cfg = _tiny_cfg()
    cfg = dataclasses.replace(cfg, train=dataclasses.replace(cfg.train,
                                                             seed=seed))
    tr = Trainer(cfg, logger=_quiet())
    want = jax.random.key(seed + 1, impl=cfg.train.dropout_rng_impl)
    np.testing.assert_array_equal(jax.random.key_data(tr.base_rng()),
                                  jax.random.key_data(want))
    assert tr.base_rng().sharding.is_fully_replicated


def test_eval_step_counts(devices8):
    cfg = _tiny_cfg(batch=16, dropout=0.0)
    tr = Trainer(cfg, logger=_quiet())
    state = tr.init_state()
    ds = SyntheticDataset(batch_size=16, image_size=32, num_classes=10, seed=1,
                          fixed=True)
    counts = jax.device_get(tr.eval_step(state, tr.shard(next(ds))))
    assert int(counts["count"]) == 16
    assert 0 <= int(counts["top1"]) <= int(counts["top5"]) <= 16


def test_trainer_fit_runs(devices8):
    cfg = _tiny_cfg(batch=16, dropout=0.5)
    cfg = dataclasses.replace(cfg, train=dataclasses.replace(cfg.train, steps=3,
                                                             log_every=1))
    tr = Trainer(cfg, logger=_quiet())
    state = tr.fit()
    assert int(jax.device_get(state.step)) == 3


@pytest.mark.slow
def test_grad_accum_matches_big_batch(devices8):
    """k micro-batches through the scan must produce EXACTLY the big-batch
    update for a BN-free model with dropout off: same data, same params →
    mean of micro-gradients == big-batch gradient (CE is a per-example mean;
    fp32 summation noise only)."""
    cfg = _tiny_cfg(batch=64, dropout=0.0)
    tr_big = Trainer(cfg, logger=_quiet())
    cfg_acc = dataclasses.replace(
        cfg, train=dataclasses.replace(cfg.train, grad_accum_steps=4))
    tr_acc = Trainer(cfg_acc, logger=_quiet())

    state_b = tr_big.init_state()
    state_a = tr_acc.init_state()
    ds = SyntheticDataset(batch_size=64, image_size=32, num_classes=10,
                          seed=0, fixed=True)
    batch = tr_big.shard(next(ds))
    rng = tr_big.base_rng()
    state_b, m_b = tr_big.train_step(state_b, batch, rng)
    state_a, m_a = tr_acc.train_step(state_a, tr_acc.shard(next(ds)), rng)

    np.testing.assert_allclose(float(jax.device_get(m_a["loss"])),
                               float(jax.device_get(m_b["loss"])), rtol=1e-6)
    for a, b in zip(jax.tree.leaves(jax.device_get(state_a.params)),
                    jax.tree.leaves(jax.device_get(state_b.params))):
        np.testing.assert_allclose(a, b, rtol=2e-6, atol=2e-7)


@pytest.mark.slow
def test_grad_accum_zero1_composition(devices8):
    """Accumulation happens BEFORE the ZeRO-1 reduce-scatter, so the two
    features compose: accumulated ZeRO-1 == accumulated replicated DP."""
    cfg = _tiny_cfg(batch=16, dropout=0.0, num_data=8)
    cfg = dataclasses.replace(
        cfg, train=dataclasses.replace(cfg.train, grad_accum_steps=2))
    cfg_z = dataclasses.replace(
        cfg, mesh=MeshConfig(num_data=8, shard_opt_state=True))
    tr = Trainer(cfg, logger=_quiet())
    tr_z = Trainer(cfg_z, logger=_quiet())
    ds = SyntheticDataset(batch_size=16, image_size=32, num_classes=10,
                          seed=1, fixed=True)
    batch = next(ds)
    s, _ = tr.train_step(tr.init_state(), tr.shard(batch), tr.base_rng())
    sz, _ = tr_z.train_step(tr_z.init_state(), tr_z.shard(batch),
                            tr_z.base_rng())
    for a, b in zip(jax.tree.leaves(jax.device_get(s.params)),
                    jax.tree.leaves(jax.device_get(sz.params))):
        np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-7)


@pytest.mark.slow
def test_grad_accum_shard_matches_unsharded_accum(devices8):
    """ZeRO-2-flavored accumulation (train.grad_accum_shard): reduce-
    scattering each micro-gradient and accumulating only the 1/N shard
    must produce the same update as accumulate-then-scatter (scatter is a
    sum over replicas — the two orderings differ only in fp summation
    order) AND as plain accumulated replicated DP."""
    cfg = _tiny_cfg(batch=16, dropout=0.0, num_data=8)
    cfg = dataclasses.replace(
        cfg, train=dataclasses.replace(cfg.train, grad_accum_steps=2))
    cfg_z = dataclasses.replace(
        cfg, mesh=MeshConfig(num_data=8, shard_opt_state=True))
    cfg_z2 = dataclasses.replace(
        cfg_z, train=dataclasses.replace(cfg_z.train,
                                         grad_accum_shard=True))
    ds = SyntheticDataset(batch_size=16, image_size=32, num_classes=10,
                          seed=1, fixed=True)
    batch = next(ds)
    states = []
    for c in (cfg, cfg_z, cfg_z2):
        tr = Trainer(c, logger=_quiet())
        s, m = tr.train_step(tr.init_state(), tr.shard(batch),
                             tr.base_rng())
        states.append((s, m))
    for (s_ref, m_ref), (s, m) in zip(states[:-1], states[1:]):
        for a, b in zip(jax.tree.leaves(jax.device_get(s_ref.params)),
                        jax.tree.leaves(jax.device_get(s.params))):
            np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-7)
        np.testing.assert_allclose(
            float(m_ref["grad_norm"]), float(m["grad_norm"]), rtol=1e-5)


@pytest.mark.slow
def test_grad_accum_shard_bf16_wire(devices8):
    """The sharded accumulator composes with mesh.reduce_dtype=bfloat16:
    k wire roundings instead of one must still track the fp32-wire update
    to bf16-rounding tolerance."""
    cfg = _tiny_cfg(batch=16, dropout=0.0, num_data=8)
    cfg = dataclasses.replace(
        cfg, train=dataclasses.replace(cfg.train, grad_accum_steps=2,
                                       grad_accum_shard=True))
    cfg_f32 = dataclasses.replace(
        cfg, mesh=MeshConfig(num_data=8, shard_opt_state=True))
    cfg_bf16 = dataclasses.replace(
        cfg, mesh=MeshConfig(num_data=8, shard_opt_state=True,
                             reduce_dtype="bfloat16"))
    ds = SyntheticDataset(batch_size=16, image_size=32, num_classes=10,
                          seed=2, fixed=True)
    batch = next(ds)
    outs = []
    for c in (cfg_f32, cfg_bf16):
        tr = Trainer(c, logger=_quiet())
        s, _ = tr.train_step(tr.init_state(), tr.shard(batch),
                             tr.base_rng())
        outs.append(s)
    for a, b in zip(jax.tree.leaves(jax.device_get(outs[0].params)),
                    jax.tree.leaves(jax.device_get(outs[1].params))):
        np.testing.assert_allclose(a, b, rtol=5e-3, atol=5e-4)


def test_grad_accum_shard_validation(devices8):
    """grad_accum_shard without ZeRO-1 or without accumulation is a config
    error (loud, not a silent fallback) — except the documented 1-device
    downgrade, which follows shard_opt_state's own."""
    import pytest
    base = _tiny_cfg(batch=16, dropout=0.0, num_data=8)
    no_zero = dataclasses.replace(
        base, train=dataclasses.replace(base.train, grad_accum_steps=2,
                                        grad_accum_shard=True))
    with pytest.raises(ValueError, match="shard_opt_state"):
        Trainer(no_zero, logger=_quiet())
    no_accum = dataclasses.replace(
        base, train=dataclasses.replace(base.train, grad_accum_shard=True),
        mesh=MeshConfig(num_data=8, shard_opt_state=True))
    with pytest.raises(ValueError, match="grad_accum_steps"):
        Trainer(no_accum, logger=_quiet())


def test_grad_accum_rejects_indivisible_batch(devices8):
    cfg = _tiny_cfg(batch=16)
    cfg = dataclasses.replace(
        cfg, train=dataclasses.replace(cfg.train, grad_accum_steps=3))
    tr = Trainer(cfg, logger=_quiet())
    ds = SyntheticDataset(batch_size=16, image_size=32, num_classes=10, seed=0)
    import pytest
    with pytest.raises(Exception, match="not divisible|divisible"):
        tr.train_step(tr.init_state(), tr.shard(next(ds)), tr.base_rng())


@pytest.mark.slow
def test_grad_accum_updates_bn_stats(devices8):
    """BN models: batch stats update sequentially per micro-batch through the
    scan carry (the standard accumulation semantics) and training proceeds."""
    import io
    from distributed_vgg_f_tpu.utils.logging import MetricLogger

    cfg = ExperimentConfig(
        name="accum_bn",
        model=ModelConfig(name="resnet50", num_classes=10,
                          compute_dtype="float32"),
        optim=OptimConfig(base_lr=0.01, reference_batch_size=16),
        data=DataConfig(name="synthetic", image_size=64, global_batch_size=16),
        train=TrainConfig(steps=1, seed=0, grad_accum_steps=2),
    )
    tr = Trainer(cfg, logger=MetricLogger(stream=io.StringIO()))
    state = tr.init_state()
    old_stats = jax.device_get(state.batch_stats)
    ds = SyntheticDataset(batch_size=16, image_size=64, num_classes=10, seed=0)
    state, metrics = tr.train_step(state, tr.shard(next(ds)), tr.base_rng())
    assert np.isfinite(float(jax.device_get(metrics["loss"])))
    new_stats = jax.device_get(state.batch_stats)
    assert any(not np.allclose(a, b) for a, b in
               zip(jax.tree_util.tree_leaves(old_stats),
                   jax.tree_util.tree_leaves(new_stats)))


def test_fit_rejects_labels_beyond_model_head(devices8):
    """First-batch guard for EVERY pipeline (code-review r3): labels >= the
    head width are a CE gather past the logits — loss=nan with finite grads
    and no error. The trainer must fail loudly instead."""
    import pytest

    cfg = _tiny_cfg(batch=16, dropout=0.0)
    cfg = dataclasses.replace(cfg, train=dataclasses.replace(cfg.train, steps=2))
    tr = Trainer(cfg, logger=_quiet())

    def bad_batches():
        rng = np.random.default_rng(0)
        while True:
            yield {"image": rng.standard_normal((16, 32, 32, 3)).astype(np.float32),
                   "label": np.full((16,), 937, np.int32)}   # >= num_classes=10

    with pytest.raises(ValueError, match="num_classes"):
        tr.fit(dataset=bad_batches())
