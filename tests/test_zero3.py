"""ZeRO-3 parameter sharding (ISSUE 18 / r21, mesh.shard_params —
parallel/buckets.py gather_param_tree + train/step.py just-in-time
gather): the config ladder validation, the kill-switch lowered-text
identity (shard_params off ≡ the zero2 step, byte-identical), the CPU
loss-trajectory EQUALITY grid zero3 vs zero2 across {bucketed on/off} x
{grad_accum 1,2} (MiniNet here, the model zoo on the trainer lane below),
the lowered-HLO gather witnesses (gathers == buckets + a dependency-free
(all_gather, conv/dot) pair), comm telemetry (`comm/gathers`,
`comm/gather_wire_bytes`), checkpoint retopology across zero2 ↔ zero3 and
the zero1-era parity gate, the typed GeometryReceiptError refusals, and
the live elastic k=1 resize cell under zero3."""

import io
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_vgg_f_tpu.config import (
    DataConfig,
    ElasticConfig,
    ExperimentConfig,
    MeshConfig,
    ModelConfig,
    OptimConfig,
    TrainConfig,
    get_config,
)
from distributed_vgg_f_tpu.parallel.buckets import (
    hlo_overlap_report,
    sharding_basis,
)
from distributed_vgg_f_tpu.parallel.mesh import MeshSpec, build_mesh
from distributed_vgg_f_tpu.parallel.zero import (
    flat_param_count,
    padded_flat_size,
    plan_exchange,
)
from distributed_vgg_f_tpu.resilience.errors import GeometryReceiptError
from distributed_vgg_f_tpu.train.state import TrainState
from distributed_vgg_f_tpu.train.step import build_train_step

from test_comm_buckets import _batches, _mesh8, _MiniNet, _plan


# ------------------------------------------------------------------- config
def test_config_zero3_ladder():
    """`mesh.shard_params` rides the cumulative ladder: it requires the
    ZeRO-2 frame, labels as zero3, and the flagship deliberately keeps
    shipping zero2 (the honest claim at VGG-F scale is the structural
    receipts, not a flagship win)."""
    assert MeshConfig(shard_opt_state=True, shard_gradients=True,
                      shard_params=True).sharding_label == "zero3"
    with pytest.raises(ValueError, match="shard_params"):
        MeshConfig(shard_opt_state=True, shard_params=True)
    with pytest.raises(ValueError, match="shard_params"):
        MeshConfig(shard_params=True)
    # shard_gradients without zero1 DOWNGRADES (the trainer precedent),
    # and the downgrade cascades through the whole ladder label
    assert MeshConfig(shard_gradients=True).sharding_label == "dp"
    assert get_config("vggf_imagenet_dp").mesh.shard_params is False
    assert get_config("vggf_imagenet_dp").mesh.sharding_label == "zero2"
    # the single source both the config label and the step receipt use
    assert sharding_basis(True, True, True) == "zero3"
    assert sharding_basis(True, True, False) == "zero2"


def test_one_shard_plan_stores_the_params_tree(devices8):
    """The ladder downgrades in the plan alone: on a one-shard mesh a
    configured zero3 is plain dp, and `TrainState.create` given that plan
    keeps the params tree and the tree-shaped optimizer state."""
    import optax
    model = _MiniNet()
    tx = optax.sgd(0.1, momentum=0.9)
    mesh1 = build_mesh(MeshSpec(("data",), (1,)), devices8[:1])
    plan = plan_exchange(MeshConfig(shard_opt_state=True,
                                    shard_gradients=True, shard_params=True),
                         mesh1, tx)
    assert plan.basis == "dp" and not plan.zero3
    sample = jnp.zeros((1, 16, 16, 3), jnp.float32)
    with_plan = TrainState.create(model, tx, jax.random.key(0), sample,
                                  exchange=plan)
    plain = TrainState.create(model, tx, jax.random.key(0), sample)
    assert jax.tree.structure(with_plan) == jax.tree.structure(plain)


def test_plan_rejects_shard_params_without_zero2():
    import optax
    mesh = build_mesh(MeshSpec(("data",), (0,)))
    # MeshConfig refuses this ladder itself; the plan checks what reaches
    # it from anywhere else
    asked = types.SimpleNamespace(
        data_axis="data", shard_opt_state=True, shard_gradients=False,
        shard_params=True, comm_bucket_mb=0.0, reduce_dtype="float32")
    with pytest.raises(ValueError, match="shard_params"):
        plan_exchange(asked, mesh, optax.sgd(0.1))


# ------------------------------------------------- step builders for grids
def _build(mesh, model, *, zero3=False, bucket_mb=0.0, accum=1,
           reduce_dtype="float32", clip=0.0, ema=0.0, sample_hw=16):
    """The zero2/zero3 pair builder: identical to test_comm_buckets._build
    at the ZeRO-2 basis, plus the shard_params layer when zero3=True."""
    import optax
    tx = optax.sgd(0.05, momentum=0.9)
    sample = jnp.zeros((1, sample_hw, sample_hw, 3), jnp.float32)
    plan = _plan(mesh, model, tx, sample,
                 basis="zero3" if zero3 else "zero2", bucket_mb=bucket_mb,
                 accum=accum, reduce_dtype=reduce_dtype, ema=ema > 0)

    def create(r):
        return TrainState.create(model, tx, r, sample, ema=ema > 0,
                                 exchange=plan)

    shardings = plan.state_shardings(mesh)
    state = jax.jit(create, out_shardings=shardings)(jax.random.key(0))
    step = build_train_step(model, mesh, 1e-4, plan, grad_clip_norm=clip,
                            ema_decay=ema)
    layout = plan.layout if bucket_mb > 0 else None
    return state, step, plan.params_struct, layout


def _run(mesh, model, batches, base, n=3, **kw):
    state, step, p_struct, layout = _build(mesh, model, **kw)
    losses = []
    for b in batches[:n]:
        state, m = step(state, b, base)
        losses.append(float(jax.device_get(m["loss"])))
    return losses, state, step, p_struct, layout


def _tree_of(state, p_struct, layout, leaf):
    """Host-side flat-shard -> tree inversion (what trainer.params_tree
    does), for comparing zero3 state against zero2's trees."""
    from distributed_vgg_f_tpu.parallel.zero import _unflatten_like
    vec = jnp.asarray(jax.device_get(leaf))
    if layout is not None:
        return jax.device_get(layout.from_global(vec))
    n = flat_param_count(p_struct)
    return jax.device_get(_unflatten_like(vec[:n], p_struct))


# ----------------------------------------------- loss-trajectory EQUALITY
def test_equality_grid_zero3_vs_zero2_mininet(devices8):
    """The r21 acceptance grid at MiniNet scale: zero3 produces the
    BITWISE-equal loss trajectory of the matching zero2 cell across
    {bucketed on/off} x {grad_accum 1,2} — the gather-once design runs
    literally zero2's math on the gathered tree (DESIGN.md §18), so the
    pin is equality, not tolerance. EMA rides the flat shard and inverts
    to exactly zero2's EMA tree."""
    mesh = _mesh8(devices8)
    model = _MiniNet()
    batches = _batches(mesh=mesh)
    base = jax.jit(lambda: jax.random.key(1))()
    for bucket_mb in (0.0, 0.0005):
        for accum in (1, 2):
            kw = dict(bucket_mb=bucket_mb, accum=accum, ema=0.9,
                      clip=1.0)
            ref, st2, _, p_struct, layout = _run(mesh, model, batches,
                                                 base, **kw)
            l3, st3, _, _, _ = _run(mesh, model, batches, base,
                                    zero3=True, **kw)
            assert l3 == ref, \
                f"bucket={bucket_mb} accum={accum}: {l3} != {ref}"
            # params persisted as the 1/N flat vector, inverted exactly
            assert st3.params.ndim == 1
            t3 = _tree_of(st3, p_struct, layout, st3.params)
            for a, b in zip(jax.tree.leaves(jax.device_get(st2.params)),
                            jax.tree.leaves(t3)):
                np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
            e3 = _tree_of(st3, p_struct, layout, st3.ema_params)
            for a, b in zip(
                    jax.tree.leaves(jax.device_get(st2.ema_params)),
                    jax.tree.leaves(e3)):
                np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ------------------------------------------------------ kill-switch identity
def test_zero3_kill_switch_lowered_text_identity(devices8):
    """`mesh.shard_params` unset lowers to EXACTLY the zero2 step — the
    off-identity pin every kill-switch in this repo carries; the zero3
    build must differ (it had better be gathering something)."""
    mesh = _mesh8(devices8)
    model = _MiniNet()
    batches = _batches(mesh=mesh, n=1)
    base = jax.jit(lambda: jax.random.key(1))()
    for bucket_mb in (0.0, 0.0005):
        state, off, _, _ = _build(mesh, model, bucket_mb=bucket_mb)
        text_off = off.lower(state, batches[0], base).as_text()
        _, z2, _, _ = _build(mesh, model, bucket_mb=bucket_mb)
        assert text_off == z2.lower(state, batches[0], base).as_text(), \
            "zero2 step stopped being deterministic"
        st3, on, _, _ = _build(mesh, model, zero3=True,
                                  bucket_mb=bucket_mb)
        assert on.lower(st3, batches[0], base).as_text() != text_off


# ------------------------------------------------- lowered-HLO assertions
def test_hlo_zero3_bucketed_gather_witness(devices8):
    """r21 acceptance: the bucketed zero3 lowering carries one param
    all_gather PER BUCKET and a committed dependency-free (all_gather,
    conv/dot) pair — each gather depends only on the param-shard step
    input, so the overlap license is structural."""
    mesh = _mesh8(devices8)
    model = _MiniNet()
    batches = _batches(mesh=mesh, n=1)
    base = jax.jit(lambda: jax.random.key(1))()
    state, step, _, _ = _build(mesh, model, zero3=True, bucket_mb=0.0005)
    rep = hlo_overlap_report(step.lower(state, batches[0], base).as_text())
    assert step.comm_meta["sharding"] == "zero3"
    assert step.comm_meta["bucketed"] is True
    assert step.comm_meta["buckets"] >= 2
    assert step.comm_meta["gathers"] == step.comm_meta["buckets"]
    assert rep["gathers"] == step.comm_meta["buckets"]
    assert rep["gather_overlap_capable"] is True
    assert rep["gather_witness"] is not None
    # the scatter side keeps its r14 witness too
    assert rep["collective_counts"]["reduce_scatter"] \
        == step.comm_meta["buckets"]
    assert rep["overlap_capable"] is True


def test_hlo_zero3_monolithic_single_gather(devices8):
    """The unbucketed zero3 exchange gathers ONCE — and that one gather
    feeds all compute, so it is honestly NOT overlap-capable (the same
    monolithic-vs-bucketed story the scatter told in r14)."""
    mesh = _mesh8(devices8)
    model = _MiniNet()
    batches = _batches(mesh=mesh, n=1)
    base = jax.jit(lambda: jax.random.key(1))()
    state, step, _, _ = _build(mesh, model, zero3=True)
    rep = hlo_overlap_report(step.lower(state, batches[0], base).as_text())
    assert step.comm_meta["gathers"] == 1
    assert rep["gathers"] == 1
    assert rep["gather_overlap_capable"] is False
    # zero2's trailing re-sync gather exists but is NOT gather-capable
    # either (it depends on the whole update) — gathers == 1 there too
    st2, z2, _, _ = _build(mesh, model)
    rep2 = hlo_overlap_report(z2.lower(st2, batches[0], base).as_text())
    assert z2.comm_meta["gathers"] == 1
    assert rep2["gather_overlap_capable"] is False


# --------------------------------------------------------------- telemetry
def test_zero3_comm_counters_and_meta(devices8):
    from distributed_vgg_f_tpu import telemetry
    from distributed_vgg_f_tpu.telemetry import schema
    telemetry.configure(enabled=True)
    try:
        mesh = _mesh8(devices8)
        model = _MiniNet()
        batches = _batches(mesh=mesh, n=2)
        base = jax.jit(lambda: jax.random.key(1))()
        state, step, _, _ = _build(mesh, model, zero3=True,
                                      bucket_mb=0.0005)
        reg = telemetry.get_registry()
        reg.delta("z3_test")
        for b in batches:
            state, _ = step(state, b, base)
        delta = reg.delta("z3_test")
        meta = step.comm_meta
        assert meta["sharding"] == "zero3" and meta["bucketed"] is True
        assert meta["gathers"] == meta["buckets"]
        assert delta.get("comm/gathers") == 2 * meta["gathers"]
        assert delta.get("comm/gather_wire_bytes") \
            == 2 * meta["gather_bytes"]
        # the per-window JSONL block schema-validates with the r21 fields
        errors = []
        schema.validate_comm_block(dict(meta), "t", errors)
        assert errors == []
    finally:
        telemetry.reset()


# ------------------------------------------------ typed receipt refusals
def _fake_manager(opt_meta, p_meta, extra):
    return types.SimpleNamespace(
        best_step=lambda: 1,
        state_metadata=lambda step: {"opt_state": opt_meta,
                                     "params": p_meta},
        extra_at=lambda step: extra,
        restore=lambda template, step: (_ for _ in ()).throw(
            AssertionError("restore reached before the receipt check")))


def test_geometry_receipt_refusals(devices8):
    """A wrong `param_layout` receipt refuses with the TYPED class before
    a single array is read — never a shape error (the r21 contract)."""
    import optax
    mesh = _mesh8(devices8)
    model = _MiniNet()
    tx = optax.sgd(0.05, momentum=0.9)
    sample = jnp.zeros((1, 16, 16, 3), jnp.float32)
    plan = _plan(mesh, model, tx, sample, basis="zero3")
    p_struct = plan.params_struct
    padded = padded_flat_size(flat_param_count(p_struct), 8)
    assert padded == plan.total_padded
    flat = jax.ShapeDtypeStruct((padded,), jnp.float32)
    opt_meta = jax.eval_shape(tx.init, flat)

    def create():
        return TrainState.create(model, tx, jax.random.key(0), sample,
                                 exchange=plan)
    shardings = plan.state_shardings(mesh)
    template = jax.jit(create, out_shardings=shardings)()

    from distributed_vgg_f_tpu.checkpoint.retopology import (
        restore_any_topology)
    # (a) receipt length disagrees with the saved vector
    mgr = _fake_manager(opt_meta, flat, {
        "param_layout": {"kind": "canonical_flat", "num_shards": 8,
                         "total_padded": padded + 8}})
    with pytest.raises(GeometryReceiptError, match="total_padded"):
        restore_any_topology(mgr, template, plan)
    # (b) bucketed_flat kind with no opt receipt naming the geometry
    mgr = _fake_manager(opt_meta, flat, {
        "param_layout": {"kind": "bucketed_flat", "num_shards": 8,
                         "total_padded": padded}})
    with pytest.raises(GeometryReceiptError, match="bucket"):
        restore_any_topology(mgr, template, plan)
    # (c) receipt present but the saved params are a TREE
    mgr = _fake_manager(opt_meta, p_struct, {
        "param_layout": {"kind": "canonical_flat", "num_shards": 8,
                         "total_padded": padded}})
    with pytest.raises(GeometryReceiptError, match="tree"):
        restore_any_topology(mgr, template, plan)


# ------------------------------------------------------- trainer-level
def _trainer_cfg(model="vggf", steps=3, ema=0.0, ckpt=None, **mesh_kw):
    tr = TrainConfig(steps=steps, seed=0, ema_decay=ema)
    if ckpt is not None:
        import dataclasses
        tr = dataclasses.replace(tr, checkpoint_dir=str(ckpt),
                                 checkpoint_every_steps=1)
    return ExperimentConfig(
        name="zero3_grid",
        model=ModelConfig(name=model, num_classes=10,
                          compute_dtype="float32", dropout_rate=0.0),
        optim=OptimConfig(base_lr=0.05, reference_batch_size=16,
                          momentum=0.9, weight_decay=1e-4),
        data=DataConfig(name="synthetic", image_size=32,
                        global_batch_size=16, num_train_examples=64),
        mesh=MeshConfig(num_data=8, **mesh_kw),
        train=tr,
    )


Z2 = dict(shard_opt_state=True, shard_gradients=True, comm_bucket_mb=0.25)
Z3 = dict(Z2, shard_params=True)


def _trainer_run(cfg, n_steps=3):
    from distributed_vgg_f_tpu.data.synthetic import SyntheticDataset
    from distributed_vgg_f_tpu.train.trainer import Trainer
    from distributed_vgg_f_tpu.utils.logging import MetricLogger
    trainer = Trainer(cfg, logger=MetricLogger(stream=io.StringIO()))
    state = trainer.restore_or_init()
    rng = trainer.base_rng()
    ds = SyntheticDataset(batch_size=cfg.data.global_batch_size,
                          image_size=32, num_classes=10, seed=0)
    losses = []
    for _ in range(n_steps):
        state, m = trainer.train_step(state, trainer.shard(next(ds)), rng)
        losses.append(float(jax.device_get(m["loss"])))
    return trainer, state, losses


@pytest.mark.parametrize("model", [
    "vggf",
    pytest.param("vgg16", marks=pytest.mark.slow),
    pytest.param("resnet50", marks=pytest.mark.slow),
    pytest.param("vit_s16", marks=pytest.mark.slow),
])
def test_equality_grid_real_models_zero3(model):
    """The zoo lane of the r21 acceptance grid: each model's zero3 CPU
    loss trajectory EQUALS its zero2 one, bucketed and monolithic (vggf
    rides the default loop as the canary; the rest are slow-lane)."""
    for extra in ({}, {"comm_bucket_mb": 0.0}):
        ref = _trainer_run(_trainer_cfg(model, **dict(Z2, **extra)))[2]
        l3 = _trainer_run(_trainer_cfg(model, **dict(Z3, **extra)))[2]
        assert l3 == ref, f"{model} {extra}: {l3} != {ref}"


@pytest.mark.slow
def test_zero3_checkpoint_retopology(tmp_path):
    """The r21 any-geometry restore gates: (a) zero3 roundtrip, (b) zero3
    checkpoint -> zero2 trainer (flat -> tree), (c) zero2 checkpoint ->
    zero3 trainer (tree -> flat), (d) the ZERO1-ERA parity gate — a
    checkpoint written before shard_gradients/shard_params existed (tree
    params + canonical flat opt) restores into the bucketed zero3 run
    with exactly equal per-parameter values."""
    from distributed_vgg_f_tpu.train.trainer import Trainer
    from distributed_vgg_f_tpu.utils.logging import MetricLogger

    def params_of(tr, state, leaf=None):
        return jax.tree.leaves(jax.device_get(
            tr.params_tree(state.params if leaf is None else leaf)))

    # (a) + (b): zero3 write, zero3 + zero2 reads
    tr3, st3, _ = _trainer_run(_trainer_cfg(ema=0.9, ckpt=tmp_path / "z3",
                                            **Z3), n_steps=2)
    tr3.checkpoints.save(st3, force=True, extra=tr3.exchange.receipts())
    tr3.checkpoints.wait()
    assert tr3.exchange.receipts()["param_layout"]["kind"] \
        == "bucketed_flat"
    r3 = tr3.restore_or_init()
    np.testing.assert_array_equal(
        np.asarray(jax.device_get(st3.params)),
        np.asarray(jax.device_get(r3.params)))
    tr2 = Trainer(_trainer_cfg(ema=0.9, ckpt=tmp_path / "z3", **Z2),
                  logger=MetricLogger(stream=io.StringIO()))
    r2 = tr2.restore_or_init()
    for a, b in zip(params_of(tr3, st3),
                    jax.tree.leaves(jax.device_get(r2.params))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for a, b in zip(params_of(tr3, st3, st3.ema_params),
                    jax.tree.leaves(jax.device_get(r2.ema_params))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # (c): zero2 write, zero3 read
    tr2b, st2b, _ = _trainer_run(_trainer_cfg(ckpt=tmp_path / "z2", **Z2),
                                 n_steps=2)
    tr2b.checkpoints.save(st2b, force=True,
                          extra=tr2b.exchange.receipts())
    tr2b.checkpoints.wait()
    tr3c = Trainer(_trainer_cfg(ckpt=tmp_path / "z2", **Z3),
                   logger=MetricLogger(stream=io.StringIO()))
    r3c = tr3c.restore_or_init()
    for a, b in zip(jax.tree.leaves(jax.device_get(st2b.params)),
                    params_of(tr3c, r3c)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # (d): zero1-era write (tree params, canonical flat opt), zero3 read
    tr1, st1, _ = _trainer_run(
        _trainer_cfg(ckpt=tmp_path / "z1", shard_opt_state=True),
        n_steps=2)
    tr1.checkpoints.save(st1, force=True)
    tr1.checkpoints.wait()
    tr3d = Trainer(_trainer_cfg(ckpt=tmp_path / "z1", **Z3),
                   logger=MetricLogger(stream=io.StringIO()))
    r3d = tr3d.restore_or_init()
    assert r3d.params.ndim == 1
    for a, b in zip(jax.tree.leaves(jax.device_get(st1.params)),
                    params_of(tr3d, r3d)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.slow
def test_elastic_resize_under_zero3(tmp_path):
    """The r21 elastic cell: preempt k=1 of 4 under bucketed zero3 —
    the live reshard re-interleaves the flat param/EMA vectors onto 3
    shards and the trajectory EQUALS the restart-from-checkpoint control
    (the r19 pin, extended to the zero3 layout)."""
    import json
    from distributed_vgg_f_tpu.train.trainer import Trainer
    from distributed_vgg_f_tpu.utils.logging import MetricLogger

    def cfg_of(ckpt, *, elastic_on=True, faults="", steps=5):
        import dataclasses
        cfg = _trainer_cfg(ckpt=ckpt, **Z3)
        cfg = dataclasses.replace(
            cfg,
            data=dataclasses.replace(cfg.data, global_batch_size=12,
                                     num_train_examples=48),
            optim=dataclasses.replace(cfg.optim, reference_batch_size=12),
            mesh=dataclasses.replace(
                cfg.mesh, num_data=0,
                elastic=ElasticConfig(enabled=elastic_on,
                                      batch_policy="keep_global")),
            train=dataclasses.replace(cfg.train, steps=steps, log_every=1,
                                      checkpoint_every_steps=100,
                                      eval_every_steps=10_000,
                                      fault_injection=faults))
        return cfg

    def run_fit(cfg, n):
        mesh = build_mesh(MeshSpec(("data",), (n,)),
                          devices=jax.devices()[:n])
        stream = io.StringIO()
        logger = MetricLogger(stream=io.StringIO())
        logger._file = stream
        tr = Trainer(cfg, mesh=mesh, logger=logger)
        state = tr.fit()
        recs = [json.loads(ln) for ln in stream.getvalue().splitlines()]
        return recs, state

    def losses(recs):
        return {r["step"]: r["loss"] for r in recs
                if r.get("event") == "train"}

    recs, state = run_fit(cfg_of(tmp_path / "el",
                                 faults="preempt@rank1:2"), 4)
    resizes = [r for r in recs if r.get("event") == "elastic_resize"]
    assert resizes and resizes[0]["topology"] == "elastic_4to3"
    assert state.params.ndim == 1  # still the flat shard on 3 survivors
    el = losses(recs)
    recs_s, _ = run_fit(cfg_of(tmp_path / "stop", elastic_on=False,
                               faults="preempt@rank1:2"), 4)
    recs_r, _ = run_fit(cfg_of(tmp_path / "stop"), 3)
    ctrl = {**losses(recs_s), **losses(recs_r)}
    for s in sorted(el):
        assert el[s] == ctrl[s], (s, el[s], ctrl[s])
