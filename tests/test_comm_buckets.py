"""Overlapped bucketed gradient exchange + ZeRO-2 (ISSUE 11,
parallel/buckets.py): bucket-geometry invariants, the kill-switch
lowered-text identity (comm_bucket_mb unset ≡ the pre-r14 step), the
committed lowered-HLO overlap assertions, the CPU loss-trajectory EQUALITY
grid across {dp, zero1, zero2} x {bucketed on/off} x {grad_accum 1,2} x
two bucket sizes, the clip-after-cast x reduce_dtype pin (ISSUE 11
bugfix satellite), checkpoint layout migration, comm telemetry, and the
scaling-model memory claims."""

import dataclasses
import io

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_vgg_f_tpu.config import (
    DataConfig,
    ExperimentConfig,
    MeshConfig,
    ModelConfig,
    OptimConfig,
    TrainConfig,
    get_config,
)
from distributed_vgg_f_tpu.parallel.buckets import (
    build_bucket_layout,
    hlo_overlap_report,
    layout_from_receipt,
)
from distributed_vgg_f_tpu.parallel.mesh import (
    MeshSpec,
    build_mesh,
    shard_host_batch,
)
from distributed_vgg_f_tpu.parallel.zero import (
    flat_param_count,
    padded_flat_size,
    plan_exchange,
)
from distributed_vgg_f_tpu.train.state import TrainState
from distributed_vgg_f_tpu.train.step import build_train_step


def _mesh8(devices8):
    return build_mesh(MeshSpec(("data",), (8,)), devices=devices8)


class _MiniNet:
    """Tiny flax model with a conv + two dense layers: enough leaves for a
    multi-bucket partition, cheap enough for the full equality grid."""

    def __new__(cls):
        import flax.linen as nn

        class Net(nn.Module):
            @nn.compact
            def __call__(self, x, *, train=False, rngs=None):
                x = nn.Conv(8, (3, 3), strides=(2, 2),
                            dtype=jnp.float32)(x)
                x = nn.relu(x)
                x = x.reshape((x.shape[0], -1))
                x = nn.Dense(32, dtype=jnp.float32)(x)
                x = nn.relu(x)
                return nn.Dense(10, dtype=jnp.float32)(x)

        return Net()


def _mini_params():
    import optax
    model = _MiniNet()
    state = TrainState.create(model, optax.sgd(0.1), jax.random.key(0),
                              jnp.zeros((1, 16, 16, 3), jnp.float32))
    return model, state.params


# ------------------------------------------------------------------- config
def test_mesh_config_validation():
    with pytest.raises(ValueError, match="comm_bucket_mb"):
        MeshConfig(comm_bucket_mb=-1.0)
    assert MeshConfig().sharding_label == "dp"
    assert MeshConfig(shard_opt_state=True).sharding_label == "zero1"
    assert MeshConfig(shard_opt_state=True,
                      shard_gradients=True).sharding_label == "zero2"
    # shard_gradients without the ZeRO-1 frame DOWNGRADES (the trainer's
    # single-device precedent) so the README's documented
    # `--set mesh.shard_opt_state=false` toggle stays valid on the
    # flagship, which ships ZeRO-2
    assert MeshConfig(shard_gradients=True).sharding_label == "dp"


def test_flagship_ships_zero2_bucketed():
    """The flagship preset carries the r14 exchange: ZeRO-2 gradient
    sharding over the ZeRO-1 frame plus 4 MB buckets — and the derived zoo
    presets inherit it."""
    flag = get_config("vggf_imagenet_dp")
    assert flag.mesh.shard_opt_state is True
    assert flag.mesh.shard_gradients is True
    assert flag.mesh.comm_bucket_mb == 4.0
    assert flag.mesh.sharding_label == "zero2"
    for name in ("vgg16_imagenet", "resnet50_imagenet", "vit_s16_imagenet"):
        assert get_config(name).mesh.sharding_label == "zero2"


def test_plan_downgrades_zero2_without_zero1():
    """`shard_gradients` without `shard_opt_state` has no 1/N frame to live
    in: the exchange plan runs it as plain dp, as the config's label says
    (the README's `--set mesh.shard_opt_state=false` toggle on presets that
    ship ZeRO-2)."""
    import optax
    mesh = build_mesh(MeshSpec(("data",), (0,)))
    asked = MeshConfig(shard_gradients=True)
    plan = plan_exchange(asked, mesh, optax.sgd(0.1))
    assert plan.basis == asked.sharding_label == "dp"


# ----------------------------------------------------------- layout geometry
def test_bucket_layout_partition_invariants():
    _, params = _mini_params()
    leaves = jax.tree.leaves(params)
    layout = build_bucket_layout(params, 8, 1024)
    # every canonical leaf appears in exactly one bucket
    seen = [i for b in layout.buckets for i in b]
    assert sorted(seen) == list(range(len(leaves)))
    # reverse-backward emission: bucket 0 starts at the LAST leaf
    assert layout.buckets[0][0] == len(leaves) - 1
    flat = [i for b in layout.buckets for i in b]
    assert flat == list(reversed(range(len(leaves))))
    # per-bucket padding is a multiple of the shard count and geometry sums
    for n, p, s in zip(layout.bucket_sizes(), layout.padded_sizes(),
                       layout.shard_sizes()):
        assert p % 8 == 0 and p - n < 8 and s == p // 8
    assert layout.total_padded == sum(layout.padded_sizes())
    assert layout.shard_size * 8 == layout.total_padded
    # leaves are atomic: a leaf above the target gets its own bucket, so
    # bucket count never exceeds leaf count
    assert 2 <= layout.num_buckets <= len(leaves)
    # kill-switch: 0 target -> no layout
    assert build_bucket_layout(params, 8, 0) is None


def test_bucket_layout_global_roundtrip():
    """to_global/from_global are exact inverses — the checkpoint layout
    permutation loses nothing, and the local shard IS row r of the global
    (N, S) view (the property the per-bucket psum_scatter relies on)."""
    _, params = _mini_params()
    for target in (512, 4096):
        layout = build_bucket_layout(params, 8, target)
        vec = layout.to_global(params)
        assert vec.shape == (layout.total_padded,)
        back = layout.from_global(vec)
        for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(back)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        mat = np.asarray(vec).reshape(8, layout.shard_size)
        # row r == concat of per-bucket pieces r
        off = 0
        leaves = jax.tree.leaves(params)
        for b, s_b in enumerate(layout.shard_sizes()):
            parts = [np.ravel(np.asarray(leaves[i]))
                     for i in layout.buckets[b]]
            bvec = np.concatenate(parts)
            bvec = np.pad(bvec, (0, layout.padded_sizes()[b] - bvec.size))
            for r in range(8):
                np.testing.assert_array_equal(
                    mat[r, off:off + s_b], bvec[r * s_b:(r + 1) * s_b])
            off += s_b


def test_layout_receipt_roundtrip_and_mismatch():
    _, params = _mini_params()
    layout = build_bucket_layout(params, 8, 1024)
    rebuilt = layout_from_receipt(params, layout.describe())
    assert rebuilt.describe() == layout.describe()
    bad = dict(layout.describe(), total_padded=layout.total_padded + 8)
    with pytest.raises(ValueError, match="does not reproduce"):
        layout_from_receipt(params, bad)
    # same TOTAL, different partition (two layers trading widths): the
    # receipt's per-bucket sizes must catch what the total cannot
    elems = list(layout.describe()["bucket_elems"])
    swapped = dict(layout.describe(),
                   bucket_elems=[elems[1], elems[0]] + elems[2:])
    with pytest.raises(ValueError, match="does not reproduce"):
        layout_from_receipt(params, swapped)
    with pytest.raises(ValueError, match="kind"):
        layout_from_receipt(params, {"kind": "nope"})


# -------------------------------------------------- step builders for grids
def _plan(mesh, model, tx, sample, *, basis="dp", bucket_mb=0.0, accum=1,
          reduce_dtype="float32", ema=False, accum_shard=False):
    """The exchange plan (parallel/zero.py) of one grid cell, bound to the
    model's parameter shapes where the basis shards anything."""
    plan = plan_exchange(
        MeshConfig(shard_opt_state=basis != "dp",
                   shard_gradients=basis in ("zero2", "zero3"),
                   shard_params=basis == "zero3",
                   comm_bucket_mb=bucket_mb, reduce_dtype=reduce_dtype),
        mesh, tx, grad_accum_steps=accum, grad_accum_shard=accum_shard)
    assert plan.basis == basis
    if plan.sharded:
        shapes = jax.eval_shape(
            lambda r: TrainState.create(model, tx, r, sample),
            jax.random.key(0))
        plan = plan.bind(shapes.params, shapes.batch_stats, ema=ema)
    return plan


def _build(mesh, model, *, zero=False, zero2=False, bucket_mb=0.0,
           accum=1, reduce_dtype="float32", clip=0.0, sample_hw=16):
    import optax
    tx = optax.sgd(0.05, momentum=0.9)
    sample = jnp.zeros((1, sample_hw, sample_hw, 3), jnp.float32)
    plan = _plan(mesh, model, tx, sample,
                 basis="zero2" if zero2 else "zero1" if zero else "dp",
                 bucket_mb=bucket_mb, accum=accum, reduce_dtype=reduce_dtype)
    if zero:
        def create(r):
            return TrainState.create(model, tx, r, sample, exchange=plan)

        shardings = plan.state_shardings(mesh)
        state = jax.jit(create, out_shardings=shardings)(jax.random.key(0))
    else:
        state = TrainState.create(model, tx, jax.random.key(0), sample)
    step = build_train_step(model, mesh, 1e-4, plan, grad_clip_norm=clip)
    return state, step


def _run(mesh, model, batches, base, n=3, **kw):
    state, step = _build(mesh, model, **kw)
    losses, norms = [], []
    for b in batches[:n]:
        state, m = step(state, b, base)
        losses.append(float(jax.device_get(m["loss"])))
        norms.append(float(jax.device_get(m["grad_norm"])))
    return losses, norms, state, step


def _batches(n=3, hw=16, classes=10, batch=16, mesh=None, seed=0):
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(n):
        b = {"image": rng.standard_normal(
                (batch, hw, hw, 3)).astype(np.float32),
             "label": rng.integers(0, classes, (batch,)).astype(np.int32)}
        out.append(shard_host_batch(b, mesh))
    return out


# ----------------------------------------------- loss-trajectory EQUALITY
def test_equality_grid_mininet(devices8):
    """The acceptance grid at MiniNet scale (the vggf/vit_s16 runs ride
    the slow marker below): {dp, zero1, zero2} x {bucketed on/off} x two
    bucket sizes produce BITWISE-equal CPU loss trajectories at
    grad_accum=1 — bucketing permutes flat layouts, never elementwise
    math — and the accum=2 compositions agree to fp-summation tolerance."""
    mesh = _mesh8(devices8)
    model = _MiniNet()
    batches = _batches(mesh=mesh)
    base = jax.jit(lambda: jax.random.key(1))()

    ref, ref_norms, _, _ = _run(mesh, model, batches, base)
    small, big = 0.0005, 0.004  # MB — two bucket geometries
    grid = {
        "dp_bucket_small": dict(bucket_mb=small),
        "dp_bucket_big": dict(bucket_mb=big),
        "zero1": dict(zero=True),
        "zero1_bucket_small": dict(zero=True, bucket_mb=small),
        "zero2_bucket_small": dict(zero=True, zero2=True, bucket_mb=small),
        "zero2_bucket_big": dict(zero=True, zero2=True, bucket_mb=big),
    }
    for name, kw in grid.items():
        losses, norms, _, step = _run(mesh, model, batches, base, **kw)
        assert losses == ref, f"{name} diverged: {losses} != {ref}"
        # the grad norm is computed from the sharded form under ZeRO
        # (psum of shard partials) and per-leaf sums under DP — fp
        # reduction ORDER differs across layouts, so the pin is a tight
        # tolerance, not bitwise (the bitwise contract covers the LOSS
        # trajectory, where no cross-element reduction reorders)
        np.testing.assert_allclose(norms, ref_norms, rtol=1e-5)
    # grad accumulation: sharded accumulator (zero2) == full-tree
    # accumulator == replicated accumulation, at fp tolerance (the scan
    # reorders gradient summation)
    acc_ref, _, _, _ = _run(mesh, model, batches, base, accum=2)
    for kw in (dict(zero=True, accum=2),
               dict(zero=True, zero2=True, accum=2),
               dict(zero=True, zero2=True, accum=2, bucket_mb=small),
               dict(zero=True, zero2=True, accum=2, bucket_mb=big)):
        losses, _, _, _ = _run(mesh, model, batches, base, **kw)
        np.testing.assert_allclose(losses, acc_ref, rtol=2e-5)


def test_zero2_accum_carry_is_sharded(devices8):
    """ZeRO-2's memory claim at the jaxpr level: with shard_gradients on,
    the scan carry is the (shard_size,) vector — O(params/N) — without
    needing the explicit grad_accum_shard flag."""
    mesh = _mesh8(devices8)
    model = _MiniNet()
    state, step = _build(mesh, model, zero=True, zero2=True, accum=2,
                         bucket_mb=0.0005)
    meta = None
    batches = _batches(mesh=mesh, n=1)
    base = jax.jit(lambda: jax.random.key(1))()
    state, m = step(state, batches[0], base)
    meta = step.comm_meta
    assert meta["sharding"] == "zero2"
    assert meta["grad_accum_steps"] == 2
    # k micro-scatters move k x the scatter-leg bytes (the explicit
    # memory-for-bandwidth trade documented in the step); the fp32 wire
    # makes scatter == gather per leg, so accum=2 doubles exactly
    assert meta["scatter_bytes"] == 2 * meta["gather_bytes"]
    assert meta["wire_bytes"] == meta["scatter_bytes"] \
        + meta["gather_bytes"]


# ------------------------------------------------------ kill-switch identity
def test_kill_switch_lowered_text_identity(devices8):
    """comm_bucket_mb unset lowers to EXACTLY the pre-r14 step — for both
    the DP and ZeRO paths (the ISSUE 11 kill-switch contract); the
    bucketed build must differ (it had better be doing something)."""
    mesh = _mesh8(devices8)
    model = _MiniNet()
    batches = _batches(mesh=mesh, n=1)
    base = jax.jit(lambda: jax.random.key(1))()
    for zero in (False, True):
        state, legacy = _build(mesh, model, zero=zero)
        _, off = _build(mesh, model, zero=zero, bucket_mb=0.0)
        _, on = _build(mesh, model, zero=zero, bucket_mb=0.0005)
        text_legacy = legacy.lower(state, batches[0], base).as_text()
        text_off = off.lower(state, batches[0], base).as_text()
        text_on = on.lower(state, batches[0], base).as_text() if not zero \
            else None  # bucketed ZeRO needs the bucketed state layout
        assert text_off == text_legacy, \
            f"kill-switch not byte-identical (zero={zero})"
        if text_on is not None:
            assert text_on != text_legacy


# ------------------------------------------------- lowered-HLO assertions
def test_hlo_monolithic_zero_is_serial_tail(devices8):
    """The committed negative: the unbucketed ZeRO exchange is ONE flat
    reduce-scatter whose ancestors include the entire backward — no
    overlap license exists."""
    mesh = _mesh8(devices8)
    model = _MiniNet()
    batches = _batches(mesh=mesh, n=1)
    base = jax.jit(lambda: jax.random.key(1))()
    state, step = _build(mesh, model, zero=True)
    rep = hlo_overlap_report(step.lower(state, batches[0], base).as_text())
    assert rep["collective_counts"].get("reduce_scatter", 0) == 1
    assert rep["serial_tail_collectives"] >= 1
    # every gradient collective (scatter AND param gather) depends on the
    # full backward: nothing can overlap
    assert rep["overlap_capable"] is False


def test_hlo_bucketed_zero_overlap_evidence(devices8):
    """ISSUE 11 acceptance: >= 2 collectives interleaved with backward
    compute when bucketing is on — one reduce-scatter PER BUCKET, and a
    committed dependency witness that some gradient collective and some
    backward matmul/conv have no path between them (the structural
    license for XLA's latency-hiding scheduler)."""
    mesh = _mesh8(devices8)
    model = _MiniNet()
    batches = _batches(mesh=mesh, n=1)
    base = jax.jit(lambda: jax.random.key(1))()
    state, step = _build(mesh, model, zero=True, zero2=True,
                         bucket_mb=0.0005)
    rep = hlo_overlap_report(step.lower(state, batches[0], base).as_text())
    assert step.comm_meta["buckets"] >= 2
    assert rep["collective_counts"]["reduce_scatter"] \
        == step.comm_meta["buckets"]
    assert rep["grad_collectives"] >= 2
    assert rep["overlap_capable"] is True, \
        "no (collective, compute) pair is schedulable concurrently"
    assert rep["witness"] is not None


def test_hlo_bucketed_dp_groups_leaf_collectives(devices8):
    """Plain DP already emits one pmean per LEAF (overlap-capable but
    message-size-hostile at scale); bucketing must GROUP them — fewer
    gradient all-reduces than leaves, count == buckets, overlap
    preserved."""
    mesh = _mesh8(devices8)
    model = _MiniNet()
    batches = _batches(mesh=mesh, n=1)
    base = jax.jit(lambda: jax.random.key(1))()
    state, mono = _build(mesh, model)
    _, bucketed = _build(mesh, model, bucket_mb=0.004)
    text_mono = mono.lower(state, batches[0], base).as_text()
    text_b = bucketed.lower(state, batches[0], base).as_text()
    n_leaves = len(jax.tree.leaves(state.params))
    rep_mono = hlo_overlap_report(text_mono)
    rep_b = hlo_overlap_report(text_b)
    assert rep_mono["collective_counts"]["all_reduce"] >= n_leaves
    assert rep_b["collective_counts"]["all_reduce"] \
        < rep_mono["collective_counts"]["all_reduce"]
    assert bucketed.comm_meta["buckets"] < n_leaves
    assert rep_b["overlap_capable"] is True


# ------------------------------------- clip-after-cast x reduce_dtype pin
def test_clip_after_cast_vs_fp32_within_wire_tolerance(devices8):
    """ISSUE 11 bugfix satellite: under ZeRO with mesh.reduce_dtype set,
    the scatter leg casts BEFORE the pad/clip interplay. Pin the
    semantics: (a) the padding region is inert through the cast (bf16(0)
    == 0 — the momentum tail stays exactly zero), (b) clip-after-cast
    (the implemented order: cast -> scatter -> fp32 norm -> clip) agrees
    with the fp32-wire clip within bf16 wire tolerance (~2^-8 relative),
    and (c) the DP and ZeRO paths implement the SAME ordering (they share
    collectives.cast_to_wire), so their clipped trajectories agree at the
    wire's own tolerance."""
    mesh = _mesh8(devices8)
    model = _MiniNet()
    batches = _batches(mesh=mesh)
    base = jax.jit(lambda: jax.random.key(1))()
    kw = dict(clip=0.05)
    f32_z, f32_zn, _, _ = _run(mesh, model, batches, base, zero=True, **kw)
    bf16_z, bf16_zn, state_z, _ = _run(mesh, model, batches, base,
                                       zero=True, reduce_dtype="bfloat16",
                                       **kw)
    bf16_d, bf16_dn, _, _ = _run(mesh, model, batches, base,
                                 reduce_dtype="bfloat16", **kw)
    bf16_zb, _, _, _ = _run(mesh, model, batches, base, zero=True,
                            zero2=True, bucket_mb=0.0005,
                            reduce_dtype="bfloat16", **kw)
    # (b) wire-dtype tolerance: bf16 keeps 8 mantissa bits -> ~0.4%
    # per-element rounding; 3 steps of momentum compound it, 2% covers it
    np.testing.assert_allclose(bf16_zn, f32_zn, rtol=2e-2)
    np.testing.assert_allclose(bf16_z, f32_z, rtol=2e-2)
    # (c) same ordering on both paths: dp-bf16 == zero-bf16 (+ bucketed)
    # to the wire's own tolerance (layouts permute the fp32 math only)
    np.testing.assert_allclose(bf16_z, bf16_d, rtol=1e-5)
    np.testing.assert_allclose(bf16_zn, bf16_dn, rtol=1e-4)
    np.testing.assert_allclose(bf16_zb, bf16_z, rtol=1e-5)
    # (a) the padded momentum tail is exactly zero after bf16+clip steps
    n_elem = flat_param_count(state_z.params)
    padded = padded_flat_size(n_elem, 8)
    for leaf in jax.tree.leaves(state_z.opt_state):
        if getattr(leaf, "ndim", 0) == 1 and leaf.shape[0] == padded \
                and padded > n_elem:
            tail = np.asarray(jax.device_get(leaf))[n_elem:]
            np.testing.assert_array_equal(tail, np.zeros_like(tail))


# ------------------------------------------------- opt-state layout moves
def test_convert_opt_state_bucketed_roundtrip(devices8):
    """canonical flat <-> bucketed flat through convert_opt_state is exact
    both ways (the checkpoint migration primitive retopology drives)."""
    import optax

    from distributed_vgg_f_tpu.parallel.zero import convert_opt_state
    model, params = _mini_params()
    tx = optax.sgd(0.05, momentum=0.9)
    mesh = _mesh8(devices8)
    zero1 = MeshConfig(shard_opt_state=True)
    canonical = plan_exchange(zero1, mesh, tx).bind(params)
    bucketed = plan_exchange(
        dataclasses.replace(zero1, comm_bucket_mb=1024 / 2 ** 20),
        mesh, tx).bind(params)
    n = flat_param_count(params)
    padded = padded_flat_size(n, 8)
    assert canonical.total_padded == padded
    layout = build_bucket_layout(params, 8, 1024)
    assert bucketed.layout == layout
    # a canonical flat state with a recognizable momentum pattern
    rng = np.random.default_rng(3)
    canon_vec = jnp.asarray(
        np.concatenate([rng.standard_normal(n).astype(np.float32),
                        np.zeros(padded - n, np.float32)]))
    canon = jax.eval_shape(tx.init,
                           jax.ShapeDtypeStruct((padded,), jnp.float32))
    canon = jax.tree.map(
        lambda l: (canon_vec if l.ndim == 1 and l.shape[0] == padded
                   else jnp.zeros(l.shape, l.dtype)), canon)
    # the source as the plan that held the state, and as the receipt a
    # checkpoint of it carries (none for the canonical layout)
    there = convert_opt_state(canon, canonical, bucketed)
    same = convert_opt_state(canon, None, bucketed)
    back = convert_opt_state(there, bucketed.receipts()["opt_layout"],
                             canonical)
    for a, b, c in zip(jax.tree.leaves(canon), jax.tree.leaves(back),
                       jax.tree.leaves(
                           convert_opt_state(same, bucketed, canonical))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        np.testing.assert_array_equal(np.asarray(a), np.asarray(c))
    # mismatched geometry must fail loudly: a saved vector of another
    # length than the source layout's
    longer = jax.tree.map(
        lambda l: jnp.pad(l, (0, 8)) if l.ndim == 1 else l, there)
    with pytest.raises(ValueError, match="total_padded"):
        convert_opt_state(longer, bucketed, canonical)


# --------------------------------------------------------------- telemetry
def test_comm_block_schema():
    from distributed_vgg_f_tpu.telemetry import schema
    good = {"sharding": "zero2", "bucketed": True, "buckets": 14,
            "bucket_mb": 4.0, "reduce_dtype": "float32",
            "grad_accum_steps": 1, "wire_bytes": 123, "scatter_bytes": 61,
            "gather_bytes": 62, "allreduce_bytes": 0}
    errors = []
    schema.validate_comm_block(good, "t", errors)
    assert errors == []
    # r21: zero3 is a legal basis and may carry the gather count
    errors = []
    schema.validate_comm_block(dict(good, sharding="zero3", gathers=14),
                               "t", errors)
    assert errors == []
    for bad, match in (
            (dict(good, sharding="zero4"), "sharding"),
            (dict(good, sharding="zero3", gathers=-1), "gathers"),
            (dict(good, buckets=0), "buckets"),
            (dict(good, bucket_mb=-1), "bucket_mb"),
            ({k: v for k, v in good.items() if k != "wire_bytes"},
             "wire_bytes"),
            (dict(good, bucketed="yes"), "bucketed")):
        errors = []
        schema.validate_comm_block(bad, "t", errors)
        assert errors and match in errors[0]
    # wired into train records
    rec = {"event": "train", "step": 1, "comm": dict(good, sharding="bad")}
    assert any("sharding" in e
               for e in schema.validate_metrics_record(rec))


def test_comm_counters_and_window_block(devices8):
    """The step wrapper increments comm/exchanges + comm/wire_bytes and
    sets the exchange-shape gauges (the README counter-table rows the
    drift guard cross-checks), single-sourced from the traced geometry."""
    from distributed_vgg_f_tpu import telemetry
    telemetry.configure(enabled=True)
    try:
        mesh = _mesh8(devices8)
        model = _MiniNet()
        batches = _batches(mesh=mesh, n=2)
        base = jax.jit(lambda: jax.random.key(1))()
        state, step = _build(mesh, model, zero=True, zero2=True,
                             bucket_mb=0.0005)
        reg = telemetry.get_registry()
        reg.delta("comm_test")
        for b in batches:
            state, _ = step(state, b, base)
        delta = reg.delta("comm_test")
        assert delta.get("comm/exchanges") == 2
        assert delta.get("comm/wire_bytes") \
            == 2 * step.comm_meta["wire_bytes"]
        snap = reg.snapshot()
        assert snap.get("comm/buckets_per_step") \
            == step.comm_meta["buckets"]
        assert snap.get("comm/bucket_mb") == step.comm_meta["bucket_mb"]
        # the JSONL block the trainer logs validates against the schema
        from distributed_vgg_f_tpu.telemetry import schema
        errors = []
        schema.validate_comm_block(dict(step.comm_meta), "t", errors)
        assert errors == []
    finally:
        telemetry.reset()


# ------------------------------------------------------- regression sentinel
def test_sentinel_basis_grows_sharding_with_pre_r14_default():
    from distributed_vgg_f_tpu.telemetry.regress import Basis, row_basis
    b = Basis("u8", True, "noise", (320, 256), True)
    assert b.sharding == "dp"                       # pre-r14 default
    assert b.describe()["sharding"] == "dp"
    row = {"mode": "comm_overlap_bench", "wire": "u8",
           "sharding": "zero2_bucketed"}
    assert row_basis(row).sharding == "zero2_bucketed"
    # r21: the zero3 bases land on their own keys
    assert row_basis(dict(row, sharding="zero3_bucketed")).sharding \
        == "zero3_bucketed"
    assert row_basis(dict(row, sharding="zero3")).sharding == "zero3"
    # absent field keeps old receipts on their existing key
    assert row_basis({"wire": "u8"}).sharding == "dp"


# ------------------------------------------------------------ scaling model
def test_scaling_model_zero2_memory_and_wire():
    from distributed_vgg_f_tpu.utils.scaling_model import (
        approx_num_buckets,
        bucketed_exposed_comm_s,
        exchange_bytes_per_chip,
        gradient_state_bytes_per_chip,
    )
    P_, N = 60_000_000, 64
    # wire: zero2 moves exactly zero1's bytes; both beat nothing (the win
    # is memory), dp's all-reduce is the same total at fp32
    z1 = exchange_bytes_per_chip(4 * P_, N, sharding="zero1")
    z2 = exchange_bytes_per_chip(4 * P_, N, sharding="zero2")
    dp = exchange_bytes_per_chip(4 * P_, N, sharding="dp")
    assert z1 == z2 == dp
    # r21: zero3 moves the same bytes at the fp32 wire (the re-sync
    # gather becomes the just-in-time gather); its gather leg may narrow
    # with the wire dtype, expressed via param_bytes
    z3 = exchange_bytes_per_chip(4 * P_, N, sharding="zero3")
    assert z3 == z2
    z3_bf16 = exchange_bytes_per_chip(2 * P_, N, sharding="zero3",
                                      param_bytes=2 * P_)
    assert z3_bf16 == z3 / 2
    with pytest.raises(ValueError):
        exchange_bytes_per_chip(4 * P_, N, sharding="zero4")
    # memory: the ZeRO-2 claim — accumulator and opt state O(params/N)
    g_dp = gradient_state_bytes_per_chip(P_, N, sharding="dp",
                                         grad_accum_steps=2)
    g_z1 = gradient_state_bytes_per_chip(P_, N, sharding="zero1",
                                         grad_accum_steps=2)
    g_z2 = gradient_state_bytes_per_chip(P_, N, sharding="zero2",
                                         grad_accum_steps=2,
                                         bucket_bytes=4 << 20)
    assert g_dp["opt_state_bytes"] == 4 * P_
    assert g_z1["opt_state_bytes"] == g_z2["opt_state_bytes"] \
        == 4 * P_ / N
    assert g_dp["grad_accumulator_bytes"] \
        == g_z1["grad_accumulator_bytes"] == 4 * P_
    assert g_z2["grad_accumulator_bytes"] == 4 * P_ / N
    # r21: zero3 keeps zero2's gradient state exactly; its own win is
    # param state — O(params) everywhere else, O(params/N) under zero3
    from distributed_vgg_f_tpu.utils.scaling_model import param_bytes_per_chip
    g_z3 = gradient_state_bytes_per_chip(P_, N, sharding="zero3",
                                         grad_accum_steps=2,
                                         bucket_bytes=4 << 20)
    assert g_z3 == g_z2
    assert param_bytes_per_chip(P_, N, sharding="dp") \
        == param_bytes_per_chip(P_, N, sharding="zero2") == 4 * P_
    assert param_bytes_per_chip(P_, N, sharding="zero3") == 4 * P_ / N
    assert param_bytes_per_chip(P_, N, sharding="zero3", ema=True) \
        == 8 * P_ / N
    with pytest.raises(ValueError):
        param_bytes_per_chip(P_, N, sharding="zero4")
    # the VGG-16 acceptance row of the README table: 528 MB -> 4.1 MB
    vgg16_p = 138_357_544
    assert round(param_bytes_per_chip(vgg16_p, 128, sharding="zero3")
                 / (1 << 20), 1) == 4.1
    # the bucketed exchange buffer is O(bucket), the monolithic O(params)
    assert g_z2["exchange_buffer_bytes"] == 4 << 20
    mono = gradient_state_bytes_per_chip(P_, N, sharding="zero2")
    assert mono["exchange_buffer_bytes"] == 4 * P_
    assert mono["grad_accumulator_bytes"] == 0
    # bucketed DP builds per-bucket concat sends too; monolithic DP's
    # per-leaf pmean consumes leaves in place
    assert gradient_state_bytes_per_chip(
        P_, N, sharding="dp",
        bucket_bytes=4 << 20)["exchange_buffer_bytes"] == 4 << 20
    assert gradient_state_bytes_per_chip(
        P_, N, sharding="dp")["exchange_buffer_bytes"] == 0
    # accum=1: no carry
    # overlap: bucketing bounds the exposed tail by the last bucket; more
    # buckets -> smaller floor but linearly growing latency term
    e1 = bucketed_exposed_comm_s(0.010, 1, overlappable_s=0.0)
    e8 = bucketed_exposed_comm_s(0.010, 8, overlappable_s=0.008)
    assert e8 < e1
    assert bucketed_exposed_comm_s(0.010, 8, overlappable_s=0.008) \
        < bucketed_exposed_comm_s(0.010, 8, overlappable_s=0.0)
    with pytest.raises(ValueError):
        bucketed_exposed_comm_s(1.0, 0, overlappable_s=0.0)
    assert approx_num_buckets(P_, 0) == 1
    assert approx_num_buckets(P_, 4.0, num_leaves=10) == 10
    assert approx_num_buckets(10, 4.0) == 1


# ------------------------------------------------------- trainer-level slow
def _trainer_cfg(model="vggf", steps=3, **mesh_kw):
    return ExperimentConfig(
        name="comm_grid",
        model=ModelConfig(name=model, num_classes=10,
                          compute_dtype="float32", dropout_rate=0.0),
        optim=OptimConfig(base_lr=0.05, reference_batch_size=16,
                          momentum=0.9, weight_decay=1e-4),
        data=DataConfig(name="synthetic", image_size=32,
                        global_batch_size=16, num_train_examples=64),
        mesh=MeshConfig(num_data=8, **mesh_kw),
        train=TrainConfig(steps=steps, seed=0),
    )


def _trainer_run(cfg, n_steps=3):
    from distributed_vgg_f_tpu.data.synthetic import SyntheticDataset
    from distributed_vgg_f_tpu.train.trainer import Trainer
    from distributed_vgg_f_tpu.utils.logging import MetricLogger
    trainer = Trainer(cfg, logger=MetricLogger(stream=io.StringIO()))
    state = trainer.init_state()
    rng = trainer.base_rng()
    ds = SyntheticDataset(batch_size=cfg.data.global_batch_size,
                          image_size=32, num_classes=10, seed=0)
    losses = []
    for _ in range(n_steps):
        state, m = trainer.train_step(state, trainer.shard(next(ds)), rng)
        losses.append(float(jax.device_get(m["loss"])))
    return trainer, state, losses


@pytest.mark.slow
@pytest.mark.parametrize("model", ["vggf", "vit_s16"])
def test_equality_grid_real_models(model):
    """ISSUE 11 test-coverage satellite at real-model scale: vggf (the
    FC-heavy stress case) and vit_s16 (many small leaves) produce EQUAL
    CPU loss trajectories across the sharding x bucketing grid."""
    ref = _trainer_run(_trainer_cfg(model))[2]
    for mesh_kw in (
            dict(comm_bucket_mb=0.25),
            dict(shard_opt_state=True),
            dict(shard_opt_state=True, comm_bucket_mb=0.25),
            dict(shard_opt_state=True, shard_gradients=True,
                 comm_bucket_mb=0.25),
            dict(shard_opt_state=True, shard_gradients=True,
                 comm_bucket_mb=1.0)):
        losses = _trainer_run(_trainer_cfg(model, **mesh_kw))[2]
        assert losses == ref, f"{model} {mesh_kw}: {losses} != {ref}"


@pytest.mark.slow
def test_zero2_bucketed_checkpoint_migration(tmp_path):
    """ISSUE 11 layout-migration parity gate: a checkpoint written by the
    bucketed ZeRO-2 run restores into (a) the same layout (roundtrip), and
    (b) an UNBUCKETED zero1 run — where the momentum must land in the
    canonical frame with exactly the same per-parameter values; and (c) a
    pre-r14-style zero1 checkpoint restores into the bucketed zero2 run.
    All through the geometry receipt in the checkpoint's `extra`."""
    import dataclasses

    import jax.flatten_util

    def with_ckpt(cfg, d):
        return dataclasses.replace(
            cfg, train=dataclasses.replace(cfg.train, checkpoint_dir=str(d),
                                           checkpoint_every_steps=1))

    cfg_b = with_ckpt(_trainer_cfg(shard_opt_state=True,
                                   shard_gradients=True,
                                   comm_bucket_mb=0.25),
                      tmp_path / "bucketed")
    tr_b, state_b, _ = _trainer_run(cfg_b, n_steps=2)
    tr_b.checkpoints.save(state_b, force=True,
                          extra=tr_b.exchange.receipts())
    tr_b.checkpoints.wait()
    # (a) same-layout roundtrip
    restored = tr_b.restore_or_init()
    for a, b in zip(jax.tree.leaves(jax.device_get(state_b.opt_state)),
                    jax.tree.leaves(jax.device_get(restored.opt_state))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # (b) bucketed -> canonical zero1
    from distributed_vgg_f_tpu.train.trainer import Trainer
    from distributed_vgg_f_tpu.utils.logging import MetricLogger
    cfg_c = with_ckpt(_trainer_cfg(shard_opt_state=True),
                      tmp_path / "bucketed")
    tr_c = Trainer(cfg_c, logger=MetricLogger(stream=io.StringIO()))
    rest_c = tr_c.restore_or_init()
    mom_b = [l for l in jax.tree.leaves(jax.device_get(state_b.opt_state))
             if getattr(l, "ndim", 0) == 1 and l.size == tr_b.exchange.total_padded][0]
    mom_c = [l for l in jax.tree.leaves(jax.device_get(rest_c.opt_state))
             if getattr(l, "ndim", 0) == 1 and l.size == tr_c.exchange.total_padded][0]
    canon_from_b = jax.flatten_util.ravel_pytree(
        tr_b._bucket_layout.from_global(jnp.asarray(mom_b)))[0]
    np.testing.assert_array_equal(np.asarray(canon_from_b),
                                  np.asarray(mom_c)[:canon_from_b.size])
    # (c) canonical zero1 checkpoint -> bucketed zero2 run
    cfg_z1 = with_ckpt(_trainer_cfg(shard_opt_state=True),
                       tmp_path / "canon")
    tr_z1, state_z1, _ = _trainer_run(cfg_z1, n_steps=2)
    tr_z1.checkpoints.save(state_z1, force=True)
    tr_z1.checkpoints.wait()
    cfg_b2 = with_ckpt(_trainer_cfg(shard_opt_state=True,
                                    shard_gradients=True,
                                    comm_bucket_mb=0.25),
                       tmp_path / "canon")
    tr_b2 = Trainer(cfg_b2, logger=MetricLogger(stream=io.StringIO()))
    rest_b2 = tr_b2.restore_or_init()
    mom_z1 = [l for l in
              jax.tree.leaves(jax.device_get(state_z1.opt_state))
              if getattr(l, "ndim", 0) == 1 and l.size == tr_z1.exchange.total_padded][0]
    mom_b2 = [l for l in
              jax.tree.leaves(jax.device_get(rest_b2.opt_state))
              if getattr(l, "ndim", 0) == 1 and l.size == tr_b2.exchange.total_padded][0]
    canon_from_b2 = jax.flatten_util.ravel_pytree(
        tr_b2._bucket_layout.from_global(jnp.asarray(mom_b2)))[0]
    np.testing.assert_array_equal(
        np.asarray(canon_from_b2),
        np.asarray(mom_z1)[:canon_from_b2.size])


@pytest.mark.slow
def test_trainer_jsonl_carries_schema_valid_comm_block(tmp_path):
    """The per-window `comm` JSONL block rides every train record and
    schema-validates (the ISSUE 11 telemetry satellite, end to end)."""
    import dataclasses
    import json as _json

    from distributed_vgg_f_tpu.telemetry import schema
    from distributed_vgg_f_tpu.train.trainer import Trainer
    from distributed_vgg_f_tpu.utils.logging import MetricLogger
    cfg = _trainer_cfg(shard_opt_state=True, shard_gradients=True,
                       comm_bucket_mb=0.25, steps=2)
    cfg = dataclasses.replace(
        cfg, train=dataclasses.replace(cfg.train, log_every=1))
    log_path = tmp_path / "train.jsonl"
    with MetricLogger(jsonl_path=str(log_path)) as logger:
        trainer = Trainer(cfg, logger=logger)
        trainer.fit()
    assert schema.validate_metrics_jsonl(str(log_path)) == []
    comm_blocks = []
    with open(log_path) as f:
        for line in f:
            rec = _json.loads(line)
            if rec.get("event") == "train" and "comm" in rec:
                comm_blocks.append(rec["comm"])
    assert comm_blocks, "no train record carried the comm block"
    assert comm_blocks[0]["sharding"] == "zero2"
    assert comm_blocks[0]["bucketed"] is True
    assert comm_blocks[0]["buckets"] >= 2
