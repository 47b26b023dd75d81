"""The exchange plan (parallel/zero.py `plan_exchange` / `Exchange`): one
value decides the basis (dp | zero1 | zero2 | zero3, after the one-shard
downgrade), the flat layout (canonical | bucket-major) and the wire, and the
step, the state, the trainer, the resize and the checkpoint converter ask
it. Here: what it answers outside the mesh, for every basis on both layouts
at four shards and again at one shard, and the ladders it refuses. What it
emits inside the mesh is pinned by the trajectory and lowered-text tests of
test_zero1 / test_zero3 / test_comm_buckets / test_train_step."""

import types

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import PartitionSpec as P

from distributed_vgg_f_tpu.config import MeshConfig
from distributed_vgg_f_tpu.parallel.buckets import GradBucketLayout
from distributed_vgg_f_tpu.parallel.mesh import MeshSpec, build_mesh
from distributed_vgg_f_tpu.parallel.zero import (
    CanonicalFlatLayout,
    layout_from_receipt,
    plan_exchange,
)
from distributed_vgg_f_tpu.train.state import TrainState

from test_comm_buckets import _MiniNet

BASES = ("dp", "zero1", "zero2", "zero3")
BUCKET_MB = 1024 / 2 ** 20          # 1 KiB buckets: several on the MiniNet
SAMPLE = jnp.zeros((1, 16, 16, 3), jnp.float32)
BATCH_STATS = {"bn": {"mean": jax.ShapeDtypeStruct((8,), jnp.float32)}}


def _asked(basis, bucket_mb=0.0):
    return MeshConfig(shard_opt_state=basis != "dp",
                      shard_gradients=basis in ("zero2", "zero3"),
                      shard_params=basis == "zero3",
                      comm_bucket_mb=bucket_mb)


def _mesh(devices8, n):
    return build_mesh(MeshSpec(("data",), (n,)), devices8[:n])


def _tree_state(tx):
    """The state's shapes in the replicated tree layout."""
    return jax.eval_shape(
        lambda r: TrainState.create(_MiniNet(), tx, r, SAMPLE),
        jax.random.key(0))


@pytest.mark.parametrize("layout", ["canonical", "bucketed"])
@pytest.mark.parametrize("basis", BASES)
def test_plan_at_four_shards(basis, layout, devices8):
    tx = optax.sgd(0.05, momentum=0.9)
    bucket_mb = BUCKET_MB if layout == "bucketed" else 0.0
    shapes = _tree_state(tx)
    plan = plan_exchange(_asked(basis, bucket_mb), _mesh(devices8, 4), tx)
    assert plan.basis == basis and plan.layout is None
    plan = plan.bind(shapes.params, BATCH_STATS, ema=True)
    assert plan.bind(None) is plan          # bound once
    assert isinstance(plan.layout, GradBucketLayout if bucket_mb
                      else CanonicalFlatLayout)
    assert plan.layout.total_padded % 4 == 0
    assert plan.layout.shard_size * 4 == plan.layout.total_padded
    assert plan.total_padded == (plan.layout.total_padded
                                 if basis != "dp" else None)

    # to_global / from_global: exact inverses on a random tree
    rng = np.random.default_rng(0)
    tree = jax.tree.map(
        lambda l: jnp.asarray(rng.standard_normal(l.shape), l.dtype),
        shapes.params)
    vec = plan.layout.to_global(tree)
    assert vec.shape == (plan.layout.total_padded,)
    for a, b in zip(jax.tree.leaves(tree),
                    jax.tree.leaves(plan.layout.from_global(vec))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    # describe() -> layout_from_receipt -> the same layout
    receipt = plan.layout.describe()
    assert receipt["kind"] == ("bucketed_flat" if bucket_mb
                               else "canonical_flat")
    assert layout_from_receipt(plan.params_struct, receipt) == plan.layout

    # the checkpoint receipts, byte for byte what the trainer wrote before
    # the plan existed
    want = {}
    if basis != "dp" and bucket_mb:
        want["opt_layout"] = receipt
    if basis == "zero3":
        want["param_layout"] = {"kind": receipt["kind"], "num_shards": 4,
                                "total_padded": plan.layout.total_padded}
    assert plan.receipts() == want

    # state specs: vectors of the padded flat length shard over the data
    # axis, everything else is replicated; plain DP replicates the lot
    if basis == "dp":
        assert plan.state_specs == P()
        return
    padded = plan.total_padded
    state = jax.eval_shape(
        lambda r: TrainState.create(_MiniNet(), tx, r, SAMPLE, ema=True,
                                    exchange=plan), jax.random.key(0))
    state = state.replace(batch_stats=BATCH_STATS,
                          ema_batch_stats=BATCH_STATS)
    by_shape = lambda l: (P("data") if l.ndim >= 1 and l.shape[0] == padded
                          else P())
    replicated = lambda t: jax.tree.map(lambda _: P(), t)
    flat_params = basis == "zero3"
    assert (state.params.shape == (padded,)) if flat_params \
        else (jax.tree.structure(state.params)
              == jax.tree.structure(shapes.params))
    want_specs = TrainState(
        step=P(),
        params=P("data") if flat_params else replicated(shapes.params),
        batch_stats=replicated(BATCH_STATS),
        opt_state=jax.tree.map(by_shape, state.opt_state),
        ema_params=P("data") if flat_params else replicated(shapes.params),
        ema_batch_stats=replicated(BATCH_STATS))
    assert plan.state_specs == want_specs
    is_spec = lambda x: isinstance(x, P)
    assert (jax.tree.structure(plan.state_specs, is_leaf=is_spec)
            == jax.tree.structure(state))
    assert P("data") in jax.tree.leaves(plan.state_specs.opt_state,
                                        is_leaf=is_spec)


@pytest.mark.parametrize("basis", BASES)
def test_plan_downgrades_at_one_shard(basis, devices8):
    """A one-shard mesh has no shard to own: whatever was asked for is
    plain dp, decided in the plan and nowhere else."""
    tx = optax.sgd(0.05, momentum=0.9)
    shapes = _tree_state(tx)
    plan = plan_exchange(_asked(basis, BUCKET_MB), _mesh(devices8, 1), tx,
                         grad_accum_steps=2,
                         grad_accum_shard=basis == "zero1")
    assert plan.basis == "dp"
    assert not (plan.sharded or plan.zero2 or plan.zero3
                or plan.accum_reduced)
    plan = plan.bind(shapes.params)
    assert plan.state_specs == P() and plan.receipts() == {}
    assert plan.total_padded is None
    params, opt_state = jax.eval_shape(plan.layout_state, shapes.params)
    assert jax.tree.structure(params) == jax.tree.structure(shapes.params)
    assert (jax.tree.structure(opt_state)
            == jax.tree.structure(shapes.opt_state))
    assert plan.params_tree(shapes.params) is shapes.params
    meta = plan.comm_meta()
    assert meta["sharding"] == "dp" and meta["gathers"] == 0
    assert meta["scatter_bytes"] == meta["gather_bytes"] == 0


@pytest.mark.parametrize("asked, accum, accum_shard, match", [
    # MeshConfig refuses this ladder itself; the plan checks what reaches
    # it from anywhere else
    (types.SimpleNamespace(data_axis="data", shard_opt_state=True,
                           shard_gradients=False, shard_params=True,
                           comm_bucket_mb=0.0, reduce_dtype="float32"),
     1, False, "shard_params"),
    (_asked("dp"), 2, True, "shard_opt_state"),
    (_asked("zero1"), 1, True, "grad_accum_steps"),
], ids=["zero3_without_zero2", "accum_shard_without_zero",
        "accum_shard_without_scan"])
def test_plan_refuses_invalid_ladders(asked, accum, accum_shard, match,
                                      devices8):
    with pytest.raises(ValueError, match=match):
        plan_exchange(asked, _mesh(devices8, 4), optax.sgd(0.1),
                      grad_accum_steps=accum, grad_accum_shard=accum_shard)


@pytest.mark.parametrize("basis, accum, accum_shard, reduced", [
    ("dp", 2, False, False), ("zero1", 2, False, False),
    ("zero1", 2, True, True), ("zero2", 1, False, False),
    ("zero2", 2, False, True), ("zero3", 2, False, True),
])
def test_plan_says_where_the_scan_reduces(basis, accum, accum_shard,
                                          reduced, devices8):
    """zero2 and up imply the 1/N scan accumulator wherever there is a
    scan; zero1 opts in with train.grad_accum_shard."""
    plan = plan_exchange(_asked(basis), _mesh(devices8, 4), optax.sgd(0.1),
                         grad_accum_steps=accum,
                         grad_accum_shard=accum_shard)
    assert plan.accum_reduced is reduced
