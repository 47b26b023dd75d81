"""`reference/hybrid_lm_step.py` on a stack whose head is its embedding's
transpose, against the plainest statement of it: `jax.grad` of the whole
loss with one matrix as both lookup and head, under `optax.sgd` with
momentum, for the same three steps. Losses, the first gradient of the
matrix and every leaf's change agree to float32 rounding. A head updated
apart from the lookup's matrix (its own copy, as an untied head) is told
apart by the same comparison."""

import numpy as np
import pytest

from _tiny_dense_lm import expert_free_program
from chipbench.drivers import hybrid_lm_train
from chipbench.reference import hybrid_lm_step, ling3
from chipbench.reference.ops import Ops

PATTERN = "DA"          # a Kimi-delta layer, then a latent one; both dense
SEQ, ROWS, VOCAB, STEPS, BLOCK = 64, 2, 256, 3, 32
#: float32 rounding: the follow reads 0, 5e-7 and 5e-7 here
TOL = {"loss": 1e-6, "first_grad": 1e-5, "change": 1e-4}
RECIPE = {"base_lr": 0.01, "momentum": 0.9, "reference_batch": ROWS,
          "global_batch": ROWS, "weight_decay": 0.0, "schedule": "constant"}


def _setup():
    """(arch, the seed's weights of every group but `lm_head`, tokens)."""
    import jax
    import jax.numpy as jnp

    from distributed_vgg_f_tpu.config import apply_overrides, get_config
    from distributed_vgg_f_tpu.models.registry import build_model
    cfg = apply_overrides(get_config("ling3_flash_tiny"), {
        "model.extra.num_hidden_layers": len(PATTERN),
        "model.extra.first_k_dense_replace": len(PATTERN),
        "model.extra.layer_group_size": len(PATTERN),
        "model.extra.hybrid_override_pattern": PATTERN})
    arch = {**cfg.model.extra, "tie_word_embeddings": True}
    with expert_free_program():
        shapes = jax.eval_shape(
            build_model(cfg.model).init, jax.random.key(0),
            jax.ShapeDtypeStruct((1, SEQ), jnp.int32))["params"]
    shapes = {g: s for g, s in shapes.items() if g != "lm_head"}
    params = hybrid_lm_train.inputs.make_params(
        shapes, hybrid_lm_train.inputs.seed_word(2147500003))
    tokens = jax.random.randint(jax.random.key(7), (ROWS, SEQ + 1), 0, VOCAB)
    return arch, params, tokens


def _follow(arch, params, tokens, *, untied=False):
    import jax
    import jax.numpy as jnp
    groups = dict(params)
    if untied:
        arch = {**arch, "tie_word_embeddings": False}
        groups["lm_head"] = {"kernel": params["embed"]["embedding"].T}
    return hybrid_lm_step.follow(
        ling3, arch, (0, 0), RECIPE,
        lambda g: jax.tree.map(jnp.copy, groups[g]), list(groups),
        tokens, steps=STEPS, probes=("embed/embedding",), block_rows=BLOCK)


def _oracle(arch, params, tokens):
    """Losses, the first gradient, every leaf's change: whole-loss
    `jax.grad` and `optax.sgd` on one tree with one matrix."""
    import jax
    import jax.numpy as jnp
    import optax

    ops = Ops("float32")

    def loss_fn(p):
        inputs, targets = tokens[:, :-1], tokens[:, 1:]
        matrix = p["embed"]["embedding"]
        total = 0.0
        for x, t in zip(matrix[inputs], targets):
            for i in range(len(PATTERN)):
                x, _ = ling3.block(p[f"layer_{i}"], x, arch, (0, 0), ops,
                                   BLOCK, layer=i)
            total += ling3.head_loss(p["norm"], {"kernel": matrix.T}, x, t,
                                     arch, ops)[0]
        return total / targets.size

    tx = optax.sgd(RECIPE["base_lr"], momentum=RECIPE["momentum"])
    grad = jax.jit(jax.value_and_grad(loss_fn))
    p, state, losses, first = params, tx.init(params), [], None
    for _ in range(STEPS):
        loss, g = grad(p)
        first = g if first is None else first
        losses.append(float(loss))
        updates, state = tx.update(g, state, p)
        p = optax.apply_updates(p, updates)
    change = jax.tree.map(lambda a, b: float(jnp.linalg.norm(a - b)),
                          p, params)
    return losses, np.asarray(first["embed"]["embedding"]), change


def _gaps(got: dict, losses, first, change) -> dict:
    import jax
    flat = lambda tree: {jax.tree_util.keystr(k): float(v) for k, v in
                         jax.tree_util.tree_leaves_with_path(tree)}
    mine, theirs = flat(got["change_norms"]), flat(change)
    assert set(mine) == set(theirs)
    g = np.asarray(got["first_grad"]["embed/embedding"])
    return {"loss": max(abs(a - b) / abs(b)
                        for a, b in zip(got["losses"], losses)),
            "first_grad": float(np.linalg.norm(g - first)
                                / np.linalg.norm(first)),
            "change": max(abs(mine[k] - theirs[k]) / theirs[k]
                          for k in theirs)}


@pytest.fixture(scope="module")
def tied():
    arch, params, tokens = _setup()
    return arch, params, tokens, _oracle(arch, params, tokens)


def test_the_tied_follow_is_the_whole_loss_under_optax_sgd(tied):
    arch, params, tokens, oracle = tied
    got = _follow(arch, params, tokens)
    assert "lm_head" not in got["grad_norms"]
    assert np.shape(got["loads"]) == (STEPS, 0, 0)
    gaps = _gaps(got, *oracle)
    assert all(gaps[k] < TOL[k] for k in TOL), gaps


def test_a_head_updated_apart_from_the_lookup_is_told_apart(tied):
    """The same weights with the head as its own copy of the matrix: its
    first gradient of the embedding lacks the head's (0.11 here), and the
    two copies drift apart (0.019)."""
    arch, params, tokens, oracle = tied
    got = _follow(arch, params, tokens, untied=True)
    got["change_norms"].pop("lm_head")
    gaps = _gaps(got, *oracle)
    assert gaps["first_grad"] > 1000 * TOL["first_grad"] \
        and gaps["change"] > 100 * TOL["change"], gaps


def test_a_tied_stack_names_no_head_group():
    arch, params, tokens = _setup()
    with pytest.raises(ValueError, match="tie_word_embeddings"):
        _follow({**arch, "tie_word_embeddings": False}, params, tokens)
