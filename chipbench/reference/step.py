"""The plain training step every training cell is compared with.

One step, as the configurations state it (`chipbench/configs/*.json`,
key `recipe`):

    u8 rows -> (x - mean) / std -> per-row horizontal flip (p = 1/2)
    -> mixup over the replica's rows (lam ~ Beta(a, a), one permutation)
    -> model forward with dropout -> lam CE(y) + (1 - lam) CE(y[perm])
    -> + wd/2 * sum |kernel|^2 -> gradients, averaged over replicas
    -> SGD with momentum on the step-decay schedule with linear warm-up.

Float32 at `highest` matmul precision. Rows go through the model in
blocks so that the step fits a chip beside nothing else; a model with
batch norm takes a replica's rows at once.

The random draws (flip bits, permutation, lam, dropout masks) are a
function of (seed, step, replica) that the program documents
(`data/augment.py`, `train/step.py`): fold the step into the base key,
then the replica, then a constant for augmentation; dropout keys fold
flax's hash of the layer's name. This file repeats that derivation with
`jax.random` and `hashlib` alone, so the two sides see the same masks as
long as JAX gives the same bits for the same key and shape on one backend.

`fault` plants the faults of "How `correct` is decided", step 3, in the
reference put in the program's place: `half_batch` (half of each replica's
rows left out, the mean taken over the rest), `no_exchange` (replica 0's
gradient used alone), `state_unchanged` (the step returns its state).
"""

from __future__ import annotations

import hashlib
import importlib
from functools import partial

import jax
import jax.numpy as jnp

from .ops import Ops

AUGMENT_FOLD = 0xA06


def load_model(name: str):
    return importlib.import_module(f"{__package__}.{name}")


def lr_at(recipe: dict, step):
    """SGD's learning rate at `step`: linear warm-up from 0 to the peak,
    then the peak times `decay_factor` for every boundary passed."""
    peak = recipe["base_lr"] * recipe["global_batch"] \
        / recipe["reference_batch"]
    spe = max(1, recipe["train_examples"] // recipe["global_batch"])
    warm = int(recipe["warmup_epochs"] * spe)
    step = jnp.asarray(step, jnp.float32)
    lr = jnp.asarray(peak, jnp.float32)
    for epoch in recipe["decay_epochs"]:
        lr = jnp.where(step - warm >= int(epoch * spe),
                       lr * recipe["decay_factor"], lr)
    if warm > 0:
        lr = jnp.where(step < warm, peak * step / warm, lr)
    return lr


def _flax_child_key(key, name: str):
    """flax's `make_rng` for the first draw of submodule `name`: the key
    folded with the first four bytes of sha1(name, counter 1)."""
    digest = hashlib.sha1()
    digest.update(name.encode("utf-8"))
    digest.update((1).to_bytes(1, "big"))
    return jax.random.fold_in(
        key, jnp.uint32(int.from_bytes(digest.digest()[:4], "big")))


def _decayed(path, leaf) -> bool:
    names = {str(getattr(p, "key", p)) for p in path}
    return leaf.ndim >= 2 and not names & {"bias", "scale"}


def _l2(params, wd: float):
    leaves = jax.tree_util.tree_leaves_with_path(params)
    return 0.5 * wd * sum(jnp.sum(jnp.square(x)) for p, x in leaves
                          if _decayed(p, x))


def _ce_sum(logits, labels):
    logp = jax.nn.log_softmax(logits.astype(jnp.float32))
    return -jnp.sum(jnp.take_along_axis(logp, labels[:, None], 1))


def _replica_inputs(model, recipe, key, images, labels):
    """What one replica feeds its model at this step: augmented float32
    rows, both label sets, lam and the dropout masks."""
    mean = jnp.asarray(recipe["mean_rgb"], jnp.float32)
    inv_std = jnp.float32(1.0) / jnp.asarray(recipe["stddev_rgb"],
                                             jnp.float32)
    x = (images.astype(jnp.float32) - mean) * inv_std
    rows = x.shape[0]
    k_flip, _, _, k_mix = jax.random.split(
        jax.random.fold_in(key, AUGMENT_FOLD), 4)
    if recipe["hflip"]:
        flip = jax.random.bernoulli(k_flip, 0.5, (rows,))
        x = jnp.where(flip[:, None, None, None], x[:, :, ::-1, :], x)
    labels2, lam = labels, jnp.float32(1.0)
    if recipe["mixup_alpha"] > 0:
        k_perm, k_lam, _, _ = jax.random.split(k_mix, 4)
        perm = jax.random.permutation(k_perm, rows)
        a = recipe["mixup_alpha"]
        lam = jax.random.beta(k_lam, a, a).astype(jnp.float32)
        x = x * lam + x[perm] * (1.0 - lam)
        labels2 = labels[perm]
    keep = 1.0 - recipe["dropout_rate"]
    masks = tuple(
        jax.random.bernoulli(_flax_child_key(key, name), keep, (rows, width))
        for name, width in model.DROPOUT_SITES) \
        if recipe["dropout_rate"] > 0 else None
    return x, labels2, lam, masks


def make_step(model_name: str, recipe: dict, *, replicas: int = 1,
              block_rows: int = 256, mode: str = "float32",
              fault: str | None = None):
    """Returns jitted `step(params, stats, trace, step, images, labels,
    base_key) -> (params, stats, trace, loss, grads)`; `images` holds the
    global batch, replica r's rows at [r * local, (r + 1) * local)."""
    model = load_model(model_name)
    ops = Ops(mode)
    if model.HAS_BATCH_STATS and replicas > 1:
        raise NotImplementedError(
            "batch norm over several replicas: the reference would need "
            "every replica's rows in one forward pass")

    def block_loss(params, stats, x, y, y2, lam, masks):
        logits, new_stats = model.forward(
            params, stats, x, ops=ops, train=True, masks=masks,
            dropout_rate=recipe["dropout_rate"])
        return (lam * _ce_sum(logits, y)
                + (1.0 - lam) * _ce_sum(logits, y2)), new_stats

    def replica_grad(params, stats, key, images, labels):
        x, labels2, lam, masks = _replica_inputs(model, recipe, key,
                                                 images, labels)
        rows = x.shape[0]
        if fault == "half_batch":
            rows //= 2
        block = rows if model.HAS_BATCH_STATS else min(block_rows, rows)
        if rows % block:
            raise ValueError(f"{rows} rows do not split into {block}")

        def one(carry, i):
            cut = lambda v: jax.lax.dynamic_slice_in_dim(v, i * block, block)
            (ce, new_stats), g = jax.value_and_grad(block_loss, has_aux=True)(
                params, stats, cut(x), cut(labels), cut(labels2), lam,
                None if masks is None else tuple(cut(m) for m in masks))
            return (carry[0] + ce, jax.tree.map(jnp.add, carry[1], g),
                    new_stats), None

        zero = (jnp.float32(0.0), jax.tree.map(jnp.zeros_like, params), stats)
        (ce, g, new_stats), _ = jax.lax.scan(one, zero,
                                             jnp.arange(rows // block))
        return ce / rows, jax.tree.map(lambda v: v / rows, g), new_stats

    @jax.jit
    def step(params, stats, trace, step_no, images, labels, base_key):
        local = images.shape[0] // replicas
        key = jax.random.fold_in(base_key, step_no)
        ce = jnp.float32(0.0)
        grads = jax.tree.map(jnp.zeros_like, params)
        new_stats = stats
        used = 1 if fault == "no_exchange" else replicas
        for r in range(used):
            rows = slice(r * local, (r + 1) * local)
            c, g, new_stats = replica_grad(
                params, stats, jax.random.fold_in(key, r), images[rows],
                labels[rows])
            ce, grads = ce + c / used, jax.tree.map(
                lambda a, b: a + b / used, grads, g)
        grads = jax.tree.map(jnp.add, grads, jax.grad(partial(
            _l2, wd=recipe["weight_decay"]))(params))
        if fault == "state_unchanged":
            return params, stats, trace, ce, grads
        trace = jax.tree.map(lambda t, g: g + recipe["momentum"] * t,
                             trace, grads)
        lr = lr_at(recipe, step_no)
        params = jax.tree.map(lambda p, t: p - lr * t, params, trace)
        return params, new_stats, trace, ce, grads

    return step


def leaf_norms(tree):
    return jax.tree.map(lambda x: jnp.sqrt(jnp.sum(jnp.square(
        x.astype(jnp.float32)))), tree)


def follow(model_name: str, recipe: dict, params, stats, batches,
           base_key, *, start_step: int, steps: int = 3, **kw) -> dict:
    """Drive the reference `steps` steps from `params`, step i on
    `batches[i]` = (u8 images, labels). Returns each step's loss, the
    first gradient (host arrays) and its per-leaf norms, and the per-leaf
    norms of the parameters' change over all the steps."""
    step = make_step(model_name, recipe, **kw)
    first = params
    trace = jax.tree.map(jnp.zeros_like, params)
    losses, grad_norms, first_grad = [], None, None
    for i in range(steps):
        images, labels = (jnp.asarray(v) for v in batches[i])
        params, stats, trace, loss, grads = step(
            params, stats, trace, jnp.int32(start_step + i), images, labels,
            base_key)
        losses.append(loss)
        if i == 0:
            grad_norms, first_grad = leaf_norms(grads), jax.device_get(grads)
    change = jax.jit(lambda a, b: leaf_norms(jax.tree.map(jnp.subtract, a,
                                                          b)))(params, first)
    return {**jax.device_get({"losses": losses, "grad_norms": grad_norms,
                              "change_norms": change}),
            "first_grad": first_grad}
