"""The plain training step a language-model cell is compared with, for
`reference/mistral4.py`.

One step, as the configuration states it (key `recipe`):

    tokens (B, S + 1) -> inputs [:, :-1], targets [:, 1:]
    -> embedding, the blocks, final norm, head
    -> mean next-token cross-entropy over B x S positions, float32 logits
    -> gradients -> SGD with momentum at a constant rate, no weight decay.

Float32 at `highest` matmul precision; no dropout, no augmentation, so no
random draw has to be repeated here.

**How it fits the chip at the cell's size** (4.6 GB each of weights and
momentum): the gradient is never whole. The forward pass keeps each
block's input; the backward pass takes one block's `vjp` at a time (its
forward made again inside), and as soon as a block's gradient is ready its
momentum and weights are updated in place and the gradient is dropped,
which is exact because blocks further down need only the cotangent, not the
updated weights. Momentum stays on the device. Per group of leaves
("embed", "layer_0", ..., "norm", "lm_head") the follower keeps the
gradient's per-leaf norms, and the gradient itself only for the handful of
`probes`. The initial weights are made again from the seed, a group at a
time, to measure the change.

`mode="fp8"` is the control (`ops.Ops`); `fault="half_batch"` repeats the
first half of every sequence in place of its second half.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from . import mistral4 as model
from .ops import Ops
from .step import leaf_norms


def _sgd(params, trace, grads, lr, momentum):
    trace = jax.tree.map(lambda t, g: g + momentum * t, trace, grads)
    return jax.tree.map(lambda p, t: p - lr * t, params, trace), trace


def _keep(grads, group: str, probes) -> dict:
    """The probe leaves of one group's gradient, by their full names."""
    flat = {f"{group}/" + "/".join(str(k.key) for k in path): leaf
            for path, leaf in jax.tree_util.tree_leaves_with_path(grads)}
    return {name: leaf for name, leaf in flat.items() if name in probes}


def make_steps(arch: dict, share, recipe: dict, probes, *,
               mode: str = "float32", block_rows: int = 512):
    """The jitted pieces of one step: `forward(p, x)`, `head(...)`,
    `backward(...)`, `embed(...)`; each updating call donates the weights
    and momentum it replaces."""
    ops = Ops(mode)
    lr, mu = recipe["base_lr"] * recipe["global_batch"] \
        / recipe["reference_batch"], recipe["momentum"]
    if recipe["weight_decay"] or recipe["schedule"] != "constant":
        raise NotImplementedError("the plain reference knows SGD with "
                                  "momentum at a constant rate, no decay")

    def rows(p, x):
        """The block on every sequence of x (B, S, hidden)."""
        outs = [model.block(p, row, arch, share, ops, block_rows)
                for row in x]
        return (jnp.stack([o[0] for o in outs]),
                sum(o[1] for o in outs))

    forward = jax.jit(rows)

    @partial(jax.jit, donate_argnums=(0, 1))
    def backward(p, trace, x, dy):
        _, vjp, _ = jax.vjp(partial(rows), p, x, has_aux=True)
        grads, dx = vjp(dy)
        new_p, new_trace = _sgd(p, trace, grads, lr, mu)
        return new_p, new_trace, dx, grads

    def head_loss(p_norm, p_head, x, targets):
        total = sum(model.head_loss(p_norm, p_head, row, t, arch, ops)[0]
                    for row, t in zip(x, targets))
        return total / targets.size

    @partial(jax.jit, donate_argnums=(0, 1, 2, 3))
    def head(p_norm, t_norm, p_head, t_head, x, targets):
        loss, (g_norm, g_head, dx) = jax.value_and_grad(
            head_loss, argnums=(0, 1, 2))(p_norm, p_head, x, targets)
        p_norm, t_norm = _sgd(p_norm, t_norm, g_norm, lr, mu)
        p_head, t_head = _sgd(p_head, t_head, g_head, lr, mu)
        return p_norm, t_norm, p_head, t_head, loss, dx, g_norm, g_head

    @partial(jax.jit, donate_argnums=(0, 1))
    def embed(p, trace, tokens, dx):
        grads = {"embedding": jnp.zeros_like(p["embedding"]).at[
            tokens.reshape(-1)].add(dx.reshape(-1, dx.shape[-1]))}
        new_p, new_trace = _sgd(p, trace, grads, lr, mu)
        return new_p, new_trace, grads

    def reduce(grads, group):
        """What is kept of one group's gradient: norms, and probe leaves."""
        return leaf_norms(grads), _keep(grads, group, probes)

    return forward, backward, head, embed, jax.jit(reduce, static_argnums=1)


def follow(arch: dict, share, recipe: dict, make_group, groups, tokens, *,
           steps: int = 3, probes=(), mode: str = "float32",
           fault: str | None = None, block_rows: int = 512) -> dict:
    """Drive the reference `steps` steps on the one batch `tokens`
    (B, S + 1), or on `tokens[i]` at step i where it is a list of
    batches. `make_group(name)` gives the seed's weights of one group;
    `groups` names them all. Returns each step's loss, the first
    gradient's per-leaf norms and its probe leaves (host arrays), the
    per-leaf norms of the weights' change over the steps, and each step's
    held-expert loads (layers, experts held)."""
    batches = list(tokens) if isinstance(tokens, (list, tuple)) \
        else [tokens] * steps
    if fault == "half_batch":
        half = (batches[0].shape[1] - 1) // 2
        batches = [jnp.concatenate([t[:, :half], t[:, :half + 1]], 1)
                   for t in batches]
    elif fault is not None:
        raise ValueError(f"no fault {fault!r} in the language-model step")
    forward, backward, head, embed, reduce = make_steps(
        arch, share, recipe, tuple(probes), mode=mode, block_rows=block_rows)
    layers = sorted((g for g in groups if g.startswith("layer_")),
                    key=lambda g: int(g[6:]))
    params = {g: make_group(g) for g in groups}
    trace = {g: jax.tree.map(jnp.zeros_like, params[g]) for g in groups}
    out = {"losses": [], "loads": [], "grad_norms": {}, "first_grad": {}}

    def note(step, group, grads):
        if step == 0:
            norms, kept = reduce(grads, group)
            out["grad_norms"][group] = norms
            out["first_grad"].update(jax.device_get(kept))

    for step in range(steps):
        inputs, targets = batches[step][:, :-1], batches[step][:, 1:]
        x = [params["embed"]["embedding"][inputs]]
        loads = []
        for name in layers:
            y, load = forward(params[name], x[-1])
            x.append(y)
            loads.append(load)
        (params["norm"], trace["norm"], params["lm_head"], trace["lm_head"],
         loss, dx, g_norm, g_head) = head(
            params["norm"], trace["norm"], params["lm_head"],
            trace["lm_head"], x.pop(), targets)
        note(step, "norm", g_norm)
        note(step, "lm_head", g_head)
        del g_norm, g_head
        for name in reversed(layers):
            params[name], trace[name], dx, grads = backward(
                params[name], trace[name], x.pop(), dx)
            note(step, name, grads)
            del grads
        params["embed"], trace["embed"], grads = embed(
            params["embed"], trace["embed"], inputs, dx)
        note(step, "embed", grads)
        del grads
        out["losses"].append(loss)
        out["loads"].append(jnp.stack(loads))
    del trace
    change = jax.jit(lambda p, p0: leaf_norms(jax.tree.map(
        jnp.subtract, p, p0)))
    out["change_norms"] = {g: change(params.pop(g), make_group(g))
                           for g in groups}
    return jax.device_get(out)
