"""The plain training step a language-model cell is compared with, for a
reference model that is handed in: `reference/lm_step.py`'s scheme with the
model as an argument, for stacks whose layers differ in kind.

One step, as the configuration states it (key `recipe`):

    tokens (B, S + 1) -> inputs [:, :-1], targets [:, 1:]
    -> embedding, the layers, final norm, head
    -> mean next-token cross-entropy over B x S positions, float32 logits
    -> gradients -> SGD with momentum at a constant rate, no weight decay.

`model` is a module of this directory (`nemotron_h`) that gives
`block(p, x, arch, share, ops, block_rows, layer=i, fault=...)` ->
(y, held experts' loads), `head_loss(...)` and `expert_layers(arch)`.
Layers of one kind (one letter of `arch["hybrid_override_pattern"]`)
share their compiled forward and backward programs.

How it fits the chip is `lm_step.py`'s: the gradient is never whole. The
forward pass keeps each layer's input; the backward pass takes one layer's
`vjp` at a time (its forward made again inside) and updates that layer's
momentum and weights in place at once. Per group of leaves ("embed",
"layer_0", ..., "norm", "lm_head") the follower keeps the gradient's
per-leaf norms, and the gradient itself only for the `probes`.

`mode="fp8"` is the control (`ops.Ops`); `fault="half_batch"` repeats the
first half of every sequence in place of its second half; any other fault
is the model's own (`chunk_reset`) and is handed to its `block`.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from .lm_step import _keep, _sgd
from .ops import Ops
from .step import leaf_norms


def make_steps(model, arch: dict, share, recipe: dict, probes, *,
               mode: str = "float32", block_rows: int = 512,
               fault: str | None = None):
    """The jitted pieces of one step: `forward(p, x, layer)`, `head(...)`,
    `backward(..., layer)`, `embed(...)`; each updating call donates the
    weights and momentum it replaces."""
    ops = Ops(mode)
    lr, mu = recipe["base_lr"] * recipe["global_batch"] \
        / recipe["reference_batch"], recipe["momentum"]
    if recipe["weight_decay"] or recipe["schedule"] != "constant":
        raise NotImplementedError("the plain reference knows SGD with "
                                  "momentum at a constant rate, no decay")

    def rows(p, x, layer):
        """Layer `layer` on every sequence of x (B, S, hidden), one
        sequence after the other and each made again in the backward pass
        (`lax.map` of a checkpointed call): one sequence's activations are
        alive at a time."""
        one = jax.checkpoint(lambda row: model.block(
            p, row, arch, share, ops, block_rows, layer=layer, fault=fault))
        y, loads = jax.lax.map(one, x)
        return y, jnp.sum(loads, 0)

    forward = jax.jit(rows, static_argnums=2)

    @partial(jax.jit, donate_argnums=(0, 1), static_argnums=4)
    def backward(p, trace, x, dy, layer):
        _, vjp, _ = jax.vjp(partial(rows, layer=layer), p, x, has_aux=True)
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


def follow(model, arch: dict, share, recipe: dict, make_group, groups,
           tokens, *, steps: int = 3, probes=(), mode: str = "float32",
           fault: str | None = None, block_rows: int = 512) -> dict:
    """Drive the reference `steps` steps on the one batch `tokens`
    (B, S + 1). `make_group(name)` gives the seed's weights of one group;
    `groups` names them all. Returns each step's loss, the first
    gradient's per-leaf norms and its probe leaves (host arrays), the
    per-leaf norms of the weights' change over the steps, and each step's
    held-expert loads (expert layers, experts held)."""
    batches = [tokens] * steps
    if fault == "half_batch":
        half = (tokens.shape[1] - 1) // 2
        batches = [jnp.concatenate([t[:, :half], t[:, :half + 1]], 1)
                   for t in batches]
        fault = None
    forward, backward, head, embed, reduce = make_steps(
        model, arch, share, recipe, tuple(probes), mode=mode,
        block_rows=block_rows, fault=fault)
    pattern = arch["hybrid_override_pattern"]
    layers = [f"layer_{i}" for i in range(len(pattern))]
    if sorted(layers) != sorted(g for g in groups if g.startswith("layer_")):
        raise ValueError(f"{len(pattern)} letters in the pattern, groups "
                         f"{sorted(groups)}")
    # one compiled program a kind of layer: the first layer of each letter
    # stands for the others
    stands_for = [pattern.index(letter) for letter in pattern]
    with_experts = model.expert_layers(arch)
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
        for i, name in enumerate(layers):
            y, load = forward(params[name], x[-1], stands_for[i])
            x.append(y)
            if i in with_experts:
                loads.append(load)
        (params["norm"], trace["norm"], params["lm_head"], trace["lm_head"],
         loss, dx, g_norm, g_head) = head(
            params["norm"], trace["norm"], params["lm_head"],
            trace["lm_head"], x.pop(), targets)
        note(step, "norm", g_norm)
        note(step, "lm_head", g_head)
        del g_norm, g_head
        for i, name in reversed(list(enumerate(layers))):
            params[name], trace[name], dx, grads = backward(
                params[name], trace[name], x.pop(), dx, stands_for[i])
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
