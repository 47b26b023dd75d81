"""The return of the expert share's window rows to their tokens
(ops/routed_combine.py) on the chip, at the language cells' shapes: XLA's
scatter-add against the Pallas kernel, forward (weighted rows into the
float32 sum) and backward (row cotangents into x's dtype).

The window is the first of a batch drawn by the expert share's own router
(`mistral4.route` on seeded router weights and a seeded input, the cell's
top-k, share, scoring and group limit), so its live rows, dead rows and the
tokens they repeat are what the layer sees. Forms:

    xla         the parent's expression: zeros, then `.at[token].add` of
                the window with its dead rows zeroed
    xla_drop    the same with the dead rows dropped (their index past the
                end, `mode="drop"`): what the dead rows cost
    xla_spread  the dead rows kept, each at a token of its own: what their
                repeated tokens cost
    kernel      ops/routed_combine_pallas.py with its sort (where `applies`
                admits the shape; else "not taken")

Each form runs `--iters` times back to back and is timed to the last
result, best of `--repeats`; the kernel's and `xla`'s outputs are compared
with the float32 sum of the same rows.
One process; refuses to run when JAX finds no TPU. Usage:

    python benchmarks/routed_combine_bench.py [--cells ling3_flash_ep64 ...]

One JSON line per cell and direction:
{"cell", "direction", "tokens", "hidden", "capacity", "live", ...,
 "ms": {form: ms}, "rel_error": {form: ...}, device}.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

#: the language cells' configurations
CELLS = ("ling3_flash_ep64", "nemotron3_nano_30b_ep8",
         "mistral_small4_119b_ep16", "solar_open2_250b_ep40")


def draw_window(preset: str, seed: int):
    """The cell's shapes and the first window of a batch its router sends
    this share: (tokens, hidden as the routed path has it, capacity, token,
    live, weight)."""
    import flax.linen as nn
    import jax
    import jax.numpy as jnp

    from distributed_vgg_f_tpu.config import get_config
    from distributed_vgg_f_tpu.models import mistral4
    from distributed_vgg_f_tpu.models.registry import build_model

    cfg = get_config(preset)
    model = build_model(cfg.model)
    # Mistral4LM's own field; LingLM's and NemotronHLM's tables of layers
    experts = getattr(model, "experts", None) or (
        getattr(model, "layers", None) or model.mixers)["experts"]
    e = {**{"scoring": "softmax", "n_group": 1, "topk_group": 1,
            "routed_scaling_factor": 1.0}, **experts}
    tokens = cfg.data.global_batch_size * cfg.model.extra["seq_len"]
    hidden = cfg.model.extra["hidden_size"]
    k, n, held = (e["num_experts_per_tok"], e["n_routed_experts"],
                  e["experts_held"])
    keys = jax.random.split(jax.random.key(seed), 3)
    x = jax.random.normal(keys[0], (tokens, hidden), jnp.bfloat16)
    router = nn.initializers.lecun_normal()(keys[1], (hidden, n), jnp.float32)
    logits = jnp.dot(x.astype(jnp.float32), router,
                     precision=jax.lax.Precision.HIGHEST)
    if e["scoring"] == "softmax":
        weights, local = mistral4.route(jax.nn.softmax(logits, axis=-1), k,
                                        0, held)
    else:
        bias = nn.initializers.normal(0.01)(keys[2], (n,), jnp.float32)
        weights, local = mistral4.route(
            jax.nn.sigmoid(logits), k, 0, held, bias=bias,
            scale=e["routed_scaling_factor"], n_group=e["n_group"],
            topk_group=e["topk_group"])
    flat = local.reshape(-1)
    order = jnp.argsort(flat, stable=True)
    load = jnp.bincount(flat, length=held + 1)[:held].astype(jnp.int32)
    capacity = mistral4.routed_capacity(tokens * k, held, n)
    order = jnp.pad(order, (0, -(tokens * k) % capacity))
    hidden += mistral4._to_whole_tiles(hidden)
    x = jnp.zeros((tokens, hidden), jnp.bfloat16)
    _, token, _, live, _, weight = mistral4._window(
        x, weights, order, load, 0, capacity)
    return tokens, hidden, capacity, token, live, weight


def row_stats(token, live, tokens):
    """Live and dead rows, and how often a token repeats among each."""
    import numpy as np
    token, live = np.asarray(token), np.asarray(live)[:, 0]
    stats = {"live": int(live.sum()), "dead": int((~live).sum())}
    for name, rows in (("live", token[live]), ("dead", token[~live])):
        counts = np.bincount(rows, minlength=tokens) if rows.size else \
            np.zeros(1, int)
        stats[f"{name}_tokens"] = int((counts > 0).sum())
        stats[f"{name}_most_repeats"] = int(counts.max())
    return stats


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--cells", nargs="+", default=list(CELLS))
    parser.add_argument("--seed", type=int, default=2160040001)
    parser.add_argument("--iters", type=int, default=20)
    parser.add_argument("--repeats", type=int, default=3)
    parser.add_argument("--hidden", type=int, default=None,
                        help="rows this wide in place of the cell's")
    args = parser.parse_args()

    import jax
    import jax.numpy as jnp

    from distributed_vgg_f_tpu.ops import routed_combine, routed_combine_pallas
    device = jax.devices()[0]
    if device.platform != "tpu":
        print(json.dumps({"error": "no_accelerator",
                          "platform": device.platform}))
        return 2

    def timed(fn, *inputs):
        out = jax.block_until_ready(fn(*inputs))
        best = float("inf")
        for _ in range(args.repeats):
            t0 = time.perf_counter()
            for _ in range(args.iters):
                last = fn(*inputs)
            jax.block_until_ready(last)
            best = min(best, (time.perf_counter() - t0) / args.iters * 1e3)
        return best, out

    for cell in args.cells:
        tokens, hidden, capacity, token, live, weight = draw_window(
            cell, args.seed)
        stats = row_stats(token, live, tokens)
        hidden = args.hidden or hidden
        spread = jnp.where(live[:, 0], token,
                           jnp.arange(capacity, dtype=jnp.int32) % tokens)
        keys = jax.random.split(jax.random.key(args.seed + 1), 2)
        rows = jax.random.normal(keys[0], (capacity, hidden), jnp.bfloat16)
        x = jnp.zeros((tokens, hidden), jnp.bfloat16)
        taken = routed_combine_pallas.applies(tokens, capacity, hidden,
                                              jnp.bfloat16)
        forward = {
            "xla": lambda r, t, lv, w: jnp.zeros(
                (tokens, hidden), jnp.float32).at[t].add(
                jnp.where(lv, r, 0).astype(jnp.float32) * w,
                mode="promise_in_bounds"),
            "xla_drop": lambda r, t, lv, w: jnp.zeros(
                (tokens, hidden), jnp.float32).at[
                jnp.where(lv[:, 0], t, tokens)].add(
                r.astype(jnp.float32) * w, mode="drop"),
            "xla_spread": lambda r, t, lv, w: jnp.zeros(
                (tokens, hidden), jnp.float32).at[
                jnp.where(lv[:, 0], t, spread)].add(
                jnp.where(lv, r, 0).astype(jnp.float32) * w,
                mode="promise_in_bounds"),
            "kernel": lambda r, t, lv, w: routed_combine.add_weighted(
                routed_combine.empty_sum(x, capacity), r, w, t, lv,
                first=True)}
        backward = {
            "xla": lambda r, t, lv, w: jnp.zeros_like(x).at[t].add(
                jnp.where(lv, r, 0), mode="promise_in_bounds"),
            "xla_drop": lambda r, t, lv, w: jnp.zeros_like(x).at[
                jnp.where(lv[:, 0], t, tokens)].add(r, mode="drop"),
            "xla_spread": lambda r, t, lv, w: jnp.zeros_like(x).at[
                jnp.where(lv[:, 0], t, spread)].add(
                jnp.where(lv, r, 0), mode="promise_in_bounds"),
            "kernel": lambda r, t, lv, w: routed_combine.to_tokens(
                r, t, lv, x)}
        for direction, forms in (("forward", forward),
                                 ("backward", backward)):
            ms, outs = {}, {}
            for form, fn in forms.items():
                if form == "kernel" and not taken:
                    ms[form] = "not taken"
                    continue
                ms[form], outs[form] = timed(jax.jit(fn), rows, token, live,
                                             weight)
            # both against the float32 sum of the same rows
            exact = jnp.zeros((tokens, hidden), jnp.float32).at[token].add(
                jnp.where(live, rows, 0).astype(jnp.float32)
                * (weight if direction == "forward" else 1.0))
            error = {form: float(
                jnp.linalg.norm(out.astype(jnp.float32) - exact)
                / jnp.linalg.norm(exact)) for form, out in outs.items()
                if form in ("xla", "kernel")}
            line = {"cell": cell, "direction": direction, "tokens": tokens,
                    "hidden": hidden, "capacity": capacity, **stats,
                    "ms": ms, "rel_error": error,
                    "platform": device.platform,
                    "device_kind": device.device_kind,
                    "device_count": jax.device_count()}
            print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
