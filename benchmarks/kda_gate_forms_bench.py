"""The delta rule's two forms of the diagonal sub-blocks (ops/kda.py) on the
chip, forward and the five gradients, on the same inputs.

`bounded` is the split at a sub-block's first position, exact for gates
>= -5 (Ling-3.0-flash's safe gate); `unbounded` forms each pair of a
diagonal 16 x 16 sub-block directly, exact for any gate <= 0 (Solar-Open2's
softplus gate). The gates here lie in [-5, 0], where both are exact, so the
two outputs are compared as well as timed: the price of the unbounded form
is the rest.

One process, the forms one after the other; refuses to run when JAX finds
no TPU. Usage:

    python benchmarks/kda_gate_forms_bench.py [--batch 1 --seq 4096 --heads 64]

One JSON line per form: {"form", "ms_per_iter", "rel_to_bounded", device}
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--batch", type=int, default=1)
    parser.add_argument("--seq", type=int, default=4096)
    parser.add_argument("--heads", type=int, default=64)
    parser.add_argument("--iters", type=int, default=10)
    parser.add_argument("--warmup", type=int, default=2)
    args = parser.parse_args()

    import jax
    import jax.numpy as jnp

    from distributed_vgg_f_tpu.ops import kda_pallas
    device = jax.devices()[0]
    if device.platform != "tpu":
        print(json.dumps({"error": "no_accelerator",
                          "platform": device.platform}))
        return 2
    shape = (args.batch, args.seq, args.heads, kda_pallas.LANES)
    keys = jax.random.split(jax.random.key(0), 6)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    q = (unit(jax.random.normal(keys[0], shape)) * shape[-1] ** -0.5
         ).astype(jnp.bfloat16)
    k = unit(jax.random.normal(keys[1], shape)).astype(jnp.bfloat16)
    v = jax.random.normal(keys[2], shape, jnp.bfloat16)
    g = -5.0 * jax.random.uniform(keys[3], shape) ** 8
    beta = 2.0 * jax.nn.sigmoid(jax.random.normal(keys[4], shape[:3]))
    weigh = jax.random.normal(keys[5], shape)
    args_ = (q, k, v, g, beta)
    results = {}
    for form in ("bounded", "unbounded"):
        step = jax.jit(jax.value_and_grad(
            lambda *a, bounded=form == "bounded": jnp.sum(kda_pallas.chunked(
                *a, bounded=bounded) * weigh), argnums=(0, 1, 2, 3, 4)))
        for _ in range(args.warmup):
            jax.block_until_ready(step(*args_))
        t0 = time.perf_counter()
        for _ in range(args.iters):
            out = step(*args_)
        jax.block_until_ready(out)
        ms = (time.perf_counter() - t0) / args.iters * 1e3
        results[form] = out
        rel = None
        if form == "unbounded":
            want = results["bounded"]
            rel = max(float(jnp.linalg.norm((a - b).astype(jnp.float32))
                            / (jnp.linalg.norm(b.astype(jnp.float32))
                               + 1e-30))
                      for a, b in zip(out[1], want[1]))
        print(json.dumps({
            "form": form, "ms_per_iter": ms, "shape": list(shape),
            "grads_rel_to_bounded": rel, "platform": device.platform,
            "device_kind": device.device_kind,
            "device_count": jax.device_count()}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
