"""Flash (Pallas) vs XLA-einsum attention on the chip, fwd+bwd, long T.

The claim under test (ops/flash_attention.py): XLA's einsum attention
materializes (B, H, T, T) probs in HBM — O(T²) bandwidth and memory — while
the Pallas kernel streams K/V blocks through VMEM. At ViT scale (T=197) the
probs tensor is ~95 MB/block and XLA hides much of it; by T=8k it is
gigabytes and dominates. This bench measures both implementations' full
train-relevant path (fwd + grads wrt q, k, v) across sequence lengths on
identical inputs, plus the largest T where each still fits.

One process, variants serial: a chip belongs to one process at a time.
Refuses to run when JAX finds no TPU, unless the CPU was asked for by name
(`JAX_PLATFORMS=cpu`, with --interpret); every row names its device.

Usage:
    python benchmarks/flash_attention_bench.py [--seqs 512,2048,8192]

JSON line per (T, impl): {"seq": T, "impl": ..., "ms_per_iter": ...}
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> None:
    parser = argparse.ArgumentParser()
    parser.add_argument("--seqs", default="512,2048,4096,8192")
    parser.add_argument("--batch", type=int, default=4)
    parser.add_argument("--heads", type=int, default=8)
    parser.add_argument("--head-dim", type=int, default=64)
    parser.add_argument("--iters", type=int, default=10)
    parser.add_argument("--warmup", type=int, default=3)
    parser.add_argument("--causal", action="store_true")
    parser.add_argument("--impls", default="",
                        help="comma list of impl names to run (default all): "
                             "flash_pallas, flash_pallas_dma_skip, "
                             "xla_einsum. Long-context rows use this to "
                             "skip xla_einsum past its compile wall (an "
                             "older run saw the T=6144 einsum compile for "
                             "~2.5 h)")
    parser.add_argument("--interpret", action="store_true",
                        help="run the Pallas kernels in the interpreter: "
                             "CPU debugging only (with JAX_PLATFORMS=cpu)")
    args = parser.parse_args()

    import jax
    import jax.numpy as jnp

    from distributed_vgg_f_tpu.utils.device import require_accelerator
    device = require_accelerator()

    from distributed_vgg_f_tpu.ops.flash_attention import flash_self_attention
    from distributed_vgg_f_tpu.parallel.ring_attention import (
        full_attention_reference)

    def naive(q, k, v):
        return full_attention_reference(q, k, v, causal=args.causal)

    def flash(q, k, v):
        # pinned to the rectangular grids so the flash vs flash_dma_skip
        # comparison stays meaningful now that the production default is
        # causal_skip="auto" (which would pick "dma" itself at long T)
        return flash_self_attention(q, k, v, causal=args.causal,
                                    causal_skip="mxu",
                                    interpret=args.interpret)

    def flash_dma_skip(q, k, v):
        # causal only: the jagged forward grid — masked blocks never DMA
        # (expected to matter most at long T)
        return flash_self_attention(q, k, v, causal=True,
                                    causal_skip="dma",
                                    interpret=args.interpret)

    def time_impl(fn, q, k, v):
        @jax.jit
        def step(q, k, v):
            def loss(q, k, v):
                return jnp.sum(fn(q, k, v).astype(jnp.float32) ** 2)
            l, grads = jax.value_and_grad(loss, argnums=(0, 1, 2))(q, k, v)
            return l, grads
        # at least one un-timed call: compile + cache before the window
        # (--warmup 0 used to hit `l` unbound here)
        for _ in range(max(args.warmup, 1)):
            l, grads = step(q, k, v)
        jax.device_get(l)
        t0 = time.monotonic()
        for _ in range(args.iters):
            l, grads = step(q, k, v)
        jax.device_get(l)
        return (time.monotonic() - t0) / args.iters * 1e3

    for t in [int(s) for s in args.seqs.split(",")]:
        shape = (args.batch, t, args.heads, args.head_dim)
        kq, kk, kv = jax.random.split(jax.random.key(0), 3)
        q = jax.random.normal(kq, shape, jnp.bfloat16)
        k = jax.random.normal(kk, shape, jnp.bfloat16)
        v = jax.random.normal(kv, shape, jnp.bfloat16)
        probs_gib = (args.batch * args.heads * t * t * 2) / 2**30
        impls = [("flash_pallas", flash), ("xla_einsum", naive)]
        if args.causal:
            impls.insert(1, ("flash_pallas_dma_skip", flash_dma_skip))
        if args.impls:
            wanted = {s.strip() for s in args.impls.split(",") if s.strip()}
            unknown = wanted - {name for name, _ in impls}
            if unknown:
                raise SystemExit(f"--impls unknown: {sorted(unknown)}")
            impls = [(n, f) for n, f in impls if n in wanted]
        for name, fn in impls:
            try:
                ms = time_impl(fn, q, k, v)
                row = {"seq": t, "impl": name, "ms_per_iter": round(ms, 2),
                       "xla_probs_gib_per_materialization": round(probs_gib, 3),
                       **device}
            except Exception as e:  # OOM at long T is a RESULT here
                row = {"seq": t, "impl": name,
                       "error": type(e).__name__,
                       "detail": str(e).splitlines()[0][:200], **device}
            print(json.dumps(row), flush=True)


if __name__ == "__main__":
    main()
