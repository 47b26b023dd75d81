"""Shared pre-import bootstrap for multi-process test CHILDREN.

Every subprocess child must pin the CPU platform and its virtual device
count BEFORE importing jax (pytest's conftest exports its own 8-device
XLA_FLAGS that children may need to override), and multi-process children
must wire the Gloo coordinator. One helper, so the bootstrap cannot silently diverge between
children (code-review r3: four hand-copies had already grown differences —
only one had the shared compile cache).

Must be imported (and `bootstrap()` called) before anything that imports
jax.
"""

from __future__ import annotations

import hashlib
import os
import platform
import re


def cpu_cache_subdir() -> str:
    """Compile-cache sub-directory keyed by the host's CPU feature set.

    XLA:CPU cache entries are AOT machine code for the COMPILING host's
    featureset; on a box whose VM migrates across heterogeneous hardware a
    stale entry loads with a `cpu_aot_loader` feature-mismatch warning and
    then miscomputes (observed r3: cached ViT train step returned loss=nan
    with finite logits — every fresh compile was correct). Keying the
    sub-directory by a fingerprint of /proc/cpuinfo flags makes a migrated
    host start a new cache instead of executing another machine's code."""
    fingerprint = platform.machine()
    try:
        with open("/proc/cpuinfo") as f:
            for line in f:
                if line.startswith(("flags", "Features")):
                    fingerprint = hashlib.md5(line.encode()).hexdigest()[:10]
                    break
    except OSError:
        pass
    return f"cpu_{fingerprint}"


def bootstrap(num_local_devices: int, *, coordinator_port=None,
              num_processes: int | None = None,
              process_id: int | None = None):
    """Pin CPU + device count and (when a coordinator port is given)
    initialize the distributed runtime. SINGLE-process children share the
    suite's persistent compile cache; multi-process children deliberately
    run WITHOUT one (see the skew rationale below). Returns the configured
    `jax` module."""
    os.environ["JAX_PLATFORMS"] = "cpu"
    flags = re.sub(r"--xla_force_host_platform_device_count=\d+", "",
                   os.environ.get("XLA_FLAGS", ""))
    os.environ["XLA_FLAGS"] = (
        flags + f" --xla_force_host_platform_device_count="
        f"{num_local_devices}").strip()

    import jax

    # Compile-skew discipline. Multi-process children get NO persistent
    # compile cache — every rank compiles every program, which is SLOWER but
    # SYMMETRIC. With a cache, jax writes entries only from process 0
    # (jax/_src/compiler.py _cache_write) and on this backend the ranks'
    # cache keys differ anyway (verified: share_binary_between_hosts
    # deadlocks waiting for a key the other rank never publishes), so rank 0
    # hits in ~0.5 s while other ranks recompile ~10 s — and that skew,
    # stacked across phases, lands a waiting rank in Gloo's fixed ~30 s TCP
    # read window mid-collective (reproduced deterministically with
    # DVGGF_CHILD_DEBUG=1 phase timestamps). Symmetric compilation keeps
    # inter-rank skew at execution noise (~1-2 s).
    if coordinator_port is None:  # the direct multi-process signal —
        # process_id could legitimately be None with env auto-detection
        from distributed_vgg_f_tpu.utils.compile_cache import (
            enable_compile_cache)
        enable_compile_cache(cpu_cache_subdir())
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)
    else:
        # off explicitly: JAX_COMPILATION_CACHE_DIR in the environment
        # would otherwise hand these ranks a cache too
        jax.config.update("jax_enable_compilation_cache", False)

    if coordinator_port is not None:
        from distributed_vgg_f_tpu.parallel.distributed import (
            initialize_distributed)
        initialize_distributed(
            coordinator_address=f"127.0.0.1:{coordinator_port}",
            num_processes=num_processes, process_id=process_id)
    return jax


def run_ring_phase(jax, nproc: int, pid: int, n_local: int, *,
                   seed: int = 42, batch: int = 1) -> dict:
    """Sequence-parallel attention across REAL process boundaries — shared
    by the 2- and 4-process children (one copy, code-review r3): einsum
    ring and ring × flash (interpreted Pallas kernels), causal forward
    exactness vs the oracle, and finiteness of ALL THREE flash-backward
    cotangents (the dK/dV accumulators travel the ring with their blocks);
    plus the Ulysses all-to-all layout — `lax.all_to_all` crosses the
    process boundary, a different Gloo collective than the ring's
    neighbor ppermute. Returns {"ring_ok", "ring_flash_ok",
    "ring_flash_grad_finite", "ulysses_ok", "ulysses_grads_ok"}."""
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from distributed_vgg_f_tpu.ops import flash_attention as fa
    from distributed_vgg_f_tpu.parallel.mesh import MeshSpec, build_mesh
    from distributed_vgg_f_tpu.parallel.ring_attention import (
        full_attention_reference, ring_attention)
    from distributed_vgg_f_tpu.parallel.ring_flash import ring_flash_attention

    n_dev = n_local * nproc
    mesh_r = build_mesh(MeshSpec(("data",), (n_dev,)))
    T = 8 * n_dev
    rng_r = np.random.default_rng(seed)   # same arrays on every process
    qg, kg, vg = (rng_r.standard_normal((batch, T, 2, 8)).astype(np.float32)
                  for _ in range(3))
    sharding = NamedSharding(mesh_r, P(None, "data"))
    t_proc = T // nproc

    def to_global(x, t_per_proc=t_proc):
        # shared by the ring block (T = 8·n_dev) and the ulysses block
        # (T = 4·n_dev): this process's contiguous sequence slice, lifted
        # into the mesh-global sharded array
        return jax.make_array_from_process_local_data(
            sharding, x[:, pid * t_per_proc:(pid + 1) * t_per_proc])

    def local_slice(arr):
        return np.concatenate(
            [s.data for s in sorted(arr.addressable_shards,
                                    key=lambda s: s.index[1].start)], axis=1)

    want = np.asarray(full_attention_reference(
        *(jax.numpy.asarray(x) for x in (qg, kg, vg)),
        causal=True))[:, pid * t_proc:(pid + 1) * t_proc]
    got = ring_attention(*(to_global(x) for x in (qg, kg, vg)),
                         mesh_r, causal=True)
    ring_ok = bool(np.allclose(local_slice(got), want, rtol=2e-5, atol=2e-5))

    old_interpret = fa.INTERPRET
    fa.INTERPRET = True
    try:
        flash_got = ring_flash_attention(
            *(to_global(x) for x in (qg, kg, vg)), mesh_r, causal=True)
        ring_flash_ok = bool(np.allclose(local_slice(flash_got), want,
                                         rtol=2e-5, atol=2e-5))
        grads = jax.grad(lambda q, k, v: jax.numpy.sum(
            ring_flash_attention(q, k, v, mesh_r) ** 2), argnums=(0, 1, 2))(
            *(to_global(x) for x in (qg, kg, vg)))
        ring_flash_grad_finite = all(
            bool(np.isfinite(np.concatenate(
                [s.data for s in g.addressable_shards], axis=None)).all())
            for g in grads)
    finally:
        fa.INTERPRET = old_interpret

    # Ulysses: heads shard across the axis, so H = n_dev (the layout's own
    # constraint); T stays a multiple of the axis. Same every-process
    # arrays, same per-process sequence slicing as the ring block above.
    from distributed_vgg_f_tpu.parallel.ulysses import ulysses_attention

    t_u = 4 * n_dev
    qu, ku, vu = (rng_r.standard_normal(
        (batch, t_u, n_dev, 8)).astype(np.float32) for _ in range(3))
    tu_proc = t_u // nproc
    want_u = np.asarray(full_attention_reference(
        *(jax.numpy.asarray(x) for x in (qu, ku, vu)),
        causal=True))[:, pid * tu_proc:(pid + 1) * tu_proc]
    got_u = ulysses_attention(*(to_global(x, tu_proc) for x in (qu, ku, vu)),
                              mesh_r, causal=True)
    ulysses_ok = bool(np.allclose(local_slice(got_u), want_u,
                                  rtol=2e-5, atol=2e-5))
    # backward: the output all_to_all transposes to its inverse, so grads
    # send a SECOND set of all_to_alls across the process boundary. Checked
    # against the oracle's gradients SLICED per process (the want_u
    # pattern), causal=True like the forward check — finiteness alone would
    # pass a Gloo-boundary transpose-ordering bug producing wrong-but-
    # finite values (ADVICE r4).
    grads_u = jax.grad(lambda q, k, v: jax.numpy.sum(
        ulysses_attention(q, k, v, mesh_r, causal=True) ** 2),
        argnums=(0, 1, 2))(
        *(to_global(x, tu_proc) for x in (qu, ku, vu)))
    want_gu = jax.grad(lambda q, k, v: jax.numpy.sum(
        full_attention_reference(q, k, v, causal=True) ** 2),
        argnums=(0, 1, 2))(*(jax.numpy.asarray(x) for x in (qu, ku, vu)))
    ulysses_grads_ok = all(
        bool(np.allclose(
            local_slice(g),
            np.asarray(w)[:, pid * tu_proc:(pid + 1) * tu_proc],
            rtol=5e-5, atol=5e-5))
        for g, w in zip(grads_u, want_gu))
    return {"ring_ok": ring_ok, "ring_flash_ok": ring_flash_ok,
            "ring_flash_grad_finite": ring_flash_grad_finite,
            "ulysses_ok": ulysses_ok,
            "ulysses_grads_ok": ulysses_grads_ok}
