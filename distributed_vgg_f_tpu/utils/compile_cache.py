"""Where JAX's persistent compilation cache lives — the one setter.

Every entry point that compiles (cli.main, bench.py, the serving and
elastic benches, chip_smoke.py, the test bootstrap) calls
`enable_compile_cache()` before its first jit. The directory is part of a
cache entry's key, so it must not move between runs: no temp dir, no pid,
no timestamp.

- `JAX_COMPILATION_CACHE_DIR` set: JAX already keeps its cache there (it
  reads the variable at import) and this function sets nothing.
- unset: one fixed, git-ignored directory at the root of the checkout.

On both paths it also hands `jax.monitoring` to the telemetry package's
compile listeners (`telemetry/compile_events.py`), once a process: what JAX
traces, lowers, compiles and reads from this cache becomes `compile` spans
and `compile/*` counters.
"""

from __future__ import annotations

import os

#: <checkout>/.jax_cache — listed in .gitignore.
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def enable_compile_cache(subdir: str = "") -> str:
    """Point JAX's persistent compilation cache at its fixed home and
    return the directory in use. `subdir` names a fixed sub-directory of
    the in-checkout default (the tests key XLA:CPU entries, which are
    machine code, by the host's CPU features); it is ignored when the
    environment variable places the cache."""
    import jax

    from distributed_vgg_f_tpu.telemetry import compile_events
    compile_events.install(jax.monitoring)
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    path = os.path.join(DEFAULT_CACHE_DIR, subdir) if subdir \
        else DEFAULT_CACHE_DIR
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def cache_entries(path: str) -> int:
    """Number of entries in a cache directory (0 when it does not exist) —
    printed before and after a run so a second run shows its hits."""
    try:
        return sum(1 for name in os.listdir(path) if name.endswith("-cache"))
    except OSError:
        return 0
