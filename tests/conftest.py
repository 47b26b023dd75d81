"""Test environment: force an 8-device virtual CPU platform BEFORE jax imports,
so every test exercises real mesh construction and cross-replica collectives
without TPU hardware (SURVEY.md §4 fake-multi-device strategy)."""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()
os.environ.setdefault("TF_CPP_MIN_LOG_LEVEL", "3")

import jax  # noqa: E402
import pytest  # noqa: E402

# Persistent XLA compilation cache: identical jitted computations (the same
# VGG-F train/eval steps rebuilt by many tests) compile once per machine, not
# once per test — the single biggest lever on suite wall-time. Placed by
# utils/compile_cache (JAX_COMPILATION_CACHE_DIR when set, else the fixed
# in-checkout directory); in the default location the entries sit under a
# sub-directory keyed by the host's CPU features
# (_child_bootstrap.cpu_cache_subdir), because XLA:CPU entries are machine
# code and another machine's entry can miscompute or SIGILL.
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from _child_bootstrap import cpu_cache_subdir  # noqa: E402

from distributed_vgg_f_tpu.utils.compile_cache import (  # noqa: E402
    enable_compile_cache)

enable_compile_cache(cpu_cache_subdir())
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)


@pytest.fixture(scope="session")
def devices8():
    devs = jax.devices()
    assert len(devs) == 8, f"expected 8 virtual CPU devices, got {len(devs)}"
    return devs
