"""Weights and resident batches, made on the device from `--seed`.

The benchmark makes both and hands them to the program and to the plain
reference alike, so neither side takes anything the other has made.
"""

from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp
import numpy as np


def leaf_name(path) -> str:
    return "/".join(str(getattr(p, "key", getattr(p, "name", p)))
                    for p in path)


def make_params(shapes, seed, init: dict | None = None):
    """One float32 tree of the given shapes (a tree of ShapeDtypeStruct),
    each leaf drawn from a key folded with the crc32 of its path: kernels
    N(0, 1/fan_in), batch-norm scales N(1, 0.1), biases N(0, 0.05). `init`
    maps the tail of a leaf's path to another (mean, std), as a
    configuration's file states it. `seed` may be traced: one compiled
    program then serves every seed."""
    key = jax.random.key(jnp.asarray(seed, jnp.uint32))
    init = init or {}

    def leaf(path, s):
        name = leaf_name(path)
        k = jax.random.fold_in(key, zlib.crc32(name.encode()))
        z = jax.random.normal(k, s.shape, jnp.float32)
        for tail, (mean, std) in init.items():
            if name.endswith(tail):
                return mean + std * z
        kind = name.rsplit("/", 1)[-1]
        if kind == "kernel":
            fan_in = 1
            for d in s.shape[:-1]:
                fan_in *= d
            return z * (1.0 / fan_in) ** 0.5
        if kind == "scale":
            return 1.0 + 0.1 * z
        if kind == "bias":
            return 0.05 * z
        return 0.02 * z

    return jax.tree_util.tree_map_with_path(leaf, shapes)


def seed_word(seed: int):
    """`seed` as the uint32 the jitted makers take."""
    return np.uint32(seed % (2 ** 32))


def make_batch(seed: int, rows: int, size: int, classes: int) -> dict:
    """`rows` distinct u8 images of (size, size, 3) and their labels, as
    the host arrays the program's own feed call takes."""
    rng = np.random.default_rng([seed, rows, size])
    return {"image": rng.integers(0, 256, (rows, size, size, 3), np.uint8),
            "label": rng.integers(0, classes, (rows,), np.int32)}
