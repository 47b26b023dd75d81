"""Chip-compiler rehearsal: the Pallas kernels of the main path, compiled by
the TPU compiler for a DESCRIBED v5e:2x2 (no chip attached), at the widths
the models really run. Interpret mode cannot show what this does: a slice
off the tiling, a kernel over its fast-memory budget, a kernel the
partitioner refuses. A compile that passes is not a chip run — nothing
executes here.

The persistent compile cache is switched off around the cases: an entry
written for a described device cannot be read back without one, and the
retry would warn on every later run.
"""

import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else libtpu logs to /tmp

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from distributed_vgg_f_tpu.ops import flash_attention as fa
from distributed_vgg_f_tpu.ops import lrn_pallas


@pytest.fixture(scope="module")
def chip():
    """One device of the described topology, as a sharding for shapes."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no libtpu / no such topology
        pytest.skip(f"TPU topology v5e:2x2 cannot be described here: "
                    f"{type(e).__name__}: {str(e)[:200]}")
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield SingleDeviceSharding(topo.devices[0])
    finally:
        jax.config.update("jax_enable_compilation_cache", was_on)
        compilation_cache.reset_cache()


def _sum_grad(fn, n_args):
    """Backward of `fn` w.r.t. every argument under a scalar loss."""
    def loss(*args):
        return jnp.sum(fn(*args).astype(jnp.float32))
    return jax.grad(loss, argnums=tuple(range(n_args)))


def _compile(fn, chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, jnp.bfloat16, sharding=chip)
            for s in shapes]
    return jax.jit(fn).lower(*args).compile()


# VGG-F's two LRN sites at the bench batch: after conv1 and after conv2.
@pytest.mark.parametrize("backward", [False, True], ids=["fwd", "bwd"])
@pytest.mark.parametrize("shape", [(256, 54, 54, 64), (256, 27, 27, 256)],
                         ids=["conv1_54x54x64", "conv2_27x27x256"])
def test_lrn_pallas_compiles_for_v5e(chip, shape, backward):
    assert not lrn_pallas.INTERPRET
    fn = lrn_pallas.local_response_norm_pallas
    if backward:
        fn = _sum_grad(fn, 1)
    compiled = _compile(fn, chip, shape)
    assert "tpu_custom_call" in compiled.as_text()


# (B, T, H, D) at flash_self_attention's default block sizes. 197 tokens is
# ViT-S/16 (padded internally to 256); the causal rows sit on both sides
# of CAUSAL_SKIP_AUTO_THRESHOLD: rectangular grids below, the jagged
# DMA-skip grids from it up.
_FLASH_CASES = {
    "vit_s16_197": ((64, 197, 6, 64), False),
    "long_4096": ((2, 4096, 6, 64), False),
    "causal_1024_mxu": ((2, 1024, 6, 64), True),
    "causal_4096_dma": ((2, 4096, 6, 64), True),
}


@pytest.mark.parametrize("backward", [False, True], ids=["fwd", "bwd"])
@pytest.mark.parametrize("case", list(_FLASH_CASES))
def test_flash_attention_compiles_for_v5e(chip, case, backward):
    shape, causal = _FLASH_CASES[case]
    assert not fa.INTERPRET
    want_skip = "dma" if case.endswith("dma") else "mxu"
    assert fa.resolve_causal_skip_auto(causal, shape[1]) == want_skip

    def fn(q, k, v):
        return fa.flash_self_attention(q, k, v, causal=causal)

    if backward:
        fn = _sum_grad(fn, 3)
    compiled = _compile(fn, chip, shape, shape, shape)
    assert "tpu_custom_call" in compiled.as_text()
