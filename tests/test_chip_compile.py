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

import re

import jax
import jax.numpy as jnp
import numpy as np
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


# VGG-F's two LRN sites: after conv1 ("sublanes" view) and after conv2
# ("rows" view).
_LRN_SITES = {
    "conv1_54x54x64": dict(inputs=(56, 56, 48), kernel=(3, 3, 48, 64),
                           padding="VALID", activation=(54, 54, 64)),
    "conv2_27x27x256": dict(inputs=(27, 27, 64), kernel=(5, 5, 64, 256),
                            padding="SAME", activation=(27, 27, 256)),
}


@pytest.mark.parametrize("backward", [False, True], ids=["fwd", "bwd"])
@pytest.mark.parametrize("site", list(_LRN_SITES))
def test_lrn_pallas_compiles_for_v5e(chip, site, backward):
    assert not lrn_pallas.INTERPRET
    fn = lrn_pallas.local_response_norm_pallas
    if backward:
        fn = _sum_grad(fn, 1)
    compiled = _compile(fn, chip, (256,) + _LRN_SITES[site]["activation"])
    assert "tpu_custom_call" in compiled.as_text()


def _entry_instructions(compiled) -> list:
    """(result type, opcode, whole line) of the entry computation."""
    text = compiled.as_text()
    out = []
    for line in text[text.index("\nENTRY "):].splitlines():
        m = re.match(r"\s*(?:ROOT )?%\S+ = (\S+) ([\w\-]+)\(", line)
        if m:
            out.append((m.group(1), m.group(2), line))
    return out


def _elements(result_type: str) -> int:
    dims = re.match(r"\w+\[([\d,]*)\]", result_type)
    return int(np.prod([int(d) for d in dims.group(1).split(",") if d])) \
        if dims else 0


@pytest.mark.parametrize("batch", [1024, 256])
@pytest.mark.parametrize("site", list(_LRN_SITES))
def test_lrn_region_keeps_the_convolutions_layout(chip, site, batch):
    """conv -> relu -> lrn -> pool and its gradient, as `lrn()` lowers it
    on a TPU: between conv and pool each direction is one kernel, its view a
    bitcast of what XLA keeps (no copy or transpose of the activation), and
    no float32 array of the activation's size reaches HBM. The guard for the
    day XLA picks another layout."""
    from distributed_vgg_f_tpu.ops.lrn import lrn, set_lrn_impl
    from distributed_vgg_f_tpu.ops.pooling import maxpool_3x3s2_ceil
    spec = _LRN_SITES[site]

    def region(x, kernel, bias):
        y = jax.lax.conv_general_dilated(
            x, kernel, (1, 1), spec["padding"],
            dimension_numbers=("NHWC", "HWIO", "NHWC")) + bias
        return maxpool_3x3s2_ceil(lrn(y, relu_input=True))

    set_lrn_impl("pallas")      # `lrn()` sees the CPU's backend here
    try:
        compiled = _compile(_sum_grad(region, 3), chip,
                            (batch,) + spec["inputs"], spec["kernel"],
                            spec["kernel"][-1:])
    finally:
        set_lrn_impl(None)
    entry = _entry_instructions(compiled)
    size = batch * int(np.prod(spec["activation"]))
    calls = [line for _, op, line in entry
             if op == "custom-call" and "tpu_custom_call" in line]
    assert len(calls) == 2, calls
    moved = [line[:200] for kind, op, line in entry
             if _elements(kind) == size
             and (op in ("copy", "transpose")
                  or re.search(r"calls=%\S*(copy|transpose)", line))]
    assert not moved, moved
    wide = [line[:200] for kind, op, line in entry
            if kind.startswith("f32[") and _elements(kind) == size]
    assert not wide, wide


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


def test_grouped_query_core_reads_two_key_heads_not_thirty_two(chip):
    """The attention core of `nemotron3_nano_30b_ep8` (2 sequences of 8,192
    tokens, 32 query heads on 2 key/value heads of 128, blocks of 1,024,
    bf16) with its three gradients: three kernels, no array of the keys or
    values at the query heads' count anywhere but the per-query-head dK and
    dV the backward kernel writes (two, summed over each group after it)."""
    q = (2, 8192, 32, 128)
    kv = (2, 8192, 2, 128)

    def fn(q, k, v):
        return fa.flash_self_attention(q, k, v, causal=True, block_q=1024,
                                       block_k=1024)

    compiled = _compile(_sum_grad(fn, 3), chip, q, kv, kv)
    text = compiled.as_text()
    assert len(re.findall(r"= .*custom-call.*tpu_custom_call", text)) == 3
    # a K or V repeated in HBM would be a broadcast or gather of the 2-head
    # array to 64 rows of (8192, 128) outside the kernels (the sum's own
    # cotangent is a broadcast constant)
    repeated = [line[:160] for line in text.splitlines()
                if re.search(r"= bf16\[64,8192,128\]\S* (broadcast|gather|"
                             r"concatenate)\(%(?!constant)", line)]
    assert not repeated, repeated


def _scan_with_gradients(chip, monkeypatch, *, b, t, h, p, g, n, chunk,
                         dtype):
    """`ssd.ssd` with all six gradients, compiled for the described chip as
    a TPU's trace would lower it (`ssd` asks the backend, which is the
    CPU's here)."""
    from distributed_vgg_f_tpu.ops import ssd
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    arg = lambda shape, kind: jax.ShapeDtypeStruct(shape, kind, sharding=chip)

    def loss(x, dt, a, b_in, c_out, d):
        return jnp.sum(ssd.ssd(x, dt, a, b_in, c_out, d, chunk=chunk))

    return jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4, 5))).lower(
        arg((b, t, h, p), dtype), arg((b, t, h), jnp.float32),
        arg((h,), jnp.float32), arg((b, t, g, n), dtype),
        arg((b, t, g, n), dtype), arg((h,), jnp.float32)).compile()


def test_chunked_scan_compiles_at_the_cell_s_widths(chip, monkeypatch):
    """`ops/ssd.py` at `nemotron3_nano_30b_ep8`'s widths (2 x 8,192
    positions, 64 heads of 64 in 8 groups, state 128, chunks of 128, bf16)
    with its gradients: the two Pallas kernels of ops/ssd_pallas.py, no
    array of chunk x chunk a head (`[2,64,8,8,128,128]`, 134 M elements)
    in any dtype or order, and the temporaries at its fullest under the
    XLA form's 0.98 GiB (0.66 as compiled: y's cotangent and the states
    entering each chunk, 0.25 GiB each in float32, and dx)."""
    compiled = _scan_with_gradients(
        chip, monkeypatch, b=2, t=8192, h=64, p=64, g=8, n=128, chunk=128,
        dtype=jnp.bfloat16)
    text = compiled.as_text()
    assert len(re.findall(r"= .*custom-call.*tpu_custom_call", text)) == 2
    products = sorted({kind for kind in re.findall(r"\w+\[[\d,]+\]", text)
                       if _elements(kind) >= 2 * 64 * 64 * 128 * 128})
    assert not products, products
    memory = compiled.memory_analysis()
    temporaries = (memory.peak_memory_in_bytes - memory.argument_size_in_bytes
                   - memory.output_size_in_bytes) / 2 ** 30
    assert temporaries < 0.75, f"{temporaries:.2f} GiB of temporaries"


def test_chunked_scan_at_the_tiny_preset_s_widths_stays_xla(chip,
                                                            monkeypatch):
    """`nemotron3_nano_tiny` (chunks of 8, 4 heads of 8 in 2 groups, state
    16, float32) on a TPU: no tile of the chip's, so no kernel."""
    compiled = _scan_with_gradients(
        chip, monkeypatch, b=2, t=32, h=4, p=8, g=2, n=16, chunk=8,
        dtype=jnp.float32)
    assert "tpu_custom_call" not in compiled.as_text()


def test_expert_share_moves_only_the_rows_its_buffers_hold(chip):
    """One expert layer of `mistral_small4_119b_ep16` (4096 tokens, hidden
    4096, width 2048, 8 of 128 experts held, top-4, bf16) with its gradient:
    no computation of the compiled module, loop bodies included, holds an
    array of tokens x top-k = 16,384 rows by the hidden size or the
    experts' width; the grouped products are still XLA:TPU's kernel; and
    the temporaries at the program's fullest stay under what the layer
    needed with 16,384-row buffers."""
    from distributed_vgg_f_tpu.models import mistral4
    tokens, hidden, width, top_k = 4096, 4096, 2048, 4
    layer = mistral4.ExpertShare(
        n_routed_experts=128, num_experts_per_tok=top_k,
        moe_intermediate_size=width, n_shared_experts=1, first_expert=0,
        experts_held=8, compute_dtype=jnp.bfloat16)
    u = jax.ShapeDtypeStruct((1, tokens, hidden), jnp.bfloat16, sharding=chip)
    params = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=chip),
        jax.eval_shape(lambda: layer.init(
            jax.random.key(0), jnp.zeros(u.shape, u.dtype)))["params"])

    def loss(params, u):
        out, counts = layer.apply({"params": params}, u)
        return jnp.sum(out.astype(jnp.float32)), counts

    compiled = jax.jit(jax.value_and_grad(
        loss, argnums=(0, 1), has_aux=True)).lower(params, u).compile()
    text = compiled.as_text()
    full = re.findall(rf"\w+\[{tokens * top_k},(?:{hidden}|{width})\]", text)
    assert not full, sorted(set(full))
    products = re.findall(r"%ragged-dot-none\S* = .*tpu_custom_call", text)
    # forward 3; backward the hidden rows again 2, to the rows 3, to the
    # weights 3, once outside the loop and once in its body
    assert len(products) == 3 + 2 * 8, len(products)
    memory = compiled.memory_analysis()
    # live bytes at the fullest point less what goes in and comes out
    # (`temp_size_in_bytes` counts a loop's state again for its body)
    temporaries = (memory.peak_memory_in_bytes - memory.argument_size_in_bytes
                   - memory.output_size_in_bytes) / 2 ** 30
    assert temporaries < 0.690, (
        f"{temporaries:.3f} GiB of temporaries; with 16,384-row buffers "
        f"(PR 30's tree) this function needed 0.690 GiB, with PR 31's 0.578")


def test_delta_rule_core_compiles_at_the_cell_s_widths(chip, monkeypatch):
    """`ops/kda.py` at `ling3_flash_ep64`'s widths (2 x 8,192 positions, 32
    heads of 128 on both sides, chunks of 64, bf16) with its five
    gradients, as a TPU's trace lowers it: the two Pallas kernels of
    ops/kda_pallas.py; no array with a chunk's 64 x 64 (the scores, their
    inverse: `[2,128,32,64,64]`, 33.5 M elements a kind) in any dtype; no
    heads-major copy of q, k, v or g (no array in which the 32 heads stand
    before a chunk's 64 or a sequence's 8,192 positions, and no layout of
    `[2,8192,32,128]` but the row-major one); the states entering each
    chunk once, float32 `[2,128,32,128,128]`; and the temporaries at its
    fullest under 1.5 GiB (1.38 as compiled: those states 0.5 GiB, the
    five arguments as (b, t, 4096) 0.67, which in the model are what the
    mixer has, and o's cotangent; the XLA form needed 1.89)."""
    from distributed_vgg_f_tpu.ops import kda
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    b, t, h, d = 2, 8192, 32, 128
    arg = lambda shape, kind: jax.ShapeDtypeStruct(shape, kind, sharding=chip)

    def loss(q, k, v, g, beta):
        return jnp.sum(kda.kda(q, k, v, g, beta))

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4))).lower(
        arg((b, t, h, d), jnp.bfloat16), arg((b, t, h, d), jnp.bfloat16),
        arg((b, t, h, d), jnp.bfloat16), arg((b, t, h, d), jnp.float32),
        arg((b, t, h), jnp.float32)).compile()
    text = compiled.as_text()
    assert len(re.findall(r"= .*custom-call.*tpu_custom_call", text)) == 2
    kinds = set(re.findall(r"\w+\[[\d,]+\]", text))
    square = sorted(kind for kind in kinds if re.search(r"[\[,]64,64[,\]]",
                                                       kind))
    assert not square, square
    heads_major = sorted(kind for kind in kinds
                         if re.search(r"[\[,]32,(64|8192),", kind))
    assert not heads_major, heads_major
    turned = sorted(set(re.findall(
        r"\w+\[2,8192,32,128\]\{(?!3,2,1,0)[\d,]+", text)))
    assert not turned, turned
    states = {kind for kind in kinds if _elements(kind) >= b * t * h * d * 2}
    assert states == {"f32[2,128,32,128,128]"}, states
    memory = compiled.memory_analysis()
    temporaries = (memory.peak_memory_in_bytes - memory.argument_size_in_bytes
                   - memory.output_size_in_bytes) / 2 ** 30
    assert temporaries < 1.5, f"{temporaries:.2f} GiB of temporaries"


def test_unbounded_delta_rule_compiles_at_solar_s_widths(chip, monkeypatch):
    """The unbounded form of `ops/kda.py` (Kimi's softplus gate: the
    diagonal sub-blocks pair by pair) at `solar_open2_250b_ep40`'s widths
    (4,096 positions, 64 heads of 128, chunks of 64, bf16) with its five
    gradients: the two Pallas kernels, whose backward with 16 heads a grid
    step asked for 74 MiB of VMEM against the 64 it may have (the compile
    refuses that), and the states entering each chunk once."""
    from distributed_vgg_f_tpu.ops import kda
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    b, t, h, d = 1, 4096, 64, 128
    arg = lambda shape, kind: jax.ShapeDtypeStruct(shape, kind, sharding=chip)

    def loss(q, k, v, g, beta):
        return jnp.sum(kda.kda(q, k, v, g, beta, bounded=False))

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4))).lower(
        arg((b, t, h, d), jnp.bfloat16), arg((b, t, h, d), jnp.bfloat16),
        arg((b, t, h, d), jnp.bfloat16), arg((b, t, h, d), jnp.float32),
        arg((b, t, h), jnp.float32)).compile()
    text = compiled.as_text()
    assert len(re.findall(r"= .*custom-call.*tpu_custom_call", text)) == 2
    kinds = set(re.findall(r"\w+\[[\d,]+\]", text))
    states = {kind for kind in kinds if _elements(kind) >= b * t * h * d * 2}
    assert states == {"f32[1,64,64,128,128]"}, states


def _short_conv_with_gradients(chip, monkeypatch, *, b, t, heads, width,
                               dtype, scale):
    """`short_conv.conv_silu_heads` with its output and both gradients,
    compiled for the described chip as a TPU's trace would lower it; y and
    its cotangent cross the program's edge as (b, t, channels), as the
    recurrence's kernels take and give them (a parameter of (b, t, heads,
    width) has another tiling and would be copied)."""
    from distributed_vgg_f_tpu.ops import short_conv
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    arg = lambda shape, kind: jax.ShapeDtypeStruct(shape, kind, sharding=chip)

    def both(x, taps, dy):
        y, back = jax.vjp(lambda x, taps: short_conv.conv_silu_heads(
            x, taps, heads, scale), x, taps)
        return y.reshape(dy.shape), back(dy.reshape(y.shape))

    return jax.jit(both).lower(
        arg((b, t, heads * width), dtype), arg((4, heads * width),
                                               jnp.float32),
        arg((b, t, heads * width), dtype)).compile()


@pytest.mark.parametrize("scale", [1.0, None], ids=["head_norm", "no_norm"])
def test_short_conv_compiles_at_the_cell_s_widths(chip, monkeypatch, scale):
    """ops/short_conv.py at `ling3_flash_ep64`'s widths (2 x 8,192
    positions, 32 heads of 128, four taps, bf16) forward and backward, as a
    TPU's trace lowers it: the two Pallas kernels of
    ops/short_conv_pallas.py under the default VMEM budget (they ask for no
    more), no float32 array of tokens x channels in any shape outside them
    (x's, p's and s's float32 copies live in the kernels' registers), and
    the temporaries no more than dtaps' partial sums."""
    compiled = _short_conv_with_gradients(
        chip, monkeypatch, b=2, t=8192, heads=32, width=128,
        dtype=jnp.bfloat16, scale=scale)
    text = compiled.as_text()
    assert len(re.findall(r"= .*custom-call.*tpu_custom_call", text)) == 2
    assert "vmem_limit_bytes" not in text
    wide = sorted({kind for kind in re.findall(r"f32\[[\d,]+\]", text)
                   if _elements(kind) >= 2 * 8192 * 4096})
    assert not wide, wide
    memory = compiled.memory_analysis()
    temporaries = (memory.peak_memory_in_bytes - memory.argument_size_in_bytes
                   - memory.output_size_in_bytes) / 2 ** 20
    assert temporaries < 1, f"{temporaries:.2f} MiB of temporaries"


def test_short_conv_at_the_tiny_preset_s_widths_stays_xla(chip, monkeypatch):
    """`ling3_flash_tiny` (4 heads of 16, float32) on a TPU: a head is no
    lane tile, so no kernel."""
    compiled = _short_conv_with_gradients(
        chip, monkeypatch, b=2, t=64, heads=4, width=16, dtype=jnp.float32,
        scale=1.0)
    assert "tpu_custom_call" not in compiled.as_text()


def test_latent_core_at_two_head_sizes_compiles_for_v5e(chip):
    """The latent layer's attention core of `ling3_flash_ep64` (2
    sequences of 8,192 tokens, 32 heads, queries and keys of 192 on values
    of 128, blocks of 1,024, bf16) with its three gradients: three
    kernels, each block its array's whole last dimension (192 is one and a
    half lane tiles), and no copy of q or k padded to 256."""
    q, v = (2, 8192, 32, 192), (2, 8192, 32, 128)

    def fn(q, k, v):
        return fa.flash_self_attention(q, k, v, causal=True, block_q=1024,
                                       block_k=1024)

    args = [jax.ShapeDtypeStruct(s, jnp.bfloat16, sharding=chip)
            for s in (q, q, v)]
    compiled = jax.jit(_sum_grad(fn, 3)).lower(*args).compile()
    text = compiled.as_text()
    assert len(re.findall(r"= .*custom-call.*tpu_custom_call", text)) == 3
    assert not re.findall(r"bf16\[\d+,8192,256\]", text)
    gradients = re.findall(r"ROOT .*\(bf16\[2,8192,32,192\]\S*, "
                           r"bf16\[2,8192,32,192\]\S*, "
                           r"bf16\[2,8192,32,128\]", text)
    assert gradients, "dq, dk at 192 and dv at 128"
