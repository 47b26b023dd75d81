"""Flash attention kernel vs naive reference — interpret mode on CPU.

The Pallas interpreter executes the real kernel bodies (same grid, same
scratch carries, same masking) without a TPU; the on-chip timing story lives
in benchmarks/flash_attention_bench.py.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_vgg_f_tpu.ops.flash_attention import flash_self_attention
# ONE oracle for every attention implementation in the repo (ring, ring×flash,
# flash) — formulation drift between hand-rolled copies is itself a bug class
# (code-review r3)
from distributed_vgg_f_tpu.parallel.ring_attention import (
    full_attention_reference as naive_attention)


def _rand_qkv(key, shape, dtype=jnp.float32):
    kq, kk, kv = jax.random.split(key, 3)
    return (jax.random.normal(kq, shape, dtype),
            jax.random.normal(kk, shape, dtype),
            jax.random.normal(kv, shape, dtype))


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("block", [64, 128])
def test_forward_matches_naive(causal, block):
    q, k, v = _rand_qkv(jax.random.key(0), (2, 256, 2, 64))
    out = flash_self_attention(q, k, v, causal=causal, block_q=block,
                               block_k=block, interpret=True)
    ref = naive_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_gradients_match_naive(causal):
    q, k, v = _rand_qkv(jax.random.key(1), (1, 128, 2, 32))
    cot = jax.random.normal(jax.random.key(2), q.shape)

    def flash_loss(q, k, v):
        out = flash_self_attention(q, k, v, causal=causal, block_q=64,
                                   block_k=64, interpret=True)
        return jnp.vdot(out, cot)

    def naive_loss(q, k, v):
        return jnp.vdot(naive_attention(q, k, v, causal=causal), cot)

    grads = jax.grad(flash_loss, argnums=(0, 1, 2))(q, k, v)
    ref_grads = jax.grad(naive_loss, argnums=(0, 1, 2))(q, k, v)
    for g, r, name in zip(grads, ref_grads, "qkv"):
        np.testing.assert_allclose(np.asarray(g), np.asarray(r),
                                   rtol=2e-4, atol=2e-4, err_msg=f"d{name}")


def test_uneven_blocks():
    """block_q != block_k exercises the rectangular masking index math."""
    q, k, v = _rand_qkv(jax.random.key(3), (1, 256, 1, 32))
    out = flash_self_attention(q, k, v, causal=True, block_q=128, block_k=64,
                               interpret=True)
    ref = naive_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_bf16_inputs_fp32_stats():
    """bf16 q/k/v: the kernel's fp32 softmax statistics keep the result
    within bf16 resolution of an fp32-softmax reference."""
    q, k, v = _rand_qkv(jax.random.key(4), (1, 128, 2, 64), jnp.bfloat16)
    out = flash_self_attention(q, k, v, interpret=True)
    assert out.dtype == jnp.bfloat16
    ref = naive_attention(q.astype(jnp.float32), k.astype(jnp.float32),
                          v.astype(jnp.float32))
    np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(ref),
                               rtol=0.02, atol=0.02)


def test_block_clamping_and_divisibility():
    q, k, v = _rand_qkv(jax.random.key(5), (1, 32, 1, 16))
    # blocks clamp to T=32 and just work
    out = flash_self_attention(q, k, v, interpret=True)
    ref = naive_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
    # EXPLICIT block sizes are strict
    with pytest.raises(ValueError, match="not divisible"):
        q2, k2, v2 = _rand_qkv(jax.random.key(6), (1, 96, 1, 16))
        flash_self_attention(q2, k2, v2, block_q=64, block_k=64,
                             interpret=True)
    # default (None) blocks auto-shrink to a divisor: T=192 → 64
    q3, k3, v3 = _rand_qkv(jax.random.key(7), (1, 192, 1, 16))
    out3 = flash_self_attention(q3, k3, v3, causal=True, interpret=True)
    np.testing.assert_allclose(
        np.asarray(out3), np.asarray(naive_attention(q3, k3, v3, causal=True)),
        rtol=2e-5, atol=2e-5)


def test_pick_block_odd_lengths():
    """Odd composite lengths must get the largest true divisor, not block 1:
    the halving loop bottoms out at b=1 (and t % 1 == 0), so the divisor
    fallback has to trigger on that case explicitly (ADVICE r3). t=195 and
    the ring_flash-reachable t=197-like odd lengths are the motivating
    shapes (e.g. T=394 ring-split over 2 devices)."""
    from distributed_vgg_f_tpu.ops.flash_attention import pick_block

    assert pick_block(195) == 65          # 195 = 3·5·13 → largest ≤128 is 65
    assert pick_block(105) == 105         # odd t ≤ requested divides itself
    assert pick_block(197) == 1           # prime: 1 really is the only choice
    assert pick_block(192) == 64          # even path unchanged: halving wins
    assert pick_block(256) == 128
    assert pick_block(105, requested=64) == 35   # 105 = 3·5·7, clamp matters
    # EVEN lengths whose large divisors are odd: halving alone bottomed out
    # at a cliff block (130 → 2, 160 → 32) though exact divisors ≥ 64 exist
    # (ADVICE r5)
    assert pick_block(130) == 65
    assert pick_block(160) == 80
    assert pick_block(136) == 68
    # and the resulting block actually runs: odd T end-to-end
    q, k, v = _rand_qkv(jax.random.key(20), (1, 195, 1, 32))
    out = flash_self_attention(q, k, v, causal=True, interpret=True)
    ref = naive_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_kv_len_padding_matches_unpadded(causal):
    """Pad 197 → 256 with kv_len=197 (the ViT contract), in BOTH masking
    modes — causal and padding masks compose: outputs on the real rows must
    equal unpadded attention, and grads of the padding must be 0."""
    T, TP = 197, 256
    q, k, v = _rand_qkv(jax.random.key(8), (2, T, 2, 32))
    pad = [(0, 0), (0, TP - T), (0, 0), (0, 0)]
    qp, kp, vp = (jnp.pad(x, pad) for x in (q, k, v))
    cot = jax.random.normal(jax.random.key(9), q.shape)

    def padded_loss(qp, kp, vp):
        out = flash_self_attention(qp, kp, vp, causal=causal, block_q=64,
                                   block_k=64, kv_len=T, interpret=True)
        return jnp.vdot(out[:, :T], cot)

    def naive_loss(q, k, v):
        return jnp.vdot(naive_attention(q, k, v, causal=causal), cot)

    out = flash_self_attention(qp, kp, vp, causal=causal, block_q=64,
                               block_k=64, kv_len=T, interpret=True)
    ref = naive_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out[:, :T]), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)

    grads = jax.grad(padded_loss, argnums=(0, 1, 2))(qp, kp, vp)
    ref_grads = jax.grad(naive_loss, argnums=(0, 1, 2))(q, k, v)
    for g, r, name in zip(grads, ref_grads, "qkv"):
        np.testing.assert_allclose(np.asarray(g[:, :T]), np.asarray(r),
                                   rtol=2e-4, atol=2e-4, err_msg=f"d{name}")
        assert np.all(np.asarray(g[:, T:]) == 0.0), f"d{name} padding nonzero"


@pytest.mark.parametrize("t,block", [(256, 64), (1024, None), (192, None)])
def test_causal_dma_skip_matches_rectangular(t, block):
    """causal_skip='dma' (flat grid over live lower-triangular pairs,
    scalar-prefetched indices — masked blocks never DMA) must be
    numerically identical to the rectangular grid AND the oracle, forward
    and backward; the backward kernels are shared."""
    q, k, v = _rand_qkv(jax.random.key(21), (2, t, 2, 32))
    kw = dict(causal=True, block_q=block, block_k=block, interpret=True)
    out_dma = flash_self_attention(q, k, v, causal_skip="dma", **kw)
    out_mxu = flash_self_attention(q, k, v, causal_skip="mxu", **kw)
    ref = naive_attention(q, k, v, causal=True)
    np.testing.assert_array_equal(np.asarray(out_dma), np.asarray(out_mxu))
    np.testing.assert_allclose(np.asarray(out_dma), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
    cot = jax.random.normal(jax.random.key(22), q.shape)
    g_dma = jax.grad(lambda a, b, c: jnp.vdot(flash_self_attention(
        a, b, c, causal_skip="dma", **kw), cot), argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(lambda a, b, c: jnp.vdot(
        naive_attention(a, b, c, causal=True), cot),
        argnums=(0, 1, 2))(q, k, v)
    for gd, gr, name in zip(g_dma, g_ref, "qkv"):
        np.testing.assert_allclose(np.asarray(gd), np.asarray(gr),
                                   rtol=2e-4, atol=2e-4, err_msg=f"d{name}")


def test_causal_dma_skip_validation_and_fallbacks():
    q, k, v = _rand_qkv(jax.random.key(23), (1, 128, 1, 16))
    with pytest.raises(ValueError, match="causal_skip"):
        flash_self_attention(q, k, v, causal_skip="dmaa", interpret=True)
    with pytest.raises(ValueError, match="only applies to causal"):
        flash_self_attention(q, k, v, causal_skip="dma", interpret=True)
    # kv_len forces the rectangular fallback but stays correct
    T, TP = 100, 128
    qs, ks, vs = _rand_qkv(jax.random.key(24), (1, T, 1, 16))
    pad = [(0, 0), (0, TP - T), (0, 0), (0, 0)]
    out = flash_self_attention(
        jnp.pad(qs, pad), jnp.pad(ks, pad), jnp.pad(vs, pad), causal=True,
        kv_len=T, causal_skip="dma", block_q=64, block_k=64, interpret=True)
    ref = naive_attention(qs, ks, vs, causal=True)
    np.testing.assert_allclose(np.asarray(out[:, :T]), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_causal_skip_auto_resolution():
    """causal_skip="auto" (the default) follows the measured r4 crossover:
    jagged DMA-skip grids from CAUSAL_SKIP_AUTO_THRESHOLD tokens, the
    rectangular schedule below and for non-causal calls."""
    from distributed_vgg_f_tpu.ops.flash_attention import (
        CAUSAL_SKIP_AUTO_THRESHOLD, resolve_causal_skip_auto)

    th = CAUSAL_SKIP_AUTO_THRESHOLD
    assert resolve_causal_skip_auto(True, th) == "dma"
    assert resolve_causal_skip_auto(True, th * 4) == "dma"
    assert resolve_causal_skip_auto(True, th - 1) == "mxu"
    assert resolve_causal_skip_auto(False, th * 4) == "mxu"
    # and the default path stays exact where auto engages the jagged grid
    q, k, v = _rand_qkv(jax.random.key(25), (1, th, 1, 16))
    out = flash_self_attention(q, k, v, causal=True, interpret=True)
    ref = naive_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_extreme_logit_stability():
    """Scores ~±900 overflow exp() without running-max shifting — the
    online-softmax state must reproduce the (max-shifted) oracle, forward
    and backward, with no inf/nan anywhere."""
    q, k, v = _rand_qkv(jax.random.key(12), (1, 128, 1, 32))
    q, k = q * 30.0, k * 30.0
    out = flash_self_attention(q, k, v, block_q=64, block_k=64,
                               interpret=True)
    ref = naive_attention(q, k, v)
    assert np.isfinite(np.asarray(out)).all()
    # At near-one-hot softmax, the kernel's (q·k)·scale vs the oracle's
    # (q·scale)·k rounding can legitimately FLIP near-tied argmaxes (~1e-4
    # relative logit noise on |s|≈900), moving those rows by O(|v_a − v_b|)
    # — no fixed tolerance absorbs that. The claim under test is NO
    # OVERFLOW: everything finite, and all but a small near-tie fraction of
    # elements exactly tracking the oracle.
    diff = np.abs(np.asarray(out) - np.asarray(ref))
    assert (diff > 1e-3).mean() < 0.02, (diff > 1e-3).mean()
    g = jax.grad(lambda a, b, c: jnp.sum(flash_self_attention(
        a, b, c, block_q=64, block_k=64, interpret=True) ** 2),
        argnums=(0, 1, 2))(q, k, v)
    assert all(np.isfinite(np.asarray(x)).all() for x in g)


def test_wide_head_dim():
    """Head dim 256 (wider than one 128-lane register) — layout-sensitive
    in compiled Mosaic, shape-correct under the interpreter either way."""
    q, k, v = _rand_qkv(jax.random.key(13), (1, 128, 1, 256))
    out = flash_self_attention(q, k, v, causal=True, interpret=True)
    ref = naive_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_long_sequence_memory_shape():
    """T=1024 runs under the interpreter with only O(T·D) outputs — the
    (T, T) probs tensor is never part of any kernel output or residual."""
    q, k, v = _rand_qkv(jax.random.key(7), (1, 1024, 1, 32))
    out = flash_self_attention(q, k, v, causal=True, interpret=True)
    ref = naive_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_pad_to_block_plan():
    """The prime-length cliff plan (VERDICT r4 weak #4), divisor-aware
    (ADVICE r5), padding on the 64-multiple lattice (VERDICT r5 #8):
    padding is reserved for lengths with genuinely NO true divisor ≥ 64 —
    pick_block's halving loop only visits t/2^k, so even lengths with
    large ODD divisors (t=130 → 65, t=134 → 67) must keep their exact
    divisor — and when a pad IS taken it targets the next 64-multiple,
    not the next 128-multiple: the b ≥ 64 acceptance threshold already
    declares block-64 grids good, so 129 → 192/block-64 (1.49×), not
    256/block-128 (1.98×). The pad, when taken, is always < block,
    preserving the kernels' no-fully-masked-KV-block invariant."""
    from distributed_vgg_f_tpu.ops.flash_attention import pad_to_block

    assert pad_to_block(197) == (256, 128)   # prime: 256 = 4·64, block 128
    assert pad_to_block(394) == (448, 64)    # 2·197: 448/block-64, was 512
    assert pad_to_block(130) == (130, 65)    # halving says 2; 65 is exact
    assert pad_to_block(134) == (134, 67)    # halving says 2; 67 is exact
    assert pad_to_block(192) == (192, 64)    # decent divisor: untouched
    assert pad_to_block(195) == (195, 65)    # odd-divisor 65 ≥ 64: keep
    assert pad_to_block(97) == (97, 97)      # ≤128 is one block: no cliff
    assert pad_to_block(129) == (192, 64)    # 64-lattice, was 256/128
    assert pad_to_block(64) == (64, 64)
    assert pad_to_block(256) == (256, 128)
    for t in (197, 394, 129, 130, 134, 1034, 2051):
        t_pad, b = pad_to_block(t)
        assert b >= 64 or t_pad == t == b, (t, t_pad, b)
        assert t_pad % b == 0
        if t_pad != t:
            assert t_pad - t < b             # every KV block keeps real keys
    # the lattice guarantee, at every tested length INCLUDING the worst
    # case (129, the smallest padded length): pad overhead ≤ 1.5×
    for t in (64, 65, 97, 127, 128, 129, 130, 131, 134, 191, 192, 193,
              195, 197, 255, 256, 257, 383, 394, 449, 1034, 2051, 4099):
        t_pad, b = pad_to_block(t)
        assert t_pad / t <= 1.5, (t, t_pad, b)
        assert t_pad % b == 0 and t_pad >= t


@pytest.mark.parametrize("causal", [False, True])
def test_even_length_odd_divisor_exact_no_pad(causal):
    """t=130 regression (ADVICE r5): auto blocks must run the EXACT 65-token
    blocks (no internal padding — output and grads vs the oracle), where the
    halving-only plan used to pad 130 → 256/block-128, ~4× the score-matmul
    work."""
    from distributed_vgg_f_tpu.ops.flash_attention import pad_to_block

    assert pad_to_block(130) == (130, 65)
    q, k, v = _rand_qkv(jax.random.key(32), (1, 130, 2, 32))
    out = flash_self_attention(q, k, v, causal=causal, interpret=True)
    assert out.shape == q.shape
    ref = naive_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)

    cot = jax.random.normal(jax.random.key(33), q.shape)
    grads = jax.grad(lambda *a: jnp.vdot(flash_self_attention(
        *a, causal=causal, interpret=True), cot), argnums=(0, 1, 2))(q, k, v)
    ref_grads = jax.grad(lambda *a: jnp.vdot(naive_attention(
        *a, causal=causal), cot), argnums=(0, 1, 2))(q, k, v)
    for g, r, name in zip(grads, ref_grads, "qkv"):
        np.testing.assert_allclose(np.asarray(g), np.asarray(r),
                                   rtol=2e-4, atol=2e-4, err_msg=f"d{name}")


@pytest.mark.parametrize("causal", [False, True])
def test_prime_length_pads_not_block1(causal):
    """t=197 (prime) with auto blocks: internal pad to 256/block-128 — the
    block-1 grid the largest-divisor fallback used to produce is a severe
    TPU perf cliff (VERDICT r4 weak #4). Exact incl. grads vs the unpadded
    oracle; output shape is the caller's 197."""
    q, k, v = _rand_qkv(jax.random.key(30), (1, 197, 2, 32))
    cot = jax.random.normal(jax.random.key(31), q.shape)

    out = flash_self_attention(q, k, v, causal=causal, interpret=True)
    assert out.shape == q.shape
    ref = naive_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)

    def flash_loss(q, k, v):
        return jnp.vdot(flash_self_attention(q, k, v, causal=causal,
                                             interpret=True), cot)

    def naive_loss(q, k, v):
        return jnp.vdot(naive_attention(q, k, v, causal=causal), cot)

    grads = jax.grad(flash_loss, argnums=(0, 1, 2))(q, k, v)
    ref_grads = jax.grad(naive_loss, argnums=(0, 1, 2))(q, k, v)
    for g, r, name in zip(grads, ref_grads, "qkv"):
        np.testing.assert_allclose(np.asarray(g), np.asarray(r),
                                   rtol=2e-4, atol=2e-4, err_msg=f"d{name}")


# ---- grouped queries: fewer key/value heads than query heads ---------------

@pytest.mark.parametrize("skip", ["mxu", "dma"])
@pytest.mark.parametrize("group", [1, 4, 16])
def test_grouped_queries_match_naive_on_repeated_keys(group, skip):
    """16 query heads on 16 / group key heads (16, 4, 1), causal, on the
    rectangular grids and the jagged ones: the output and the three
    gradients against the naive oracle on keys and values repeated in
    memory, whose dK and dV summed over each group are what the kernel
    owes. Query head j reads key head j // group."""
    heads, t, d = 16, 128, 32
    kq, kk, kv, kc = jax.random.split(jax.random.key(11), 4)
    q = jax.random.normal(kq, (2, t, heads, d))
    k = jax.random.normal(kk, (2, t, heads // group, d))
    v = jax.random.normal(kv, (2, t, heads // group, d))
    cot = jax.random.normal(kc, q.shape)

    def flash_loss(q, k, v):
        out = flash_self_attention(q, k, v, causal=True, block_q=64,
                                   block_k=64, causal_skip=skip,
                                   interpret=True)
        return jnp.vdot(out, cot), out

    def naive_loss(q, k, v):
        out = naive_attention(q, jnp.repeat(k, group, axis=2),
                              jnp.repeat(v, group, axis=2), causal=True)
        return jnp.vdot(out, cot), out

    (_, out), grads = jax.value_and_grad(
        flash_loss, argnums=(0, 1, 2), has_aux=True)(q, k, v)
    (_, ref), ref_grads = jax.value_and_grad(
        naive_loss, argnums=(0, 1, 2), has_aux=True)(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
    for g, r, name in zip(grads, ref_grads, "qkv"):
        assert g.shape == r.shape, name
        np.testing.assert_allclose(np.asarray(g), np.asarray(r),
                                   rtol=2e-4, atol=2e-4, err_msg=f"d{name}")


def test_grouped_queries_refuse_heads_that_do_not_divide():
    q = jnp.zeros((1, 64, 6, 32))
    kv = jnp.zeros((1, 64, 4, 32))
    with pytest.raises(ValueError, match="shapes differ"):
        flash_self_attention(q, kv, kv, causal=True, interpret=True)
    with pytest.raises(ValueError, match="shapes differ"):
        flash_self_attention(q, kv, jnp.zeros((1, 64, 2, 32)), causal=True,
                             interpret=True)


# ---- two head sizes: queries and keys of one, values of another ------------

@pytest.mark.parametrize("skip", ["mxu", "dma"])
@pytest.mark.parametrize("group", [1, 4])
@pytest.mark.parametrize("sizes", [(192, 128), (48, 32), (32, 48)])
def test_two_head_sizes_match_explicit_scores(sizes, group, skip):
    """Latent attention's shape (models/ling3.py: 192-wide queries and keys
    on 128-wide values, one and a half lane tiles against one) and two
    sizes off the lanes, 8 query heads on 8 / group key heads, causal, on
    the rectangular grids and the jagged ones: the output, in the values'
    size, and the three gradients against the naive oracle. The scores are
    scaled by the queries' size."""
    d, dv = sizes
    heads, t = 8, 128
    kq, kk, kv, kc = jax.random.split(jax.random.key(13), 4)
    q = jax.random.normal(kq, (2, t, heads, d))
    k = jax.random.normal(kk, (2, t, heads // group, d))
    v = jax.random.normal(kv, (2, t, heads // group, dv))
    cot = jax.random.normal(kc, (2, t, heads, dv))

    def flash_loss(q, k, v):
        out = flash_self_attention(q, k, v, causal=True, block_q=64,
                                   block_k=64, causal_skip=skip,
                                   interpret=True)
        return jnp.vdot(out, cot), out

    def naive_loss(q, k, v):
        out = naive_attention(q, jnp.repeat(k, group, axis=2),
                              jnp.repeat(v, group, axis=2), causal=True)
        return jnp.vdot(out, cot), out

    (_, out), grads = jax.value_and_grad(
        flash_loss, argnums=(0, 1, 2), has_aux=True)(q, k, v)
    (_, ref), ref_grads = jax.value_and_grad(
        naive_loss, argnums=(0, 1, 2), has_aux=True)(q, k, v)
    assert out.shape == (2, t, heads, dv)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
    for g, r, name in zip(grads, ref_grads, "qkv"):
        assert g.shape == r.shape, name
        np.testing.assert_allclose(np.asarray(g), np.asarray(r),
                                   rtol=2e-4, atol=2e-4, err_msg=f"d{name}")


def test_two_head_sizes_pad_and_mask_like_one():
    """The internal pad-to-block path (a prime-ish length) with another
    value size, non-causal."""
    kq, kk, kv = jax.random.split(jax.random.key(17), 3)
    q = jax.random.normal(kq, (1, 197, 2, 24))
    k = jax.random.normal(kk, (1, 197, 2, 24))
    v = jax.random.normal(kv, (1, 197, 2, 16))
    out = flash_self_attention(q, k, v, interpret=True)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(naive_attention(q, k, v)),
                               rtol=2e-5, atol=2e-5)
    with pytest.raises(ValueError, match="shapes differ"):
        flash_self_attention(q, k[..., :16], v, interpret=True)
