"""`chipbench/hybrid_lm_counts.py`: pinned to a hand count at the published
widths and at the tiny preset's, and its scan to what `counts.jaxpr_ops`
finds in a chunked form written out product by product."""

import jax
import jax.numpy as jnp
import pytest

from chipbench import counts, hybrid_lm_counts
from distributed_vgg_f_tpu.config import NEMOTRON3_NANO_PUBLISHED, get_config

PUBLISHED = {**NEMOTRON3_NANO_PUBLISHED,
             "hybrid_override_pattern": "MEMEM*EME"}


def test_the_cell_s_step_by_hand():
    """A token, forward: a Mamba layer's projections 2 x 2688 x 10304 +
    2 x 4096 x 2688 and its scan 8 x 2 x 128 x 128 + 64 x (2 x 128 x 64 +
    4 x 64 x 128); an expert layer's router 2 x 2688 x 128, shared expert
    4 x 2688 x 3712 and 0.75 routed assignments (6 x 16 / 128) of
    4 x 2688 x 1856; the attention layer's projections and its causal core
    4 x 8192 x 32 x 128 / 2; the head 2 x 2688 x 16384. 747 MFLOP, three
    passes, 16,384 tokens: 36.7 TFLOP a step."""
    mamba = 2 * 2688 * 10304 + 2 * 4096 * 2688
    scan = 8 * 2 * 128 * 128 + 64 * (2 * 128 * 64 + 4 * 64 * 128)
    assert (mamba, scan) == (77_414_400, 3_407_872)
    experts = 2 * 2688 * 128 + 4 * 2688 * 3712 + 0.75 * 4 * 2688 * 1856
    attention = 2 * 2 * 2688 * 4096 + 2 * 2 * 2688 * 256 \
        + 4 * 8192 * 32 * 128 // 2
    head = 2 * 2688 * 16384
    token = 4 * (mamba + scan) + 4 * experts + attention + head
    assert round(token / 1e6) == 748
    ops = hybrid_lm_counts.step_ops(
        arch=PUBLISHED, layers=9, vocab_rows=16384, experts_held=16,
        seq_len=8192, rows=2, assignments_held=[12288] * 4)
    assert sum(op["flops"] for op in ops) == pytest.approx(
        3 * 16384 * token, rel=1e-12)
    # M: 3 entries, E: 5, *: 6, and the head; every one three times
    assert len(ops) == 3 * (4 * 3 + 4 * 5 + 6 + 1)
    grouped = [op for op in ops if op["kind"] == "grouped"]
    assert len(grouped) == 3 * 4 * 2            # TWO products a layer
    assert grouped[0]["elements"] == 16 * 2688 * 1856 \
        + 12288 * (2688 + 1856)
    core = [op for op in ops if op["kind"] == "attention"]
    assert len(core) == 3 * 2
    # the keys' and values' bytes by the 2 heads there are, not by 32
    assert core[0]["elements"] == 2 * 8192 * 128 * (32 + 2)
    scans = [op for op in ops if op["kind"] == "scan"]
    assert len(scans) == 3 * 4
    assert scans[0]["elements"] == 16384 * (2 * 4096 + 2 * 1024 + 64)


@pytest.mark.parametrize("held", [0, 16384 * 6])
def test_routed_work_follows_the_assignments_held(held):
    ops = hybrid_lm_counts.expert_ops(PUBLISHED, 16, held)
    assert len(ops) == 2
    assert sum(op["flops"] for op in ops) == held * 4 * 2688 * 1856
    assert all(op["elements"] >= 16 * 2688 * 1856 for op in ops)


def test_the_tiny_step_by_hand():
    """`nemotron3_nano_tiny` (`MEM*E`, hidden 64, two sequences of 32,
    chunks of 8), experts 2-5 of 8 held with 40 and 50 assignments."""
    cfg = get_config("nemotron3_nano_tiny")
    arch, tokens = dict(cfg.model.extra), 2 * 32
    inner, state, heads, dim, groups, chunk = 32, 16, 4, 8, 2, 8
    mamba = tokens * (2 * 64 * (2 * inner + 2 * groups * state + heads)
                      + 2 * inner * 64)
    scan = tokens * (groups * 2 * chunk * state
                     + heads * (2 * chunk * dim + 4 * dim * state))
    shared = tokens * 4 * 64 * 48
    router = tokens * 2 * 64 * 8
    routed = (40 + 50) * 4 * 64 * 32
    attention = tokens * (2 * 2 * 64 * 64 + 2 * 2 * 64 * 32) \
        + 2 * (4 * 32 * 32 * 4 * 16 // 2)
    head = tokens * 2 * 64 * 256
    ops = hybrid_lm_counts.step_ops(
        arch=arch, layers=5, vocab_rows=256, experts_held=4, seq_len=32,
        rows=2, assignments_held=[40, 50])
    assert sum(op["flops"] for op in ops) == 3 * (
        2 * (mamba + scan) + 2 * (shared + router) + routed + attention
        + head)
    with pytest.raises(ValueError):
        hybrid_lm_counts.step_ops(
            arch=arch, layers=5, vocab_rows=256, experts_held=4, seq_len=32,
            rows=2, assignments_held=[40])


def test_the_scan_s_count_is_the_chunked_form_s_products():
    """The four products of the chunked form, written out for one sequence
    of 4 chunks of 8 (2 groups, 4 heads of 8, state 16), as
    `counts.jaxpr_ops` counts them."""
    arch = dict(get_config("nemotron3_nano_tiny").model.extra)
    c, q, g, r, p, n = 4, 8, 2, 2, 8, 16

    def chunked(x, b_in, c_out, decay, entering):
        cb = jnp.einsum("cign,cjgn->cgij", c_out, b_in)
        y = jnp.einsum("cgrij,cjgrp->cigrp", cb[:, :, None] * decay, x)
        own = jnp.einsum("cjgn,cjgrp->cgrpn", b_in, x)
        return y + jnp.einsum("cign,cgrpn->cigrp", c_out, entering), own

    f = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32)
    traced = counts.jaxpr_ops(chunked, f(c, q, g, r, p), f(c, q, g, n),
                              f(c, q, g, n), f(c, g, r, q, q),
                              f(c, g, r, p, n))
    mine = hybrid_lm_counts.scan_ops(arch, 32, 1)
    assert len(traced) == 4 and len(mine) == 1
    assert mine[0]["flops"] == sum(op["flops"] for op in traced)
