"""`chipbench/ling_lm_counts.py`: pinned to a hand count at the published
widths and at the tiny preset's, its delta-rule core to what
`counts.jaxpr_ops` finds in a chunked form written out product by product,
and its rooflines under 100 at the chip's peak rates."""

import jax
import jax.numpy as jnp
import pytest

from chipbench import counts, ling_lm_counts
from distributed_vgg_f_tpu.config import LING3_FLASH_PUBLISHED, get_config

PUBLISHED = {**LING3_FLASH_PUBLISHED, "num_hidden_layers": 7,
             "first_k_dense_replace": 1,
             "hybrid_override_pattern": "DKKKKLK"}


def test_the_cell_s_step_by_hand():
    """A token, forward. A KDA layer: five projections of 2560 x 4096, two
    of 2560 x 32, and a head's core 4 x 64 x 128 (both scores) + 2 x 64^2 /
    3 (the solve) + 4 x 64 x 128 (W, U) + 6 x 128^2 (the state in, twice,
    and out) + 2 x 64 x 128 (the read-out). The latent layer: 2560 x 6144,
    2560 x 576, 512 x 8192, 4096 x 2560 and the causal core 8192 x 32 x
    (192 + 128). An expert layer: the router 2560 x 512, the shared
    expert's three of 2560 x 768 and 8 x 8 / 512 routed assignments of
    three 2560 x 768. The dense layer three of 2560 x 6144; the head 2560
    x 19648. 1.10 GFLOP, three passes, 16,384 tokens: 54.3 TFLOP a step."""
    kda = 2 * (5 * 2560 * 4096 + 2 * 2560 * 32)
    core = 32 * (4 * 64 * 128 + 2 * 64 * 64 / 3 + 4 * 64 * 128
                 + 6 * 128 * 128 + 2 * 64 * 128)
    assert kda == 105_185_280 and round(core) == 5_854_549
    latent = 2 * (2560 * 6144 + 2560 * 576 + 512 * 8192 + 4096 * 2560) \
        + 2 * 8192 * 32 * (192 + 128) // 2
    experts = 2 * 2560 * 512 + 6 * 2560 * 768 + 0.125 * 6 * 2560 * 768
    dense = 6 * 2560 * 6144
    head = 2 * 2560 * 19648
    token = 6 * (kda + core) + latent + 6 * experts + dense + head
    assert round(token / 1e6) == 1104
    ops = ling_lm_counts.step_ops(
        arch=PUBLISHED, layers=7, vocab_rows=19648, experts_held=8,
        seq_len=8192, rows=2, assignments_held=[2048] * 6)
    assert sum(op["flops"] for op in ops) == pytest.approx(
        3 * 16384 * token, rel=1e-12)
    # KDA: 8 entries, latent: 6, experts: 7, dense: 3, and the head
    assert len(ops) == 3 * (6 * 8 + 6 + 6 * 7 + 3 + 1)
    grouped = [op for op in ops if op["kind"] == "grouped"]
    assert len(grouped) == 3 * 6 * 3            # three products a layer
    assert grouped[0]["elements"] == 8 * 2560 * 768 + 2048 * (2560 + 768)
    attention = [op for op in ops if op["kind"] == "attention"]
    assert len(attention) == 3 * 2
    # the true 192 on the scores' side, 128 on the values'
    assert attention[0]["flops"] / attention[3]["flops"] == 1.5
    scans = [op for op in ops if op["kind"] == "scan"]
    assert len(scans) == 3 * 6
    assert scans[0]["elements"] == 16384 * 32 * (6 * 128 + 2)


def test_the_rooflines_stay_under_100_at_the_peak_rates():
    """A step's least time by each reader's own operations and bytes is a
    time the chip could not beat: at the peak rates every kernel's least
    time is under the whole step's, and the delta rule's is bound by its
    bytes."""
    lm = {"arch": PUBLISHED, "layers": 7, "vocab_rows": 19648,
          "experts_held": 8, "seq_len": 8192, "rows": 2,
          "assignments_held": [2048.0] * 6}
    peaks = counts.peaks("TPU v5 lite")
    least = lambda ops: counts.roofline_seconds(ops, peaks)["seconds"]
    step = least(ling_lm_counts.step_ops(**lm))
    core = ling_lm_counts.kda_core_step_ops(lm)
    by_bytes = 2 * sum(op["elements"] for op in core) / 819e9
    by_flops = sum(op["flops"] for op in core) / 197e12
    assert by_bytes > by_flops
    assert least(core) == pytest.approx(by_bytes, rel=1e-6)
    for ops in (core, ling_lm_counts.attention_core_step_ops(lm),
                ling_lm_counts.expert_step_ops(lm)):
        assert 0 < least(ops) < step
    # the whole step at the chip's peak: 0.28 s
    assert 0.2 < sum(op["flops"] for op in ling_lm_counts.step_ops(**lm)) \
        / 197e12 < 0.3


@pytest.mark.parametrize("held", [0, 16384 * 8])
def test_routed_work_follows_the_assignments_held(held):
    ops = ling_lm_counts.expert_ops(PUBLISHED, 8, held)
    assert len(ops) == 3
    assert sum(op["flops"] for op in ops) == held * 6 * 2560 * 768
    assert all(op["elements"] >= 8 * 2560 * 768 for op in ops)


def test_the_tiny_step_by_hand():
    """`ling3_flash_tiny` (`DKKKKLK`, hidden 64, two sequences of 128,
    chunks of 64), experts 4-7 of 16 held with 40 to 90 assignments."""
    arch, tokens = dict(get_config("ling3_flash_tiny").model.extra), 2 * 128
    loads = [40, 50, 60, 70, 80, 90]
    kda = tokens * 2 * (5 * 64 * 64 + 2 * 64 * 4)
    core = tokens * 4 * (4 * 64 * 16 + 2 * 64 * 64 / 3 + 4 * 64 * 16
                         + 6 * 16 * 16 + 2 * 64 * 16)
    latent = tokens * 2 * (64 * 4 * 24 + 64 * 24 + 16 * 4 * 32 + 64 * 64) \
        + 2 * (2 * 128 * 128 * 4 * (24 + 16) // 2)
    shared = tokens * 6 * 64 * 32
    router = tokens * 2 * 64 * 16
    routed = sum(loads) * 6 * 64 * 32
    dense = tokens * 6 * 64 * 96
    head = tokens * 2 * 64 * 256
    ops = ling_lm_counts.step_ops(
        arch=arch, layers=7, vocab_rows=256, experts_held=4, seq_len=128,
        rows=2, assignments_held=loads)
    assert sum(op["flops"] for op in ops) == pytest.approx(3 * (
        6 * (kda + core) + latent + 6 * (shared + router) + routed + dense
        + head), rel=1e-12)
    with pytest.raises(ValueError):
        ling_lm_counts.step_ops(
            arch=arch, layers=7, vocab_rows=256, experts_held=4,
            seq_len=128, rows=2, assignments_held=loads[:5])


def test_the_core_s_count_is_the_chunked_form_s_products():
    """The products of the chunked WY form, written out for one sequence
    of 2 chunks of 64 (4 heads of 16), as `counts.jaxpr_ops` counts them;
    the solve, which is no product of the trace's, by C^3 / 3."""
    arch = dict(get_config("ling3_flash_tiny").model.extra)
    n, c, h, d = 2, 64, 4, 16

    def chunked(q, k, v, T, S):
        kk = jnp.einsum("nhid,nhjd->nhij", k, k)
        qk = jnp.einsum("nhid,nhjd->nhij", q, k)
        W = jnp.einsum("nhij,nhjd->nhid", T, k)
        U = jnp.einsum("nhij,nhjd->nhid", T, v)
        U = U - jnp.einsum("nhik,nhkv->nhiv", W, S)
        o = jnp.einsum("nhik,nhkv->nhiv", q, S) \
            + jnp.einsum("nhij,nhjv->nhiv", qk, U)
        return o, kk, jnp.einsum("nhjk,nhjv->nhkv", k, U)

    f = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32)
    traced = counts.jaxpr_ops(chunked, f(n, h, c, d), f(n, h, c, d),
                              f(n, h, c, d), f(n, h, c, c), f(n, h, d, d))
    mine = ling_lm_counts.kda_core_ops(arch, 128, 1)
    assert len(traced) == 8 and len(mine) == 1
    solve = n * h * 2 * c ** 3 / 3
    assert mine[0]["flops"] == pytest.approx(
        sum(op["flops"] for op in traced) + solve, rel=1e-12)
