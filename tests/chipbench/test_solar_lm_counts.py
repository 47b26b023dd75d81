"""`chipbench/solar_lm_counts.py`: pinned to a hand count at the published
widths and at the tiny preset's, its delta-rule core to Ling's count at
the linear layers' heads, and its rooflines under 100 at the chip's peak
rates."""

import pytest

from chipbench import counts, ling_lm_counts, solar_lm_counts
from distributed_vgg_f_tpu.config import SOLAR_OPEN2_PUBLISHED, get_config

PUBLISHED = {**SOLAR_OPEN2_PUBLISHED, "num_hidden_layers": 4,
             "hybrid_override_pattern": "GKKK"}


def _lm(**kw):
    return {"arch": PUBLISHED, "layers": 4, "vocab_rows": 24576,
            "experts_held": 8, "seq_len": 4096, "rows": 1,
            "assignments_held": [102.4] * 4, **kw}


def test_the_cell_s_step_by_hand():
    """A token, forward. A KDA layer: q, k, v 4096 x 8192, the two gates'
    4096 x 128 and 128 x 8192 each, beta 4096 x 64, o 8192 x 4096, and 64
    heads' core 4 x 64 x 128 (both scores) + 2 x 64^2 / 3 (the solve) + 4
    x 64 x 128 (W, U) + 6 x 128^2 (the state in, twice, and out) + 2 x 64
    x 128 (the read-out). The GQA layer: q and the gate 4096 x 8192, k and
    v 4096 x 1024, o 8192 x 4096, the causal core 4096 x 64 x 128 twice.
    An expert layer: the router 4096 x 320, the shared expert's three of
    4096 x 1280 and 8 x 8 / 320 routed assignments of three 4096 x 1280.
    The head 4096 x 24576. 1.51 GFLOP, three passes, 4,096 tokens: 18.5
    TFLOP a step."""
    kda = 2 * (3 * 4096 * 8192 + 2 * (4096 * 128 + 128 * 8192)
               + 4096 * 64 + 8192 * 4096)
    core = 64 * (4 * 64 * 128 + 2 * 64 * 64 / 3 + 4 * 64 * 128
                 + 6 * 128 * 128 + 2 * 64 * 128)
    gqa = 2 * (2 * 4096 * 8192 + 2 * 4096 * 1024 + 8192 * 4096) \
        + 2 * 4096 * 64 * 128
    experts = 2 * 4096 * 320 + 6 * 4096 * 1280 + 0.2 * 6 * 4096 * 1280
    head = 2 * 4096 * 24576
    token = 3 * (kda + core) + gqa + 4 * experts + head
    assert round(token / 1e7) == 151
    ops = solar_lm_counts.step_ops(**_lm(assignments_held=[4096 * 0.2] * 4))
    assert sum(op["flops"] for op in ops) == pytest.approx(
        3 * 4096 * token, rel=1e-12)
    # KDA: 10 entries, GQA: 7, experts: 7 a layer, and the head
    assert len(ops) == 3 * (3 * 10 + 7 + 4 * 7 + 1)
    grouped = [op for op in ops if op["kind"] == "grouped"]
    assert len(grouped) == 3 * 4 * 3
    attention = [op for op in ops if op["kind"] == "attention"]
    assert len(attention) == 3 * 2
    assert attention[0]["elements"] == 4096 * 128 * (64 + 8)
    scans = [op for op in ops if op["kind"] == "scan"]
    assert len(scans) == 3 * 3
    assert scans[0]["elements"] == 4096 * 64 * (6 * 128 + 2)


def test_the_core_is_ling_s_count_at_the_linear_heads():
    """The recurrence's work is the chunked form's whatever implements it:
    Ling's count, at `linear_attn_config`'s heads and head size."""
    mine = solar_lm_counts.kda_core_ops(PUBLISHED, 4096, 1)
    assert mine == ling_lm_counts.kda_core_ops(
        {"num_attention_heads": 64, "head_dim": 128}, 4096, 1)
    assert len(solar_lm_counts.kda_core_step_ops(_lm())) == 3 * 3
    assert len(solar_lm_counts.attention_core_step_ops(_lm())) == 3 * 2


def test_the_rooflines_stay_under_100_at_the_peak_rates():
    """A step's least time by each reader's own operations and bytes is a
    time the chip could not beat: each kernel's least time is under the
    whole step's, and the delta rule's is bound by its bytes."""
    lm = _lm()
    peaks = counts.peaks("TPU v5 lite")
    least = lambda ops: counts.roofline_seconds(ops, peaks)["seconds"]
    step = least(solar_lm_counts.step_ops(**lm))
    core = solar_lm_counts.kda_core_step_ops(lm)
    by_bytes = 2 * sum(op["elements"] for op in core) / 819e9
    assert by_bytes > sum(op["flops"] for op in core) / 197e12
    assert least(core) == pytest.approx(by_bytes, rel=1e-6)
    for ops in (core, solar_lm_counts.attention_core_step_ops(lm)):
        assert 0 < least(ops) < step
    # the whole step at the chip's peak: 0.094 s
    assert 0.09 < sum(op["flops"] for op in solar_lm_counts.step_ops(**lm)) \
        / 197e12 < 0.13


def test_the_tiny_step_by_hand():
    """`solar_open2_tiny` (`GKKK`, hidden 64, two sequences of 128, 4
    delta-rule heads of 16 through a rank of 16), experts 4-7 of 16 held
    with 40 to 70 assignments."""
    arch, tokens = dict(get_config("solar_open2_tiny").model.extra), 2 * 128
    loads = [40, 50, 60, 70]
    kda = tokens * 2 * (3 * 64 * 64 + 2 * (64 * 16 + 16 * 64) + 64 * 4
                        + 64 * 64)
    core = tokens * 4 * (4 * 64 * 16 + 2 * 64 * 64 / 3 + 4 * 64 * 16
                         + 6 * 16 * 16 + 2 * 64 * 16)
    gqa = tokens * 2 * (64 * 64 + 2 * 64 * 32 + 64 * 64 + 64 * 64) \
        + 2 * 2 * (2 * 128 * 128 * 4 * 16 // 2)
    expert = tokens * (6 * 64 * 32 + 2 * 64 * 16)
    routed = sum(loads) * 6 * 64 * 32
    head = tokens * 2 * 64 * 256
    ops = solar_lm_counts.step_ops(
        arch=arch, layers=4, vocab_rows=256, experts_held=4, seq_len=128,
        rows=2, assignments_held=loads)
    assert sum(op["flops"] for op in ops) == pytest.approx(3 * (
        3 * (kda + core) + gqa + 4 * expert + routed + head), rel=1e-12)
    with pytest.raises(ValueError):
        solar_lm_counts.step_ops(
            arch=arch, layers=4, vocab_rows=256, experts_held=4,
            seq_len=128, rows=2, assignments_held=loads[:3])
