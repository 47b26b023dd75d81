"""`chipbench/jamba_lm_counts.py`: pinned to a hand count at the published
widths and at the tiny preset's, the selective scan's operations and bytes
by hand, and its roofline under 100 at the chip's peak rates, bound by its
bytes."""

import pytest

from chipbench import counts, jamba_lm_counts
from distributed_vgg_f_tpu.config import JAMBA2_3B_PUBLISHED, get_config

PUBLISHED = {**JAMBA2_3B_PUBLISHED, "num_hidden_layers": 14,
             "hybrid_override_pattern": "MMMMMMMAMMMMMM"}


def _lm(**kw):
    return {"arch": PUBLISHED, "layers": 14, "vocab_rows": 8192,
            "experts_held": 0, "seq_len": 4096, "rows": 1,
            "assignments_held": [], **kw}


def test_the_cell_s_step_by_hand():
    """A token, forward. A Mamba layer: in_proj 2560 x 10240, x_proj 5120 x
    192, dt_proj 160 x 5120, out_proj 5120 x 2560. The attention layer: q
    and o 2560 x 2560, k and v 2560 x 128, the causal core 4096 x 20 x 128
    twice. Every layer's MLP three of 2560 x 8192. The tied head 2560 x
    8192, once. 2.92 GFLOP, three passes, 4,096 tokens: 35.9 TFLOP a
    step."""
    mamba = 2 * (2560 * 10240 + 5120 * 192 + 160 * 5120 + 5120 * 2560)
    attention = 2 * (2 * 2560 * 2560 + 2 * 2560 * 128) \
        + 2 * 4096 * 20 * 128
    mlp = 2 * 3 * 2560 * 8192
    head = 2 * 2560 * 8192
    token = 13 * mamba + attention + 14 * mlp + head
    assert round(token / 1e7) == 292
    ops = jamba_lm_counts.step_ops(**_lm())
    assert sum(op["flops"] for op in ops) == pytest.approx(
        3 * 4096 * token, rel=1e-12)
    # a Mamba layer: 4 + 3 entries, the attention layer 6 + 3, the head
    assert len(ops) == 3 * (13 * 7 + 9 + 1)
    attention_ops = [op for op in ops if op["kind"] == "attention"]
    assert len(attention_ops) == 3 * 2
    assert attention_ops[0]["elements"] == 4096 * 128 * (20 + 1)
    # the scan is no matrix product: not among the step's products
    assert not [op for op in ops if op["kind"] == "scan"]
    with pytest.raises(ValueError):
        jamba_lm_counts.step_ops(**_lm(layers=13))
    with pytest.raises(ValueError):
        jamba_lm_counts.step_ops(**_lm(experts_held=8))


def test_the_scan_by_hand():
    """One pass of one layer: 7 operations a position, channel and state;
    x (2 bytes), dt and y (4 each) a position and channel, B and C (2 each)
    a position and state, and the float32 state entering each of the 32
    chunks of 128; thirteen layers, three passes each."""
    (op,) = jamba_lm_counts.scan_ops(PUBLISHED, 4096, 1)
    assert op["kind"] == "scan"
    assert op["flops"] == 7 * 4096 * 5120 * 16
    moved = 4096 * (5120 * 10 + 2 * 16 * 2) + 32 * 16 * 5120 * 4
    assert op["elements"] == moved / 2
    assert jamba_lm_counts.scan_step_ops(_lm()) == [op] * (13 * 3)


def test_the_roofline_stays_under_100_at_the_peak_rates():
    """The scan's least time is its bytes' (10.5 ms a step), under the
    whole step's least time, which is 0.18 s at the chip's peak."""
    lm = _lm()
    peaks = counts.peaks("TPU v5 lite")
    least = lambda ops: counts.roofline_seconds(ops, peaks)
    scan = least(jamba_lm_counts.scan_step_ops(lm))
    assert scan["compute_bound_s"] == 0
    assert scan["seconds"] == pytest.approx(0.0105, rel=0.01)
    step = least(jamba_lm_counts.step_ops(**lm))["seconds"]
    assert 0 < scan["seconds"] < step
    assert 0.17 < sum(op["flops"] for op in jamba_lm_counts.step_ops(**lm)) \
        / 197e12 < 0.19


def test_the_tiny_step_by_hand():
    """`jamba2_3b_tiny` (`MMAM`, hidden 32, 64 Mamba channels, dt rank 8,
    state 16, 2 heads of 16 on 1, an MLP of 64, vocabulary 256), two
    sequences of 256."""
    arch, tokens = dict(get_config("jamba2_3b_tiny").model.extra), 2 * 256
    mamba = tokens * 2 * (32 * 128 + 64 * 40 + 8 * 64 + 64 * 32)
    attention = tokens * 2 * (2 * 32 * 32 + 2 * 32 * 16) \
        + 2 * 2 * (2 * 256 * 256 * 2 * 16 // 2)
    mlp = tokens * 2 * 3 * 32 * 64
    head = tokens * 2 * 32 * 256
    ops = jamba_lm_counts.step_ops(
        arch=arch, layers=4, vocab_rows=256, experts_held=0, seq_len=256,
        rows=2, assignments_held=[])
    assert sum(op["flops"] for op in ops) == pytest.approx(
        3 * (3 * mamba + attention + 4 * mlp + head), rel=1e-12)
