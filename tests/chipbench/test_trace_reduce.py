"""The reduction from a trace to busy time, operations and gaps."""

from chipbench import trace_reduce as tr


def _ev(name, start, end, category=""):
    return {"name": name, "start": float(start), "end": float(end),
            "category": category, "stats": {}}


def test_union_of_two_overlapping_and_one_disjoint_interval():
    spans = [(0, 10), (5, 20), (30, 40)]
    assert tr.union_ns(spans) == 30
    assert tr.gaps_ns(spans) == [(20, 30)]
    assert tr.union_ns([]) == 0


def test_self_time_leaves_out_nested_events():
    events = [_ev("while", 0, 100), _ev("conv", 10, 40, "convolution"),
              _ev("add", 50, 60), _ev("copy", 120, 130)]
    assert sorted(tr.self_times(events)) == sorted([
        ("while", "", 60.0), ("conv", "convolution", 30.0),
        ("add", "", 10.0), ("copy", "", 10.0)])


def test_gaps_go_to_the_span_that_covers_them():
    trace = {"devices": {"/device:TPU:0": [
        _ev("fusion.1", 0, 100_000, "convolution"),
        _ev("fusion.2", 400_000, 500_000, "loop fusion"),
        _ev("fusion.1", 510_000, 600_000, "convolution")]},
        "spans": [_ev("chipbench:waiting_for_batch", 90_000, 390_000),
                  _ev("chipbench:dispatch", 390_000, 400_000)]}
    out = tr.reduce(trace)
    assert out["window_s"] == 600_000 / 1e9
    assert out["busy_s"] == 290_000 / 1e9
    assert out["device_ops"][0] == ["fusion.1", 190_000 / 1e9]
    assert out["idle_gaps"] == [["waiting_for_batch", 300_000 / 1e9],
                                ["other", 10_000 / 1e9]]
    assert out["categories"]["convolution"] == 190_000 / 1e9


def test_the_drivers_span_is_the_window():
    """With the driver's `traced_window` span in the trace, the window is
    that span: the wait before the first operation and after the last one
    is idle time too, and the span itself covers no gap."""
    trace = {"devices": {"/device:TPU:0": [
        _ev("fusion.1", 100_000, 200_000), _ev("fusion.2", 200_000, 900_000)]},
        "spans": [_ev("chipbench:traced_window", 0, 1_000_000),
                  _ev("chipbench:dispatch", 0, 90_000),
                  _ev("chipbench:device_ahead", 850_000, 1_000_000)]}
    out = tr.reduce(trace)
    assert out["window_s"] == 1_000_000 / 1e9
    assert out["busy_s"] == 800_000 / 1e9
    assert out["idle_gaps"] == [["dispatch", 100_000 / 1e9],
                                ["device_ahead", 100_000 / 1e9]]


def test_hlo_text_is_shortened_and_classified():
    text = ("%fusion.512 = (bf16[64]{0:T(256)}, bf16[1024,54,54,64]{0,3,2,1})"
            " fusion(bf16[1024,54,54,64]{0,3,2,1} %select-and-scatter.2), "
            "kind=kOutput, calls=%fused_computation.1122")
    assert tr.short_name(text) == \
        "%fusion.512 fusion/kOutput bf16[1024,54,54,64]"
    assert tr.category(text) == "matmul"
    loop = "%add.3 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop, calls=%c"
    assert tr.category(loop) == "fusion/kLoop"
    assert tr.category("%reverse = f32[2,3]{1,0} reverse(f32[2,3]{1,0} %b), "
                       "dimensions={1}") == "reverse"
    assert tr.category("$train.py:244 one_step") == ""
    assert tr.short_name("PjitFunction(step)") == "PjitFunction(step)"


def test_recorded_chip_trace_gives_what_was_read_off_it_by_hand():
    """Three steps of `vggf_b1024_step` on a TPU v5e (this PR's first traced
    chip run, cut to the first three steps, the driver's own spans kept).
    Read off by hand: three steps of 64.36 ms back to back, so a window of
    193.1 ms with 60 us of it idle; the LRN-backward output fusion on top
    with 5.13 ms a step; the only gaps over 2 us lie under the driver's
    `device_ahead` wait."""
    import os
    path = os.path.join(os.path.dirname(__file__), "data",
                        "vggf_b1024_step_3steps.xplane.pb")
    out = tr.reduce_dir(path)
    assert out["devices"] == 1
    assert abs(out["window_s"] - 0.193095) < 1e-6
    assert abs(out["busy_s"] - 0.193035) < 1e-6
    idle_pct = 100 * (1 - out["busy_s"] / out["window_s"])
    assert 0.02 < idle_pct < 0.05
    name, seconds = out["device_ops"][0]
    assert name == "%fusion.512 fusion/kOutput bf16[1024,54,54,64]"
    assert abs(seconds - 0.015402) < 1e-6
    assert out["device_ops"][1][0].startswith("%reverse reverse f32[1024,224")
    assert abs(out["categories"]["matmul"] - 0.125995) < 1e-6
    assert out["idle_gaps"][0][0] == "device_ahead"
    assert abs(out["idle_gaps"][0][1] - 42.3e-6) < 1e-6
