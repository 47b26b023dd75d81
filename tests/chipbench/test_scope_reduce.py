"""Device time by the program's names: the wire-format reader, the rule
from a name stack to a scope, and the reduction, pinned on two traces
recorded on the chip (before the program named its work, and after)."""

import json
import os

import pytest

from chipbench import scope_reduce as sr
from chipbench import trace_reduce as tr

DATA = os.path.join(os.path.dirname(__file__), "data")
BEFORE = os.path.join(DATA, "vggf_b1024_step_3steps.xplane.pb")
AFTER = os.path.join(DATA, "vggf_b1024_step_3steps_scoped.xplane.pb")
NAMES = sr.declared()


def _pct(table, scopes):
    return sr.share_pct(table, scopes)


def test_wire_reader_gives_what_profile_data_gives():
    """Names, starts and ends of the device's operations and of the
    driver's spans, read from the bytes, against `jax.profiler.ProfileData`
    (which cannot give the metadata's stats, the reason for the reader)."""
    ours, theirs = sr.load(BEFORE), tr.load(BEFORE)
    assert sorted(ours["devices"]) == sorted(theirs["devices"])
    for plane, events in theirs["devices"].items():
        mine = ours["devices"][plane]
        assert [e["name"] for e in mine] == [e["name"] for e in events]
        for a, b in zip(mine, events):      # theirs are whole nanoseconds
            assert a["start"] == pytest.approx(b["start"], abs=2.0)
            assert a["end"] == pytest.approx(b["end"], abs=2.0)
    spans = lambda t: sorted((s["name"], s["start"], s["end"])
                             for s in t["spans"]
                             if s["name"].startswith(tr.SPAN_PREFIX))
    assert len(spans(ours)) == len(spans(theirs)) > 0
    for a, b in zip(spans(ours), spans(theirs)):
        assert a[0] == b[0] and a[1:] == pytest.approx(b[1:], abs=2.0)
    first = ours["devices"][sorted(ours["devices"])[0]][0]
    assert first["category"]["hlo_category"] == "non-fusion elementwise"
    assert first["category"]["tf_op"] == \
        "jit(step_fn)/jvp(VGGF)/conv1/convert_element_type:"
    assert ours["modules"] == ["jit_step_fn"]


@pytest.mark.parametrize("tf_op, scope, backward", [
    ("jit(train_step)/jvp(VGGF)/conv2/conv_general_dilated:", "conv2", False),
    ("jit(train_step)/transpose(jvp(VGGF))/lrn1/dot_general:", "lrn1", True),
    ("jit(train_step)/jvp(VGGF)/cast_in/convert_element_type:", "cast_in",
     False),
    ("jit(train_step)/augment/flip/jit(_where)/select_n:", "flip", False),
    ("jit(train_step)/augment/mix/jit(_gamma)/while/body/closed_call/add:",
     "mix", False),
    ("jit(train_step)/augment/convert_element_type:", "augment", False),
    ("jit(train_step)/transpose(jvp(loss))/mul:", "loss", True),
    ("jit(train_step)/jvp(ResNet)/stage1_block1/bn1/reduce_sum:",
     "stage1_block1/bn1", False),
    ("jit(train_step)/transpose(jvp(ResNet))/pool_init/select_and_scatter:",
     "pool_init", True),
    ("jit(train_step)/jvp(VGGF)/jit(relu)/max:", "", False),
    ("jit(train_step)/transpose(jvp(VGGF))/broadcast_in_dim:", "", True),
    ("jit(step_fn)/jit(_where)/select_n:", "", False),
    ("jit(train_step)/mul:", "", False),
    ("jit(flip)/add:", "", False),      # a jitted function is no scope
    ("", "", False),
])
def test_scope_of_a_name_stack(tf_op, scope, backward):
    assert sr.scope_of(tf_op, NAMES) == (scope, backward)


def _op(tf_op, start, end):
    return {"name": "%x", "start": float(start), "end": float(end),
            "category": {"tf_op": tf_op, "hlo_category": "loop fusion"}}


def _span(name, start, end):
    return {"name": name, "start": float(start), "end": float(end),
            "category": {}}


def test_reduction_clips_to_the_window_and_gives_gaps_to_the_programs_spans():
    trace = {"devices": {"/device:TPU:0": [
        _op("jit(train_step)/augment/flip/select_n:", 0, 100_000),
        _op("jit(train_step)/jvp(VGGF)/conv2/conv:", 100_000, 300_000),
        _op("jit(train_step)/transpose(jvp(VGGF))/lrn1/dot:", 600_000,
            700_000),
        _op("", 700_000, 800_000)]},
        "spans": [_span("chipbench:traced_window", 50_000, 1_000_000),
                  _span("dvggf:infeed:next_batch", 290_000, 590_000),
                  _span("dvggf:dispatch:train_step_dispatch", 590_000,
                        600_000),
                  _span("chipbench:dispatch", 0, 1_000_000)],
        "modules": ["jit_train_step"]}
    out = sr.reduce(trace, NAMES)
    assert out["total_s"] == pytest.approx(450_000 / 1e9)   # flip cut in half
    assert out["scopes"]["flip"] == {"forward": pytest.approx(50e-6),
                                     "backward": 0.0}
    assert out["scopes"]["lrn1"]["backward"] == pytest.approx(100e-6)
    assert out["phases_found"] == ["flip"]
    assert sr.share_pct(out, ["flip", "lrn1"]) == pytest.approx(100 / 3)
    assert sr.share_pct(out, [sr.UNNAMED]) == pytest.approx(100 / 4.5)
    assert sum(sum(t.values()) for t in out["scopes"].values()) == \
        pytest.approx(out["total_s"])
    assert out["idle_gaps"] == [("infeed:next_batch", pytest.approx(300e-6)),
                                ("other", pytest.approx(200e-6))]


def test_recorded_trace_before_the_program_named_its_work():
    """Three steps of `vggf_b1024_step` as PR 25 recorded them: 193.0 ms of
    device self time, 38.7 % under a module's name, 39.7 % under the model
    with no layer, 6.0 % under `jit(_where)`, 5.4 % under the bare step,
    10.1 % with no name stack at all. No declared phase: the metrics'
    readers give None, never 0."""
    table = sr.reduce_dir(BEFORE)
    assert table["total_s"] == pytest.approx(0.19303, abs=1e-5)
    assert table["phases_found"] == [] and table["modules"] == ["jit_step_fn"]
    modules = ["conv1", "conv2", "conv3", "conv4", "conv5", "fc6", "fc7",
               "fc8"]
    assert _pct(table, modules) == pytest.approx(38.74, abs=0.02)
    assert _pct(table, ["conv2"]) == pytest.approx(15.53, abs=0.02)
    assert _pct(table, [sr.UNNAMED]) == pytest.approx(61.20, abs=0.02)
    stem = lambda *keys: 100 * sum(table["unnamed"].get(k, 0.0)
                                   for k in keys) / table["total_s"]
    assert stem("jit(step_fn)/jvp(VGGF)",
                "jit(step_fn)/transpose(jvp(VGGF))") == \
        pytest.approx(39.65, abs=0.05)
    assert stem("jit(step_fn)/jit(_where)") == pytest.approx(6.0, abs=0.05)
    assert stem("") == pytest.approx(10.1, abs=0.05)
    rest = 100 * sum(v for k, v in table["unnamed"].items()
                     if k.startswith("jit(step_fn)") and "VGGF" not in k
                     and "_where" not in k) / table["total_s"]
    assert rest == pytest.approx(5.4, abs=0.1)
    assert 100 * table["categories"]["reverse"] / table["total_s"] == \
        pytest.approx(7.4, abs=0.05)
    facts = {"trace_dir": BEFORE}
    from chipbench.layer_metrics import (lrn_pool_pct, prologue_pct,
                                         step_unnamed_pct)
    assert step_unnamed_pct.read(facts) is None
    assert prologue_pct.read(facts) is None
    assert lrn_pool_pct.read(facts) is None
    assert prologue_pct.read({"trace_dir": None}) is None


def test_recorded_trace_of_the_step_that_names_its_work():
    """The same cell from this PR's first traced chip run of the changed
    program, on a compile cache the parent had filled; cut to its first
    three steps on TPU:0 (the driver's `traced_window` span cut to end with
    the third, the long `source_stack` stat dropped). The same 193.0 ms,
    instruction for instruction the same program: what moved is what the
    time is called. Unnamed is what XLA made without a name stack (the
    `%reverse` it splits from the flip, a u8 copy, the pool's index
    arithmetic); a fusion is its root's, so the space-to-depth relayout
    reads `cast_in` and the optimiser's update reads `step_metrics` (fused
    into the non-finite guard's select)."""
    table = sr.reduce_dir(AFTER)
    assert table["modules"] == ["jit_train_step"]
    assert table["total_s"] == pytest.approx(0.19302, abs=1e-5)
    assert table["phases_found"] == [
        "augment", "cast_in", "finish_u8", "flip", "loss", "mix",
        "optimizer", "step_metrics"]
    for scopes, share in (
            (["conv1", "conv2", "conv3", "conv4", "conv5", "fc6", "fc7",
              "fc8"], 38.74),
            (["lrn1", "lrn2"], 24.37), (["pool1", "pool2", "pool5"], 6.80),
            (["cast_in"], 8.47), (["flip"], 3.70), (["mix"], 3.37),
            (["finish_u8"], 2.00), (["step_metrics"], 2.32),
            (["loss"], 0.05), (["optimizer", "augment", "exchange"], 0.01)):
        assert _pct(table, scopes) == pytest.approx(share, abs=0.02), scopes
    assert table["scopes"]["lrn1"]["backward"] == pytest.approx(0.015398,
                                                                abs=2e-6)
    assert table["scopes"]["flip"]["backward"] == 0.0
    named_but_no_scope = sum(v for k, v in table["unnamed"].items() if k)
    assert named_but_no_scope < 0.0005 * table["total_s"]
    assert sum(sum(t.values()) for t in table["scopes"].values()) == \
        pytest.approx(table["total_s"])
    assert table["idle_gaps"][0][0] == "dispatch:train_step_dispatch"
    facts = {"trace_dir": AFTER}
    from chipbench.layer_metrics import (lrn_pool_pct, prologue_pct,
                                         step_unnamed_pct)
    assert step_unnamed_pct.read(facts) == pytest.approx(10.12, abs=0.02)
    assert prologue_pct.read(facts) == pytest.approx(9.07, abs=0.02)
    assert lrn_pool_pct.read(facts) == pytest.approx(31.17, abs=0.02)
    # the outside-in reduction reads the cut trace as it read the first
    whole = tr.reduce_dir(AFTER)
    assert whole["window_s"] == pytest.approx(0.19373, abs=1e-5)
    assert whole["busy_s"] == pytest.approx(0.19302, abs=1e-5)


def test_benchmarks_names_are_the_programs():
    """`scopes.json` is the benchmark's own copy; this is the one place
    that holds it to the program's declared lists."""
    from distributed_vgg_f_tpu import scopes
    assert set(NAMES["phases"]) == set(scopes.PHASES)
    assert set(NAMES["layers"]) == set(scopes.LAYERS)
    known = set(NAMES["phases"]) | set(NAMES["layers"])
    assert set(NAMES["prologue"]) <= known and set(NAMES["lrn_pool"]) <= known
    with open(os.path.join(sr.ROOT, "BENCHMARK.json")) as f:
        entered = {m["name"]: m for m in json.load(f)["per_layer"]}
    for name in ("step_unnamed_pct", "prologue_pct", "lrn_pool_pct"):
        assert entered[name]["source"] == "device_trace"
        assert os.path.isfile(os.path.join(
            sr.ROOT, "chipbench", "layer_metrics", f"{name}.py"))
    assert entered["lrn_pool_pct"]["workloads"] == ["vggf_b1024_step"]
