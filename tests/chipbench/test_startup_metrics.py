"""The six `setup_*` readers (`chipbench/layer_metrics/_startup.py`): each
on a hand-made ring gives the hand-computed number, None wherever the ring
cannot say, and the benchmark's copy of the span names is the program's."""

import importlib
import json
import os

import pytest

from _tiny import ROOT
from chipbench.layer_metrics import _startup
from distributed_vgg_f_tpu import scopes, telemetry

MS = 1_000_000
START_NS = 5 * MS      # the process started 5 ms into the ring's clock
MAIN, OTHER = 1, 2     # thread ids


def _span(name, category, start_ms, end_ms, tid=MAIN):
    return (name, category, START_NS + start_ms * MS,
            (end_ms - start_ms) * MS, tid)


#: One set-up, by hand. The cut is the fourth dispatch's start, 11,000 ms
#: after the process's.
RING = [
    _span("import_trainer", "startup", 1000, 3000),
    # `Trainer.__init__` 2,000 ms, of which a program 500 ms on its thread
    _span("distributed_init", "startup", 3000, 3100),
    _span("build_model", "startup", 3100, 3200),
    _span("build_optimizer", "startup", 3200, 3300),
    _span("plan_exchange", "startup", 3300, 3400),
    _span("lower:jit(a)", "compile", 3500, 3700),
    _span("backend:jit(a)", "compile", 3700, 4000),
    # another thread's trace inside the interval is not the trainer's
    _span("trace:x", "compile", 4100, 4200, OTHER),
    _span("build_steps", "startup", 4300, 4400),
    _span("trainer_init", "startup", 3000, 5000),
    # `init_state` 2,000 ms, of which 900 ms traced (nested), lowered, read
    _span("trace:inner", "compile", 5200, 5300),
    _span("trace:init_fn", "compile", 5100, 5600),
    _span("lower:jit(init_fn)", "compile", 5600, 5700),
    _span("cache_read:jit(init_fn)", "compile", 5700, 6000),
    _span("init_state", "startup", 5000, 7000),
    # 1,000 ms nothing names, then the first step with its compile
    _span("trace:train_step", "compile", 8000, 8500),
    _span("lower:jit(train_step)", "compile", 8500, 8600),
    _span("backend:jit(train_step)", "compile", 8600, 9900),
    _span("train_step_dispatch", "dispatch", 8000, 10000),
    _span("train_step_dispatch", "dispatch", 10000, 10010),
    _span("train_step_dispatch", "dispatch", 10020, 10030),
    # a lowering that straddles the cut counts up to it
    _span("lower:jit(late)", "compile", 10900, 11100),
    _span("train_step_dispatch", "dispatch", 11000, 11010),
    # the window and the reference: after the cut, left out
    _span("train_step_dispatch", "dispatch", 11010, 11020),
    _span("trace:reference", "compile", 11500, 12000),
    _span("backend:jit(reference)", "compile", 12000, 13000),
    _span("eval_pass", "eval", 2000, 9000),   # no category of set-up
]

WANT = {
    "setup_import_s": 2.0,
    # (2000 - 500) + (2000 - 900)
    "setup_trainer_init_s": 2.6,
    # lower a 200, trace x 100, trace init_fn 500 (inner inside it), lower
    # 100, trace train_step 500, lower 100, late 100
    "setup_trace_lower_s": 1.6,
    # backend a 300, cache read 300, backend train_step 1300
    "setup_backend_s": 1.9,
    "setup_programs_compiled": 2,
    # 11000 less import 2000, init 2000, state 2000, dispatches 2000 + 10
    # + 10, late 100 (trace x lies inside the init)
    "setup_unspanned_s": 2.88,
}


def test_phases_of_a_hand_made_ring():
    got = _startup.phases(RING, START_NS, 3)
    assert set(got) == set(_startup.METRICS) == set(WANT)
    for name, want in WANT.items():
        assert got[name] == pytest.approx(want, abs=1e-9), name
    # the phases and what is left add up to the set-up, less the three
    # dispatches' own self time (2,020 ms less the 1,900 compiled in them);
    # the other thread's 100 ms ran beside the init and count in both
    total = sum(v for k, v in got.items() if k.endswith("_s"))
    assert total == pytest.approx(11.0 - 0.12 + 0.1, abs=1e-9)


def test_union_of_intervals():
    assert _startup.union_ns([]) == 0
    assert _startup.union_ns([(0, 10), (2, 4), (5, 12), (20, 21)]) == 13


@pytest.fixture
def ring():
    """The hand-made ring in the process's own recorder and registry."""
    telemetry.reset()
    telemetry.configure(enabled=True)
    for name, category, start_ns, dur_ns, _ in RING:
        telemetry.record(name, category, start_ns, dur_ns)
    telemetry.set_gauge("startup/process_start_ns", START_NS)
    yield telemetry.get_recorder()
    telemetry.configure(enabled=True, span_capacity=8192)
    telemetry.reset()


def _on_one_thread(want: dict) -> dict:
    """`telemetry.record` stamps the calling thread, so `trace:x` is the
    trainer's own here: 100 ms more of its init are a compile's."""
    return {**want, "setup_trainer_init_s": want["setup_trainer_init_s"] - .1}


@pytest.mark.parametrize("metric", _startup.METRICS)
def test_reader_gives_the_hand_computed_number(ring, metric):
    reader = importlib.import_module(f"chipbench.layer_metrics.{metric}")
    assert reader.read({}) == pytest.approx(_on_one_thread(WANT)[metric],
                                            abs=1e-9)


def _dropped(recorder):
    recorder.set_capacity(len(RING))
    telemetry.record("train_step_dispatch", "dispatch", 20_000 * MS, MS)
    assert recorder.dropped == 1


def _no_start_gauge(recorder):
    telemetry.get_registry().reset()


def _too_few_dispatches(recorder):
    kept = [s for s in recorder.snapshot() if s[1] != "dispatch"]
    recorder.clear()
    for name, category, start_ns, dur_ns, _ in kept:
        telemetry.record(name, category, start_ns, dur_ns)
    for i in range(3):      # the three checked steps and no window
        telemetry.record("train_step_dispatch", "dispatch",
                         START_NS + (8000 + i) * MS, MS)


def _telemetry_off(recorder):
    telemetry.configure(enabled=False)


def _no_startup_spans(recorder):
    """The parent of PR 36, given a start: dispatches and nothing else."""
    kept = [s for s in recorder.snapshot() if s[1] == "dispatch"]
    recorder.clear()
    for name, category, start_ns, dur_ns, _ in kept:
        telemetry.record(name, category, start_ns, dur_ns)


@pytest.mark.parametrize("spoil", [
    _dropped, _no_start_gauge, _too_few_dispatches, _telemetry_off,
    _no_startup_spans], ids=lambda f: f.__name__.strip("_"))
def test_every_reader_gives_none_where_the_ring_cannot_say(ring, spoil):
    spoil(ring)
    for metric in _startup.METRICS:
        reader = importlib.import_module(f"chipbench.layer_metrics.{metric}")
        assert reader.read({}) is None, metric


def test_an_unreadable_ring_is_none_and_no_exception(ring, monkeypatch,
                                                     capsys):
    monkeypatch.setattr(telemetry.get_recorder(), "snapshot",
                        lambda: [("short",)])
    assert _startup.of({}) is None
    assert "unreadable" in capsys.readouterr().err


def test_benchmarks_host_span_names_are_the_programs():
    """`host_spans.json` is the benchmark's own copy; this is the one place
    that holds it to the program's declared lists."""
    names = _startup.NAMES
    assert tuple(names["startup"]) == scopes.STARTUP_SPANS
    assert tuple(names["compile"]) == scopes.COMPILE_SPANS
    # the three the readers cut by are among them
    assert {"import_trainer", "trainer_init", "init_state"} \
        <= set(names["startup"])
    assert names["step_dispatch"] == ["dispatch", "train_step_dispatch"]


@pytest.mark.parametrize("metric", _startup.METRICS)
def test_entry_in_benchmark_json(metric):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = next(m for m in bench["per_layer"] if m["name"] == metric)
    assert entry["source"] == "program_counter"
    assert entry["moves"] == "setup_s" and entry["better"] == "lower"
    assert entry["layer"] in ("entry point", "train step")
    assert entry["workloads"] == [
        "vggf_b1024_step", "resnet50_b256_step",
        "mistral_small4_ep16_step", "nemotron3_nano_ep8_step"]
    # appended together, in their order, after the entries accepted before
    # them (the last of which is `ling_moe_pct`); later cells' metrics
    # come after them
    names = [m["name"] for m in bench["per_layer"]]
    first = names.index("ling_moe_pct") + 1
    assert names[first:first + len(_startup.METRICS)] \
        == list(_startup.METRICS)
