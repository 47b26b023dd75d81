"""What the six `setup_*` readers share: set-up by phase, read from the
program's own telemetry ring in the run's own process.

The program (PR 36) records what it does before its first timed step as
host spans on one clock (`time.monotonic_ns`): `startup:*` around the
trainer module's imports, `Trainer.__init__` with its children and
`Trainer.init_state`; `compile:<stage>:<fun>` for every trace of 1 ms and
more, every lowering, and every backend compile or cache read that JAX
reports; `dispatch:train_step_dispatch` around every call of the step. The
names are `host_spans.json`'s. Set-up starts where the gauge
`startup/process_start_ns` says the process did, and ends at the **cut**:
the start of the `(CHECK_STEPS + 1)`-th step dispatch, the window's first
step, which every driver dispatches right after it takes `setup_s`.

Spans nest and overlap (`trace:inner` lies inside `trace:outer`, a compile
inside `init_state`), so every total here is the length of a union of
intervals clipped at the cut, never a sum of durations.

`of(facts)` gives None, and with it every reader, where the answer would
be a guess: telemetry is off, the ring has dropped a span (its oldest, so
the set-up's), the start gauge or the cut is missing, or a `startup` span
that must be there is not (the parent of PR 36 has none). Never 0, and no
exception.

By hand, for a cell whose `BENCHMARK.json` entry cannot list the metrics
yet: `python3 chipbench/layer_metrics/_startup.py --workload <cell> --seed
<n>` runs the cell as `run.py` does and prints the six readings with the
spans behind them on standard error, before the result line.
"""

import contextlib
import io
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
with open(os.path.join(ROOT, "chipbench", "host_spans.json")) as _f:
    NAMES = json.load(_f)

METRICS = ("setup_import_s", "setup_trainer_init_s", "setup_trace_lower_s",
           "setup_backend_s", "setup_programs_compiled", "setup_unspanned_s")


def union_ns(intervals) -> int:
    """Length of the union of `(start, end)` intervals."""
    total, reach = 0, None
    for lo, hi in sorted(intervals):
        if reach is None or lo > reach:
            total, reach = total + hi - lo, hi
        elif hi > reach:
            total, reach = total + hi - reach, hi
    return total


def _inside(intervals, lo: int, hi: int) -> list:
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if a < hi and b > lo]


def phases(spans, start_ns, check_steps: int):
    """The six readings (seconds; programs) from a list of span tuples
    `(name, category, start_ns, dur_ns, tid, ...)`, or None."""
    category, name = NAMES["step_dispatch"]
    steps = sorted(s[2] for s in spans if s[1] == category and s[0] == name)
    if start_ns is None or len(steps) <= check_steps:
        return None
    cut = steps[check_steps]
    before = [(s[0], s[1], s[2], min(s[2] + s[3], cut), s[4])
              for s in spans if s[2] < cut]
    startup = {n: [s for s in before if s[1] == "startup" and s[0] == n]
               for n in ("import_trainer", "trainer_init", "init_state")}
    if not all(startup.values()):
        return None

    def stage(*stages):
        return [s for s in before if s[1] == "compile"
                and s[0].split(":", 1)[0] in stages]

    span_of = lambda group: [(s[2], s[3]) for s in group]
    compiles = stage(*NAMES["compile"])
    init_ns = 0
    for s in startup["trainer_init"] + startup["init_state"]:
        same_thread = span_of([c for c in compiles if c[4] == s[4]])
        init_ns += s[3] - s[2] - union_ns(_inside(same_thread, s[2], s[3]))
    named = [s for s in before
             if s[1] in ("startup", "compile", "dispatch")]
    return {
        "setup_import_s": union_ns(span_of(startup["import_trainer"])) / 1e9,
        "setup_trainer_init_s": init_ns / 1e9,
        "setup_trace_lower_s":
            union_ns(span_of(stage("trace", "lower"))) / 1e9,
        "setup_backend_s":
            union_ns(span_of(stage("backend", "cache_read"))) / 1e9,
        "setup_programs_compiled": len(stage("backend")),
        "setup_unspanned_s": (cut - start_ns - union_ns(
            _inside(span_of(named), start_ns, cut))) / 1e9,
    }


def of(facts: dict):
    """`phases` of this process's ring and registry, or None; read once a
    run and kept in `facts`, so the six readers cut one snapshot."""
    if "setup_phases" not in facts:
        facts["setup_phases"] = _of_this_process()
    return facts["setup_phases"]


def _of_this_process():
    try:
        from distributed_vgg_f_tpu import telemetry
        from chipbench.drivers.train import CHECK_STEPS
        recorder = telemetry.get_recorder()
        if not recorder.enabled or recorder.dropped:
            return None
        start_ns = telemetry.get_registry().gauge(
            NAMES["process_start_gauge"])
        return phases(recorder.snapshot(), start_ns, CHECK_STEPS)
    except Exception as e:  # a reader never takes the run's line with it
        print(f"[chipbench] set-up spans unreadable: {e!r}", file=sys.stderr)
        return None


def read(facts: dict, metric: str):
    got = of(facts)
    return None if got is None else got[metric]


def main(argv=None) -> int:
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    from chipbench import run
    line = io.StringIO()
    with contextlib.redirect_stdout(line):
        code = run.main(argv)
    from distributed_vgg_f_tpu import telemetry
    start_ns = telemetry.get_registry().gauge(NAMES["process_start_gauge"])
    for s in telemetry.get_recorder().snapshot():
        if s[1] in ("startup", "compile", "dispatch") and s[3] >= 50e6:
            print(f"[startup] {(s[2] - (start_ns or 0)) / 1e9:9.3f} s "
                  f"+{s[3] / 1e9:8.3f} s  {s[1]}:{s[0]}", file=sys.stderr)
    recorder = telemetry.get_recorder()
    print(f"[startup] ring: {recorder.recorded} recorded, "
          f"{recorder.dropped} dropped", file=sys.stderr)
    print(f"[startup] {json.dumps(of({}))}", file=sys.stderr, flush=True)
    sys.stdout.write(line.getvalue())
    return code


if __name__ == "__main__":
    sys.exit(main())
