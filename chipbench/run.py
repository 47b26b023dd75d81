"""One cell of the benchmark, once, in one process.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything that belongs to one cell, configuration, driver, reference or
per-layer metric is a file found by the name `BENCHMARK.json` gives it:

    chipbench/workloads/<cell>.json        the cell's traffic, as data
    <configs[].file>                       the configuration as it is run
    chipbench/drivers/<driver>.py          run(ctx) -> result
    chipbench/reference/<model>.py         the plain reference
    chipbench/layer_metrics/<metric>.py    read(facts) -> number or None

The last line of standard output is the result; everything else goes to
standard error. Without a TPU, or with fewer chips than the cell asks
for, the exit code is 2 and no result is printed.
"""

from __future__ import annotations

import time

T0 = time.perf_counter()  # set-up is counted from here

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


@dataclasses.dataclass
class Context:
    root: str
    bench: dict
    cell: dict
    config: dict
    seed: int
    seconds: float
    trace: bool
    t0: float
    out_dir: str
    fault: str | None = None   # only tests plant one


def _load(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(root: str, name: str):
    """(BENCHMARK.json, the cell, its configuration), each cell and
    configuration entry merged over its own data file."""
    bench = _load(os.path.join(root, "BENCHMARK.json"))
    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    if entry is None:
        raise KeyError(f"BENCHMARK.json has no workload {name!r}")
    cell = {**_load(os.path.join(root, "chipbench", "workloads",
                                 f"{name}.json")), **entry}
    conf = next(c for c in bench["configs"] if c["name"] == entry["config"])
    config = {**_load(os.path.join(root, conf["file"])), **conf}
    return bench, cell, config


def applies(metric: dict, cell_name: str) -> bool:
    return "workloads" not in metric or cell_name in metric["workloads"]


def report(ctx: Context, result: dict) -> dict:
    """The result line: the cell's end-to-end metrics, or with `--trace 1`
    its per-layer metrics, each from its own reader."""
    name = ctx.cell["name"]
    metrics = {}
    if not ctx.trace:
        for m in ctx.bench["end_to_end"]:
            if applies(m, name):
                metrics[m["name"]] = {
                    "value": result["end_to_end"][m["name"]],
                    "unit": m["unit"]}
    else:
        from chipbench import trace_reduce
        facts = result["facts"]
        facts["trace"] = trace_reduce.reduce_dir(facts["trace_dir"]) \
            if facts.get("trace_dir") else None
        for m in ctx.bench["per_layer"]:
            if not applies(m, name):
                continue
            reader = importlib.import_module(
                f"chipbench.layer_metrics.{m['name']}")
            value = reader.read(facts)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    line = {"correct": all(c["ok"] for c in result["checks"]),
            "attempted": result["attempted"], "failed": result["failed"],
            "metrics": metrics, "device": dict(result["device"])}
    if ctx.trace and result["facts"].get("trace"):
        trace = result["facts"]["trace"]
        line["device"]["busy_s"] = trace["busy_s"]
        line["device"]["window_s"] = trace["window_s"]
        line["breakdown"] = {"device_ops": trace["device_ops"][:10],
                             "idle_gaps": trace["idle_gaps"][:10]}
    line["checks"] = {c["name"]: {"value": c["value"], "limit": c["limit"]}
                      for c in result["checks"]}
    return line


def run_cell(ctx: Context) -> dict:
    driver = importlib.import_module(f"chipbench.drivers.{ctx.cell['driver']}")
    result = driver.run(ctx)
    line = report(ctx, result)
    for c in result["checks"]:
        print(f"[chipbench] check {c['name']}: {c['value']!r} "
              f"(limit {c['limit']!r}){'' if c['ok'] else '  <-- FAILS'}"
              f"{' at ' + c['where'] if c.get('where') else ''}",
              file=sys.stderr, flush=True)
    return line


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--seconds", type=float, default=None)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = parser.parse_args(argv)
    bench, cell, config = load_cell(ROOT, args.workload)
    seconds = args.seconds if args.seconds is not None \
        else float(bench["run_seconds"])

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell["chips"]:
        print(f"[chipbench] needs {cell['chips']} TPU chip(s); JAX found "
              f"{len(devices)} x {devices[0].platform}", file=sys.stderr)
        return 2
    out_dir = os.path.join(ROOT, ".chipbench_out", args.workload)
    shutil.rmtree(out_dir, ignore_errors=True)
    ctx = Context(root=ROOT, bench=bench, cell=cell, config=config,
                  seed=args.seed, seconds=seconds, trace=bool(args.trace),
                  t0=T0, out_dir=out_dir)
    line = run_cell(ctx)
    shutil.rmtree(out_dir, ignore_errors=True)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
