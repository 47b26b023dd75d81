"""Whether Solar-Open2's cell tells its program from one that leaves out a
mechanism of the configuration, at the cell's own size, in one process (the
benchmark's own runs never run this): `hybrid_lm_limits.py`'s scheme with
programs in place of the reference's controls.

    python3 chipbench/solar_lm_mechanisms.py --workload solar_open2_ep40_step --seeds 2

Programs, each built through the trainer as the cell builds it:

    program       the configuration as it is
    gate_clamped  Kimi's gate clamped at the bounded form's -5 (what a
                  kernel with Ling's contract may assume)
    bounded_form  the unbounded gate through the bounded form of ops/kda.py
                  (the split at a sub-block's first position with its
                  clamp, which is exact only for gates >= -5)
    beta_halved   beta = sigmoid(u W_b) in (0, 1): no negative eigenvalues

For every seed the programs' first steps are judged against the one plain
reference of that seed by the cell's limits, as a run judges them. Each
program's first step also reports what the layers sowed: the share of gate
entries under -5 (`kda_gate_below_bound`), the largest beta
(`kda_beta_max`), whether the recurrence took the kernels (`kda_kernel`).
One JSON object per line on standard output, a summary last; the exit code
is 1 where `program` is not correct on every seed.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

PROGRAMS = ("program", "gate_clamped", "bounded_form", "beta_halved")
SOWN = ("kda_gate_below_bound/", "kda_beta_max/", "kda_kernel/",
        "kda_decay_min/")


@contextlib.contextmanager
def _left_out(program: str):
    """The program with one mechanism left out, while the trainer is built
    and its step traced."""
    import jax.numpy as jnp

    from distributed_vgg_f_tpu.models import ling3, solar_open2
    from distributed_vgg_f_tpu.ops import kda
    saved = (ling3._kimi_gate, kda.kda, solar_open2.build)
    if program == "gate_clamped":
        ling3._kimi_gate = lambda *a: jnp.maximum(saved[0](*a),
                                                  kda.LOWER_BOUND)
    elif program == "bounded_form":
        ling3.kda.kda = lambda *a, **kw: saved[1](*a, **{**kw,
                                                         "bounded": True})
    elif program == "beta_halved":
        def build(*a):
            model = saved[2](*a)
            return model.clone(layers={**model.layers, "kda": {
                **model.layers["kda"], "beta_scale": 1.0}})
        solar_open2.build = build
    elif program != "program":
        raise ValueError(f"no program {program!r}")
    try:
        yield
    finally:
        ling3._kimi_gate, kda.kda, solar_open2.build = saved


def readings(cell: dict, config: dict, seeds, programs=PROGRAMS,
             emit=print, root: str = ROOT) -> dict:
    import jax

    from chipbench import compare, run as harness
    from chipbench.drivers import hybrid_lm_train as driver

    ctx = harness.Context(root=root, bench={}, cell=cell, config=config,
                          seed=seeds[0], seconds=0.0, trace=False, t0=0.0,
                          out_dir="")
    got: dict = {}
    for program in programs:
        with _left_out(program):
            trainer, cfg, _ = driver.base.build_trainer(ctx)
            for seed in seeds:
                live = driver.first_steps(trainer, cfg, config, seed)
                sown = {k: float(v) for k, v in jax.device_get(
                    live["metrics"]).items() if k.startswith(SOWN)}
                got[seed, program] = (live["got"], live["shapes"], sown)
                del live            # the state goes before the reference
        del trainer
    recipe = driver.recipe_of(cfg, config)
    correct: dict = {}
    for seed in seeds:
        shapes = got[seed, programs[0]][1]
        want = driver.follow_reference(config, cfg, recipe, shapes, seed)
        for program in programs:
            gave, _, sown = got[seed, program]
            gaps = compare.training_gaps(gave, want, config["probe_leaf"])
            checks = compare.judge(gaps, cell["limits"]) \
                + driver.routing_checks(gave, want, cell)
            ok = all(c["ok"] for c in checks)
            done = correct.setdefault(program, [0, 0])
            done[0] += ok
            done[1] += 1
            emit(json.dumps({
                "seed": seed, "program": program, "correct": ok,
                "failing": [c["name"] for c in checks if not c["ok"]],
                **{c["name"]: c["value"] for c in checks},
                "sown_last_step": sown}), flush=True)
    emit(json.dumps({"correct_of_rows": correct, "limits": cell["limits"],
                     "load_diff_limit": cell["load_diff_limit"]}))
    return correct


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", default="solar_open2_ep40_step")
    parser.add_argument("--seeds", type=int, default=2)
    parser.add_argument("--first-seed", type=int, default=2147600003)
    args = parser.parse_args(argv)
    from chipbench import run as harness
    _, cell, config = harness.load_cell(ROOT, args.workload)
    seeds = [args.first_seed + 7919 * i for i in range(args.seeds)]
    ok, n = readings(cell, config, seeds)["program"]
    return 0 if ok == n else 1


if __name__ == "__main__":
    sys.exit(main())
