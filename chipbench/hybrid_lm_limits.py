"""The readings a hybrid language-model cell's limits are set from, at the
cell's own size, in one process (the benchmark's own runs never run this):
`chipbench/lm_limits.py`'s scheme for `drivers/hybrid_lm_train.py`.

    python3 chipbench/hybrid_lm_limits.py --workload <cell> --seeds 12 --controls 6

For every seed: the program's first steps against the plain reference (the
lower readings), `expert_load_diff` among them. For the first `--controls`
seeds also the control (the reference computed in fp8, put in the
program's place) and the faults against the same reference (the upper
readings): `half_batch`; `state_unchanged`, which needs no run; and the
reference module's own (`chunk_reset`: the state-space layers' state set to
zero at every chunk boundary). Every row is also judged as a run judges it
(`correct`). One JSON object per line on standard output, a summary last;
the exit code is 1 where a program row is not correct or a control or
fault row is.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def readings(cell: dict, config: dict, seeds, controls: int,
             emit=print, root: str = ROOT) -> dict:
    from chipbench import compare, limits, run as harness
    from chipbench.drivers import hybrid_lm_train as driver

    ctx = harness.Context(root=root, bench={}, cell=cell, config=config,
                          seed=seeds[0], seconds=0.0, trace=False, t0=0.0,
                          out_dir="")
    trainer, cfg, _ = driver.base.build_trainer(ctx)
    recipe = driver.recipe_of(cfg, config)
    worst: dict = {}
    correct: dict = {}
    for n, seed in enumerate(seeds):
        live = driver.first_steps(trainer, cfg, config, seed)
        got, shapes = live["got"], live["shapes"]
        del live                       # the state goes before the reference
        follow = lambda **kw: driver.follow_reference(
            config, cfg, recipe, shapes, seed, **kw)
        want = follow()
        sides = {"program": got}
        if n < controls:
            sides["control_fp8"] = follow(mode="fp8")
            sides["fault_half_batch"] = follow(fault="half_batch")
            sides["fault_state_unchanged"] = limits._unchanged(want)
            for fault in driver.REFERENCE_FAULTS:
                sides[f"fault_{fault}"] = follow(fault=fault)
        for side, gave in sides.items():
            gaps = compare.training_gaps(gave, want, config["probe_leaf"])
            checks = compare.judge(gaps, cell["limits"]) \
                + driver.routing_checks(gave, want, cell)
            gaps["expert_load_diff"] = (checks[-2]["value"], "")
            ok = all(c["ok"] for c in checks)
            done = correct.setdefault(side, [0, 0])
            done[0] += ok
            done[1] += 1
            emit(json.dumps({"seed": seed, "side": side, "correct": ok,
                             "losses": [float(x) for x in want["losses"]],
                             **{k: v[0] for k, v in gaps.items()},
                             "where": {k: v[1] for k, v in gaps.items()
                                       if v[1]}}), flush=True)
            for k, (v, _) in gaps.items():
                k = "loss_gap" if k.startswith("loss_gap") else k
                lo, hi = worst.setdefault(side, {}).get(k, (v, v))
                worst[side][k] = (min(lo, v), max(hi, v))
    emit(json.dumps({"summary_min_max": worst, "correct_of_rows": correct,
                     "limits": cell["limits"],
                     "load_diff_limit": cell["load_diff_limit"]}))
    return {"worst": worst, "correct": correct}


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", type=int, default=12)
    parser.add_argument("--first-seed", type=int, default=2147500003)
    parser.add_argument("--controls", type=int, default=6)
    args = parser.parse_args(argv)
    from chipbench import run as harness
    _, cell, config = harness.load_cell(ROOT, args.workload)
    seeds = [args.first_seed + 7919 * i for i in range(args.seeds)]
    out = readings(cell, config, seeds, args.controls)["correct"]
    sound = all(ok == n if side == "program" else ok == 0
                for side, (ok, n) in out.items())
    return 0 if sound else 1


if __name__ == "__main__":
    sys.exit(main())
