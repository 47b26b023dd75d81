"""The readings a training cell's limits are set from, at the cell's own
size, in one process (the benchmark's own runs never run this).

    python3 chipbench/limits.py --workload <cell> --seeds 12 --controls 6

For every seed: the program's first steps against the plain reference (the
lower readings). For the first `--controls` seeds also the control (the
reference computed in fp8, put in the program's place) and each planted
fault against the same reference (the upper readings): `half_batch`,
`no_exchange` on a cell of several chips, and `state_unchanged`, which
needs no run (the reference's own first gradient and first loss, no
change). Every row is also judged by the cell's limits as a run would be
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


def _unchanged(want: dict) -> dict:
    """What a step that returns its state unchanged gives: the same loss
    every step, the right first gradient, no change."""
    import jax
    return {**want, "losses": [want["losses"][0]] * len(want["losses"]),
            "change_norms": jax.tree.map(lambda x: 0.0 * x,
                                         want["change_norms"])}


def readings(cell: dict, config: dict, seeds, controls: int,
             emit=print, root: str = ROOT) -> dict:
    import jax

    from chipbench import compare, run as harness
    from chipbench.drivers import train as driver

    ctx = harness.Context(root=root, bench={}, cell=cell, config=config,
                          seed=seeds[0], seconds=0.0, trace=False, t0=0.0,
                          out_dir="")
    trainer, cfg, _ = driver.build_trainer(ctx)
    recipe = driver.recipe_of(cfg, config)
    replicated = trainer.base_rng().sharding
    probe, limits = config.get("probe_leaf"), cell["limits"]
    faults = ["half_batch"] + (["no_exchange"] if cell["chips"] > 1 else [])
    worst: dict = {}
    correct: dict = {}
    for n, seed in enumerate(seeds):
        key = jax.random.key(seed % (2 ** 31 - 1) + 1,
                             impl=recipe["rng_impl"])
        live = driver.first_steps(trainer, cfg, cell, config, seed,
                                  rng=jax.device_put(key, replicated))
        got, shapes = live["got"], live["shapes"]
        del live
        follow = lambda **kw: driver.follow_reference(
            config, cell, cfg, recipe, shapes, seed, **kw)
        want = follow()
        sides = {"program": got}
        if n < controls:
            sides["control_fp8"] = follow(mode="fp8")
            for fault in faults:
                sides[f"fault_{fault}"] = follow(fault=fault)
            sides["fault_state_unchanged"] = _unchanged(want)
        for side, gave in sides.items():
            gaps = compare.training_gaps(gave, want, probe)
            ok = all(c["ok"] for c in compare.judge(gaps, limits))
            done = correct.setdefault(side, [0, 0])
            done[0] += ok
            done[1] += 1
            emit(json.dumps({"seed": seed, "side": side, "correct": ok,
                             "losses": [float(x) for x in want["losses"]],
                             **{k: v[0] for k, v in gaps.items()},
                             "where": {k: v[1] for k, v in gaps.items()
                                       if v[1]}}))
            for k, (v, _) in gaps.items():
                k = "loss_gap" if k.startswith("loss_gap") else k
                lo, hi = worst.setdefault(side, {}).get(k, (v, v))
                worst[side][k] = (min(lo, v), max(hi, v))
    emit(json.dumps({"summary_min_max": worst,
                     "correct_of_rows": correct, "limits": limits}))
    return {"worst": worst, "correct": correct}


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", type=int, default=12)
    parser.add_argument("--first-seed", type=int, default=1000003)
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
