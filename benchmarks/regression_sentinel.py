"""Perf regression sentinel CLI (telemetry/regress.py engine).

The machine-checked half of the r5–r10 receipt discipline:

    # tier-1 / CI consistency: pins == committed receipts, trajectory
    # monotone-or-receipted, trajectory.json fresh
    python benchmarks/regression_sentinel.py --check-committed

    # regenerate the machine-readable trajectory after committing a new
    # receipt round or moving a pin
    python benchmarks/regression_sentinel.py --write-trajectory

    # pre-commit gate for a fresh bench artifact (non-zero exit on
    # regression past the tolerance band):
    python benchmarks/host_pipeline_bench.py --decode-bench --layout \
        tfrecord --repeats 6 --wire u8 --space-to-depth --json-out /tmp/a.json
    python benchmarks/regression_sentinel.py --check /tmp/a.json

Exit code: 0 = green, 1 = any check failed. One JSON line per finding on
stdout plus a final summary line — greppable in CI logs, parseable by
scripts.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from distributed_vgg_f_tpu.telemetry import regress  # noqa: E402

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(
        description="receipt-driven perf regression sentinel")
    parser.add_argument("--repo", default=REPO,
                        help="repository root (default: this checkout)")
    parser.add_argument("--check-committed", action="store_true",
                        help="verify pins vs committed receipts, monotone-"
                             "or-receipted trajectory, and trajectory.json "
                             "freshness")
    parser.add_argument("--write-trajectory", nargs="?", const="",
                        default=None, metavar="PATH",
                        help="(re)generate the machine-readable trajectory "
                             "(default path: benchmarks/runs/"
                             "trajectory.json)")
    parser.add_argument("--check", nargs="*", default=[], metavar="ARTIFACT",
                        help="gate new --json-out artifacts against the "
                             "pinned trajectory with noise-aware tolerance "
                             "bands")
    parser.add_argument("--require-pin", action="store_true",
                        help="--check: an artifact whose basis matches no "
                             "gating pin is an ERROR, not a note")
    args = parser.parse_args(argv)
    if not (args.check_committed or args.check
            or args.write_trajectory is not None):
        parser.error("nothing to do: pass --check-committed, "
                     "--write-trajectory, and/or --check ARTIFACT...")

    errors = []
    if args.write_trajectory is not None:
        path = args.write_trajectory or os.path.join(
            args.repo, "benchmarks", "runs", "trajectory.json")
        trajectory = regress.build_trajectory(args.repo)
        os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
        with open(path, "w") as f:
            json.dump(trajectory, f, indent=1)
            f.write("\n")
        print(json.dumps({"wrote": os.path.relpath(path, args.repo),
                          "rounds": len(trajectory["host_decode"])}))

    if args.check_committed:
        found = regress.check_committed(args.repo)
        found += regress.check_trajectory_file(args.repo)
        for e in found:
            print(json.dumps({"check": "committed", "error": e}))
        if not found:
            pins = {p.name: regress.pin_value(p)
                    for p in regress.PINS + regress.SERVING_PINS}
            print(json.dumps({"check": "committed", "ok": True,
                              "pins": pins}))
        errors += found

    for artifact in args.check:
        found, report = regress.check_artifact(
            artifact, args.repo, require_pin=args.require_pin)
        print(json.dumps({"check": "artifact", **report,
                          "errors": found or None}))
        errors += found

    print(json.dumps({"sentinel": "fail" if errors else "pass",
                      "errors": len(errors)}))
    return 1 if errors else 0


if __name__ == "__main__":
    sys.exit(main())
