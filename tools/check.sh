#!/bin/sh
# The repo's static correctness gate (r15) — one entry point, three passes:
#
#   1. unified invariant linter   (tools/lint: counter-table drift, pins
#      isolation, schema_version stamping, kill-switch completeness —
#      native DVGGF_* triples AND the declared config-plane switches
#      (r18: data.iterator_state.enabled; off = epoch-boundary replay,
#      byte-identical to r17, stream identity pinned in tier-1) —
#      config-field docs, telemetry import isolation)
#   2. ctypes<->ABI contract      (tools/abi_check.py: every extern "C"
#      export declared, arity/width-matched, ABI constants consistent)
#   3. committed-receipt check    (benchmarks/regression_sentinel.py
#      --check-committed: pins == artifacts, trajectory provenance)
#
# All three are stdlib-only static passes — no toolchain, no jax, no
# native build — so the gate runs anywhere in ~seconds. Exercised on
# every default test loop (tests/test_check_gate.py); run it before a chip
# call too: scarce hardware must not start on a tree that fails its own
# invariants.
#
# Exit: 0 all green; the first failing pass's exit code otherwise (every
# pass still runs, so one invocation reports everything).

set -u
REPO=$(cd "$(dirname "$0")/.." && pwd)
cd "$REPO" || exit 2
PY=${PYTHON:-python}

rc=0

echo "== tools/check.sh: invariant linter =="
"$PY" -m tools.lint
r=$?
if [ "$r" -ne 0 ] && [ "$rc" -eq 0 ]; then rc=$r; fi

echo "== tools/check.sh: ABI contract checker =="
"$PY" tools/abi_check.py
r=$?
if [ "$r" -ne 0 ] && [ "$rc" -eq 0 ]; then rc=$r; fi

echo "== tools/check.sh: regression sentinel (committed receipts) =="
"$PY" benchmarks/regression_sentinel.py --check-committed
r=$?
if [ "$r" -ne 0 ] && [ "$rc" -eq 0 ]; then rc=$r; fi

if [ "$rc" -eq 0 ]; then
    echo "== tools/check.sh: ALL GREEN =="
else
    echo "== tools/check.sh: FAILED (rc=$rc) ==" >&2
fi
exit "$rc"
