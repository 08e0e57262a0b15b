#!/bin/bash
# The static-analysis CI gate, one command: strict lint + perf-contract
# budgets over the serving package. Exit code is the gate verdict:
#   0  clean (suppressions all justified; every contract's counter is
#      registered and fed from its hot functions)
#   1  findings — a hazard landed without a reason, or a hot function
#      stopped feeding a contract's counter
#   2  usage/config error (malformed budgets.toml, bad path)
#
# Usage: scripts/analyze_gate.sh [OUT_JSON]
#   OUT_JSON    where to write the JSON report (default: stdout)
set -u
cd "$(dirname "$0")/.."

out="${1:-}"

args=(--strict --json --budget budgets.toml defer_tpu/)

if [ -n "$out" ]; then
  JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}" \
    python -m defer_tpu.analysis "${args[@]}" > "$out"
  rc=$?
  echo "analyze gate: rc=$rc report=$out" >&2
else
  JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}" \
    python -m defer_tpu.analysis "${args[@]}"
  rc=$?
fi
exit $rc
