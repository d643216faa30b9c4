#!/usr/bin/env bash
# Repo lint gate — exits non-zero on ANY finding. Four passes:
#
#   1. `python -m shifu_tpu.analysis` over the package, tools/ and
#      tests/synth.py — all sixteen repo-native rules (see README
#      "Static analysis" for the table),
#      including the whole-program concurrency/atomicity four:
#      raw-lock, thread-shared-mutation, non-atomic-write,
#      swallowed-exception. Runs with --timings and a 10s wall budget:
#      a rule that turns quadratic fails the gate loudly instead of
#      silently taxing every push (`--changed` exists for the
#      edit-loop; the gate always scans everything).
#   2. `python -m compileall` — syntax across every tree we ship.
#   3. hygiene: no tracked .pyc/__pycache__ artifacts, and the
#      fault-site registry must agree with the chaos matrix driver
#      (tools/chaos_sweep.sh enumerates resilience.FAULT_SITES, so a
#      site that import fails would silently shrink the sweep).
#   4. steps.jsonl schema: every stage field README documents must be
#      in the emitted vocabulary (tools/check_steps_schema.py).
#
# tests/test_lint.py runs pass 1 in tier-1; this script is the full
# pre-push/CI gate. Suppress an intentional finding inline with
#   # lint: disable=<rule> -- reason
#
# Usage: tools/lint.sh

set -u -o pipefail

REPO="$(cd "$(dirname "${BASH_SOURCE[0]}")/.." && pwd)"
cd "$REPO"
export PYTHONPATH="$REPO${PYTHONPATH:+:$PYTHONPATH}"
export JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}"

rc=0

echo "== shifu_tpu.analysis (static rules) =="
python -m shifu_tpu.analysis shifu_tpu/ tools/ tests/synth.py \
  --timings --budget-s 10 \
  || rc=1

echo "== compileall (syntax) =="
python -m compileall -q shifu_tpu tools tests || rc=1

echo "== hygiene: tracked bytecode =="
TRACKED_PYC="$(git -C "$REPO" ls-files | grep -E '(\.pyc$|__pycache__/)' || true)"
if [ -n "$TRACKED_PYC" ]; then
  echo "tracked bytecode artifacts (git rm --cached them):" >&2
  echo "$TRACKED_PYC" >&2
  rc=1
else
  echo "clean"
fi

echo "== fault-site registry vs chaos matrix =="
python - <<'PYEOF' || rc=1
from shifu_tpu.resilience import FAULT_SITES

sites = list(FAULT_SITES)
dupes = {s for s in sites if sites.count(s) > 1}
assert not dupes, f"duplicate FAULT_SITES entries: {sorted(dupes)}"
assert sites, "FAULT_SITES is empty — the chaos matrix would be a no-op"
print(f"{len(sites)} fault sites registered; "
      "tools/chaos_sweep.sh sweeps all of them")
PYEOF

echo "== steps.jsonl schema (README vs emitted keys) =="
python tools/check_steps_schema.py || rc=1

if [ "$rc" -ne 0 ]; then
  echo "lint: FAILED" >&2
else
  echo "lint: OK"
fi
exit "$rc"
