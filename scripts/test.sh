#!/bin/bash
# Single test entry point. Default: THE tier-1 gate from ROADMAP.md —
# the exact command the reviewer runs, so builder and reviewer can never
# drift (pipefail + DOTS_PASSED echo included).
#
#   scripts/test.sh              # tier-1 gate (non-slow tests, CPU devices)
#   FULL=1 scripts/test.sh       # native build + entire suite (slow included)
#   CHECK=1 scripts/test.sh      # correctness-tooling gate: the static
#                                # invariant lints (scripts/check.py) +
#                                # the native churn stress under TSan
#                                # (make -C native tsan) — fails on any
#                                # lint finding or data race; see
#                                # docs/operations.md "Static analysis
#                                # & sanitizers"

set -u
cd "$(dirname "$0")/.."

if [ "${CHECK:-0}" = "1" ]; then
    set -ex
    python scripts/check.py
    make -C native tsan
    exit 0
fi

if [ "${FULL:-0}" = "1" ]; then
    set -ex
    make -j -C native
    exec python -m pytest tests/ -q
fi

# Rebuild the native lib if its sources moved so tests never run
# against a stale tracked-nowhere .so (artifacts left by an old
# checkout). Quiet + incremental: a no-op when up to date; tolerated
# to fail (control/_native.py builds on demand as the fallback).
make -C native >/dev/null 2>&1 || true

# T1_TIMEOUT: ROADMAP's 870s by default. The 10 heaviest tests (>=25s
# each, ~775s combined on this 2-core box) are marked `slow` (pytest.ini)
# so the non-slow gate fits the budget (~8 min measured); FULL=1 runs
# them all.
set -o pipefail
rm -f /tmp/_t1.log
timeout -k 10 "${T1_TIMEOUT:-870}" env JAX_PLATFORMS=cpu python -m pytest tests/ -q \
    -m 'not slow' --continue-on-collection-errors \
    -p no:cacheprovider -p no:xdist -p no:randomly \
    2>&1 | tee /tmp/_t1.log
rc=${PIPESTATUS[0]}
echo DOTS_PASSED=$(grep -aE '^[.FEsx]+( *\[ *[0-9]+%\])?$' /tmp/_t1.log | tr -cd . | wc -c)
exit $rc
