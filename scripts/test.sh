#!/bin/bash
# Single test entry point. Default: the tier-1 gate as the driver runs it
# after every PR (`commands` of /root/TESTS_LAST_RUN.json): six xdist
# workers that are handed whole files, 1470 s, and the count of passes
# taken from the junit file.
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

# T1_TIMEOUT: the driver's 1470 s by default (exit code 124 past it: the
# run then counts only as far as it got). The heaviest integration tests
# are marked `slow` (pytest.ini); FULL=1 runs them all.
set -o pipefail
rm -rf /tmp/_t1.log /tmp/_t1.xml
timeout -k 10 "${T1_TIMEOUT:-1470}" env JAX_PLATFORMS=cpu \
    ALLOW_MULTIPLE_LIBTPU_LOAD=1 python -m pytest tests/ -q \
    -m 'not slow' --continue-on-collection-errors \
    -p no:cacheprovider -p xdist -n 6 --dist loadfile \
    --junitxml=/tmp/_t1.xml -p no:randomly \
    2>&1 | tee /tmp/_t1.log
rc=${PIPESTATUS[0]}
said=$(sed -n 's/.*<testsuite [^>]*errors="\([0-9]*\)" failures="\([0-9]*\)" skipped="\([0-9]*\)" tests="\([0-9]*\)".*/\4 \1 \2 \3/p' /tmp/_t1.xml 2>/dev/null | head -n 1 | awk '{n=$1-$2-$3-$4; print (n<0 ? 0 : n)}')
echo DOTS_PASSED=${said:-$(grep -aE '^[.FEsx]+( *\[ *[0-9]+%\])?$' /tmp/_t1.log | tr -cd . | wc -c)}
echo WORKERS_DOWN=$(grep -acE '\[gw[0-9]+\] node down' /tmp/_t1.log 2>/dev/null)
exit $rc
