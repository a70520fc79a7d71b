#!/usr/bin/env bash
# What the growth driver runs after every PR, plus the one black-box smoke.
#
#   tools/ci_check.sh            # tier-1, then the gateway smoke
#   tools/ci_check.sh --gateway  # gateway smoke only
#
# Tier-1 is the driver's own command (`commands` in /root/TESTS_LAST_RUN.json):
# every test under tests/ that is not marked slow, six workers, single tests
# dealt as workers free up (--dist load; tests/conftest.py keeps the files
# whose cases share step programs on one worker each), cut at 1,470 s.
# ALLOW_MULTIPLE_LIBTPU_LOAD lets the workers
# load the TPU compiler side by side on a machine without a chip; never set it
# on a machine that has one. DOTS_PASSED is the count the driver holds a PR to.
# Exit code is nonzero if either part fails.
set -u -o pipefail
cd "$(dirname "$0")/.."

gateway_smoke() {
  echo "== gateway smoke =="
  # black-box lifecycle of `python -m deepspeed_tpu.serving`: ephemeral
  # port, one streamed completion, one shed (429 + Retry-After), the
  # compiled-program bound via /v1/metrics, SIGTERM drain exits 0
  timeout -k 10 420 env JAX_PLATFORMS=cpu python tools/gateway_smoke.py
}

if [ "${1:-}" = "--gateway" ]; then
  gateway_smoke
  exit $?
fi

echo "== tier-1 =="
# the driver's pytest flags; the log and the junit file go to a directory of
# this run's own (the driver's are /tmp/_t1.*: never touch those)
out=$(mktemp -d "${TMPDIR:-/tmp}/ci_check.XXXXXX")
trap 'rm -rf "$out"' EXIT
timeout -k 10 1470 env JAX_PLATFORMS=cpu ALLOW_MULTIPLE_LIBTPU_LOAD=1 python -m pytest tests/ \
  -q -m 'not slow' --continue-on-collection-errors -p no:cacheprovider -p xdist -n 6 \
  --dist load --junitxml="$out/t1.xml" -p no:randomly 2>&1 | tee "$out/t1.log"
t1_rc=${PIPESTATUS[0]}
# the wall the driver's limit cuts, and the sum of the cases' own times (what a
# file costs whichever worker runs it): tools/junit_times.py FILE lists it a file
python tools/junit_times.py --total "$out/t1.xml"
echo "DOTS_PASSED=$(grep -aE '^[.FEsx]+( *\[ *[0-9]+%\])?$' "$out/t1.log" | tr -cd . | wc -c)"
echo "WORKERS_DOWN=$(grep -acE '\[gw[0-9]+\] node down' "$out/t1.log")"

gateway_smoke
gw_rc=$?

[ "$t1_rc" -eq 0 ] && [ "$gw_rc" -eq 0 ]
