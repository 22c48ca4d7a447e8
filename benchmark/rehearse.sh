#!/bin/sh
# CPU rehearsal of the benchmark: HIGGS and MS-LTR (the latter added as a later
# PR would add it, from benchmark/tests/data/msltr.json) at a tiny size through
# the harness's own entry point (chip look stubbed in the tests, not by an
# option), --trace 0 and --trace 1 (the latter reduced from the recorded trace
# under benchmark/tests/data), the control and every planted fault. Exits
# non-zero on a malformed result line or a comparison that does not fail where
# it must. No chip, no timing: a number printed here is not a measurement.
cd "$(dirname "$0")/.." || exit 2
exec env JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests -q -p no:cacheprovider "$@"
