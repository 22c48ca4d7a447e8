#!/bin/sh
# CPU rehearsal of expo_cat.train_steady (benchmark/rehearse.sh runs it too, with
# every other file under benchmark/tests): the configuration's shape, the
# generator, the guard, the reference's categorical walk and one traced and one
# untraced run of the cell at a tiny size through the harness's own entry point,
# with the planted faults and the moved category each failing a limit. No chip,
# no timing: a number printed here is not a measurement.
cd "$(dirname "$0")/.." || exit 2
exec env JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests/test_expo_cat.py -q -p no:cacheprovider "$@"
