#!/bin/sh
# CPU rehearsal of epsilon.train_steady (benchmark/rehearse.sh runs it too, with
# every other file under benchmark/tests): the generator's law and one traced
# run of the cell at a tiny size through the harness's own entry point. No
# chip, no timing: a number printed here is not a measurement.
cd "$(dirname "$0")/.." || exit 2
exec env JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests/test_epsilon.py -q -p no:cacheprovider "$@"
