#!/bin/sh
# CPU rehearsal of criteo.train_data4 (benchmark/rehearse.sh runs it too, with
# every other file under benchmark/tests): the configuration's shape, the
# generator's law, drivers/train_mesh.py and one traced run of the cell at a
# tiny size on a mesh of four virtual CPU devices, through the harness's own
# entry point. No chip, no timing: a number printed here is not a measurement.
cd "$(dirname "$0")/.." || exit 2
exec env JAX_PLATFORMS=cpu XLA_FLAGS="$XLA_FLAGS --xla_force_host_platform_device_count=4" python3 -m pytest benchmark/tests/test_criteo.py -q -p no:cacheprovider "$@"
