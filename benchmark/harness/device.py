"""The device this process runs on, and its published peaks."""
import json
import os
import sys

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def load_peaks():
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)
    return {k: v for k, v in table.items() if not k.startswith("_")}


def check_device(chips):
    """Device facts and peaks, or exit 2: no CPU fallback, no default peak."""
    import jax
    devs = jax.devices()
    dev = devs[0]
    if dev.platform != "tpu":
        sys.stderr.write("benchmark: needs a TPU, JAX found platform %r\n"
                         % dev.platform)
        sys.exit(2)
    if len(devs) < chips:
        sys.stderr.write("benchmark: the cell needs %d chips, JAX found %d\n"
                         % (chips, len(devs)))
        sys.exit(2)
    peaks = load_peaks()
    if dev.device_kind not in peaks:
        sys.stderr.write("benchmark: no peaks for device_kind %r in "
                         "benchmark/peaks.json\n" % dev.device_kind)
        sys.exit(2)
    return ({"platform": dev.platform, "kind": dev.device_kind,
             "count": chips}, peaks[dev.device_kind])


def memory_peak_bytes(chips):
    """Peak bytes in use on the fullest of the first ``chips`` devices."""
    import jax
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.local_devices()[:chips]]
    return int(max(peaks))
