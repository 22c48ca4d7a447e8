"""For the rehearsal (``python3 -m pytest benchmark/tests``, on the CPU): an
allocator to read. The CPU backend keeps no allocator statistics, so the run
record's spans carry no ``hbm`` there and a rehearsed cell would report none
of the six byte metrics of ``readers/program_hbm.py``. Like the chip look,
that is stubbed here, in the tests, never by an option of the harness or of
the program: ``bytes_in_use`` is the bytes of the live arrays on each device
and the peak their highest reading so far. A number read here is not a
measurement."""
import pytest


class LiveArrays:
    """``lightgbm_tpu.telemetry.events.device_memory_stats`` for a backend
    without allocator statistics."""

    def __init__(self):
        self.peak = {}

    def __call__(self):
        import jax
        in_use = {d.id: 0 for d in jax.local_devices()}
        for array in jax.live_arrays():
            for shard in array.addressable_shards:
                in_use[shard.device.id] += shard.data.nbytes
        out = []
        for dev, nbytes in sorted(in_use.items()):
            self.peak[dev] = max(self.peak.get(dev, 0), nbytes)
            out.append({"bytes_in_use": nbytes,
                        "peak_bytes_in_use": self.peak[dev]})
        return out


@pytest.fixture(autouse=True)
def allocator_to_read(request, monkeypatch):
    """Every rehearsal test but the ones marked ``real_allocator``."""
    if request.node.get_closest_marker("real_allocator"):
        return
    from lightgbm_tpu.telemetry import events
    if hasattr(events, "device_memory_stats"):
        monkeypatch.setattr(events, "device_memory_stats", LiveArrays())


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "real_allocator: read the backend's own allocator "
        "statistics (none on the CPU), not the rehearsal's stand-in")
