"""Which device this process runs on: the one backend test of the package
and the table of TPU generations it knows by ``device_kind``.

``on_tpu()`` decides every Mosaic-vs-XLA choice. ``detect_profile()`` names
the attached chip for ``chip_smoke.py``; the capacities are what the
kernels' ``vmem_limit_bytes`` clamps (96-100MB, ``ops/pallas_*.py``) are
sized under. A kernel's own need is the ``*_vmem_bytes`` helper beside it;
peaks for roofline shares live with the benchmark (``benchmark/peaks.json``).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict

MIB = 1 << 20
GIB = 1 << 30


@dataclass(frozen=True)
class DeviceProfile:
    name: str
    vmem_bytes: int            # VMEM per core
    hbm_bytes: int             # HBM per chip


DEVICE_PROFILES: Dict[str, DeviceProfile] = {
    # the tuning target: every kernel vmem_limit comment assumes v5e
    "v5e": DeviceProfile("v5e", vmem_bytes=128 * MIB, hbm_bytes=16 * GIB),
    "v5p": DeviceProfile("v5p", vmem_bytes=128 * MIB, hbm_bytes=95 * GIB),
    # older generation: much smaller VMEM — kernels that size their
    # limit near 100MB do NOT fit
    "v4": DeviceProfile("v4", vmem_bytes=32 * MIB, hbm_bytes=32 * GIB),
}

# ``jax.devices()[0].device_kind`` (lowercased) -> profile name. The
# v5e chip reports itself as "TPU v5 lite"; a kind that is not listed
# here is an error, never a default.
DEVICE_KINDS: Dict[str, str] = {
    "tpu v5 lite": "v5e",
    "tpu v5e": "v5e",
    "tpu v5p": "v5p",
    "tpu v5": "v5p",
    "tpu v4": "v4",
}


def on_tpu() -> bool:
    """The one backend test of the package: True when JAX's default
    backend is the TPU. Every Mosaic-vs-XLA choice (histogram, split
    scan, persist kernels, interpret mode) keys off this and nothing
    else, so a process that is not on the chip never claims it is."""
    import jax
    return jax.default_backend() == "tpu"


def detect_profile() -> DeviceProfile:
    """Profile of the attached accelerator, matched on ``device_kind``.

    A backend that cannot be initialised raises whatever JAX raises; a
    ``device_kind`` with no row in :data:`DEVICE_KINDS` (the CPU among
    them) is a ``ValueError`` — nothing is assumed for an unknown
    device."""
    import jax
    kind = jax.devices()[0].device_kind
    name = DEVICE_KINDS.get(kind.lower())
    if name is None:
        raise ValueError(
            "no device profile for device_kind %r (known kinds: %s)"
            % (kind, ", ".join(sorted(DEVICE_KINDS))))
    return DEVICE_PROFILES[name]
