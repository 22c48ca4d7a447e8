"""Static TPU device profiles: the per-core/per-chip resource budgets.

One canonical table for the numbers that were previously scattered as
comments next to individual kernels ("v5e carries 128MB of VMEM", the
16MB default scoped-vmem limit, HBM per chip). Consumers:

* :mod:`lightgbm_tpu.analysis.resource_audit` — the static VMEM/HBM
  budget gate checks every Pallas kernel's footprint against the active
  profile BEFORE a rewrite lands, instead of discovering a
  scoped-vmem OOM on the first real-TPU run;
* kernel authors — ``vmem_limit_bytes`` requests must stay under
  ``profile.vmem_bytes`` (the kernels cap themselves at 96-100MB, sized
  for the v5e default profile).

The budgets are deliberately conservative fractions of the hardware
numbers: ``vmem_budget`` leaves headroom for Mosaic's own temporaries
and ``hbm_budget`` for XLA's allocator slack + the runtime; a kernel or
dataset plan that fits the budget fits the device.
"""
from __future__ import annotations

import os
from dataclasses import dataclass
from typing import Dict

MIB = 1 << 20
GIB = 1 << 30

# Mosaic's scoped-vmem default when a kernel sets no vmem_limit_bytes
# (the limit the pallas_grow chunk-sizing comments work around)
DEFAULT_VMEM_LIMIT = 16 * MIB


@dataclass(frozen=True)
class DeviceProfile:
    """Per-core VMEM + per-chip HBM capacities, audit budgets, and the
    roofline peaks (:mod:`perfmodel` divides measured rates by these)."""

    name: str
    vmem_bytes: int            # VMEM per core
    hbm_bytes: int             # HBM per chip
    vmem_headroom: float = 0.9  # fraction a kernel may claim
    hbm_headroom: float = 0.9   # fraction resident planes may claim
    # roofline peaks (datasheet numbers, per chip). peak_flops is the
    # dense bf16 MXU rate; the f32 paths the histogram/scan kernels run
    # land near half of it, which perfmodel accounts for itself.
    peak_flops: float = 0.0        # bf16 FLOP/s per chip
    hbm_bw_bytes: float = 0.0      # HBM bytes/s per chip
    ici_bw_bytes: float = 0.0      # interconnect bytes/s per chip

    @property
    def vmem_budget(self) -> int:
        return int(self.vmem_bytes * self.vmem_headroom)

    @property
    def hbm_budget(self) -> int:
        return int(self.hbm_bytes * self.hbm_headroom)

    def to_dict(self) -> dict:
        return {"name": self.name, "vmem_bytes": self.vmem_bytes,
                "hbm_bytes": self.hbm_bytes,
                "vmem_budget": self.vmem_budget,
                "hbm_budget": self.hbm_budget,
                "peak_flops": self.peak_flops,
                "hbm_bw_bytes": self.hbm_bw_bytes,
                "ici_bw_bytes": self.ici_bw_bytes}


DEVICE_PROFILES: Dict[str, DeviceProfile] = {
    # the tuning target: every kernel vmem_limit comment assumes v5e
    "v5e": DeviceProfile("v5e", vmem_bytes=128 * MIB, hbm_bytes=16 * GIB,
                         peak_flops=197e12, hbm_bw_bytes=819e9,
                         ici_bw_bytes=200e9),
    "v5p": DeviceProfile("v5p", vmem_bytes=128 * MIB, hbm_bytes=95 * GIB,
                         peak_flops=459e12, hbm_bw_bytes=2765e9,
                         ici_bw_bytes=600e9),
    # older generation: much smaller scoped VMEM — kernels that size
    # their limit near 100MB do NOT fit; the audit reports it per profile
    "v4": DeviceProfile("v4", vmem_bytes=32 * MIB, hbm_bytes=32 * GIB,
                        peak_flops=275e12, hbm_bw_bytes=1228e9,
                        ici_bw_bytes=300e9),
    # NOT a device: an envelope for phase snapshots recorded on the CPU
    # platform, so the bound CLASSIFICATION of a perf card still reads
    # "host" there. Kept because three tier-1 tests stamp a card on the
    # CPU (test_perf_gate::test_build_meta_roundtrips_through_validator,
    # ::test_profile_perf_card_cli, test_expo_fastpath's profile-CLI
    # smoke); nothing computed against it is a device metric.
    "cpu": DeviceProfile("cpu", vmem_bytes=16 * MIB, hbm_bytes=16 * GIB,
                         peak_flops=1e12, hbm_bw_bytes=50e9,
                         ici_bw_bytes=10e9),
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
    "cpu": "cpu",
}


def on_tpu() -> bool:
    """The one backend test of the package: True when JAX's default
    backend is the TPU. Every Mosaic-vs-XLA choice (histogram, split
    scan, persist kernels, interpret mode) keys off this and nothing
    else, so a process that is not on the chip never claims it is."""
    import jax
    return jax.default_backend() == "tpu"


def get_profile(name: str) -> DeviceProfile:
    try:
        return DEVICE_PROFILES[name]
    except KeyError:
        raise ValueError("unknown device profile %r (have: %s)"
                         % (name, ", ".join(sorted(DEVICE_PROFILES))))


def detect_profile() -> DeviceProfile:
    """Profile of the attached accelerator, matched on ``device_kind``.

    The ``LGBTPU_DEVICE_PROFILE`` override wins outright (a machine
    without the accelerator names the device it reasons about, and on a
    multi-host setup mid-init ``jax.devices()`` must not be touched).
    A backend that cannot be initialised raises whatever JAX raises; a
    ``device_kind`` with no row in :data:`DEVICE_KINDS` is a
    ``ValueError`` — peaks are never assumed for an unknown device."""
    override = os.environ.get("LGBTPU_DEVICE_PROFILE", "")
    if override:
        return get_profile(override)
    import jax
    kind = jax.devices()[0].device_kind
    name = DEVICE_KINDS.get(kind.lower())
    if name is None:
        raise ValueError(
            "no device profile for device_kind %r (known kinds: %s); set "
            "LGBTPU_DEVICE_PROFILE to one of %s to name the device"
            % (kind, ", ".join(sorted(DEVICE_KINDS)),
               ", ".join(sorted(DEVICE_PROFILES))))
    return DEVICE_PROFILES[name]
