"""Static VMEM/HBM budget estimator for the Pallas kernel fleet.

Level-wise GPU learners pin their memory plan before training starts
("XGBoost: Scalable GPU Accelerated Learning" builds its entire
device-memory layout up front); the TPU kernels here instead size
per-kernel ``vmem_limit_bytes`` requests at build time — numbers that
were only ever validated by running on a real TPU. This module makes
the plan static: for every ``pallas_call`` family in
``ops/pallas_histogram.py`` / ``ops/pallas_scan.py`` /
``ops/pallas_grow.py`` it derives, per bench shape
(higgs/expo/allstate/yahoo/msltr — the ``data/synth.py`` generators'
geometries), two numbers:

* the **request** — the scoped-vmem limit the kernel itself asks for,
  computed by the SAME helper the kernel calls
  (``hist_vmem_plan`` / ``scan_pair_vmem_bytes`` /
  ``split_pass_vmem_bytes`` …), so the audit can never drift from the
  code;
* an independent **estimate** — the double-buffered BlockSpec blocks
  plus scratch shapes plus the kernel's arithmetic temporaries, derived
  here from the grid/block geometry.

The gate fails when an estimate exceeds its request (the kernel would
OOM inside its own limit) or a request exceeds the per-core VMEM budget
of the active device profile (``telemetry/devices.py``). An HBM tally
(payload + binned planes + scores/gradients + per-leaf histogram
planes) is checked against the per-chip HBM budget the same way.

``tables()`` renders both as rows for the CLI (text + ``--json``).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List, Optional

import numpy as np

from ..telemetry import events as telemetry
from ..telemetry.devices import DeviceProfile, detect_profile, get_profile
from .config import GraftlintConfig, load_config
from .jaxpr_audit import AuditResult

C_KERNELS = "analysis::resource_kernels"
C_OVER = "analysis::resource_over_budget"

MIB = 1 << 20

# persist level-program batching (pallas_grow make_level_pass defaults)
_S_MAXL = 16
_NUM_LEAVES = 255          # the bench configs' tree size (255-leaf trees)


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


@dataclass(frozen=True)
class BenchShape:
    """Static geometry of one bench dataset (data/synth.py defaults).

    ``groups`` is the post-EFB feature-group count: unbundled datasets
    carry one byte group per feature; bundled ones pack their one-hot
    blocks into <=255-offset byte groups (Expo's 700 features bundle to
    16 groups; Allstate's ~4218 one-hot columns to ~17 plus the 8
    numerics)."""

    name: str
    rows: int
    features: int
    groups: int
    bundled: bool
    max_bin: int = 255

    @property
    def W(self) -> int:
        return 256


BENCH_SHAPES: Dict[str, BenchShape] = {
    "higgs": BenchShape("higgs", rows=10_500_000, features=28, groups=28,
                        bundled=False),
    "expo": BenchShape("expo", rows=2_000_000, features=700, groups=16,
                       bundled=True),
    "allstate": BenchShape("allstate", rows=1_000_000, features=4226,
                           groups=25, bundled=True),
    "yahoo": BenchShape("yahoo", rows=473_134, features=700, groups=700,
                        bundled=False),
    "msltr": BenchShape("msltr", rows=2_270_000, features=137, groups=137,
                        bundled=False),
}


@dataclass
class KernelEstimate:
    """One (kernel, shape) VMEM check."""

    kernel: str
    shape: str
    geometry: str
    request: int               # vmem_limit_bytes the kernel asks for
    estimate: int              # BlockSpec+scratch footprint derived here
    budget: int                # per-core VMEM budget of the profile
    ok: bool = True
    why: str = ""

    def to_dict(self) -> dict:
        return {"kernel": self.kernel, "shape": self.shape,
                "geometry": self.geometry, "request": self.request,
                "estimate": self.estimate, "budget": self.budget,
                "ok": self.ok, "why": self.why}


@dataclass
class HBMEstimate:
    """One shape's resident-plane tally."""

    shape: str
    components: Dict[str, int]
    budget: int

    @property
    def total(self) -> int:
        return sum(self.components.values())

    @property
    def ok(self) -> bool:
        return self.total <= self.budget

    def to_dict(self) -> dict:
        return {"shape": self.shape, "components": dict(self.components),
                "total": self.total, "budget": self.budget, "ok": self.ok}


def _check(est: KernelEstimate) -> KernelEstimate:
    if est.request > est.budget:
        est.ok = False
        est.why = ("requests %.1fMB scoped vmem > %.1fMB per-core budget"
                   % (est.request / MIB, est.budget / MIB))
    elif est.estimate > est.request:
        est.ok = False
        est.why = ("blocks+scratch need %.1fMB > the %.1fMB limit the "
                   "kernel requests" % (est.estimate / MIB,
                                        est.request / MIB))
    return est


def _payload_geom(shape: BenchShape):
    """(WPA, C, NP, nbw) via the REAL grow_persist plan/geometry."""
    from ..ops.grow_persist import _payload_geometry, _payload_plan
    widths = np.full(shape.groups, shape.max_bin + 1, np.int64)
    _plan, nbw = _payload_plan(widths)
    WPA, C, NP = _payload_geometry(shape.rows, nbw, 0, 16384)
    return WPA, C, NP, nbw


# ---------------------------------------------------------------------------
# per-kernel estimators (geometry -> KernelEstimate)
# ---------------------------------------------------------------------------

def estimate_hist_window(shape: BenchShape,
                         profile: DeviceProfile) -> KernelEstimate:
    from ..ops.pallas_histogram import hist_vmem_plan
    G = shape.groups
    # the serial learner's auto chunk: bound the scatter tensor to ~256MB
    C = max(1 << 14, int(2 ** 25 / max(G, 1)))
    plan = hist_vmem_plan(shape.W, G, C)
    ct, w_pad = plan["ct"], plan["w_pad"]
    out_bytes = (G * 16 * 16 * 2 * 4 if plan["use_radix"]
                 else G * w_pad * 2 * 4)
    temps = (3 * 16 * ct * 2 + 4 * 16 * 16 * 4 if plan["use_radix"]
             else w_pad * ct * 2 + w_pad * 4 * 4)
    est = 2 * (G * ct * 4 + ct * 4 * 2 + out_bytes) + temps
    return _check(KernelEstimate(
        kernel="hist_window", shape=shape.name,
        geometry="G=%d ct=%d %s" % (G, ct,
                                    "radix" if plan["use_radix"]
                                    else "onehot"),
        request=plan["vmem_limit"], estimate=int(est),
        budget=profile.vmem_budget))


def estimate_scan_pair(shape: BenchShape,
                       profile: DeviceProfile) -> KernelEstimate:
    from ..ops.pallas_scan import scan_pair_vmem_bytes
    Fp = _round_up(max(shape.features, 8), 8)
    Wp = _round_up(shape.W, 128)
    blocks = 2 * (6 * Fp * Wp * 4 + 128 * 4 + 2 * 8 * Fp * 4)
    temps = 12 * Fp * Wp * 4 + Wp * Wp * 4 + 8 * Fp * Wp * 4
    return _check(KernelEstimate(
        kernel="scan_pair", shape=shape.name,
        geometry="Fp=%d Wp=%d" % (Fp, Wp),
        request=scan_pair_vmem_bytes(Fp, Wp),
        estimate=int(blocks + temps), budget=profile.vmem_budget))


def estimate_scan_blocks(shape: BenchShape,
                         profile: DeviceProfile) -> KernelEstimate:
    from ..ops.pallas_scan import scan_blocks_vmem_bytes
    Gp = _round_up(max(shape.groups, 8), 8)
    Wp = _round_up(shape.W, 128)
    blocks = 2 * (2 * Gp * Wp * 4 + 8 * Gp * Wp * 4 + 128 * 4
                  + 8 * Gp * 4)
    temps = 12 * Gp * Wp * 4 + Wp * Wp * 4 + 10 * Gp * Wp * 4
    return _check(KernelEstimate(
        kernel="scan_blocks", shape=shape.name,
        geometry="Gp=%d Wp=%d" % (Gp, Wp),
        request=scan_blocks_vmem_bytes(Gp, Wp),
        estimate=int(blocks + temps), budget=profile.vmem_budget))


def estimate_split_pass(shape: BenchShape, profile: DeviceProfile,
                        level: bool = False) -> KernelEstimate:
    from ..ops.pallas_grow import _ceil8, split_pass_vmem_bytes
    WPA, C, _NP, nbw = _payload_geom(shape)
    E = C + 128
    G = shape.groups
    # scratch_shapes: wbuf/obuf + 4 FIFO slots (WP_LIVE <= WPA rows, one
    # lane tile past E) + the drain's two open tiles + the partition's
    # two control planes
    scratch = ((2 * WPA * E + 4 * WPA * (E + 128) + 2 * WPA * 128) * 4
               + G * 16 * 64 * 4 + 2 * _ceil8(E // 128) * 128 * 4)
    # decode temporaries: group-bin planes + the radix one-hot contraction
    temps = G * E * 4 + 64 * E * 2 + 2 * 16 * E * 2
    return _check(KernelEstimate(
        kernel="level_pass" if level else "split_pass", shape=shape.name,
        geometry="WPA=%d E=%d G=%d nbw=%d" % (WPA, E, G, nbw),
        request=split_pass_vmem_bytes(WPA, E, G),
        estimate=int(scratch + temps), budget=profile.vmem_budget))


def estimate_seg_hist(shape: BenchShape, profile: DeviceProfile,
                      root: bool = False) -> KernelEstimate:
    from ..ops.pallas_grow import seg_hist_vmem_bytes
    WPA, C, _NP, nbw = _payload_geom(shape)
    E = 16384 if root else C + 128      # root_hist streams CR=16384 chunks
    G = shape.groups
    scratch = (2 if not root else 1) * WPA * E * 4 + G * 16 * 64 * 4
    temps = G * E * 4 + 64 * E * 2 + 2 * 16 * E * 2
    return _check(KernelEstimate(
        kernel="root_hist" if root else "seg_hist", shape=shape.name,
        geometry="WPA=%d E=%d G=%d" % (WPA, E, G),
        request=seg_hist_vmem_bytes(WPA, E, G),
        estimate=int(scratch + temps), budget=profile.vmem_budget))


def estimate_hbm(shape: BenchShape, profile: DeviceProfile) -> HBMEstimate:
    WPA, _C, NP, _nbw = _payload_geom(shape)
    comps = {
        # the persist payload: every training plane in one [WPA, NP] u32
        "payload": WPA * NP * 4,
        # the binned Dataset (byte groups; the payload is packed FROM it,
        # both resident during build)
        "binned": shape.rows * shape.groups,
        # f64 score buffer + f32 grad/hess (v1/fallback paths)
        "scores": shape.rows * 8,
        "grad_hess": 2 * shape.rows * 4,
        # per-leaf parent histograms retained for parent-minus-smaller
        "hist_planes": _NUM_LEAVES * shape.groups * shape.W * 2 * 4,
        # the level program's batched smaller-child histograms
        "level_hists": _S_MAXL * shape.groups * 16 * 64 * 4,
    }
    return HBMEstimate(shape=shape.name, components=comps,
                       budget=profile.hbm_budget)


# ---------------------------------------------------------------------------
# the audit
# ---------------------------------------------------------------------------

def _resolve_profile(config: Optional[GraftlintConfig]) -> DeviceProfile:
    config = config or load_config()
    name = getattr(config, "audit_device", "v5e")
    if name != "auto":
        return get_profile(name)
    return detect_profile()


def estimate_all(profile: Optional[DeviceProfile] = None,
                 config: Optional[GraftlintConfig] = None):
    """(kernel estimates, hbm estimates) over every bench shape, routing
    each shape through the kernels it actually runs (bundled shapes take
    the block scan; unbundled the per-feature pair scan)."""
    profile = profile or _resolve_profile(config)
    kernels: List[KernelEstimate] = []
    hbm: List[HBMEstimate] = []
    for shape in BENCH_SHAPES.values():
        kernels.append(estimate_hist_window(shape, profile))
        if shape.bundled:
            kernels.append(estimate_scan_blocks(shape, profile))
        else:
            kernels.append(estimate_scan_pair(shape, profile))
        kernels.append(estimate_split_pass(shape, profile))
        kernels.append(estimate_split_pass(shape, profile, level=True))
        kernels.append(estimate_seg_hist(shape, profile))
        kernels.append(estimate_seg_hist(shape, profile, root=True))
        hbm.append(estimate_hbm(shape, profile))
    return kernels, hbm


def check_fixture(geom: dict) -> List[str]:
    """Uniform fixture hook: budget violations for a synthetic geometry
    dict (name/rows/features/groups/bundled [+ profile])."""
    profile = get_profile(geom.get("profile", "v5e"))
    shape = BenchShape(name=geom.get("name", "fixture"),
                       rows=int(geom["rows"]),
                       features=int(geom["features"]),
                       groups=int(geom["groups"]),
                       bundled=bool(geom.get("bundled", False)))
    ests = [estimate_hist_window(shape, profile),
            (estimate_scan_blocks if shape.bundled
             else estimate_scan_pair)(shape, profile),
            estimate_split_pass(shape, profile)]
    out = [("%s@%s: %s" % (e.kernel, e.geometry, e.why))
           for e in ests if not e.ok]
    h = estimate_hbm(shape, profile)
    if not h.ok:
        out.append("hbm: %.2fGB resident > %.2fGB budget"
                   % (h.total / 2 ** 30, h.budget / 2 ** 30))
    return out


def tables(profile: Optional[DeviceProfile] = None,
           config: Optional[GraftlintConfig] = None,
           artifact=None) -> dict:
    """The budget tables for the CLI (text renderer + --json payload)."""
    if artifact is not None:
        profile, kernels, hbm = artifact
    else:
        profile = profile or _resolve_profile(config)
        kernels, hbm = estimate_all(profile)
    return {"profile": profile.to_dict(),
            "vmem": [k.to_dict() for k in kernels],
            "hbm": [h.to_dict() for h in hbm]}


def render_tables(t: dict) -> str:
    lines = ["resource budgets (profile %s: vmem %dMB/core, hbm %.0fGB"
             "/chip)" % (t["profile"]["name"],
                         t["profile"]["vmem_budget"] // MIB,
                         t["profile"]["hbm_budget"] / 2 ** 30)]
    lines.append("  %-12s %-9s %-28s %9s %9s %s"
                 % ("kernel", "shape", "geometry", "req(MB)", "est(MB)",
                    "ok"))
    for k in t["vmem"]:
        lines.append("  %-12s %-9s %-28s %9.1f %9.1f %s"
                     % (k["kernel"], k["shape"], k["geometry"],
                        k["request"] / MIB, k["estimate"] / MIB,
                        "ok" if k["ok"] else "OVER: " + k["why"]))
    lines.append("  %-12s %-9s %14s %14s %s"
                 % ("hbm", "shape", "resident(GB)", "budget(GB)", "ok"))
    for h in t["hbm"]:
        lines.append("  %-12s %-9s %14.2f %14.2f %s"
                     % ("hbm", h["shape"], h["total"] / 2 ** 30,
                        h["budget"] / 2 ** 30,
                        "ok" if h["ok"] else "OVER"))
    return "\n".join(lines)


def run(config: Optional[GraftlintConfig] = None,
        artifact=None) -> List[AuditResult]:
    """The gate entry point: one AuditResult for VMEM, one for HBM.

    ``artifact`` takes a precomputed ``(profile, kernels, hbm)`` so the
    --json CLI path estimates the kernel fleet once, not twice."""
    if artifact is not None:
        profile, kernels, hbm = artifact
    else:
        profile = _resolve_profile(config)
        kernels, hbm = estimate_all(profile)
    telemetry.count(C_KERNELS, len(kernels), category="analysis")
    bad_k = [k for k in kernels if not k.ok]
    bad_h = [h for h in hbm if not h.ok]
    if bad_k or bad_h:
        telemetry.count(C_OVER, len(bad_k) + len(bad_h),
                        category="analysis")
    vmem = AuditResult(
        name="vmem_budget",
        ok=not bad_k,
        detail=("%d kernel/shape combos within %dMB (%s)"
                % (len(kernels), profile.vmem_budget // MIB, profile.name))
        if not bad_k else "; ".join(
            "%s@%s %s" % (k.kernel, k.shape, k.why) for k in bad_k[:3]))
    hbm_res = AuditResult(
        name="hbm_budget",
        ok=not bad_h,
        detail=("%d shapes resident within %.0fGB (%s)"
                % (len(hbm), profile.hbm_budget / 2 ** 30, profile.name))
        if not bad_h else "; ".join(
            "%s: %.2fGB > %.2fGB" % (h.shape, h.total / 2 ** 30,
                                     h.budget / 2 ** 30)
            for h in bad_h[:3]))
    return [vmem, hbm_res]
