"""Abstract interpretation over closed jaxprs: dtype, range, error.

Every jaxpr audit before this module was a bespoke recursive walk:
``jaxpr_audit`` re-implemented sub-jaxpr traversal per check and could
only ask *structural* questions (is there a ``convert_element_type`` to
f64 anywhere?).  It could not see an f64 constant closed over inside a
``custom_jvp`` body (consts are not equation outputs), and it could not
say whether a narrowing is *safe* — that needs to know what values flow
through it.  This module is the shared engine those audits (and the new
precision-flow / transfer / quantization auditors) run on: a forward
abstract interpreter that propagates, per value,

* a **dtype** (read off the avals — exact, this is jax's own type
  lattice; the analysis records where f64 appears and where a float
  narrows),
* an **interval** value-range domain seeded from input contracts (bin
  indices in ``[0, max_bin)``, counts in ``[0, rows]``, hessians >= 0 —
  the ops modules export these as ``*_input_contract`` annotations),
* an accumulated **absolute error bound** versus exact real arithmetic
  (unit roundoff per float dtype, classic forward-error recurrences per
  primitive — see the rule table),

through every primitive *including all sub-jaxpr carriers* (``pjit``,
``scan``, ``while``, ``cond``, ``custom_jvp_call``/``custom_vjp_call``,
``closed_call``, ``xla_pmap``) with a fixpoint for loop bodies:

* a ``scan`` with a small static ``length`` is unrolled exactly (the
  carry bound is tight: summing L values in [0, 1] proves [0, L]);
* longer scans and ``while`` loops iterate the body to a join-fixpoint,
  widening unstable bounds to +-inf after :data:`WIDEN_AFTER` rounds so
  termination is guaranteed (``report.fixpoint`` records rounds /
  converged / widened for the tests to pin).

Soundness posture: unknown primitives degrade to TOP (unbounded range,
unknown error) — the analysis never *invents* a bound, so a "proven"
range out of :func:`interpret` is trustworthy while an unbounded one
just means "could not prove".  Loop-replayed sites JOIN into one record
per equation (interval hull, max error), so a narrowing inside a scan
body reports the bound over every iteration.

Site records the auditors consume:

* ``narrowings`` — every float->narrower-float ``convert_element_type``
  with the incoming range/error and whether the range provably fits the
  target dtype; sites whose result directly feeds a comparison /
  ``reduce_max`` / ``argmax`` are flagged ``decision_relevant`` (the
  tie-flip geometry: range arguments cannot prove those safe, ties flip
  inside the retained ULP — they must be blessed).
* ``f64_sites`` — f64-producing equations AND f64 consts/constvars,
  including ones reached only through call primitives (the class the
  old walk missed).
* ``transfers`` — host/transfer primitives at any loop depth (alias-
  semantics ``device_put`` staging marked benign).
* ``replicated_large`` / ``alias_sites`` — explicit replication ops
  (``all_gather``) over the size threshold, and ``pallas_call``
  ``input_output_aliases`` (the donation/in-place-partition queries).
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Dict, Iterator, List, Optional, Sequence, Tuple

import numpy as np

from ..telemetry import events as telemetry

C_VALUES = "analysis::dataflow_values"

INF = float("inf")

# unit roundoff per float dtype (half-ulp of the mantissa)
UNIT_ROUNDOFF = {
    "float64": 2.0 ** -53,
    "float32": 2.0 ** -24,
    "bfloat16": 2.0 ** -8,
    "float16": 2.0 ** -11,
}
# mantissa bits: "narrowing" = strictly fewer (f64 -> f32/bf16/f16,
# f32 -> bf16/f16); bf16 vs f16 conversions are lateral, not narrowing
_MANTISSA = {"float64": 52, "float32": 23, "float16": 10, "bfloat16": 7}
_FLOAT_MAX = {"float64": 1.7976931348623157e308,
              "float32": 3.4028235e38,
              "float16": 65504.0,
              "bfloat16": 3.3895314e38}

# primitives that round-trip to the host or move buffers (the transfer
# audit forbids them outright on device programs; the legacy loop audit
# forbids them inside fori_loop/scan/while bodies)
HOST_PRIMS = {
    "pure_callback", "io_callback", "debug_callback", "callback",
    "infeed", "outfeed", "device_put", "copy_to_host_async",
}
# primitives that explicitly materialize a replicated copy on every
# participant — the "sharding degraded to replicated" detector keys on
# these (plus any future gather-to-all collectives)
REPLICATING_PRIMS = {"all_gather", "all_gather_invariant"}
# a narrowed value directly consumed by one of these is decision-
# relevant: the comparison outcome lives inside the discarded mantissa
_DECISION_PRIMS = {"eq", "ne", "lt", "le", "gt", "ge", "max", "min",
                   "reduce_max", "reduce_min", "argmax", "argmin",
                   "select_n", "sort"}

# loop handling knobs (tests pin both paths)
UNROLL_CAP = 32        # scans with static length <= this unroll exactly
FIXPOINT_MAX = 12      # hard iteration cap for the join-fixpoint
WIDEN_AFTER = 3        # rounds of plain joins before widening kicks in

_F64 = np.dtype("float64")


# ---------------------------------------------------------------------------
# domains
# ---------------------------------------------------------------------------

def _pmul(a: float, b: float) -> float:
    """Interval-product term: 0 * inf is 0 here (a value pinned at zero
    stays zero no matter the other factor's bound)."""
    if a == 0.0 or b == 0.0:
        return 0.0
    return a * b


@dataclass(frozen=True)
class Interval:
    """A closed real interval; +-inf bounds mean "unproven"."""

    lo: float = -INF
    hi: float = INF

    @staticmethod
    def top() -> "Interval":
        return Interval(-INF, INF)

    @staticmethod
    def exact(v: float) -> "Interval":
        v = float(v)
        return Interval(v, v)

    @property
    def bounded(self) -> bool:
        return math.isfinite(self.lo) and math.isfinite(self.hi)

    def mag(self) -> float:
        """max |x| over the interval (inf when unbounded)."""
        return max(abs(self.lo), abs(self.hi))

    def join(self, other: "Interval") -> "Interval":
        return Interval(min(self.lo, other.lo), max(self.hi, other.hi))

    def widen(self, newer: "Interval") -> "Interval":
        """Classic interval widening: a bound still moving after the
        join rounds jumps straight to +-inf so fixpoints terminate."""
        return Interval(-INF if newer.lo < self.lo else self.lo,
                        INF if newer.hi > self.hi else self.hi)

    def add(self, o: "Interval") -> "Interval":
        return Interval(self.lo + o.lo, self.hi + o.hi)

    def sub(self, o: "Interval") -> "Interval":
        return Interval(self.lo - o.hi, self.hi - o.lo)

    def neg(self) -> "Interval":
        return Interval(-self.hi, -self.lo)

    def mul(self, o: "Interval") -> "Interval":
        ps = (_pmul(self.lo, o.lo), _pmul(self.lo, o.hi),
              _pmul(self.hi, o.lo), _pmul(self.hi, o.hi))
        return Interval(min(ps), max(ps))

    def scale(self, k: float) -> "Interval":
        ps = (_pmul(self.lo, k), _pmul(self.hi, k))
        return Interval(min(ps), max(ps))

    def square(self) -> "Interval":
        if self.lo >= 0.0:
            return Interval(_pmul(self.lo, self.lo),
                            _pmul(self.hi, self.hi))
        if self.hi <= 0.0:
            return Interval(_pmul(self.hi, self.hi),
                            _pmul(self.lo, self.lo))
        return Interval(0.0, _pmul(self.mag(), self.mag()))


@dataclass
class AbsVal:
    """One abstract value: dtype + shape (from the aval — exact),
    interval range, and an accumulated absolute error bound (vs exact
    real arithmetic; inf = unknown)."""

    dtype: Optional[np.dtype]
    shape: Tuple[int, ...]
    rng: Interval
    err: float

    def join(self, other: "AbsVal") -> "AbsVal":
        return AbsVal(self.dtype, self.shape, self.rng.join(other.rng),
                      max(self.err, other.err))

    def nbytes(self) -> int:
        n = 1
        for d in self.shape:
            n *= int(d)
        return n * (self.dtype.itemsize if self.dtype is not None else 1)


def _dtype_name(dt) -> str:
    return np.dtype(dt).name if dt is not None else "?"


def _roundoff(dt) -> float:
    return UNIT_ROUNDOFF.get(_dtype_name(dt), 0.0)


def is_narrowing(src, dst) -> bool:
    """float -> float conversion losing mantissa bits (f64->f32/bf16/
    f16, f32->bf16/f16)."""
    s, d = _dtype_name(src), _dtype_name(dst)
    return (s in _MANTISSA and d in _MANTISSA
            and _MANTISSA[d] < _MANTISSA[s])


def _default_for_aval(aval, err: float = INF) -> AbsVal:
    dt = getattr(aval, "dtype", None)
    shape = tuple(getattr(aval, "shape", ()) or ())
    try:
        dt = None if dt is None else np.dtype(dt)
    except TypeError:       # a DMA semaphore, a PRNG key: no numbers
        dt = None
    if dt is None:
        return AbsVal(None, shape, Interval.top(), err)
    if dt.kind == "b":
        return AbsVal(dt, shape, Interval(0.0, 1.0), 0.0)
    if dt.kind in "iu":
        info = np.iinfo(dt)
        return AbsVal(dt, shape, Interval(float(info.min),
                                          float(info.max)), 0.0)
    return AbsVal(dt, shape, Interval.top(), err)


def _const_absval(c) -> AbsVal:
    arr = np.asarray(c)
    rng = Interval.top()
    if arr.size and arr.dtype.kind in "iufb":
        lo = float(arr.min())
        hi = float(arr.max())
        if math.isfinite(lo) and math.isfinite(hi):
            rng = Interval(lo, hi)
    return AbsVal(arr.dtype, tuple(arr.shape), rng, 0.0)


# ---------------------------------------------------------------------------
# site records
# ---------------------------------------------------------------------------

@dataclass
class NarrowSite:
    """One float-narrowing ``convert_element_type`` equation."""

    src: str                    # source dtype name
    dst: str                    # target dtype name
    rng: Interval               # incoming value range (joined over loops)
    err: float                  # incoming accumulated error bound
    depth: int                  # enclosing loop depth
    decision_relevant: bool = False   # result feeds a compare/argmax
    # the source is a weak-typed SCALAR: a python-float literal x64
    # promoted to f64 and narrowed straight back — the JG003 source
    # class, not materialized f64 data flowing through the program
    weak_src: bool = False

    @property
    def fits(self) -> bool:
        """The proven range fits the target dtype's finite span — a
        point interval at +-inf is an exact sentinel (inf is
        representable in every float dtype), not an unproven range."""
        if self.rng.lo == self.rng.hi and self.err == 0.0:
            return abs(self.rng.lo) == INF \
                or abs(self.rng.lo) <= _FLOAT_MAX.get(self.dst, INF)
        return (self.rng.bounded
                and self.rng.mag() <= _FLOAT_MAX.get(self.dst, INF))

    def describe(self) -> str:
        r = ("[%.6g, %.6g]" % (self.rng.lo, self.rng.hi)
             if self.rng.bounded else "unbounded")
        bits = "%s->%s range %s err %.3g" % (self.src, self.dst, r,
                                             self.err)
        if self.decision_relevant:
            bits += " (feeds a comparison)"
        return bits

    def to_dict(self) -> dict:
        return {"src": self.src, "dst": self.dst,
                "lo": self.rng.lo, "hi": self.rng.hi, "err": self.err,
                "depth": self.depth, "fits": self.fits,
                "decision_relevant": self.decision_relevant}


@dataclass
class TransferSite:
    prim: str
    depth: int
    benign: bool      # alias-semantics device_put (const staging)

    def describe(self) -> str:
        return "%s at loop depth %d%s" % (
            self.prim, self.depth, " (alias staging)" if self.benign
            else "")


@dataclass
class DataflowReport:
    """Everything one :func:`interpret` walk learned."""

    n_values: int = 0
    n_eqns: int = 0
    narrowings: List[NarrowSite] = field(default_factory=list)
    f64_sites: List[str] = field(default_factory=list)
    f64_converts: List[str] = field(default_factory=list)
    transfers: List[TransferSite] = field(default_factory=list)
    replicated_large: List[Tuple[str, int, int]] = field(
        default_factory=list)       # (prim, bytes, depth)
    alias_sites: List[Tuple[str, tuple]] = field(default_factory=list)
    fixpoint: Dict[str, object] = field(default_factory=dict)
    out_vals: List[AbsVal] = field(default_factory=list)

    def host_in_loop(self) -> List[str]:
        return [t.prim for t in self.transfers if t.depth > 0]


# ---------------------------------------------------------------------------
# structural walk (the legacy-audit compatibility surface)
# ---------------------------------------------------------------------------

def _sub_jaxprs(eqn) -> Iterator:
    """Raw jaxprs reachable through an equation's params (ClosedJaxpr
    or raw, single or in tuples — pjit's ``jaxpr``, call prims'
    ``call_jaxpr``, while's two, cond's ``branches``)."""
    for val in eqn.params.values():
        if hasattr(val, "jaxpr"):          # ClosedJaxpr
            yield val.jaxpr
        elif hasattr(val, "eqns"):         # raw Jaxpr
            yield val
        elif isinstance(val, (list, tuple)):
            for v in val:
                if hasattr(v, "jaxpr"):
                    yield v.jaxpr
                elif hasattr(v, "eqns"):
                    yield v


def iter_eqns(jaxpr, loop_depth: int = 0) -> Iterator[Tuple[object, int]]:
    """(eqn, loop_depth) over a jaxpr and every sub-jaxpr — including
    the ones reached through call primitives (pjit/custom_jvp/
    closed_call); loop_depth counts enclosing while/scan bodies."""
    for eqn in jaxpr.eqns:
        yield eqn, loop_depth
        inner = loop_depth + (1 if eqn.primitive.name in ("while", "scan")
                              else 0)
        for sub in _sub_jaxprs(eqn):
            yield from iter_eqns(sub, inner)


def _closed_subs(closed) -> Iterator:
    """Every ClosedJaxpr reachable from ``closed`` (itself included) —
    the const-bearing objects the f64-const check must visit."""
    yield closed
    seen = {id(closed)}

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            for val in eqn.params.values():
                vals = val if isinstance(val, (list, tuple)) else (val,)
                for v in vals:
                    if hasattr(v, "jaxpr") and id(v) not in seen:
                        seen.add(id(v))
                        yield v
                        yield from walk(v.jaxpr)
                    elif hasattr(v, "eqns"):
                        yield from walk(v)
    yield from walk(closed.jaxpr)


def find_f64_consts(closed) -> List[str]:
    """f64 constants closed over anywhere in a ClosedJaxpr — including
    inside sub-jaxprs reached through call primitives.  These are NOT
    equation outputs, which is exactly why the old per-check walk
    missed them (the custom_jvp regression fixture)."""
    out: List[str] = []
    for sub in _closed_subs(closed):
        for c in getattr(sub, "consts", ()) or ():
            try:
                arr = np.asarray(c)
            except Exception:       # pragma: no cover - exotic consts
                continue
            if arr.dtype == _F64:
                out.append("const f64%s closed over"
                           % (list(arr.shape),))
    return out


def alias_sites(jaxpr) -> List[Tuple[str, tuple]]:
    """(primitive, input_output_aliases) for every aliasing-capable
    call — the donation / in-place-partition contract query."""
    out = []
    for eqn, _ in iter_eqns(jaxpr):
        ioa = eqn.params.get("input_output_aliases")
        if ioa is not None:
            out.append((eqn.primitive.name, tuple(ioa)))
    return out


# ---------------------------------------------------------------------------
# primitive transfer functions
# ---------------------------------------------------------------------------

def _rerr(rng: Interval, prop: float, dt) -> float:
    """Forward error of one rounded float op: propagated error plus one
    roundoff at the result's magnitude."""
    u = _roundoff(dt)
    if u == 0.0:
        return prop
    m = rng.mag()
    if not math.isfinite(m):
        return INF
    return prop + u * m


def _r_add(eqn, vals, out_aval):
    a, b = vals
    rng = a.rng.add(b.rng)
    return rng, _rerr(rng, a.err + b.err, out_aval.dtype)


def _r_sub(eqn, vals, out_aval):
    a, b = vals
    rng = a.rng.sub(b.rng)
    return rng, _rerr(rng, a.err + b.err, out_aval.dtype)


def _r_mul(eqn, vals, out_aval):
    a, b = vals
    rng = a.rng.mul(b.rng)
    prop = (_pmul(a.rng.mag(), b.err) + _pmul(b.rng.mag(), a.err)
            + _pmul(a.err, b.err))
    return rng, _rerr(rng, prop, out_aval.dtype)


def _r_div(eqn, vals, out_aval):
    a, b = vals
    blo, bhi = b.rng.lo, b.rng.hi
    if not b.rng.bounded or blo <= 0.0 <= bhi:
        return Interval.top(), INF
    inv = Interval(min(1.0 / blo, 1.0 / bhi), max(1.0 / blo, 1.0 / bhi))
    rng = a.rng.mul(inv)
    bmin = min(abs(blo), abs(bhi))
    prop = (a.err / bmin
            + _pmul(a.rng.mag(), b.err) / (bmin * bmin))
    return rng, _rerr(rng, prop, out_aval.dtype)


def _r_neg(eqn, vals, out_aval):
    a = vals[0]
    return a.rng.neg(), a.err


def _r_abs(eqn, vals, out_aval):
    a = vals[0]
    lo = 0.0 if a.rng.lo <= 0.0 <= a.rng.hi else min(abs(a.rng.lo),
                                                     abs(a.rng.hi))
    return Interval(lo, a.rng.mag()), a.err


def _r_max(eqn, vals, out_aval):
    a, b = vals
    return (Interval(max(a.rng.lo, b.rng.lo), max(a.rng.hi, b.rng.hi)),
            max(a.err, b.err))


def _r_min(eqn, vals, out_aval):
    a, b = vals
    return (Interval(min(a.rng.lo, b.rng.lo), min(a.rng.hi, b.rng.hi)),
            max(a.err, b.err))


def _r_clamp(eqn, vals, out_aval):
    # clamp(lo, x, hi) = min(max(x, lo), hi) is monotone in every
    # operand, so the interval bounds are the expression applied to
    # the per-operand bounds — correct for non-point clamp bounds too
    # (max(lo.lo, ...) alone would wrongly exclude a reachable hi.lo)
    lo_v, x, hi_v = vals
    lo = min(max(x.rng.lo, lo_v.rng.lo), hi_v.rng.lo)
    hi = min(max(x.rng.hi, lo_v.rng.hi), hi_v.rng.hi)
    return Interval(lo, hi), max(x.err, lo_v.err, hi_v.err)


def _r_select(eqn, vals, out_aval):
    cases = vals[1:] if len(vals) > 1 else vals
    rng, err = cases[0].rng, cases[0].err
    for c in cases[1:]:
        rng = rng.join(c.rng)
        err = max(err, c.err)
    return rng, err


def _r_identity(eqn, vals, out_aval):
    a = vals[0]
    return a.rng, a.err


def _r_join_all(eqn, vals, out_aval):
    rng, err = vals[0].rng, vals[0].err
    for v in vals[1:]:
        rng = rng.join(v.rng)
        err = max(err, v.err)
    return rng, err


def _contract_size(eqn, vals) -> int:
    (lhs_c, _), _ = eqn.params["dimension_numbers"]
    shape = vals[0].shape
    k = 1
    for d in lhs_c:
        k *= int(shape[d]) if d < len(shape) else 1
    return max(k, 1)


def _r_dot(eqn, vals, out_aval):
    a, b = vals[0], vals[1]
    k = _contract_size(eqn, vals)
    prod = a.rng.mul(b.rng)
    rng = prod.scale(float(k))
    ma, mb = a.rng.mag(), b.rng.mag()
    u = _roundoff(eqn.params.get("preferred_element_type")
                  or out_aval.dtype)
    prop = k * (_pmul(ma, b.err) + _pmul(mb, a.err)
                + _pmul(a.err, b.err) + _pmul(u, _pmul(ma, mb)))
    if not math.isfinite(prop):
        prop = INF
    return rng, prop


def _reduced_size(eqn, vals) -> int:
    axes = eqn.params.get("axes", ())
    shape = vals[0].shape
    k = 1
    for d in axes:
        k *= int(shape[d]) if d < len(shape) else 1
    return max(k, 1)


def _r_reduce_sum(eqn, vals, out_aval):
    a = vals[0]
    k = _reduced_size(eqn, vals)
    rng = a.rng.scale(float(k))
    u = _roundoff(out_aval.dtype)
    err = k * a.err + _pmul(u * k, rng.mag())
    if not math.isfinite(err):
        err = INF
    return rng, err


def _r_reduce_minmax(eqn, vals, out_aval):
    a = vals[0]
    return a.rng, a.err


def _r_cumsum(eqn, vals, out_aval):
    a = vals[0]
    axis = eqn.params.get("axis", 0)
    shape = vals[0].shape
    n = int(shape[axis]) if axis < len(shape) else 1
    full = a.rng.scale(float(n))
    rng = a.rng.join(full).join(Interval(min(0.0, full.lo),
                                         max(0.0, full.hi)))
    u = _roundoff(out_aval.dtype)
    err = n * a.err + _pmul(u * n, rng.mag())
    if not math.isfinite(err):
        err = INF
    return rng, err


def _mono(fn, dfn_max):
    """Monotone unary float fn with a derivative bound callable."""
    def rule(eqn, vals, out_aval):
        a = vals[0]
        try:
            lo = fn(a.rng.lo)
            hi = fn(a.rng.hi)
        except (ValueError, OverflowError):
            return Interval.top(), INF
        rng = Interval(lo, hi)
        if a.err == 0.0:
            return rng, _rerr(rng, 0.0, out_aval.dtype)
        d = dfn_max(a.rng)
        prop = _pmul(d, a.err) if math.isfinite(d) else INF
        return rng, _rerr(rng, prop, out_aval.dtype)
    return rule


def _safe_exp(x):
    return math.exp(x) if x < 709.0 else INF


def _r_log(eqn, vals, out_aval):
    a = vals[0]
    if a.rng.lo <= 0.0:
        return Interval.top(), INF
    rng = Interval(math.log(a.rng.lo), math.log(a.rng.hi)
                   if math.isfinite(a.rng.hi) else INF)
    prop = a.err / a.rng.lo if a.err else 0.0
    return rng, _rerr(rng, prop, out_aval.dtype)


def _r_sqrt(eqn, vals, out_aval):
    a = vals[0]
    if a.rng.lo < 0.0:
        return Interval.top(), INF
    rng = Interval(math.sqrt(a.rng.lo), math.sqrt(a.rng.hi)
                   if math.isfinite(a.rng.hi) else INF)
    if a.err == 0.0:
        prop = 0.0
    elif a.rng.lo > 0.0:
        prop = a.err / (2.0 * math.sqrt(a.rng.lo))
    else:
        prop = INF
    return rng, _rerr(rng, prop, out_aval.dtype)


def _r_floorlike(fn):
    def rule(eqn, vals, out_aval):
        a = vals[0]
        lo = fn(a.rng.lo) if math.isfinite(a.rng.lo) else a.rng.lo
        hi = fn(a.rng.hi) if math.isfinite(a.rng.hi) else a.rng.hi
        err = 0.0 if a.err == 0.0 else (a.err + 1.0)
        return Interval(lo, hi), err
    return rule


def _r_sign(eqn, vals, out_aval):
    a = vals[0]
    return Interval(-1.0, 1.0), 0.0 if a.err == 0.0 else INF


def _r_integer_pow(eqn, vals, out_aval):
    a = vals[0]
    y = int(eqn.params.get("y", 2))
    if y == 0:
        return Interval(1.0, 1.0), 0.0
    n = abs(y)
    if n == 2:
        rng = a.rng.square()
        prop = 2.0 * _pmul(a.rng.mag(), a.err) + _pmul(a.err, a.err)
        rng, err = rng, _rerr(rng, prop, out_aval.dtype)
    else:
        cur = AbsVal(a.dtype, a.shape, a.rng, a.err)
        for _ in range(n - 1):
            r, e = _r_mul(eqn, [cur, a], out_aval)
            cur = AbsVal(a.dtype, a.shape, r, e)
        rng, err = cur.rng, cur.err
    if y < 0:
        # x ** -n = 1 / x**n: invertible only when x**n is bounded
        # away from zero; anything else is TOP, never a tight lie
        if not rng.bounded or rng.lo <= 0.0 <= rng.hi:
            return Interval.top(), INF
        inv = Interval(min(1.0 / rng.lo, 1.0 / rng.hi),
                       max(1.0 / rng.lo, 1.0 / rng.hi))
        prop = err / (min(abs(rng.lo), abs(rng.hi)) ** 2)
        return inv, _rerr(inv, prop, out_aval.dtype)
    return rng, err


def _r_iota(eqn, vals, out_aval):
    shape = tuple(getattr(out_aval, "shape", ()) or ())
    dim = eqn.params.get("dimension", 0)
    n = int(shape[dim]) if dim < len(shape) else 1
    return Interval(0.0, float(max(n - 1, 0))), 0.0


def _r_bool(eqn, vals, out_aval):
    return Interval(0.0, 1.0), 0.0


def _r_argminmax(eqn, vals, out_aval):
    axes = eqn.params.get("axes", (0,))
    shape = vals[0].shape
    n = 1
    for d in axes:
        n *= int(shape[d]) if d < len(shape) else 1
    return Interval(0.0, float(max(n - 1, 0))), 0.0


def _r_pad(eqn, vals, out_aval):
    a, pv = vals[0], vals[1]
    return a.rng.join(pv.rng), max(a.err, pv.err)


_RULES = {
    "add": _r_add, "sub": _r_sub, "mul": _r_mul, "div": _r_div,
    "neg": _r_neg, "abs": _r_abs, "max": _r_max, "min": _r_min,
    "clamp": _r_clamp, "select_n": _r_select,
    "dot_general": _r_dot,
    "reduce_sum": _r_reduce_sum, "cumsum": _r_cumsum,
    "reduce_max": _r_reduce_minmax, "reduce_min": _r_reduce_minmax,
    "exp": _mono(_safe_exp, lambda r: _safe_exp(r.hi)),
    "log": _r_log, "sqrt": _r_sqrt,
    "tanh": _mono(math.tanh, lambda r: 1.0),
    "logistic": _mono(lambda x: 1.0 / (1.0 + _safe_exp(-x)),
                      lambda r: 0.25),
    "erf": _mono(math.erf, lambda r: 1.13),
    "floor": _r_floorlike(math.floor), "ceil": _r_floorlike(math.ceil),
    "round": _r_floorlike(round),
    "sign": _r_sign, "integer_pow": _r_integer_pow,
    "iota": _r_iota,
    "argmax": _r_argminmax, "argmin": _r_argminmax,
    "eq": _r_bool, "ne": _r_bool, "lt": _r_bool, "le": _r_bool,
    "gt": _r_bool, "ge": _r_bool, "is_finite": _r_bool,
    "and": _r_bool, "or": _r_bool, "not": _r_bool, "xor": _r_bool,
    "broadcast_in_dim": _r_identity, "reshape": _r_identity,
    "transpose": _r_identity, "squeeze": _r_identity,
    "rev": _r_identity, "slice": _r_identity,
    "dynamic_slice": _r_identity, "expand_dims": _r_identity,
    "copy": _r_identity, "stop_gradient": _r_identity,
    "device_put": _r_identity, "gather": _r_identity,
    "convert_element_type": None,       # handled inline (narrow sites)
    "concatenate": _r_join_all, "pad": _r_pad,
    "dynamic_update_slice": lambda e, v, o: _r_join_all(e, v[:2], o),
    "scatter": lambda e, v, o: _r_join_all(e, [v[0], v[-1]], o),
}


# ---------------------------------------------------------------------------
# the interpreter
# ---------------------------------------------------------------------------

def _is_alias_device_put(eqn) -> bool:
    sem = eqn.params.get("copy_semantics")
    if not sem:
        return False
    return all("ALIAS" in str(s) for s in sem)


class _Interp:
    def __init__(self, report: DataflowReport,
                 replicated_threshold: int):
        self.report = report
        self.threshold = replicated_threshold
        # site records keyed by equation identity: loop replays JOIN
        # into one record instead of duplicating per iteration
        self._narrow: Dict[int, NarrowSite] = {}
        self._transfer: Dict[int, TransferSite] = {}
        self._f64: Dict[int, str] = {}
        self._conv64: Dict[int, str] = {}
        self._repl: Dict[int, Tuple[str, int, int]] = {}
        self._alias: Dict[int, Tuple[str, tuple]] = {}

    # -- env helpers --------------------------------------------------
    def _read(self, env, atom) -> AbsVal:
        if hasattr(atom, "val"):            # Literal
            return _const_absval(atom.val)
        v = env.get(atom)
        if v is None:
            v = _default_for_aval(atom.aval)
        return v

    # -- one jaxpr ----------------------------------------------------
    def run(self, jaxpr, consts: Sequence[AbsVal],
            args: Sequence[AbsVal], depth: int,
            in_keys: Optional[Sequence[Optional[int]]] = None
            ) -> Tuple[List[AbsVal], List[Optional[int]]]:
        """Interpret one jaxpr.  ``in_keys`` carries narrowing-site
        keys for the inputs and the return pairs each output with its
        key — that is how decision-relevance crosses sub-jaxpr
        boundaries: `jit(argmax)(g32)` must mark g32's narrowing site
        even though the compare lives one call frame down."""
        env: Dict[object, AbsVal] = {}
        cvars = list(jaxpr.constvars)
        for var, cv in zip(cvars, consts):
            env[var] = cv
            if cv.dtype is not None and cv.dtype == _F64:
                self._f64.setdefault(
                    -id(var), "const f64%s closed over (depth %d)"
                    % (list(cv.shape), depth))
        ivars = list(jaxpr.invars)
        args = list(args)
        keys = list(in_keys or [])
        if len(keys) < len(args):
            keys = [None] * (len(args) - len(keys)) + keys
        if len(args) < len(ivars):
            pad = len(ivars) - len(args)
            args = [_default_for_aval(v.aval)
                    for v in ivars[:pad]] + args
            keys = [None] * pad + keys
        narrowed_vars: Dict[object, int] = {}
        off = len(args) - len(ivars)
        for var, av, key in zip(ivars, args[off:], keys[off:]):
            env[var] = av
            if key is not None:
                narrowed_vars[var] = key

        def key_of(atom) -> Optional[int]:
            if hasattr(atom, "val"):        # Literal: unhashable
                return None
            return narrowed_vars.get(atom)

        for eqn in jaxpr.eqns:
            self.report.n_eqns += 1
            invals = [self._read(env, a) for a in eqn.invars]
            eqn_keys = [key_of(a) for a in eqn.invars]
            # decision-relevance: a previously-narrowed var feeding a
            # comparison (in this body or, via eqn_keys threading,
            # inside a callee) marks its site
            if eqn.primitive.name in _DECISION_PRIMS:
                for key in eqn_keys:
                    if key is not None and key in self._narrow:
                        self._narrow[key].decision_relevant = True
            outs, out_keys = self._apply(eqn, invals, depth, eqn_keys)
            for i, (var, out) in enumerate(zip(eqn.outvars, outs)):
                aval = getattr(var, "aval", None)
                if aval is not None:
                    dt = getattr(aval, "dtype", None)
                    out.dtype = np.dtype(dt) if dt is not None else None
                    out.shape = tuple(getattr(aval, "shape", ()) or ())
                self.report.n_values += 1
                if out.dtype is not None and out.dtype == _F64:
                    self._f64.setdefault(
                        id(eqn), "%s -> f64%s"
                        % (eqn.primitive.name, list(out.shape)))
                if type(var).__name__ != "DropVar":
                    env[var] = out
                    if i < len(out_keys) and out_keys[i] is not None:
                        narrowed_vars[var] = out_keys[i]
            if eqn.primitive.name == "convert_element_type" \
                    and eqn.outvars:
                self._record_convert(eqn, invals[0], depth,
                                     narrowed_vars)
            self._record_structural(eqn, depth)
        return ([self._read(env, a) for a in jaxpr.outvars],
                [key_of(a) for a in jaxpr.outvars])

    # -- records ------------------------------------------------------
    def _record_convert(self, eqn, inval: AbsVal, depth: int,
                        narrowed_vars: Dict[object, int]) -> None:
        new_dt = eqn.params.get("new_dtype")
        if new_dt is None:
            return
        if np.dtype(new_dt) == _F64:
            self._conv64.setdefault(id(eqn), str(eqn))
        src = inval.dtype
        if src is not None and is_narrowing(src, new_dt):
            in_aval = getattr(eqn.invars[0], "aval", None)
            weak = bool(getattr(in_aval, "weak_type", False)) \
                and not tuple(getattr(in_aval, "shape", ()) or ())
            key = id(eqn)
            site = self._narrow.get(key)
            if site is None:
                self._narrow[key] = NarrowSite(
                    src=_dtype_name(src), dst=_dtype_name(new_dt),
                    rng=inval.rng, err=inval.err, depth=depth,
                    weak_src=weak)
            else:
                site.rng = site.rng.join(inval.rng)
                site.err = max(site.err, inval.err)
            narrowed_vars[eqn.outvars[0]] = key

    def _record_structural(self, eqn, depth: int) -> None:
        name = eqn.primitive.name
        if name in HOST_PRIMS:
            self._transfer.setdefault(
                id(eqn), TransferSite(
                    prim=name, depth=depth,
                    benign=(name == "device_put"
                            and _is_alias_device_put(eqn))))
        if name in REPLICATING_PRIMS:
            nbytes = 0
            for var in eqn.outvars:
                aval = getattr(var, "aval", None)
                if aval is not None:
                    n = 1
                    for d in getattr(aval, "shape", ()) or ():
                        n *= int(d)
                    nbytes += n * np.dtype(aval.dtype).itemsize
            if nbytes >= self.threshold:
                self._repl.setdefault(id(eqn), (name, nbytes, depth))
        ioa = eqn.params.get("input_output_aliases")
        if ioa is not None:
            self._alias.setdefault(id(eqn), (name, tuple(ioa)))

    # -- dispatch -----------------------------------------------------
    def _apply(self, eqn, invals: List[AbsVal], depth: int,
               in_keys: List[Optional[int]]
               ) -> Tuple[List[AbsVal], List[Optional[int]]]:
        name = eqn.primitive.name
        if name == "scan":
            return self._scan(eqn, invals, depth, in_keys)
        if name == "while":
            return self._while(eqn, invals, depth, in_keys)
        if name == "cond":
            return self._cond(eqn, invals, depth, in_keys)
        sub = eqn.params.get("jaxpr") or eqn.params.get("call_jaxpr")
        if sub is not None and (hasattr(sub, "jaxpr")
                                or hasattr(sub, "eqns")):
            return self._call(eqn, sub, invals, depth, in_keys)
        out_avals = [getattr(v, "aval", None) for v in eqn.outvars]
        no_keys: List[Optional[int]] = [None] * len(eqn.outvars)
        rule = _RULES.get(name)
        if rule is not None and out_avals and out_avals[0] is not None:
            try:
                rng, err = rule(eqn, invals, out_avals[0])
            except Exception:       # pragma: no cover - rule robustness
                rng, err = Interval.top(), INF
            outs = [AbsVal(None, (), rng, err)]
            outs += [_default_for_aval(a) for a in out_avals[1:]]
            return outs, no_keys
        if name == "convert_element_type" and invals \
                and out_avals and out_avals[0] is not None:
            return [self._convert(invals[0], out_avals[0])], no_keys
        return ([_default_for_aval(a) if a is not None
                 else AbsVal(None, (), Interval.top(), INF)
                 for a in out_avals], no_keys)

    def _convert(self, a: AbsVal, out_aval) -> AbsVal:
        dt = np.dtype(out_aval.dtype)
        rng, err = a.rng, a.err
        if dt.kind in "iu":
            info = np.iinfo(dt)
            lo = max(min(rng.lo, float(info.max)), float(info.min)) \
                if math.isfinite(rng.lo) else float(info.min)
            hi = min(max(rng.hi, float(info.min)), float(info.max)) \
                if math.isfinite(rng.hi) else float(info.max)
            rng = Interval(math.floor(lo), math.ceil(hi))
            err = 0.0
        elif dt.kind == "f":
            u = _roundoff(dt)
            m = rng.mag()
            err = (a.err + u * m) if math.isfinite(m) else \
                (a.err if u == 0.0 else INF)
        return AbsVal(dt, a.shape, rng, err)

    # -- sub-jaxpr carriers -------------------------------------------
    def _run_closed(self, sub, args: Sequence[AbsVal], depth: int,
                    in_keys: Optional[Sequence[Optional[int]]] = None
                    ) -> Tuple[List[AbsVal], List[Optional[int]]]:
        if hasattr(sub, "jaxpr"):
            consts = [_const_absval(c) for c in sub.consts]
            return self.run(sub.jaxpr, consts, args, depth,
                            in_keys=in_keys)
        return self.run(sub, [], args, depth, in_keys=in_keys)

    def _call(self, eqn, sub, invals, depth, in_keys
              ) -> Tuple[List[AbsVal], List[Optional[int]]]:
        outs, out_keys = self._run_closed(sub, invals, depth,
                                          in_keys=in_keys)
        n = len(eqn.outvars)
        if len(outs) < n:
            outs = outs + [
                _default_for_aval(getattr(v, "aval", None))
                for v in eqn.outvars[len(outs):]]
        out_keys = (list(out_keys) + [None] * n)[:n]
        return outs[:n], out_keys

    def _scan(self, eqn, invals, depth, in_keys
              ) -> Tuple[List[AbsVal], List[Optional[int]]]:
        p = eqn.params
        nc, nk = int(p["num_consts"]), int(p["num_carry"])
        body = p["jaxpr"]
        length = int(p.get("length", 0) or 0)
        consts = invals[:nc]
        carry = list(invals[nc:nc + nk])
        xs = [AbsVal(v.dtype, v.shape[1:] if v.shape else (),
                     v.rng, v.err) for v in invals[nc + nk:]]
        n_ys = len(eqn.outvars) - nk
        ys: Optional[List[AbsVal]] = None
        body_keys = list(in_keys or [None] * len(invals))
        out_keys: List[Optional[int]] = [None] * len(eqn.outvars)

        def step(cur):
            outs, step_keys = self._run_closed(
                body, list(consts) + cur + xs, depth + 1,
                in_keys=body_keys)
            for i, k in enumerate(step_keys[:nk + n_ys]):
                if k is not None:
                    out_keys[i] = k
            return outs[:nk], outs[nk:nk + n_ys]

        if 0 < length <= UNROLL_CAP:
            for _ in range(length):
                carry, step_ys = step(carry)
                ys = step_ys if ys is None else [
                    a.join(b) for a, b in zip(ys, step_ys)]
            self.report.fixpoint = {"rounds": length,
                                    "converged": True,
                                    "widened": False,
                                    "mode": "unrolled"}
        else:
            widened = False
            rounds = 0
            for i in range(FIXPOINT_MAX):
                rounds = i + 1
                new_carry, step_ys = step(carry)
                ys = step_ys if ys is None else [
                    a.join(b) for a, b in zip(ys, step_ys)]
                joined = [c.join(n) for c, n in zip(carry, new_carry)]
                if all(j.rng == c.rng and j.err == c.err
                       for j, c in zip(joined, carry)):
                    self.report.fixpoint = {"rounds": rounds,
                                            "converged": True,
                                            "widened": widened,
                                            "mode": "fixpoint"}
                    break
                if i + 1 >= WIDEN_AFTER:
                    widened = True
                    joined = [
                        AbsVal(c.dtype, c.shape, c.rng.widen(j.rng),
                               j.err if j.err == c.err else INF)
                        for c, j in zip(carry, joined)]
                carry = joined
            else:       # pragma: no cover - widening guarantees exit
                self.report.fixpoint = {"rounds": rounds,
                                        "converged": False,
                                        "widened": widened,
                                        "mode": "fixpoint"}
        ys = ys or []
        return list(carry) + ys, out_keys

    def _while(self, eqn, invals, depth, in_keys
               ) -> Tuple[List[AbsVal], List[Optional[int]]]:
        p = eqn.params
        cn, bn = int(p["cond_nconsts"]), int(p["body_nconsts"])
        cond, body = p["cond_jaxpr"], p["body_jaxpr"]
        cconsts = invals[:cn]
        bconsts = invals[cn:cn + bn]
        carry = list(invals[cn + bn:])
        keys = list(in_keys or [None] * len(invals))
        body_keys = keys[cn:cn + bn] + keys[cn + bn:]
        self._run_closed(cond, list(cconsts) + carry, depth + 1,
                         in_keys=keys[:cn] + keys[cn + bn:])
        widened = False
        for i in range(FIXPOINT_MAX):
            new_carry = self._run_closed(
                body, list(bconsts) + carry, depth + 1,
                in_keys=body_keys)[0][:len(carry)]
            joined = [c.join(n) for c, n in zip(carry, new_carry)]
            if all(j.rng == c.rng and j.err == c.err
                   for j, c in zip(joined, carry)):
                self.report.fixpoint = {"rounds": i + 1,
                                        "converged": True,
                                        "widened": widened,
                                        "mode": "fixpoint"}
                break
            if i + 1 >= WIDEN_AFTER:
                widened = True
                joined = [AbsVal(c.dtype, c.shape, c.rng.widen(j.rng),
                                 j.err if j.err == c.err else INF)
                          for c, j in zip(carry, joined)]
            carry = joined
        return carry, [None] * len(carry)

    def _cond(self, eqn, invals, depth, in_keys
              ) -> Tuple[List[AbsVal], List[Optional[int]]]:
        branches = eqn.params["branches"]
        ops = invals[1:]
        op_keys = list(in_keys or [None] * len(invals))[1:]
        joined: Optional[List[AbsVal]] = None
        out_keys: List[Optional[int]] = [None] * len(eqn.outvars)
        for br in branches:
            outs, br_keys = self._run_closed(br, ops, depth,
                                             in_keys=op_keys)
            for i, k in enumerate(br_keys[:len(out_keys)]):
                if k is not None:
                    out_keys[i] = k
            joined = outs if joined is None else [
                a.join(b) for a, b in zip(joined, outs)]
        return joined or [], out_keys


def interpret(closed, in_ranges: Optional[Dict[int, Tuple[float, float]]]
              = None, in_errs: Optional[Dict[int, float]] = None,
              replicated_threshold: int = 1 << 20) -> DataflowReport:
    """Interpret a ClosedJaxpr abstractly and return the report.

    ``in_ranges`` maps input position -> (lo, hi) from the input
    contract; unmapped float inputs are TOP with error 0 (exact but
    unbounded inputs).  ``in_errs`` optionally seeds per-input error
    bounds (quantized inputs)."""
    report = DataflowReport()
    interp = _Interp(report, replicated_threshold)
    jaxpr = closed.jaxpr
    consts = [_const_absval(c) for c in closed.consts]
    args = []
    for i, var in enumerate(jaxpr.invars):
        av = _default_for_aval(var.aval, err=0.0)
        if in_ranges and i in in_ranges:
            lo, hi = in_ranges[i]
            av.rng = Interval(float(lo), float(hi))
        if in_errs and i in in_errs:
            av.err = float(in_errs[i])
        args.append(av)
    report.out_vals, _ = interp.run(jaxpr, consts, args, 0)
    report.narrowings = list(interp._narrow.values())
    report.transfers = list(interp._transfer.values())
    report.f64_sites = list(interp._f64.values())
    report.f64_converts = list(interp._conv64.values())
    report.replicated_large = list(interp._repl.values())
    report.alias_sites = list(interp._alias.values())
    telemetry.count(C_VALUES, report.n_values, category="analysis")
    return report
