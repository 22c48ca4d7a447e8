"""Transfer auditor: device programs must stay on the device, sharded.

ROADMAP items 2 and 3 push the persist/level/scan programs to pod
scale, where two silent program shapes turn a compiled hot loop into a
host-bound or HBM-bound one:

* an **implicit device<->host transfer** — a callback / infeed /
  ``device_put`` materializing inside a compiled program serializes the
  pipeline at host speed (the legacy jaxpr audit only checked loop
  *bodies*; a transfer anywhere in a persist program is a per-launch
  stall);
* an **unsharded intermediate** — a value whose sharding degrades to
  replicated above a size threshold multiplies its HBM cost by the
  mesh size and usually rides an ``all_gather`` that DCN pays for.

Both are structural program properties the :mod:`dataflow` engine
records while abstract-evaluating the traced programs: transfer
primitives at any loop depth (alias-semantics ``device_put`` const
staging is benign and marked as such), and explicit replication
collectives (``all_gather``) whose output exceeds
:data:`REPLICATED_BYTES`.  The CPU-traced persist/level/scan and
predict programs must show ZERO of both — the sharded multihost
programs keep their collectives in the host-side guarded DCN layer
(see ``collective_audit``), never inside the compiled level program.
"""
from __future__ import annotations

from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..telemetry import events as telemetry
from . import dataflow, precision_audit
from .config import GraftlintConfig
from .jaxpr_audit import AuditResult

C_TRANSFERS = "analysis::transfer_sites"

# a replicated intermediate below 1MB is noise; above it, the copy is
# real HBM and real DCN on every mesh participant
REPLICATED_BYTES = 1 << 20


# ---------------------------------------------------------------------------
# audited programs
# ---------------------------------------------------------------------------

def _persist_programs() -> List[Tuple[str, object]]:
    def build():
        from ..ops.pallas_grow import make_level_pass, make_split_pass
        WPA, NP, G, nbw = 8, 1024, 2, 2
        plan = ((0, 0, 255), (1, 0, 255))
        i32 = jnp.int32
        sp = make_split_pass(WPA, NP, G, plan, nbw, C=256)
        closed_sp = jax.make_jaxpr(sp)(
            jax.ShapeDtypeStruct((WPA, NP), jnp.uint32),
            jax.ShapeDtypeStruct((16,), i32))
        S_max, T_max = 4, 16
        lp = make_level_pass(WPA, NP, G, plan, nbw, S_max, T_max,
                             C=256)
        closed_lp = jax.make_jaxpr(lp)(
            jax.ShapeDtypeStruct((WPA, NP), jnp.uint32),
            jax.ShapeDtypeStruct((S_max, 16), i32),
            jax.ShapeDtypeStruct((T_max,), i32),
            jax.ShapeDtypeStruct((S_max,), i32),
            jax.ShapeDtypeStruct((), i32))
        return [("persist_split_pass", closed_sp),
                ("persist_level_pass", closed_lp)]

    return precision_audit._memo("transfer_persist", build)


def _shared_programs() -> List[Tuple[str, object]]:
    """scan_pair + predict, traced ONCE per process and shared with
    the precision-flow auditor (same memo — see precision_audit)."""
    progs = (precision_audit._memo(
        "scan_pair", precision_audit._scan_pair_program)
        + precision_audit._memo(
            "predict", precision_audit._predict_program))
    return [(name, closed) for name, closed, _rng, _bless in progs]


def _fused_iteration_programs() -> List[Tuple[str, object]]:
    """The whole-iteration persist drivers (PR 17) — gbdt k-batch scan
    and the RF variant, same memoized traces as
    jaxpr_audit.audit_fused_iteration: a transfer anywhere between
    tree boundaries is a per-batch host stall on the fused fast
    path."""
    from .jaxpr_audit import build_fused_iteration_programs
    art = precision_audit._memo("fused_drivers",
                                build_fused_iteration_programs)
    return list(art["programs"])


# fixture programs ----------------------------------------------------------

def _callback_in_scan():
    """Seeded violation: a host callback inside a scan body — the
    per-level host round-trip the persist design exists to avoid."""
    def prog(x):
        def body(c, _):
            v = jax.pure_callback(
                lambda a: np.asarray(a),
                jax.ShapeDtypeStruct((), x.dtype), c[0])
            return c + v, None
        return jax.lax.scan(body, x, None, length=64)[0]

    return [("callback_in_scan", jax.make_jaxpr(prog)(
        jax.ShapeDtypeStruct((4,), jnp.float32)))]


def _clean_scan():
    def prog(x):
        def body(c, _):
            return c * jnp.float32(0.5) + jnp.float32(1.0), None
        return jax.lax.scan(body, x, None, length=64)[0]

    return [("clean_scan", jax.make_jaxpr(prog)(
        jax.ShapeDtypeStruct((4,), jnp.float32)))]


def _all_gather_large():
    """Seeded violation: an in-program all_gather materializing a
    256KB replicated copy on every participant — over the fixture
    threshold, under a lax one (the fixture hook passes its own)."""
    fn = jax.pmap(lambda x: jax.lax.all_gather(x, "i"), axis_name="i")
    closed = jax.make_jaxpr(fn)(
        jax.ShapeDtypeStruct((1, 1 << 16), jnp.float32))
    return [("all_gather_large", closed)]


# ---------------------------------------------------------------------------
# the audit
# ---------------------------------------------------------------------------

def _violations(name: str, closed,
                threshold: int = REPLICATED_BYTES) -> List[str]:
    rep = dataflow.interpret(closed, replicated_threshold=threshold)
    out = []
    for t in rep.transfers:
        if t.benign:
            continue
        out.append("%s: implicit device<->host transfer (%s)"
                   % (name, t.describe()))
    for prim, nbytes, depth in rep.replicated_large:
        out.append("%s: %s materializes a replicated %.1fMB "
                   "intermediate (loop depth %d) — shard it or move "
                   "the exchange to the guarded DCN layer"
                   % (name, prim, nbytes / float(1 << 20), depth))
    return out


def compute_artifact(config: Optional[GraftlintConfig] = None) -> dict:
    programs = _persist_programs() + _shared_programs() \
        + _fused_iteration_programs()
    violations: List[str] = []
    for name, closed in programs:
        violations += _violations(name, closed)
    return {"programs": [n for n, _ in programs],
            "violations": violations}


def run(config: Optional[GraftlintConfig] = None,
        artifact=None) -> List[AuditResult]:
    name = "transfer"
    try:
        art = artifact if isinstance(artifact, dict) \
            else compute_artifact(config)
    except Exception as e:      # pragma: no cover - defensive
        return [AuditResult(name=name, ok=False,
                            detail="auditor raised: %r" % e)]
    if art["violations"]:
        telemetry.count(C_TRANSFERS, len(art["violations"]),
                        category="analysis")
    return [AuditResult(
        name=name, ok=not art["violations"],
        detail="; ".join(art["violations"][:3]) if art["violations"]
        else "%d program(s) transfer-free with no replicated "
             "intermediate over %dMB"
             % (len(art["programs"]), REPLICATED_BYTES >> 20))]


def check_fixture(payload: dict) -> List[str]:
    """Uniform fixture hook: {"program": "callback_in_scan" |
    "clean_scan" | "all_gather_large"[, "threshold": bytes]}."""
    program = payload["program"]
    threshold = int(payload.get("threshold", REPLICATED_BYTES))
    if program == "all_gather_large":
        progs = _all_gather_large()
    elif program == "callback_in_scan":
        progs = _callback_in_scan()
    else:
        progs = _clean_scan()
    out: List[str] = []
    for name, closed in progs:
        out += _violations(name, closed, threshold=threshold)
    return out
