"""Per-layer metrics read from the program's own run record: the spans and
counters ``lightgbm_tpu.telemetry`` keeps in process in every mode (telemetry
off included), each span with the number of the ``lgb.train`` call and of the
fused launch it belongs to. The newest ``engine::train`` in this process is
the job; launch 0 and everything before it is set-up, launches from 1 on are
the window. Seconds are self seconds (a span's wall less its child spans), so
that the set-up parts add up. Returns None where the program keeps no such
record (a commit from before it did), and where the job left nothing to read.
"""
import statistics

TRACE_STEPS = ("jax::jaxpr_trace", "jax::lower", "jax::backend_compile")
HOST_LAYERS = ("boosting::", "tree_learner::", "ops::")


def record():
    """(root span, spans of its train, counters), or None."""
    try:
        from lightgbm_tpu import telemetry
    except ImportError:
        return None
    ring = getattr(telemetry, "ring_snapshot", None)
    if ring is None:
        return None
    spans = ring()
    roots = [e for e in spans if e["name"] == "engine::train"]
    if not roots:
        return None
    root = roots[-1]
    return (root, [e for e in spans if e.get("train") == root["train"]],
            telemetry.counts_snapshot())


def setup(e):
    return e.get("launch") in (None, 0)


def self_seconds(spans, names, when):
    return sum(e["self"] for e in spans if e["name"] in names and when(e))


def setup_parts(spans):
    return {
        "payload_pack_s": self_seconds(
            spans, ("ops::BuildPersistPayload(pack)",), setup),
        "carry_init_s": self_seconds(
            spans, ("tree_learner::InitCarry(H2D launch)",), setup),
        "compile_s": self_seconds(spans, TRACE_STEPS, setup),
        "cache_load_s": self_seconds(spans, ("jax::cache_load",), setup),
    }


def train_host_setup_s(root, spans):
    """engine::train's start to the end of launch 0's dispatch, less the
    four parts that have a metric of their own."""
    first = [e for e in spans if e["name"] == "ops::persist_scan(launch)"
             and e.get("launch") == 0]
    if not first:
        return None
    until = first[0]["ts"] + first[0]["dur"]
    return until - root["ts"] - sum(setup_parts(spans).values())


def per_launch(spans, keep, seconds):
    """Median over the launches from 1 on of the summed ``seconds`` of the
    spans ``keep`` takes; None when no such launch left one."""
    by_launch = {}
    for e in spans:
        if e.get("launch", 0) >= 1 and keep(e):
            n = e["launch"]
            by_launch[n] = by_launch.get(n, 0.0) + e[seconds]
    return statistics.median(by_launch.values()) if by_launch else None


def host_busy(e):
    return e["name"].startswith(HOST_LAYERS) and e["cat"] != "device_wait"


def read(spec, ctx):
    got = record()
    if got is None:
        return None
    root, spans, counts = got
    what = spec["metric"]
    if what in ("payload_pack_s", "carry_init_s", "compile_s",
                "cache_load_s"):
        return setup_parts(spans)[what]
    if what == "train_host_setup_s":
        return train_host_setup_s(root, spans)
    if what == "host_busy_per_launch_ms":
        s = per_launch(spans, host_busy, "self")
        return None if s is None else 1e3 * s
    if what == "materialize_ms":
        s = per_launch(
            spans, lambda e: e["name"] ==
            "boosting::MaterializePending(host trees)", "dur")
        return None if s is None else 1e3 * s
    if what == "steady_compiles":
        return sum(1 for e in spans if e.get("launch", 0) >= 1 and e["name"]
                   in ("jax::backend_compile", "jax::cache_load"))
    if what == "fastpath_tree_pct":
        fast = counts.get("tree_learner::persist_scan_trees", 0.0)
        slow = counts.get("tree_learner::v1_grow_trees", 0.0)
        return 100.0 * fast / (fast + slow) if fast + slow else None
    raise KeyError("readers/program.py has no metric %r" % what)
