"""Per-layer metrics read from what the run record's spans say of the HBM
allocator, and the part of a first launch that no span names.

Since PR 40 every ring entry of a recorded span carries ``hbm``:
``open`` and ``close``, each ``[bytes_in_use, peak_bytes_in_use]`` a local
device, read when the span opened and closed. The byte metrics are read on
the device whose peak ends highest (``peak_hbm``'s fullest device), so that
they can be held against ``peak_hbm``; GB = 1e9 B as there. The newest
``engine::train`` is the job, as in ``readers/program.py``; its set-up also
holds the spans of no train since the train before it (``Dataset.construct``
runs before ``lgb.train`` does), as ``readers/program_named.py`` finds them.

``peak_bytes_in_use`` is a watermark over the life of the process: what a
stretch of the set-up added to the peak is the later reading less the
earlier one.

Returns None where the program keeps no such record or no such span, and
for the byte metrics where its spans carry no ``hbm`` (a commit from before
PR 40; a backend with no allocator statistics, the CPU).
"""
from readers import program, program_named

CONTAINER = "boosting::TrainMultiIterFast(launch)"
# the fused launch's dispatch: the serial path's name and the sharded path's
DISPATCH = ("ops::persist_scan(launch)", "collective::persist_scan(launch)")
ROOT = "engine::train"
BYTES, PEAK = 0, 1


def fullest_device(spans):
    """Index of the local device whose peak ends highest, or None."""
    last = {}
    for e in spans:
        for dev, pair in enumerate(e.get("hbm", {}).get("close", ())):
            last[dev] = max(last.get(dev, 0), pair[PEAK])
    return max(last, key=lambda dev: (last[dev], -dev)) if last else None


def first(spans, names, launch=None):
    hit = [e for e in spans if e["name"] in names
           and (launch is None or e.get("launch") == launch)]
    return min(hit, key=lambda e: e["ts"]) if hit else None


def reading(e, end, dev, what):
    """One number of the span's ``hbm`` field, or None."""
    if e is None or "hbm" not in e:
        return None
    return e["hbm"][end][dev][what]


def byte_metrics(setup, spans, dev):
    """The six readings in bytes; a reading that is not there is None."""
    def at(e, end, what):
        return reading(e, end, dev, what)

    def rise(e_from, e_to):
        a, b = at(e_from, "open", BYTES), at(e_to, "close", BYTES)
        return None if a is None or b is None else b - a

    layout = first(setup, ("tree_learner::ToDevice(layout H2D)",))
    steady = [e for e in spans if e["name"] == CONTAINER
              and e.get("launch", 0) >= 1]
    return {
        "hbm_peak_before_construct_gb":
            at(first(setup, ("io::Construct",)), "open", PEAK),
        "hbm_layout_gb": rise(layout, layout),
        "hbm_payload_gb":
            rise(first(setup, ("ops::BuildPersistPayload(pack)",)),
                 first(setup, ("tree_learner::InitCarry(H2D launch)",))),
        "hbm_peak_before_launch_gb":
            at(first(spans, DISPATCH, launch=0), "open", PEAK),
        "hbm_peak_first_launch_gb":
            at(first(spans, (CONTAINER,), launch=0), "close", PEAK),
        "hbm_resident_gb":
            at(max(steady, key=lambda e: e["ts"]) if steady else None,
               "close", BYTES),
    }


def covered(intervals):
    """Length of the union of (start, end) intervals."""
    total, upto = 0.0, float("-inf")
    for lo, hi in sorted(intervals):
        if hi > upto:
            total += hi - max(lo, upto)
            upto = hi
    return total


def setup_unnamed_s(root, spans):
    """The two containers' own time up to the close of launch 0: the wall
    from ``engine::train``'s start that none of its direct children covers,
    plus the self seconds of launch 0's container."""
    launch0 = first(spans, (CONTAINER,), launch=0)
    if launch0 is None:
        return None
    until = launch0["ts"] + launch0["dur"]
    kids = [(e["ts"], min(e["ts"] + e["dur"], until)) for e in spans
            if e.get("parent") == ROOT and e["tid"] == root["tid"]
            and root["ts"] <= e["ts"] < until]
    return until - root["ts"] - covered(kids) + launch0["self"]


def read(spec, ctx):
    got = program.record()
    if got is None:
        return None
    root, spans, _ = got
    what = spec["metric"]
    if what == "setup_unnamed_s":
        return setup_unnamed_s(root, spans)
    setup = program_named.setup_spans(root)
    dev = fullest_device(setup + spans)
    if dev is None:
        return None
    nbytes = byte_metrics(setup, spans, dev)
    if what not in nbytes:
        raise KeyError("readers/program_hbm.py has no metric %r" % what)
    return None if nbytes[what] is None else nbytes[what] / 1e9
