"""Per-layer metrics read from the program's run record by names the spec
gives, so that a new span or counter needs a spec and no code:

* ``"spans": [names]`` — summed self seconds of those spans in the set-up of
  the newest ``engine::train``: the train's own spans before its first
  launch, and the spans of no train (``Dataset.construct`` runs before
  ``lgb.train`` does) since the train before it ended;
* ``"counter": name, "of": name`` — 100 x the first counter over the second;
  a first counter that was never counted reads 0 (a run off the path the
  counter marks must read 0, not fall silent).

Returns None where the program keeps no such record, no such span or no
``of`` counter (a commit from before it had them), as ``readers/program.py``
does.
"""
from readers import program


def setup_spans(root):
    """The ring's spans that belong to the set-up of the train ``root``."""
    from lightgbm_tpu import telemetry
    ring = telemetry.ring_snapshot()
    since = max((e["ts"] + e["dur"] for e in ring
                 if e["name"] == "engine::train" and e["ts"] < root["ts"]),
                default=float("-inf"))
    return [e for e in ring if program.setup(e) and (
        e.get("train") == root["train"]
        or (not e.get("train") and since <= e["ts"] <= root["ts"]))]


def read(spec, ctx):
    got = program.record()
    if got is None:
        return None
    root, _, counts = got
    if "spans" in spec:
        hit = [e["self"] for e in setup_spans(root)
               if e["name"] in spec["spans"]]
        return sum(hit) if hit else None
    whole = counts.get(spec["of"])
    if not whole:
        return None
    return 100.0 * counts.get(spec["counter"], 0.0) / whole
