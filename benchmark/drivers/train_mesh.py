"""Traffic driver ``train_mesh``: the ``train`` driver's job, window, trace
reduction and comparison, for a cell whose one ``lgb.train`` runs sharded by
rows over the cell's chips (``tree_learner=data``, a mesh of ``chips``).

Everything is ``drivers/train.py``, imported, but two things.

The work model counts the whole job (all rows of every histogram, partition
and fill), while ``harness/xtrace.py:reduce`` gives device time as the mean
over the device planes and ``peaks.json`` holds one chip's peaks. So the
work of the traced launch is divided by ``chips`` here: ``train_step_mfu``,
``hist_roofline`` and ``partition_roofline`` then hold one chip's share of
the work against one chip's peak and one chip's time. Undivided, every share
of a four-chip cell would read four times too high.

And the path: the run counts as failed (all iterations) unless, at every
mark, the learner is the data-parallel one on ``chips`` shards with a
sharded persist grower, which ``train.run``'s own check (a live carry, the
Mosaic kernels, every tree in the model text) cannot see.
"""
import contextlib

from drivers import train


@contextlib.contextmanager
def mesh_learner(chips, seen):
    """``train.learner_of`` that also notes, at every mark, whether the
    learner is the sharded one (``seen["off"]``: what was found instead) and
    what each device holds (``seen["peaks"]``, bytes at the newest call)."""
    import jax
    real = train.learner_of

    def learner_of(bst):
        learner = real(bst)
        gr = getattr(learner, "_persist_gr", None)
        found = (type(learner).__name__, getattr(learner, "num_shards", 1),
                 getattr(gr, "axis_name", None) is not None)
        if found != ("DataParallelTreeLearner", chips, True):
            seen["off"] = found
        seen["peaks"] = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                         for d in jax.local_devices()[:chips]]
        return learner
    train.learner_of = learner_of
    try:
        yield
    finally:
        train.learner_of = real


def per_chip(work, chips):
    """One chip's share of the work model's (operations, bytes)."""
    return {part: tuple(v / chips for v in pair)
            for part, pair in work.items()}


def run(cell, cfg, traffic, args, device, peak, t_start):
    chips = int(cell["chips"])
    seen = {}
    with mesh_learner(chips, seen):
        out = train.run(cell, cfg, traffic, args, device, peak, t_start)
    train.say("mesh: learner on %d shards with a sharded persist grower at "
              "every mark=%s%s; peak bytes by device %s"
              % (chips, "off" not in seen,
                 "" if "off" not in seen else " (found %s)" % (seen["off"],),
                 seen.get("peaks")))
    if "off" in seen or "peaks" not in seen:
        out["failed"] = out["attempted"]
        out["correct"] = False
    work = out["ctx"].get("work")
    if work:
        out["ctx"]["work"] = per_chip(work, chips)
        train.say("work model of the traced launch, one chip's share of %d "
                  "(ops, bytes): %s" % (chips, out["ctx"]["work"]))
    return out
