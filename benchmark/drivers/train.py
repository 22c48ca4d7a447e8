"""Traffic driver ``train``: one long ``lgb.train`` job on one constructed
Dataset, timed by a callback at every fused launch (k iterations, the
program's own batch size).

Set-up is everything up to the first launch's mark: rows made on the device
from the seed and copied to the host, binning, payload build + H2D, compile
or cache load, the first k iterations. The window runs from that mark until
``lgb.train`` has returned and the final device score is ready, and is cut at
the first mark at or after ``--seconds``. After the window: the peak memory
reading, the held-out score of the first trees, then the program's state is
freed and the reference follows the trees the run produced.
"""
import gc
import importlib
import os
import shutil
import time

import numpy as np

from harness import device as device_mod
from harness import quality, reference, workmodel, xtrace


def say(msg):
    print(msg, flush=True)


def seed_key(seed):
    import jax
    seed = int(seed)
    return jax.random.wrap_key_data(
        np.asarray([(seed >> 32) & 0xffffffff, seed & 0xffffffff], np.uint32))


class Rows:
    """The cell's rows as blocks of one shape, each made on the device by one
    compiled function of (seed key, block index); any block can be made again,
    bit for bit."""

    def __init__(self, cfg, seed):
        import jax
        self.gen = importlib.import_module("generators." + cfg["generator"])
        self.group = self.gen.GROUP
        self.features = self.gen.FEATURES
        self.rows = cfg["rows"] // self.group * self.group
        self.block = cfg["block_rows"] // self.group * self.group
        self.blocks = -(-self.rows // self.block)
        self.heldout = cfg["heldout_rows"] // self.group * self.group
        assert self.heldout <= self.block
        self.key = seed_key(seed)
        self._make = jax.jit(self.gen.make_block, static_argnums=(2,))

    def device_block(self, index):
        """(x, label, valid) on the device; ``valid`` masks the padding of
        the last block."""
        import jax.numpy as jnp
        x, y = self._make(self.key, index, self.block)
        live = min(self.block, self.rows - index * self.block)
        return x, y, jnp.arange(self.block) < live

    def to_host(self):
        """(X [rows, F] f32, y [rows] f32) and the held-out pair, the latter
        from the block after the last training block."""
        X = np.empty((self.rows, self.features), np.float32)
        y = np.empty((self.rows,), np.float32)
        pending = self._make(self.key, 0, self.block)
        for b in range(self.blocks):
            x_d, y_d = pending
            # one ahead, so the copy overlaps the making; the last is held out
            pending = self._make(self.key, b + 1, self.block)
            lo = b * self.block
            hi = min(lo + self.block, self.rows)
            X[lo:hi] = np.asarray(x_d)[:hi - lo]
            y[lo:hi] = np.asarray(y_d)[:hi - lo]
        xh, yh = pending
        return (X, y, np.asarray(xh)[:self.heldout].copy(),
                np.asarray(yh)[:self.heldout].copy())


def train_call(lgb, params, X, y, group, rounds, callbacks, spans):
    """The timed path: Dataset construction and one ``lgb.train``. Kept as one
    function so that a test can break it underneath the harness."""
    t0 = time.time()
    ds = lgb.Dataset(X, y, group=group, params=dict(params))
    ds.construct()
    spans["binning_s"] = time.time() - t0
    spans["train_entry"] = time.time()
    return lgb.train(dict(params), ds, rounds, verbose_eval=False,
                     callbacks=callbacks)


def inputs(cfg, seed):
    """(rows, X, y, held-out X, held-out y, group sizes or None) of a seed."""
    rows = Rows(cfg, seed)
    X, y, Xh, yh = rows.to_host()
    group = (np.full(rows.rows // rows.group, rows.group, np.int32)
             if rows.group > 1 else None)
    return rows, X, y, Xh, yh, group


def init_score(params, y):
    """The score boosting starts from, which the model folds into tree 0."""
    if params["objective"] == "binary":
        return reference.binary_init(float(np.mean(y, dtype=np.float64)))
    return 0.0


def learner_of(bst):
    return bst._booster.tree_learner


def run(cell, cfg, traffic, args, device, peak, t_start):
    import jax
    import lightgbm_tpu as lgb

    k = int(traffic["launch_iterations"])
    spans = {}
    say("cell %s: config %s rows=%d traffic %s seed=%d seconds=%g trace=%d"
        % (cell["name"], cfg["name"], cfg["rows"], cell["traffic"],
           args.seed, args.seconds, args.trace))
    say("compile cache: %s" % jax.config.jax_compilation_cache_dir)

    # ---- set-up: rows from the seed ---------------------------------------
    t0 = time.time()
    rows, X, y, Xh, yh, group = inputs(cfg, args.seed)
    spans["datagen_s"] = time.time() - t0
    n = rows.rows
    say("data: %d rows x %d features (+%d held out) in %d blocks of %d, "
        "%.1fs" % (n, rows.features, len(yh), rows.blocks, rows.block,
                   spans["datagen_s"]))

    # ---- the callback: marks, window, trace -------------------------------
    marks = []                       # (iterations done, host time)
    state = {"tracing": False, "traced": None, "path_ok": True}
    logdir = os.path.join(args.workdir, "trace")

    def on_iteration(env):
        done = env.iteration + 1
        if done % k:
            return
        with jax.profiler.TraceAnnotation("bench:callback_wait_for_launch"):
            carry = getattr(learner_of(env.model), "_persist_carry", None)
            if carry is None:
                state["path_ok"] = False
            jax.block_until_ready(carry)
        now = time.time()
        if state["tracing"]:
            state["traced"] = (state["trace_from"], now, done - k, done)
            with jax.profiler.TraceAnnotation("bench:stop_trace"):
                jax.profiler.stop_trace()
            state["tracing"] = False
            now = time.time()
        marks.append((done, now))
        trace_at = int(traffic["trace_launch"]) if args.trace else 0
        # the window holds its least number of whole launches, and a traced
        # run its traced launch, however short --seconds is
        if len(marks) > max(int(traffic.get("min_window_launches", 1)),
                            trace_at) \
                and now - marks[0][1] >= args.seconds:
            raise lgb.callback.EarlyStopException(env.iteration, [])
        if len(marks) == trace_at:
            shutil.rmtree(logdir, ignore_errors=True)
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0     # TraceAnnotations only
            options.enable_hlo_proto = False
            jax.profiler.start_trace(logdir, profiler_options=options)
            state["tracing"] = True
            state["trace_from"] = time.time()

    # ---- the timed path ----------------------------------------------------
    params = dict(cfg["params"])
    bst = train_call(lgb, params, X, y, group, int(traffic["num_boost_round"]),
                     [on_iteration], spans)
    with jax.profiler.TraceAnnotation("bench:final_block"):
        jax.block_until_ready(bst._booster.train_score.score_device(0))
    t_end = time.time()
    if state["tracing"]:
        jax.profiler.stop_trace()
    window_start = marks[0][1]
    window_s = t_end - window_start
    iters = marks[-1][0] - marks[0][0]
    spans["first_launch_s"] = window_start - spans.pop("train_entry")
    setup_s = window_start - t_start
    walls = [b[1] - a[1] for a, b in zip(marks, marks[1:])
             if not (state["traced"] and b[0] == state["traced"][3])]
    if walls:
        spans["launch_wall_ms"] = 1e3 * float(np.median(walls))
    spans["host_tail_s"] = t_end - marks[-1][1]
    mem_peak = device_mod.memory_peak_bytes(cell["chips"])
    say("window: %d iterations in %.3fs (%d launches of %d; marks at %s s), "
        "set-up %.2fs (rows %.1f, binning %.1f, first launch %.1f), "
        "host tail %.3fs"
        % (iters, window_s, len(marks) - 1, k,
           ["%.2f" % (m[1] - window_start) for m in marks], setup_s,
           spans["datagen_s"], spans["binning_s"], spans["first_launch_s"],
           spans["host_tail_s"]))

    # ---- which path ran ----------------------------------------------------
    learner = learner_of(bst)
    mode = tuple(learner._persist_kernel_effective()[:2])
    gc_ = learner.grow_config
    n_trees = len(bst._booster.models)
    path_ok = (state["path_ok"] and mode == tuple(traffic["kernel_mode"])
               and n_trees == marks[-1][0])
    say("path: persist carry live at every mark=%s kernel_mode=%s "
        "scan_impl=%s hist_impl=%s trees=%d (marks say %d)"
        % (state["path_ok"], mode, gc_.scan_impl, gc_.hist_impl, n_trees,
           marks[-1][0]))

    # ---- held-out quality of the first trees -------------------------------
    t0 = time.time()
    q_trees = int(cfg["quality_trees"])
    pred = bst.predict(Xh.astype(np.float64), num_iteration=q_trees,
                       raw_score=True)
    score = quality.METRICS[cfg["quality"]](yh, pred, rows.group)
    say("held-out %s of the first %d trees on %d rows: %.6f (%.1fs)"
        % (cfg["quality"], q_trees, len(yh), score, time.time() - t0))

    # ---- the model, then free the program's state --------------------------
    all_trees = reference.parse_model(bst.model_to_string(num_iteration=-1))
    sizes = [t["num_leaves"] for t in all_trees]
    finite = all(np.all(np.isfinite(t["leaf_value"])) for t in all_trees)
    say("model: %d trees in the text of %d trained, leaves a tree min %d "
        "median %d max %d, leaf values finite=%s"
        % (len(all_trees), n_trees, min(sizes), np.median(sizes), max(sizes),
           finite))
    if len(all_trees) != n_trees or not finite:
        path_ok = False          # the model handed back is not the one trained
    init = init_score(params, y)
    del bst, learner, X, Xh, pred
    gc.collect()

    # ---- the traced launch -------------------------------------------------
    ctx = {"spans": spans, "peak": peak}
    breakdown = None
    if args.trace and state["traced"]:
        t_from, t_to, it_from, it_to = state["traced"]
        t0 = time.time()
        path = xtrace.find_xplane(logdir)
        reduced = xtrace.reduce(xtrace.load(path), t_to - t_from)
        say("trace: %s, %d bytes, launch of iterations %d..%d took %.3fs, "
            "read in %.1fs" % (path, os.path.getsize(path), it_from, it_to,
                               t_to - t_from, time.time() - t0))
        if reduced:
            ctx["trace"] = reduced
            ctx["work"] = workmodel.launch_work(all_trees[it_from:it_to], n,
                                                rows.features)
            breakdown = {"device_ops": reduced["device_ops"],
                         "idle_gaps": reduced["idle_gaps"]}
            say("work model of the traced launch (ops, bytes): %s"
                % ctx["work"])
        if not args.keep_trace:
            shutil.rmtree(logdir, ignore_errors=True)

    # ---- correct: the reference follows the trees --------------------------
    t0 = time.time()
    # the same number of trees in every run, however many the window grew
    trees = all_trees[:int(traffic.get("reference_trees", len(all_trees)))]
    numbers, per_tree = check(rows, trees, cfg, init)
    limits = cfg["limits"]
    checks = {name: {"value": numbers[name], "limit": limits[name]}
              for name in limits}
    correct = bool(path_ok and all(c["value"] <= c["limit"]
                                   for c in checks.values()))
    say("reference followed %d trees over %d rows in %.1fs"
        % (len(trees), n, time.time() - t0))
    for col, what in ((1, "leaf value"), (2, "split gain")):
        worst = max(range(len(per_tree)), key=lambda t: per_tree[t][col])
        say("widest %s gap at tree %d: %s" % (what, worst, per_tree[worst]))
    say("leaf value gap by tree: %s"
        % " ".join("%.2g" % p[1] for p in per_tree))
    say("gain gap by tree: %s" % " ".join("%.2g" % p[2] for p in per_tree))

    e2e = {"train_throughput": n * iters / window_s / 1e6,
           "peak_hbm": mem_peak / 1e9,
           "heldout_score": score,
           "setup_s": setup_s}
    return {"correct": correct, "attempted": iters,
            "failed": 0 if path_ok else iters, "end_to_end": e2e, "ctx": ctx,
            "memory_peak_bytes": mem_peak, "breakdown": breakdown,
            "checks": checks, "trees": trees, "rows": rows, "init": init}


def follow(rows, trees, cfg, init, low=False):
    """The reference's per-tree, per-leaf (rows, G, H) over all of the cell's
    rows, each block made again on the device from the seed."""
    return reference.follow(
        (rows.device_block(b) for b in range(rows.blocks)), trees, init,
        cfg["params"]["objective"], rows.group,
        int(cfg["params"]["num_leaves"]), low=low)


def check(rows, trees, cfg, init):
    """The numbers that decide ``correct`` for the model ``trees``."""
    sums = follow(rows, trees, cfg, init)
    return reference.compare(trees, sums, cfg["params"]["learning_rate"],
                             init)


def control(rows, trees, cfg, init, sums=None):
    """The same numbers for the control: the reference in the next lower
    precision (gradients, hessians and their per-leaf sums in bfloat16) put
    in the program's place, answering on the program's own tree structure.
    ``sums``: the float32 reference's, where the caller has them already."""
    lr = cfg["params"]["learning_rate"]
    low = follow(rows, trees, cfg, init, low=True)
    low[..., 1:] = reference.to_bfloat16(low[..., 1:])
    answers = [reference.expected(tree, low[t], lr, init if t == 0 else 0.0)
               for t, tree in enumerate(trees)]
    if sums is None:
        sums = follow(rows, trees, cfg, init)
    return reference.compare(trees, sums, lr, init, answers)

