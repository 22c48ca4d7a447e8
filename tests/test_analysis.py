"""Graft-lint: rule fixtures, engine mechanics, jaxpr audits, self-scan.

Layout mirrors the acceptance criteria:

* every registered JG rule is exercised against seeded-violation
  fixture snippets (positive) and clean twins (negative) — the
  parametrization is driven by the registry, so adding a rule without
  fixtures fails here by construction;
* engine mechanics: inline suppression, skip-file, baseline
  round-trip, unused-import autofix;
* the jaxpr audits run green (the two pinned invariants — no f64
  convert in persist-f32 kernels, serve ladder bound — are tier-1);
* the repo self-scan: ZERO unsuppressed findings, same gate as
  `python -m lightgbm_tpu.analysis`.
"""
import json
import os
import textwrap

import pytest

from lightgbm_tpu.analysis import (GraftlintConfig, all_auditors,
                                   load_config, run_auditors, run_audits,
                                   run_lint)
from lightgbm_tpu.analysis.config import _parse_table
from lightgbm_tpu.analysis.lint import (apply_baseline, iter_py_files,
                                        lint_source, load_baseline,
                                        prune_baseline, write_baseline)
from lightgbm_tpu.analysis.rules import all_rules

OPS = "lightgbm_tpu/ops/fake.py"          # hot path, kernel-bearing
COLD = "lightgbm_tpu/data/fake.py"        # not a hot path


def _ids(findings, rule=None):
    return [f.rule for f in findings
            if not f.suppressed and (rule is None or f.rule == rule)]


def _lint(src, relpath=OPS, **cfg):
    config = GraftlintConfig(**cfg) if cfg else GraftlintConfig()
    return lint_source(textwrap.dedent(src), relpath, config)


# ---------------------------------------------------------------------------
# per-rule fixtures: positive (fires) + negative (clean twin)
# ---------------------------------------------------------------------------

FIXTURES = {
    "JG001": {
        "positive": """
            import jax
            import jax.numpy as jnp

            @jax.jit
            def f(x):
                if jnp.any(x > 0):
                    return x + 1
                return x
            """,
        "negative": """
            import jax
            import jax.numpy as jnp

            @jax.jit
            def f(x, flag: bool):
                if flag:                       # static python value: fine
                    return x + 1
                return jax.lax.cond(jnp.any(x > 0),
                                    lambda v: v + 1, lambda v: v, x)

            def host(x):
                if jnp.any(x > 0):             # not a jitted scope
                    return 1
                return 0
            """,
    },
    "JG002": {
        "positive": """
            import numpy as np

            def serve(batches, dev):
                out = []
                for b in batches:
                    out.append(np.asarray(dev(b)))     # per-batch sync
                    total = dev(b).sum().item()        # and another
                    scale = float(dev(b)[0])           # and another
                return out, total, scale
            """,
        "negative": """
            import numpy as np

            def serve(batches, dev):
                outs = [dev(b) for b in batches]
                return np.asarray(outs)                # one batched sync
            """,
    },
    "JG003": {
        "positive": """
            import jax.numpy as jnp

            def setup(m):
                pad = jnp.zeros((4, 4))                # f64 under x64
                half = jnp.asarray(0.5)                # f64 under x64
                y = jnp.where(m, 1.0, -1.0)            # f64 select
                return pad, half, y

            def _scan_kernel(hb, cf):
                return jnp.floor(hb * cf + 0.5)        # kernel literal
            """,
        "negative": """
            import jax.numpy as jnp

            def setup(m, x):
                pad = jnp.zeros((4, 4), jnp.float32)
                half = jnp.asarray(0.5, jnp.float32)
                y = jnp.where(m, 1.0, -1.0).astype(x.dtype)
                keep = jnp.where(m, 1.0, x)            # one literal: weak
                return pad, half, y, keep

            def _scan_kernel(hb, cf):
                return jnp.floor(hb * cf + jnp.float32(0.5))

            def host_math(a):
                return a * 0.5                         # not a kernel
            """,
    },
    "JG004": {
        "positive": """
            import jax

            from lightgbm_tpu.ops.pallas_grow import make_level_pass

            def train(trees, step, geo):
                outs = []
                for t in trees:
                    f = jax.jit(step)                  # recompile storm
                    outs.append(f(t))
                return outs

            def grow_levels(levels, geo):
                for lv in levels:
                    lp = make_level_pass(*geo)         # builder per level:
                    lv.run(lp)                         # same storm, hidden
            """,
        "negative": """
            import jax

            from lightgbm_tpu.ops.pallas_grow import make_level_pass

            def train(trees, step):
                f = jax.jit(step)                      # hoisted
                outs = []
                for t in trees:
                    outs.append(f(t))

                def make(c):                           # builder in loop is
                    return jax.jit(lambda x: x + c)    # a def, not a call
                return outs, [make(c) for c in (1, 2)]

            def grow_levels(levels, geo):
                lp = make_level_pass(*geo)             # once per geometry
                for lv in levels:
                    lv.run(lp)
            """,
    },
    "JG005": {
        "positive": """
            import time
            import numpy as np

            def sample(n):
                idx = np.random.permutation(n)         # global RNG
                rng = np.random.default_rng(time.time())   # clock seed
                return idx, rng
            """,
        "negative": """
            import numpy as np

            def sample(n, seed):
                rng = np.random.default_rng(seed)
                return rng.permutation(n), np.random.RandomState(seed)
            """,
    },
    "JG006": {
        "positive": """
            from jax.experimental import pallas as pl
            from jax.experimental.pallas import tpu as pltpu

            def kernel_call(f, shape):
                return pl.pallas_call(f, out_shape=shape)
            """,
        "negative": """
            from .pallas_compat import pl, pltpu

            def kernel_call(f, shape):
                return pl.pallas_call(f, out_shape=shape)
            """,
    },
    "JG007": {
        "positive": """
            import json
            from typing import Dict, List

            def f(d: Dict) -> Dict:
                return d
            """,
        "negative": """
            import json
            from typing import Dict

            try:
                import exotic_backend              # probing idiom: skipped
            except ImportError:
                exotic = None

            import unused_but_marked  # noqa: F401

            def f(d: Dict) -> str:
                return json.dumps(d)
            """,
    },
    # JG009 is scoped to the collective paths (parallel/, resilience/)
    "JG009": {
        "relpath": "lightgbm_tpu/parallel/fake.py",
        "positive": """
            import numpy as np
            from jax.experimental import multihost_utils

            def sync_counts(n_local):
                return multihost_utils.process_allgather(   # no guard
                    np.asarray([n_local], np.int64))
            """,
        "negative": """
            import numpy as np
            from jax.experimental import multihost_utils

            from lightgbm_tpu.resilience import retry as resilience_retry

            def sync_counts(n_local):
                return resilience_retry.guard(
                    "allgather:row_counts",
                    multihost_utils.process_allgather,
                    np.asarray([n_local], np.int64))

            def sync_lazy(arr):
                # a closure handed to guard still runs under its deadline
                return resilience_retry.guard(
                    "allgather:lazy",
                    lambda: multihost_utils.process_allgather(arr))
            """,
    },
    # JG010 is scoped to ops//predict/ MINUS the narrow-ok-paths
    # allowlist; the fixture relpath (ops/fake.py) is not allowlisted
    "JG010": {
        "positive": """
            import jax.numpy as jnp
            import numpy as np

            def shrink(x, leaves):
                small = x.astype(jnp.float32)          # unblessed narrow
                tiny = leaves.astype("bfloat16")       # string form too
                half = leaves.astype(dtype=jnp.float16)  # kwarg form
                q = jnp.asarray(x, dtype=jnp.int8)     # quantized payload
                return small, tiny, half, q
            """,
        "negative": """
            import jax.numpy as jnp

            def widen(x, y):
                big = x.astype(jnp.float64)            # widening: fine
                dyn = x.astype(y.dtype)                # dynamic: fine
                arr = jnp.asarray(x, dtype=jnp.float64)
                return big, dyn, arr
            """,
    },
    # JG008 is scoped to the resilience durability paths; its fixtures
    # carry their own relpath (the "relpath" key overrides the OPS default)
    "JG008": {
        "relpath": "lightgbm_tpu/resilience/fake.py",
        "positive": """
            import json

            def save_state(path, state):
                with open(path, "w") as f:         # in-place: torn on kill
                    json.dump(state, f)
            """,
        "negative": """
            import json
            import os

            def save_state(path, state):
                tmp_path = path + ".tmp"
                with open(tmp_path, "w") as f:
                    json.dump(state, f)
                    f.flush()
                    os.fsync(f.fileno())
                os.replace(tmp_path, path)

            def load_state(path):
                with open(path) as f:              # reads are never flagged
                    return json.load(f)
            """,
    },
    # JG011/JG012 are scoped to the threaded host layer
    # (concurrency_paths); their fixtures live in serving/
    "JG011": {
        "relpath": "lightgbm_tpu/serving/fake.py",
        "positive": """
            import threading

            class Server:
                def __init__(self):
                    self._lock = threading.Lock()
                    self._count = 0
                    self._thread = None

                def start(self):
                    self._thread = threading.Thread(target=self._loop,
                                                    daemon=True)
                    self._thread.start()

                def _loop(self):
                    self._count += 1          # racing submit(), no lock

                def submit(self):
                    self._count += 1
            """,
        "negative": """
            import threading

            class Server:
                def __init__(self):
                    self._lock = threading.Lock()
                    self._count = 0
                    self._thread = None

                def start(self):
                    self._thread = threading.Thread(target=self._loop,
                                                    daemon=True)
                    self._thread.start()

                def _loop(self):
                    with self._lock:
                        self._count += 1

                def submit(self):
                    with self._lock:
                        self._count += 1
            """,
    },
    "JG012": {
        "relpath": "lightgbm_tpu/serving/fake.py",
        "positive": """
            import threading

            class Pool:
                def __init__(self):
                    self._lock = threading.Lock()
                    self._done = 0

                def flush(self, fut):
                    with self._lock:
                        out = fut.result()    # convoy: blocks lock-holders
                        self._done += 1
                    return out
            """,
        "negative": """
            import threading

            class Pool:
                def __init__(self):
                    self._lock = threading.Lock()
                    self._done = 0

                def flush(self, fut):
                    out = fut.result()        # block FIRST, then lock
                    with self._lock:
                        self._done += 1
                    return out
            """,
    },
}


def test_every_rule_has_fixtures():
    ids = {r.id for r in all_rules()}
    assert ids == set(FIXTURES), "every JG rule needs fixture snippets"
    assert ids == {"JG001", "JG002", "JG003", "JG004", "JG005", "JG006",
                   "JG007", "JG008", "JG009", "JG010", "JG011", "JG012"}


def test_jg010_scope_and_allowlist():
    """The same narrowing cast is fine outside ops//predict/ (host
    tooling narrows freely) and inside an allowlisted module (the
    blessed kernels); predict/ is in scope."""
    pos = FIXTURES["JG010"]["positive"]
    assert _ids(_lint(pos, relpath=COLD), "JG010") == []
    assert _ids(_lint(pos,
                      relpath="lightgbm_tpu/ops/pallas_histogram.py"),
                "JG010") == []
    assert len(_ids(_lint(pos, relpath="lightgbm_tpu/predict/fake.py"),
                    "JG010")) == 4


def test_jg009_outside_scope_is_silent():
    """The same direct collective call is fine outside the collective
    paths (a test helper gathering once at setup is not the hot DCN
    contract)."""
    hits = _ids(_lint(FIXTURES["JG009"]["positive"], relpath=COLD),
                "JG009")
    assert hits == []


@pytest.mark.parametrize("rule_id", sorted(FIXTURES))
def test_rule_fires_on_seeded_violation(rule_id):
    rp = FIXTURES[rule_id].get("relpath", OPS)
    hits = _ids(_lint(FIXTURES[rule_id]["positive"], relpath=rp), rule_id)
    assert hits, "%s stayed silent on its seeded violation" % rule_id


@pytest.mark.parametrize("rule_id", sorted(FIXTURES))
def test_rule_silent_on_clean_twin(rule_id):
    rp = FIXTURES[rule_id].get("relpath", OPS)
    hits = _ids(_lint(FIXTURES[rule_id]["negative"], relpath=rp), rule_id)
    assert not hits, "%s false-positived on its clean twin" % rule_id


def test_jg008_outside_scope_is_silent():
    """The same in-place write is fine outside the durability paths (the
    CLI writing a predictions file is not a checkpoint)."""
    hits = _ids(_lint(FIXTURES["JG008"]["positive"], relpath=COLD), "JG008")
    assert hits == []


def test_jg002_fixture_counts_and_cold_path():
    pos = FIXTURES["JG002"]["positive"]
    assert len(_ids(_lint(pos), "JG002")) == 3     # asarray + item + float
    assert _ids(_lint(pos, relpath=COLD), "JG002") == []


def test_jg003_flags_each_shape_once():
    hits = _ids(_lint(FIXTURES["JG003"]["positive"]), "JG003")
    assert len(hits) == 4   # zeros, asarray-literal, where, kernel literal


def test_jg007_fix_wraps_long_from_imports(tmp_path):
    """The rewritten statement must stay valid Python: long from-imports
    wrap in parentheses; plain `import a, b` (no legal paren form) is
    left long rather than broken."""
    import ast as ast_mod

    pkg = tmp_path / "lightgbm_tpu"
    pkg.mkdir()
    mod = pkg / "mod.py"
    mod.write_text(
        "import json, very_long_module_name_aaaa, "
        "very_long_module_name_bbbb, very_long_module_name_cccc\n"
        "from some.rather.deep.package.path import (unused_name_xx, "
        "kept_name_aaaaaaaa, kept_name_bbbbbbbb, kept_name_cccccccc)\n"
        "print(very_long_module_name_aaaa, very_long_module_name_bbbb,\n"
        "      very_long_module_name_cccc, kept_name_aaaaaaaa,\n"
        "      kept_name_bbbbbbbb, kept_name_cccccccc)\n")
    cfg = GraftlintConfig(root=str(tmp_path), baseline="baseline.json")
    report = run_lint(config=cfg, autofix=True)
    assert report.autofixed == 2
    fixed = mod.read_text()
    ast_mod.parse(fixed)                     # must still be valid Python
    assert "json" not in fixed and "unused_name_xx" not in fixed
    from_lines = [ln for ln in fixed.splitlines()
                  if ln.startswith("from ")]
    assert all(len(ln) <= 79 for ln in from_lines), from_lines


def test_jg007_autofix_idempotent(tmp_path):
    """Running --autofix twice must be a byte-for-byte no-op. The pinned
    regression: `import os` next to `from os import path` — os's only
    other mention is inside the deletable second import, so pass 1 used
    to keep it and pass 2 deleted it. Both go in pass 1 now."""
    pkg = tmp_path / "lightgbm_tpu"
    pkg.mkdir()
    mod = pkg / "mod.py"
    mod.write_text("import os\n"
                   "from os import path\n"
                   "from some.rather.deep.package.path import ("
                   "unused_name_xx, kept_name_aaaaaaaa, "
                   "kept_name_bbbbbbbb, kept_name_cccccccc)\n"
                   "\n"
                   "def f():\n"
                   "    return (kept_name_aaaaaaaa, kept_name_bbbbbbbb,\n"
                   "            kept_name_cccccccc)\n")
    cfg = GraftlintConfig(root=str(tmp_path), baseline="baseline.json")
    r1 = run_lint(config=cfg, autofix=True)
    t1 = mod.read_text()
    assert r1.autofixed == 3                  # os + path + unused_name_xx
    assert "import os" not in t1 and "unused_name_xx" not in t1
    r2 = run_lint(config=cfg, autofix=True)
    assert r2.autofixed == 0
    assert mod.read_text() == t1, "second --autofix pass changed bytes"


def test_prune_baseline_drops_stale_entries(tmp_path):
    """Stale baseline entries (fixed or deleted findings) are dropped;
    live ones are kept with counts clamped to what still matches —
    a stale suppression can't sit around hiding a regression."""
    src = """
        import jax.numpy as jnp

        def setup():
            return jnp.zeros((4,))
        """
    findings = _lint(src)
    bl = str(tmp_path / "b.json")
    write_baseline(findings, bl)
    # graft in a stale entry + an overcounted live one
    data = json.load(open(bl))
    data["findings"].append({"rule": "JG003", "path": OPS,
                             "snippet": "gone = jnp.ones((4,))",
                             "count": 2})
    data["findings"][0]["count"] += 3        # overcount the live entry
    json.dump(data, open(bl, "w"))
    kept, dropped = prune_baseline(_lint(src), bl)
    assert (kept, dropped) == (1, 5)         # stale 2 + overcount 3
    pruned = load_baseline(bl)
    assert sum(pruned.values()) == 1
    fresh = _lint(src)
    apply_baseline(fresh, pruned)
    assert _ids(fresh) == []                 # live entry still suppresses
    # idempotent: nothing left to prune
    assert prune_baseline(_lint(src), bl) == (1, 0)


def test_write_baseline_keeps_grandfathered(tmp_path):
    """Refreshing the baseline from a report whose findings are already
    baseline-suppressed must re-emit them, not silently drop them (the
    CLI --write-baseline path)."""
    src = """
        import jax.numpy as jnp

        def setup():
            return jnp.zeros((4,))
        """
    findings = _lint(src)
    bl = str(tmp_path / "b.json")
    assert write_baseline(findings, bl) == 1
    again = _lint(src)
    apply_baseline(again, load_baseline(bl))
    assert all(f.suppression == "baseline" for f in again)
    # the refresh the CLI performs: full findings list, suppressed or not
    assert write_baseline(again, bl) == 1
    assert load_baseline(bl)


def test_jg007_fix_rewrites_imports(tmp_path):
    pkg = tmp_path / "lightgbm_tpu"
    pkg.mkdir()
    mod = pkg / "mod.py"
    mod.write_text(textwrap.dedent("""\
        import json
        from typing import Dict, List

        def f(d: Dict) -> Dict:
            return d
        """))
    cfg = GraftlintConfig(root=str(tmp_path), baseline="baseline.json")
    report = run_lint(config=cfg, autofix=True)
    assert report.autofixed == 2
    assert [f for f in report.findings if not f.suppressed] == []
    fixed = mod.read_text()
    assert "import json" not in fixed
    assert "from typing import Dict" in fixed and "List" not in fixed


# ---------------------------------------------------------------------------
# engine mechanics
# ---------------------------------------------------------------------------

def test_inline_suppression_same_line_and_above():
    src = """
        import jax.numpy as jnp

        def setup():
            a = jnp.zeros((4,))  # graftlint: disable=JG003
            # graftlint: disable=JG003
            b = jnp.zeros((4,))
            c = jnp.zeros((4,))
            return a, b, c
        """
    fs = [f for f in _lint(src) if f.rule == "JG003"]
    assert [f.suppressed for f in fs] == [True, True, False]
    assert {f.suppression for f in fs if f.suppressed} == {"inline"}


def test_skip_file_marker():
    src = "# graftlint: skip-file\nimport jax.numpy as jnp\n" \
          "bad = jnp.zeros((4,))\n"
    assert lint_source(src, OPS, GraftlintConfig()) == []


def test_baseline_roundtrip(tmp_path):
    src = """
        import jax.numpy as jnp

        def setup():
            a = jnp.zeros((4,))
            return a, jnp.zeros((8,))
        """
    findings = _lint(src)
    assert len(_ids(findings)) == 2
    bl_path = str(tmp_path / "baseline.json")
    assert write_baseline(findings, bl_path) == 2
    baseline = load_baseline(bl_path)
    fresh = _lint(src)
    apply_baseline(fresh, baseline)
    assert _ids(fresh) == []
    assert all(f.suppression == "baseline" for f in fresh)
    # baseline matches by source line, not line number: new unrelated
    # findings stay unsuppressed
    grown = _lint(src.rstrip() + "\n\n        more = jnp.zeros((2,))\n")
    apply_baseline(grown, baseline)
    assert len(_ids(grown)) == 1


def test_config_table_parsing():
    table = _parse_table(textwrap.dedent("""\
        [tool.other]
        x = 1
        [tool.graftlint]
        include = ["lightgbm_tpu"]
        exclude = [
            "__pycache__",
            "native",
        ]
        baseline = "b.json"
        disable = []
        [tool.after]
        y = 2
        """))
    assert table["include"] == ["lightgbm_tpu"]
    assert table["exclude"] == ["__pycache__", "native"]
    assert table["baseline"] == "b.json"
    assert table["disable"] == []


def test_repo_config_loads_and_walks():
    cfg = load_config()
    files = iter_py_files(cfg)
    assert any(p.endswith("ops/pallas_scan.py") for p in files)
    assert not any("__pycache__" in p for p in files)
    assert cfg.is_hot_path("lightgbm_tpu/ops/grow.py")
    assert not cfg.is_hot_path("lightgbm_tpu/data/dataset.py")


# ---------------------------------------------------------------------------
# jaxpr audits (the two pinned invariants are tier-1 here)
# ---------------------------------------------------------------------------

def test_audits_all_green():
    results = {r.name: r for r in run_audits()}
    assert set(results) == {
        "hist_window_f32", "scan_pair_f32", "scan_blocks_f32",
        "persist_split_pass", "persist_level_pass",
        "predict_traversal_f32", "predict_donation",
        "serve_ladder_bound", "fused_iteration"}
    bad = {n: r.detail for n, r in results.items() if not r.ok}
    assert not bad, bad


def test_audit_catches_f64_convert():
    """The f64 detector actually detects: a deliberately-widening
    program must fail the same check the kernels pass."""
    import jax
    import jax.numpy as jnp

    from lightgbm_tpu.analysis.jaxpr_audit import find_f64_converts

    def leaky(x):
        return x.astype(jnp.float64) * 2.0

    closed = jax.make_jaxpr(leaky)(
        jax.ShapeDtypeStruct((8,), jnp.float32))
    assert find_f64_converts(closed.jaxpr)


def test_audit_catches_callback_in_loop():
    import jax
    import jax.numpy as jnp
    import numpy as np

    from lightgbm_tpu.analysis.jaxpr_audit import find_host_prims_in_loops

    def bad(x):
        def body(_, v):
            return v + jax.pure_callback(
                lambda a: np.asarray(a), jax.ShapeDtypeStruct((), v.dtype),
                v[0])
        return jax.lax.fori_loop(0, 3, body, x)

    closed = jax.make_jaxpr(bad)(jax.ShapeDtypeStruct((4,), jnp.float32))
    assert find_host_prims_in_loops(closed.jaxpr)


# ---------------------------------------------------------------------------
# the gate: repo self-scan
# ---------------------------------------------------------------------------

def test_repo_self_scan_clean():
    """`python -m lightgbm_tpu.analysis` must exit 0: zero unsuppressed
    findings over the whole package (baseline-suppressed grandfathered
    ones are allowed, parse errors are not)."""
    report = run_lint()
    assert report.parse_errors == []
    bad = [(f.path, f.line, f.rule, f.message)
           for f in report.unsuppressed]
    assert not bad, "unsuppressed graft-lint findings:\n%s" % \
        "\n".join("%s:%d %s %s" % b for b in bad)
    assert report.files_scanned > 60


def test_baseline_is_empty():
    """The baseline must shrink, never grow — and since the PR 8
    burn-down of the 8 grandfathered JG002 multihost setup-loop syncs it
    is EMPTY. A PR that adds entries has to justify itself here."""
    cfg = load_config()
    with open(cfg.baseline_path()) as f:
        data = json.load(f)
    assert data["findings"] == [], data["findings"]


def test_lint_lands_on_telemetry_counters():
    """Findings/files land on `analysis::*` counters when telemetry is
    on, so services embedding the gate see lint drift next to their
    perf counters."""
    from lightgbm_tpu.telemetry import events

    prev = events.mode()
    events.enable("timers")
    events.reset()
    try:
        run_lint(paths=["lightgbm_tpu/analysis/lint.py"])
        counts = events.counts_snapshot()
        assert counts.get("analysis::files_scanned", 0) == 1
        assert "analysis::findings" in counts
    finally:
        events.reset()
        if prev == events.OFF:
            events.disable()


def test_cli_smoke(capsys):
    from lightgbm_tpu.analysis.__main__ import main
    assert main(["--list-rules"]) == 0
    out = capsys.readouterr().out
    assert "JG001" in out and "JG007" in out and "JG010" in out
    # --list-audits mirrors --list-rules for the audit registry
    assert main(["--list-audits"]) == 0
    out = capsys.readouterr().out
    for name in ("hist_window", "precision_flow", "transfer",
                 "quant_certify"):
        assert name in out, name
    # lint-only over one file: exits 0 and prints the summary line
    assert main(["lightgbm_tpu/analysis/lint.py", "--no-audit"]) == 0
    assert "graft-lint:" in capsys.readouterr().out


# ---------------------------------------------------------------------------
# whole-program auditors: fixtures enumerated from the registry
# ---------------------------------------------------------------------------
#
# Same contract as the JG rules: every registered auditor needs a
# seeded-violation payload its check_fixture() flags and a clean twin it
# stays silent on — an auditor added without fixtures fails here by
# construction.

AUDITOR_FIXTURES = {
    "collective_order": {
        # rank 0 gathers, everyone else never arrives: deadlock
        "positive": """
            from jax.experimental import multihost_utils

            from lightgbm_tpu.resilience import retry as resilience_retry

            def sync_stats(rank, stats):
                if rank == 0:
                    return resilience_retry.guard(
                        "allgather:stats",
                        multihost_utils.process_allgather, stats)
                return stats
            """,
        # unconditional collective; only the logging is rank-dependent
        "negative": """
            from jax.experimental import multihost_utils

            from lightgbm_tpu.resilience import retry as resilience_retry

            def sync_stats(rank, stats):
                agg = resilience_retry.guard(
                    "allgather:stats",
                    multihost_utils.process_allgather, stats)
                if rank == 0:
                    print(agg)
                return agg
            """,
    },
    "compile_surface": {
        # a per-iteration Python int marked static: unbounded recompiles
        "positive": """
            import functools

            import jax

            @functools.partial(jax.jit, static_argnames=("n_iter",))
            def step(x, n_iter):
                return x * n_iter
            """,
        "negative": """
            import functools

            import jax

            @functools.partial(jax.jit, static_argnames=("interpret",))
            def step(x, interpret):
                return x * 2
            """,
    },
    # f64 gains narrowed to f32 BEFORE the argmax (the tie-flip
    # geometry) vs a range-proven narrowing feeding plain arithmetic
    "precision_flow": {
        "positive": {"program": "tie_flip"},
        "negative": {"program": "bounded_narrow"},
    },
    # a host callback inside a scan body vs the same loop kept on-device
    "transfer": {
        "positive": {"program": "callback_in_scan"},
        "negative": {"program": "clean_scan"},
    },
    # a module that builds a persist scan driver without any path to
    # the numerics::* health flush vs the same module flushing
    "health_covered": {
        "positive": """
            from lightgbm_tpu.ops.grow_persist import make_scan_driver

            def build(gr, gc, k, fn):
                return make_scan_driver(gr, gc, k, fn)
            """,
        "negative": """
            from lightgbm_tpu.ops.grow_persist import make_scan_driver
            from lightgbm_tpu.telemetry.health import flush_device_stats

            def build_and_train(gr, gc, k, fn, pay, args):
                driver = make_scan_driver(gr, gc, k, fn)
                pay, stacked, stats = driver(pay, *args)
                flush_device_stats(stats[2:])
                return stacked
            """,
    },
    # int8 at full plane scale blows the split-decision budget; int16
    # at the higgs geometry certifies (the shipped certificate)
    "quant_certify": {
        "positive": {"name": "hist_int8", "kind": "histogram",
                     "target": "int8", "stochastic": True,
                     "rows_per_rank": 1_312_500, "ranks": 8,
                     "bins": 256, "g_max": 1.0, "h_max": 0.25,
                     "lambda": 1.0},
        "negative": {"name": "hist_int16", "kind": "histogram",
                     "target": "int16", "stochastic": True,
                     "rows_per_rank": 1_312_500, "ranks": 8,
                     "bins": 256, "g_max": 1.0, "h_max": 0.25,
                     "lambda": 1.0},
    },
    # a service-loop thread and submit() racing on an unguarded counter
    # vs the same pair sharing the lock (the deeper per-analysis cases —
    # blocking-hold, lock-order cycles, guarded-by — live in
    # tests/test_concurrency_audit.py)
    "concurrency": {
        "positive": """
            import threading

            class Server:
                def __init__(self):
                    self._lock = threading.Lock()
                    self._count = 0
                    self._thread = None

                def start(self):
                    self._thread = threading.Thread(target=self._loop,
                                                    daemon=True)
                    self._thread.start()

                def _loop(self):
                    self._count += 1

                def submit(self):
                    self._count += 1
            """,
        "negative": """
            import threading

            class Server:
                def __init__(self):
                    self._lock = threading.Lock()
                    self._count = 0
                    self._thread = None

                def start(self):
                    self._thread = threading.Thread(target=self._loop,
                                                    daemon=True)
                    self._thread.start()

                def _loop(self):
                    with self._lock:
                        self._count += 1

                def submit(self):
                    with self._lock:
                        self._count += 1
            """,
    },
}


def test_every_auditor_has_fixtures():
    assert set(AUDITOR_FIXTURES) == set(all_auditors()), \
        "every registered auditor needs fixture payloads"


@pytest.mark.parametrize("name", sorted(AUDITOR_FIXTURES))
def test_auditor_fires_on_seeded_violation(name):
    mod = all_auditors()[name]
    payload = AUDITOR_FIXTURES[name]["positive"]
    if isinstance(payload, str):
        payload = textwrap.dedent(payload)
    hits = mod.check_fixture(payload)
    assert hits, "%s stayed silent on its seeded violation" % name


@pytest.mark.parametrize("name", sorted(AUDITOR_FIXTURES))
def test_auditor_silent_on_clean_twin(name):
    mod = all_auditors()[name]
    payload = AUDITOR_FIXTURES[name]["negative"]
    if isinstance(payload, str):
        payload = textwrap.dedent(payload)
    hits = mod.check_fixture(payload)
    assert not hits, "%s false-positived on its clean twin: %s" \
        % (name, hits)


def test_collective_auditor_divergence_forms():
    """Beyond the registry fixture: symmetric branches are rank-safe,
    early exits under rank branches are not, and derived rank values
    (cuts[rank]) taint through arithmetic but not through calls."""
    from lightgbm_tpu.analysis import collective_audit as co
    symmetric = """
        from jax.experimental import multihost_utils

        from lightgbm_tpu.resilience import retry as resilience_retry

        def sync(rank, a, b):
            if rank == 0:
                out = resilience_retry.guard(
                    "allgather:x", multihost_utils.process_allgather, a)
            else:
                out = resilience_retry.guard(
                    "allgather:x", multihost_utils.process_allgather, b)
            return out
        """
    assert co.check_fixture(textwrap.dedent(symmetric)) == []
    early_exit = """
        from jax.experimental import multihost_utils

        from lightgbm_tpu.resilience import retry as resilience_retry

        def sync(rank, cuts, stats):
            start = cuts[rank]
            if start < 0:
                return None
            return resilience_retry.guard(
                "allgather:stats",
                multihost_utils.process_allgather, stats)
        """
    hits = co.check_fixture(textwrap.dedent(early_exit))
    assert hits and "early exit" in hits[0]
    call_barrier = """
        from jax.experimental import multihost_utils

        from lightgbm_tpu.resilience import retry as resilience_retry

        def sync(rank, stats):
            counts = resilience_retry.guard(
                "allgather:counts",
                multihost_utils.process_allgather, stats)
            if counts.sum() > 0:     # collective result: rank-uniform
                return resilience_retry.guard(
                    "allgather:stats",
                    multihost_utils.process_allgather, stats)
            return None
        """
    assert co.check_fixture(textwrap.dedent(call_barrier)) == []


def test_collective_trace_extracts_repo_sites():
    """The abstract trace covers the known DCN call sites with their
    guard labels — the artifact the item-2 collectives rewrite diffs."""
    from lightgbm_tpu.analysis import collective_audit as co
    trace = co.extract_repo_trace()
    names = {s["name"] for s in trace["sites"] if s["name"]}
    assert {"allgather:binning_sizes", "allgather:binning_mappers",
            "allreduce:metrics_values", "allgather:row_counts",
            # the ONE resume-agreement exchange (reshard.agree_generation)
            # every resuming rank joins — same-mesh and elastic alike —
            # guarded and rank-uniform like any other DCN site
            "allgather:resume_agree"} <= names
    assert all(s["guarded"] for s in trace["sites"])
    assert trace["findings"] == []
    # the item-2 wire format: in-program mesh collectives (quantized
    # plane reductions + the PV-Tree vote allgather) ride the trace as
    # mesh_sites — the top-k vote exchange and every histogram-plane
    # reduce site must be labeled and present in BOTH growers
    mesh = trace["mesh_sites"]
    assert all(s["mesh"] and s["name"] for s in mesh), \
        "every mesh-collective wrapper call needs a literal label"
    mesh_names = {s["name"] for s in mesh}
    assert {"allgather:vote_topk", "psum:vote_windows",
            "psum:vote_planes", "psum:hist_root", "psum:hist_level",
            "psum:hist_split", "psum:hist_plane"} <= mesh_names
    by_path = {}
    for s in mesh:
        by_path.setdefault(s["path"], set()).add(s["name"])
    assert "allgather:vote_topk" in by_path["lightgbm_tpu/ops/grow.py"]
    assert "allgather:vote_topk" \
        in by_path["lightgbm_tpu/ops/grow_persist.py"]


def test_compile_audit_enumerates_known_entry_points():
    """The AST walk must see the real jit surface: the kernel entry
    points, the predict runtime's static raw flag, and the factories."""
    from lightgbm_tpu.analysis import compile_audit as ca
    surf = ca.compile_surface()
    funcs = {s["func"] for s in surf["sites"]}
    assert {"hist_window", "scan_pair", "scan_blocks",
            "build_histogram"} <= funcs
    assert any(s["static_nums"] == [1] for s in surf["sites"]
               if "runtime.py" in s["path"])
    assert surf["serve_ladder_bound"] == 9     # ceil(log2(65536/256))+1
    assert surf["total_bound"] <= 64
    assert all(not s["unbounded"] for s in surf["sites"])


def test_auditors_all_green_on_repo():
    """The whole-program auditors pass on the repo itself — the same
    results the CLI gate appends to the jaxpr audits."""
    results = {r.name: r for r in run_auditors()}
    assert set(results) == {"collective_order", "collective_guarded",
                            "collective_observed", "compile_surface",
                            "precision_flow", "transfer",
                            "quant_certify", "health_covered",
                            "concurrency_discipline",
                            "concurrency_blocking_hold",
                            "concurrency_lock_order"}
    bad = {n: r.detail for n, r in results.items() if not r.ok}
    assert not bad, bad


def test_transfer_auditor_flags_large_all_gather():
    """Beyond the registry fixture: the replicated-intermediate arm —
    an in-program all_gather whose output exceeds the size threshold
    is a finding, the same program under a lax threshold is not."""
    from lightgbm_tpu.analysis import transfer_audit as ta
    hits = ta.check_fixture({"program": "all_gather_large",
                             "threshold": 1 << 16})
    assert hits and "replicated" in hits[0]
    assert ta.check_fixture({"program": "all_gather_large",
                             "threshold": 1 << 30}) == []


def test_gate_flips_on_seeded_tie_flip(monkeypatch, capsys):
    """LGBTPU_SEED_TIE_FLIP=1 arms the seeded tie-flip program as a
    live precision_flow audit: the CLI gate must exit 1."""
    from lightgbm_tpu.analysis.__main__ import main
    from lightgbm_tpu.analysis.precision_audit import SEED_TIE_FLIP_ENV
    monkeypatch.setenv(SEED_TIE_FLIP_ENV, "1")
    code = main(["--json", "--audit-only"])
    payload = json.loads(capsys.readouterr().out)
    assert code == 1 and payload["exit_code"] == 1
    bad = [a for a in payload["audits"]
           if a["name"] == "precision_flow" and not a["ok"]]
    assert bad and "tie_flip" in bad[0]["detail"]


def test_gate_flips_on_seeded_custom_jvp_f64(monkeypatch, capsys):
    """LGBTPU_SEED_CUSTOM_JVP_F64=1 arms the f64-const-in-custom_jvp
    fixture as a live jaxpr audit: the CLI gate must exit 1 with the
    const named (the class the pre-dataflow walk missed)."""
    from lightgbm_tpu.analysis.__main__ import main
    from lightgbm_tpu.analysis.jaxpr_audit import SEED_CUSTOM_JVP_ENV
    monkeypatch.setenv(SEED_CUSTOM_JVP_ENV, "1")
    code = main(["--json", "--audit-only"])
    payload = json.loads(capsys.readouterr().out)
    assert code == 1 and payload["exit_code"] == 1
    bad = [a for a in payload["audits"]
           if a["name"] == "seeded_custom_jvp_f64"]
    assert bad and not bad[0]["ok"]
    assert "const f64" in bad[0]["detail"]


def test_cli_gate_json_green(capsys):
    """`python -m lightgbm_tpu.analysis --json` — the EXACT gate
    pre-commit runs — exits 0 on the repo, reports all five new audit
    results, and ships the auditor artifacts in the payload."""
    from lightgbm_tpu.analysis.__main__ import main
    code = main(["--json"])
    payload = json.loads(capsys.readouterr().out)
    assert code == 0 and payload["exit_code"] == 0
    audit_names = {a["name"] for a in payload["audits"]}
    assert {"collective_order", "collective_guarded",
            "collective_observed",
            "compile_surface", "precision_flow", "transfer",
            "quant_certify", "health_covered"} <= audit_names
    assert payload["lint"]["counts"]["unsuppressed"] == 0
    assert payload["collective_trace"]["findings"] == []
    assert payload["compile_surface"]["total_bound"] <= 64
    # the machine-checkable quantization certificate: every spec green,
    # and the int16 histogram bound within the pinned decision budget
    qc = payload["quant_certificate"]
    assert qc["all_ok"]
    hist16 = [c for c in qc["certificates"]
              if c["spec"]["name"].startswith("hist_int16")]
    assert hist16 and all(
        c["bound"] <= qc["budgets"]["split_decision"] for c in hist16)


def test_jg007_skips_imports_sharing_a_line(tmp_path):
    """An import sharing a source line with other code (or a trailing
    comment) is not removable: both the usage count and the fix are
    line-grained, so deleting the line would take the neighbour with
    it (`import os; x = os.path` used to lose the assignment)."""
    pkg = tmp_path / "lightgbm_tpu"
    pkg.mkdir()
    mod = pkg / "mod.py"
    text = ("import os; x = os.path\n"
            "import json  # tooling hook\n"
            "print(x)\n")
    mod.write_text(text)
    cfg = GraftlintConfig(root=str(tmp_path), baseline="baseline.json")
    report = run_lint(config=cfg, autofix=True)
    assert _ids(report.findings, "JG007") == []
    assert report.autofixed == 0
    assert mod.read_text() == text, "autofix touched a shared line"


def test_baseline_rewrites_refuse_filtered_scans(capsys):
    """--prune-baseline / --write-baseline under --rules or path args
    exit 2 without touching the file: a filtered report would mark
    every out-of-scope baseline entry stale and destroy it."""
    from lightgbm_tpu.analysis.__main__ import main
    bl = load_config().baseline_path()
    before = open(bl).read()
    assert main(["--prune-baseline", "--rules", "JG007"]) == 2
    assert main(["lightgbm_tpu/ops", "--prune-baseline"]) == 2
    assert main(["--write-baseline", "--rules", "JG002"]) == 2
    err = capsys.readouterr().err
    assert "full unfiltered scan" in err
    assert open(bl).read() == before


def test_compile_audit_sees_nondecorator_partial_sites():
    """partial(jax.jit, ...) used as an expression (assignment/factory
    form, not a decorator) is the same recompile surface and must be
    enumerated — an unregistered static name there fails the gate."""
    from lightgbm_tpu.analysis.compile_audit import analyze_source
    src = textwrap.dedent("""
        import functools

        import jax

        def body(x, n_iter):
            return x * n_iter

        step = functools.partial(
            jax.jit, static_argnames=("n_iter",))(body)
        """)
    sites = analyze_source(src, "lightgbm_tpu/ops/fixture.py")
    assert [s.kind for s in sites] == ["call"]
    assert sites[0].unbounded == ["n_iter"]


def test_auditor_artifacts_single_pass_matches_fresh():
    """compute_artifacts + run_all(artifacts=...) — the --json CLI's
    single-pass path — must produce the same verdicts and payload as
    fresh per-consumer computation."""
    from lightgbm_tpu.analysis import auditors
    from lightgbm_tpu.analysis import collective_audit, compile_audit
    config = load_config()
    art = auditors.compute_artifacts(config)
    assert set(art) == set(auditors.all_auditors())
    cached = auditors.run_all(config, artifacts=art)
    fresh = auditors.run_all(config)
    assert [(a.name, a.ok, a.detail) for a in cached] \
        == [(a.name, a.ok, a.detail) for a in fresh]
    assert collective_audit.extract_repo_trace(
        config, artifact=art["collective_order"]) \
        == collective_audit.extract_repo_trace(config)
    assert compile_audit.compile_surface(
        config, artifact=art["compile_surface"]) \
        == compile_audit.compile_surface(config)
