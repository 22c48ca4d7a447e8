"""The present-tense documents name files that exist.

README.md and docs/COMPONENTS.md describe the tree as it is; a backticked
path (`lightgbm_tpu/ops/grow.py`, `ops/grow.py`, `benchmark/run.py`, or a
bare `chip_smoke.py`) that is gone sends a new owner to nothing. History
(VERDICT.md, BASELINE.md, CHANGES.md) may name what was deleted.
"""
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# `dir/file.ext` or `file.py`, optionally followed by :line, ::test, :symbol
PATH = re.compile(r"`((?:[A-Za-z0-9_.\-]+/)*[A-Za-z0-9_\-]+"
                  r"\.(?:py|json|sh|cpp|toml|yaml))(?:[:#][^`]*)?`")
SKIP_DIRS = {".git", ".cache", "__pycache__", "chiprun_out", "_scratch",
             "_archive_check", ".pytest_cache"}


@pytest.fixture(scope="module")
def tree_files():
    """Every file of the checkout, as a path from the root."""
    out = []
    for dirpath, dirs, files in os.walk(ROOT):
        dirs[:] = [d for d in dirs if d not in SKIP_DIRS]
        rel = os.path.relpath(dirpath, ROOT)
        out += [os.path.normpath(os.path.join(rel, f)) for f in files]
    return out


@pytest.mark.parametrize("doc", ["README.md", "docs/COMPONENTS.md"])
def test_paths_exist(doc, tree_files):
    """A document may shorten a path from the left (`ops/grow.py` for
    `lightgbm_tpu/ops/grow.py`): some file must end with what it names."""
    with open(os.path.join(ROOT, doc), encoding="utf-8") as f:
        paths = sorted(set(PATH.findall(f.read())))
    assert len(paths) > 20, "the pattern has rotted: %r" % paths
    missing = [p for p in paths
               if not any(("/" + t).endswith("/" + p) for t in tree_files)]
    assert not missing, missing
