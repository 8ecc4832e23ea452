"""Doctest harness: every ``>>>`` example in a public docstring runs in
CI, exactly like the reference's doctest pass over
``python/pathway/**`` (their public docstrings double as tested
examples — e.g. ``xpacks/llm/embedders.py:118-138``).

Each example runs against a FRESH parse graph so examples cannot leak
tables into each other, and a failure reports the owning module/object.
"""

from __future__ import annotations

import doctest
import importlib
import os
import pkgutil
import re
import subprocess

import pytest

import pathway_tpu as pw

#: packages scanned for docstring examples.  Import side effects must be
#: safe on CPU (tests force JAX_PLATFORMS=cpu via conftest).
_SCAN_ROOTS = [
    "pathway_tpu.internals.table",
    "pathway_tpu.internals.expression",
    "pathway_tpu.internals.expressions",
    "pathway_tpu.internals.sql",
    "pathway_tpu.internals.joins",
    "pathway_tpu.internals.groupbys",
    "pathway_tpu.internals.udfs",
    "pathway_tpu.reducers",
    "pathway_tpu.io.gdrive",
    "pathway_tpu.stdlib.temporal",
    "pathway_tpu.stdlib.indexing",
    "pathway_tpu.stdlib.stateful",
    "pathway_tpu.stdlib.ml",
    "pathway_tpu.stdlib.graphs",
    "pathway_tpu.xpacks.llm.parsers",
    "pathway_tpu.xpacks.llm.splitters",
    "pathway_tpu.xpacks.llm.embedders",
    "pathway_tpu.xpacks.llm.document_store",
    "pathway_tpu.xpacks.llm.question_answering",
]


def _iter_doctests():
    finder = doctest.DocTestFinder(exclude_empty=True)
    seen = set()
    for root in _SCAN_ROOTS:
        mod = importlib.import_module(root)
        mods = [mod]
        if hasattr(mod, "__path__"):
            for info in pkgutil.iter_modules(mod.__path__):
                try:
                    mods.append(
                        importlib.import_module(f"{root}.{info.name}")
                    )
                except ImportError:
                    continue
        for m in mods:
            for test in finder.find(m, name=m.__name__):
                if test.examples and test.name not in seen:
                    seen.add(test.name)
                    yield test


_ALL = list(_iter_doctests())


def test_doctest_corpus_nonempty():
    """The harness must actually be covering examples — an import
    regression that silently empties the corpus should fail loudly."""
    assert len(_ALL) >= 12, [t.name for t in _ALL]


@pytest.mark.parametrize("dt_case", _ALL, ids=lambda t: t.name)
def test_docstring_example(dt_case):
    pw.G.clear()
    runner = doctest.DocTestRunner(
        optionflags=doctest.NORMALIZE_WHITESPACE | doctest.ELLIPSIS
    )
    result = runner.run(dt_case)
    assert result.failed == 0, f"{dt_case.name}: {result.failed} failed"


# ---------------------------------------------------------------------------
# the README names files that exist

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _repo_files() -> list[str]:
    """The tracked files; where the checkout is no git repository, the
    files on disk outside dot-directories and build output."""
    try:
        out = subprocess.run(
            ["git", "-C", REPO, "ls-files"], capture_output=True, text=True, timeout=30
        )
        if out.returncode == 0 and out.stdout.strip():
            return out.stdout.split("\n")
    except (OSError, subprocess.TimeoutExpired):
        pass
    files = []
    for root, dirs, names in os.walk(REPO):
        dirs[:] = [d for d in dirs if not d.startswith(".") and d not in ("__pycache__", "build", "chiprun_out", "chip_work")]
        files += [os.path.relpath(os.path.join(root, n), REPO) for n in names]
    return files


def test_readme_names_only_files_that_exist():
    """Every file name the README puts in backticks is, at a ``/``
    boundary, the tail of a file's path in the repo: a document that
    cites a deleted artifact or a renamed module fails here, not in a
    reader's hands."""
    with open(os.path.join(REPO, "README.md")) as f:
        named = set(re.findall(r"`([^`\s]+\.(?:py|md|json|jsonl|sh|cpp))`", f.read()))
    assert len(named) >= 40, sorted(named)  # the pattern still finds them
    files = _repo_files()
    missing = sorted(
        n for n in named if not any(("/" + f).endswith("/" + n) for f in files)
    )
    assert not missing, missing
