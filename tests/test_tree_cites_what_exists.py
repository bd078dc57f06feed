"""The tree's own account of itself names only what is in the tree.

PR 45 deleted the pre-chip benchmark script, its rows' tests, the scripts that
drove it and the records it wrote; `chipbench/` is the one instrument and
`PERF_LEDGER.jsonl` / `PERF.md` the one account. A comment, docstring or
document that still names one of those files argues from a record nobody can
open, and a file of that name is one of them come back. `CHANGES.md`,
`ROADMAP.md`, `SURVEY.md` (history and the blueprint) and `chipbench/` (the
benchmark's own files) are not searched.
"""

import functools
import os

import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_DIRS = ("shuffle_exchange_tpu", "tests", "scripts")
_FILES = ("chip_smoke.py", "README.md", "PERF.md", "pytest.ini",
          os.path.join(".claude", "skills", "verify", "SKILL.md"))
_GONE = ("bench.py", "test_bench_smoke", "profile_config", "tune_config2",
         "bench_ring_hop", "moe_micro", "benchmarks/micro", "ROUND5_NOTES",
         "BASELINE.md", "engine_decode_sweep", "MULTICHIP_r0", "TESTS_r03",
         "ADVICE.md", "notes-survey")


@functools.lru_cache(maxsize=None)
def _texts():
    """{path from the repo's root: text} of every file that is searched."""
    paths = [os.path.join(_REPO, f) for f in _FILES]
    for d in _DIRS:
        for root, dirs, files in os.walk(os.path.join(_REPO, d)):
            dirs[:] = [x for x in dirs if x != "__pycache__"]
            paths += [os.path.join(root, f) for f in files
                      if not f.endswith((".pyc", ".so"))]
    out = {}
    for p in paths:
        if os.path.abspath(p) == os.path.abspath(__file__) or not os.path.isfile(p):
            continue
        with open(p, errors="ignore") as f:
            out[os.path.relpath(p, _REPO)] = f.read()
    return out


@functools.lru_cache(maxsize=None)
def _names():
    """Files by name: the searched ones, the repo's top level and what an
    old `benchmarks/` would hold."""
    top = [f for f in os.listdir(_REPO) if os.path.isfile(os.path.join(_REPO, f))]
    old = os.path.join(_REPO, "benchmarks")
    held = [os.path.join("benchmarks", f) for f in os.listdir(old)] \
        if os.path.isdir(old) else []
    return sorted({*_texts(), *top, *held})


@pytest.mark.parametrize("name", _GONE)
def test_no_file_cites_a_deleted_one(name):
    texts = _texts()
    assert len(texts) > 200, "the walk found too few files to mean anything"
    hits = [f"{path}:{i}" for path, text in texts.items() if name in text
            for i, line in enumerate(text.splitlines(), 1) if name in line]
    hits += [path for path in _names() if name in path]
    assert not hits, f"{name!r} is gone from the tree but still cited at {hits}"
