"""The repo's front page describes the system the ledger measures.

``README.md`` and ``docs/*.md`` may name only files that exist, and the
README names the one benchmark (``BENCHMARK.json``'s command) and its two
records.  PR 28 deleted the pre-chip bench stack; these cases fail when a
document cites a file that is gone (or never was).
"""

import functools
import glob
import itertools
import json
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

DOCS = ["README.md"] + sorted(
    os.path.join("docs", f)
    for f in os.listdir(os.path.join(ROOT, "docs"))
    if f.endswith(".md")
)

#: Where a cited path may be rooted.
BASES = ("", "combblas_tpu", "tests")

_TOKEN = re.compile(r"`([^`\n]+)`")
_FILE = re.compile(r"^[^\s()=]+\.(?:py|md|jsonl|json)$")

#: Outside the checkout, or a placeholder (``<workdir>/...``,
#: ``$COMBBLAS_WAL/...``).  Upstream CombBLAS sources cited from
#: SURVEY.md end in .cpp / .h and are not swept.
_ALLOWED_PREFIXES = ("/root/", "$")


def _strip_anchor(tok: str) -> str:
    """``path.py:123`` / ``path.py::name`` -> ``path.py``."""
    return re.sub(r"(\.(?:py|md|jsonl|json))(?::{1,2}[\w.\[\]-]+)+$",
                  r"\1", tok)


def _expand_braces(tok: str) -> list[str]:
    m = re.search(r"\{([^{}]*)\}", tok)
    if not m:
        return [tok]
    head, tail = tok[:m.start()], tok[m.end():]
    return list(itertools.chain.from_iterable(
        _expand_braces(head + alt + tail) for alt in m.group(1).split(",")
    ))


@functools.cache
def _file_names() -> frozenset:
    """Every file name the checkout's own directories hold."""
    names = {f for f in os.listdir(ROOT)
             if os.path.isfile(os.path.join(ROOT, f))}
    for top in ("combblas_tpu", "tests", "docs", "chipbench", "scripts"):
        for _d, _s, files in os.walk(os.path.join(ROOT, top)):
            names.update(files)
    return frozenset(names)


def _exists(path: str) -> bool:
    if "/" not in path and "*" not in path:
        return path in _file_names()  # a bare name: anywhere in the tree
    return any(
        glob.glob(os.path.join(ROOT, base, path)) for base in BASES
    )


def cited_files(text: str) -> list[str]:
    out = []
    for tok in _TOKEN.findall(text):
        tok = _strip_anchor(tok.strip())
        if not _FILE.match(tok):
            continue
        if tok.startswith(_ALLOWED_PREFIXES) or "<" in tok:
            continue
        out.extend(_expand_braces(tok))
    return out


@pytest.mark.parametrize("doc", DOCS)
def test_document_names_only_files_that_exist(doc):
    with open(os.path.join(ROOT, doc), encoding="utf-8") as f:
        cited = cited_files(f.read())
    assert cited, f"{doc}: the sweep found no cited file at all"
    missing = sorted({p for p in cited if not _exists(p)})
    assert not missing, f"{doc} names files that do not exist: {missing}"


def test_readme_names_the_benchmark_and_its_records():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        command = " ".join(json.load(f)["command"])
    with open(os.path.join(ROOT, "README.md"), encoding="utf-8") as f:
        readme = f.read()
    for needle in (command, "BENCHMARK.json", "PERF.md",
                   "PERF_LEDGER.jsonl"):
        assert needle in readme, f"README.md does not name {needle!r}"


def test_the_sweep_sees_a_missing_file():
    """The checker itself: anchors stripped, braces expanded, a file
    that is gone reported."""
    text = ("see `serve/engine.py:12`, `tests/test_obs.py::test_x`, "
            "`serve/{api,nonesuch}.py`, `bench.py` and `$COMBBLAS_WAL/wal.jsonl`")
    cited = cited_files(text)
    assert cited == ["serve/engine.py", "tests/test_obs.py",
                     "serve/api.py", "serve/nonesuch.py", "bench.py"]
    assert [p for p in cited if not _exists(p)] == [
        "serve/nonesuch.py", "bench.py"
    ]
