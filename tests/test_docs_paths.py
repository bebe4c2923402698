"""The documents a new owner opens first name files that exist.

README.md, PERF.md and ROADMAP.md cite code by path. A back-ticked path
that ends in ``.py``, ``.json`` or ``.md`` and starts at the root of the
repo (``tools/loadgen.py``, ``chip_smoke.py``) must be a file of the tree;
a bare name (``engine.py``) must be the name of one. Paths that start
elsewhere (inside a package, in the reference repo, absolute) are not
judged. A line that names a file which went on purpose says so with the
word ``deleted``. CHANGES.md is history and is not read.
"""
import functools
import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
GONE = "deleted"
#: files the program writes, named where the README describes a format
WRITTEN_AT_RUN_TIME = {"metadata.json", "manifest.json", "autotune.json"}
_TICKED = re.compile(r"`([^`\s]+)`")
_PATH = re.compile(r"^[A-Za-z0-9_.-]+(?:/[A-Za-z0-9_.-]+)*\.(?:py|json|md)$")


@functools.lru_cache(maxsize=None)
def _tree():
    """Every file of the tree, relative to the root, '/'-separated; the
    directories .gitignore lists (build and run leftovers) left out."""
    with open(os.path.join(REPO, ".gitignore")) as f:
        skip = {line.strip().rstrip("/") for line in f
                if line.strip().endswith("/")} | {".git"}
    files = set()
    for root, dirs, names in os.walk(REPO):
        dirs[:] = [d for d in dirs if d not in skip]
        rel = os.path.relpath(root, REPO).replace(os.sep, "/")
        files.update(n if rel == "." else f"{rel}/{n}" for n in names)
    return frozenset(files)


def _cited_paths(line):
    for token in _TICKED.findall(line):
        # `path.py::TestClass::test`, `path.py:41`, `path.py:170-177`
        token = re.split(r"::|:\d", token, maxsplit=1)[0]
        if _PATH.match(token):      # not absolute, a glob or a placeholder
            yield token


@pytest.mark.parametrize("doc", ["README.md", "PERF.md", "ROADMAP.md"])
def test_documents_name_files_that_exist(doc):
    files = _tree()
    tops = {f.split("/", 1)[0] for f in files}
    names = {f.rsplit("/", 1)[-1] for f in files} | WRITTEN_AT_RUN_TIME
    missing = []
    with open(os.path.join(REPO, doc)) as f:
        for n, line in enumerate(f, 1):
            if GONE in line.lower():
                continue
            for path in _cited_paths(line):
                if "/" not in path:
                    found = path in names
                elif path.split("/", 1)[0] in tops:
                    found = path in files
                else:
                    continue
                if not found:
                    missing.append(f"{doc}:{n}: `{path}`")
    assert not missing, (
        "cited files that are not in the tree (a line that names a file "
        f"which went on purpose says `{GONE}`):\n" + "\n".join(missing))
