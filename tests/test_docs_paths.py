"""The documents name only files that exist.

README.md and every live document under docs/ are scanned for backticked
repo paths (`tools/x.py`, `paddle_tpu/a/b.py`, `benchmark/...`, a bare
`name.py` / `name.json`); each has to exist. The three documents that are
history by their own banner are exempt from that and held to the banner
instead: forty comments in the code cite them by path, so they stay where
they are and say up front that nothing in them is current."""

import fnmatch
import functools
import glob
import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HISTORY = ("docs/PERF.md", "docs/STATUS.md", "docs/PLANNER.md")
LIVE = ["README.md"] + sorted(
    p for p in (os.path.relpath(f, REPO)
                for f in glob.glob(os.path.join(REPO, "docs", "*.md")))
    if p not in HISTORY)

_TICKED = re.compile(r"`([^`\n]+)`")
_ROOTED = re.compile(
    r"^(?:tools|paddle_tpu|benchmark|tests|docs|paddle)/[\w./*-]+\.(?:py|json|md|cc)$")
_BARE = re.compile(r"^[\w*-]+\.(?:py|json)$")
# what the program writes beside a saved model, not a file of the repo
WRITTEN_AT_RUN_TIME = {"MANIFEST.json"}


@functools.lru_cache(maxsize=None)
def _basenames():
    names = set()
    for _root, dirs, files in os.walk(REPO):
        dirs[:] = [d for d in dirs if not d.startswith(".")
                   and d not in ("__pycache__", "chiprun_out", "_build")]
        names.update(files)
    return frozenset(names)


def _named_files(text):
    """(token, exists) for every backticked token that reads as a repo
    file. `path.py::name`, `path.py:123` and `path.py --flag` name
    `path.py`; a `*` is a glob that has to match something; a bare name
    may live anywhere in the tree (`executor.py`)."""
    for ticked in _TICKED.findall(text):
        token = ticked.split()[0].split("::")[0]
        token = re.sub(r":[\d,:-]+$", "", token).rstrip(".,;:")
        if _ROOTED.match(token):
            yield token, bool(glob.glob(os.path.join(REPO, token)))
        elif _BARE.match(token) and token not in WRITTEN_AT_RUN_TIME:
            yield token, bool(fnmatch.filter(_basenames(), token))


@pytest.mark.parametrize("doc", LIVE)
def test_document_names_only_files_that_exist(doc):
    with open(os.path.join(REPO, doc)) as f:
        text = f.read()
    gone = sorted({t for t, ok in _named_files(text) if not ok})
    assert not gone, f"{doc} names files that are not in the repo: {gone}"


@pytest.mark.parametrize("doc", HISTORY)
def test_history_document_opens_with_its_banner(doc):
    with open(os.path.join(REPO, doc)) as f:
        head = f.readline()
    assert head.startswith("> **History, not current fact.**"), (doc, head)
