"""The hand-written documents and the CI script against the tree they describe.

No tracked file is the output of running another, so nothing regenerates a
document when a file goes: these cases are what notices.  A document may name
only paths that exist, ``scripts/ci.sh`` may run only files that exist, the
README names every cell of ``BENCHMARK.json`` (read here, never written), and
the operator console still renders a frame.  No case builds a model.
"""

import functools
import json
import os
import re
import sys
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "scripts"))

import mtop  # noqa: E402

DOCUMENTS = [
    "README.md",
    "docs/DESIGN.md",
    "docs/RESILIENCE.md",
    "docs/TELEMETRY.md",
    "docs/ANALYSIS.md",
    "docs/MIGRATION.md",
    "moolib_tpu/examples/README.md",
]
# This repository's top-level directories; the reference's ``src/...`` and
# ``examples/...``, which the README's table cites, start with neither.
_OWN_DIRS = ("moolib_tpu", "benchmarks", "scripts", "tests", "docs",
             "chipbench", "native")
_SUFFIXES = (".py", ".sh", ".json", ".md", ".cc", ".h")
_QUOTED = re.compile(r"`([^`\s]+)`")
_CI_PATH = re.compile(r"\b((?:benchmarks|scripts|tests|docs)/[\w/]+\.py)\b")


def _read(rel):
    with open(os.path.join(ROOT, rel)) as f:
        return f.read()


def _named_paths(text):
    """Back-quoted paths of this repository in ``text``: those under one of
    its own directories, and bare file names (``bench.py``, ``group.py``).
    Of bare ``.json`` names only the capitalised count: the root's records
    are named so, and a lower-case one is a file that a run writes."""
    out = set()
    for token in _QUOTED.findall(text):
        token = token.rstrip(".,;:)")
        if not token.endswith(_SUFFIXES) or "*" in token or "<" in token:
            continue
        if "/" in token:
            if token.split("/", 1)[0] in _OWN_DIRS:
                out.add(token)
        elif not token.endswith(".json") or token[0].isupper():
            out.add(token)
    return sorted(out)


@functools.lru_cache(maxsize=None)
def _tree_names():
    """Every file name of the tree (a document names a module by its file
    name alone where the section says which package it is about)."""
    names = set(os.listdir(ROOT))
    for top in _OWN_DIRS:
        for _dir, _subdirs, files in os.walk(os.path.join(ROOT, top)):
            names.update(files)
    return names


def _in_tree(path):
    if "/" in path:
        return os.path.exists(os.path.join(ROOT, path))
    return path in _tree_names()


def _ci_paths():
    lines = [l for l in _read("scripts/ci.sh").splitlines()
             if not l.lstrip().startswith("#")]
    return sorted(set(_CI_PATH.findall("\n".join(lines))))


def _cells():
    return [w["name"] for w in json.loads(_read("BENCHMARK.json"))["workloads"]]


@pytest.mark.parametrize("doc", DOCUMENTS)
def test_a_document_names_only_paths_that_exist(doc):
    missing = [p for p in _named_paths(_read(doc)) if not _in_tree(p)]
    assert not missing, f"{doc} names paths that are not in the tree: {missing}"


@pytest.mark.parametrize("path", _ci_paths())
def test_ci_runs_only_files_that_exist(path):
    assert os.path.isfile(os.path.join(ROOT, path)), (
        f"scripts/ci.sh runs {path}, which is not in the tree")


@pytest.mark.parametrize("cell", _cells())
def test_readme_names_every_cell(cell):
    assert f"`{cell}`" in _read("README.md"), (
        f"README.md's chip paragraph does not name the cell {cell}")


def test_mtop_prints_one_plain_frame(free_port, capsys):
    """``scripts/mtop.py --once`` against a loopback broker with one peer:
    the frame has the header, the column titles and the peer's row."""
    import numpy as np

    from moolib_tpu import Accumulator, Broker

    addr = f"127.0.0.1:{free_port}"
    broker = Broker()
    broker.set_name("broker")
    broker.listen(addr)
    acc = Accumulator("mtopdoc", {"w": np.zeros(2, np.float32)})
    acc._rpc.set_name("mtop-peer")
    acc.listen("127.0.0.1:0")
    acc.connect(addr)
    try:
        deadline = time.time() + 30
        while not acc.connected():
            assert time.time() < deadline, "the peer never joined the group"
            broker.update()
            acc.update()
            time.sleep(0.02)
        rc = mtop.main(["--broker", addr, "--group", "mtopdoc", "--once",
                        "--require-peers", "1"])
    finally:
        acc.close()
        broker.close()
    frame = capsys.readouterr().out.splitlines()
    assert rc == 0, frame
    assert frame[0].startswith("mtop ") and "peers live=1 shown=1" in frame[0]
    assert frame[1].split() == [title for title, _w in mtop.COLUMNS]
    assert frame[2].split()[:2] == ["mtop-peer", "member"]
