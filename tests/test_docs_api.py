"""The generated API reference (docs/gen_api.py) renders.

The reference ships a Sphinx tree (``/root/reference/docs/source/``); here
the reference pages are generated on demand from live docstrings (the
output directory is not tracked), and these tests are what CI runs: every
listed module imports and a docstring that breaks a page fails.
"""

import os
import sys

sys.path.insert(0, os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "docs"))

import gen_api  # noqa: E402


def test_render_covers_core_surface():
    page = gen_api.render_module("moolib_tpu.broker", "Broker")
    assert "class `Broker`" in page
    assert "Broker.update" in page
    # Docstrings flow through verbatim.
    assert "Evict silent peers" in page


def test_all_modules_import_and_render():
    pages = gen_api.render_all()
    assert "README.md" in pages
    failures = [f for f, c in pages.items() if "import failed" in c]
    assert not failures, failures
    # Every listed module produced a non-trivial page.
    thin = [f for f, c in pages.items() if len(c) < 80]
    assert not thin, thin
