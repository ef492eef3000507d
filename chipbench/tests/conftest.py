import os
import sys

import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


@pytest.fixture(autouse=True)
def _a_trace_directory_of_its_own(monkeypatch, tmp_path):
    """A traced run empties and fills ``<checkout>/.chipbench_trace/<cell>``, and
    the readers take the newest file there: two tests of one cell in two
    workers (``-n 6`` spreads a file's cases) shared it, one's ``rmtree`` under
    the other's profiler or reader (the two cases of ``test_window_cell.py``
    that failed among six workers and passed alone).  Every test gets its own."""
    from chipbench import harness

    monkeypatch.setattr(harness, "TRACE_DIR", str(tmp_path / "chipbench_trace"))
