"""One case of PR 23's tests pins the exact set of per-layer metrics the
serving cell reported then.  PR 24 adds four that read the program's registry,
which a CPU run reports too, and may not edit that file: the case is marked as
expected to fail (strictly, so that a benchmark PR that updates it is told to
drop this hook) and ``tests/test_span_time.py`` runs the same cell against the
set as it is now."""

import pytest

SUPERSEDED = "test_runner_end_to_end[lm_serve_steady-1-expect4]"


def pytest_collection_modifyitems(items):
    for item in items:
        if item.name == SUPERSEDED:
            item.add_marker(pytest.mark.xfail(
                strict=True, reason="pins PR 23's metric set; superseded by "
                "test_span_time.py::test_serving_cell_reports_the_registry_metrics"))
