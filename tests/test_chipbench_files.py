"""The benchmark's file-only tests, in tier 1.

``chipbench/tests`` is run by whoever changes the benchmark
(``chipbench/README.md``); the driver's tier-1 command collects ``tests/``
alone, so a later PR's new entries in ``BENCHMARK.json``, its metric files and
its traffic files were held to the contract only if its builder remembered to.
The modules below need no chip, no engine and no model: the contract, the
traffic generator, the readers against recorded traces and counters, the
operation and byte counts.  They are IMPORTED here, test functions and the
fixtures they use alike, under the module's name: there is no second
definition of any of them, and a case added there is collected here.

Left out by name: the ``test_*_cell.py`` modules but this PR's and
``test_runners.py`` / ``test_span_time.py`` (they build models and engines and
run cells end to end: minutes, and tier 1's own tests of those models cover
the program's side)."""

import importlib
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

MODULES = ("test_contract", "test_traffic", "test_readers", "test_flops", "test_trace_reduce",
           "test_paged_roofline", "test_program_time", "test_registry_delta_span_tail",
           "test_completion_cell")


@pytest.fixture(autouse=True)
def _a_trace_directory_of_its_own(monkeypatch, tmp_path):
    """``chipbench/tests/conftest.py``'s: a traced run, or a reader, of one
    cell in two workers must not share ``<checkout>/.chipbench_trace``."""
    from chipbench import harness

    monkeypatch.setattr(harness, "TRACE_DIR", str(tmp_path / "chipbench_trace"))


def _import_tests():
    for name in MODULES:
        module = importlib.import_module("chipbench.tests." + name)
        for attr, value in vars(module).items():
            if attr.startswith("test_") and callable(value):
                globals()[f"test_{name[len('test_'):]}__{attr[len('test_'):]}"] = value
            elif type(value).__name__ == "FixtureFunctionDefinition":
                # a module's fixture keeps its own name: its tests ask for it by
                # that, and no two of the modules above define the same one
                assert attr not in globals() or globals()[attr] is value, (name, attr)
                globals()[attr] = value


_import_tests()
