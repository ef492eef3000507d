"""``BENCHMARK.json`` against the harness's files: everything a cell or a
metric names is there, found by name."""

import importlib
import os
import re

from chipbench import harness

BENCH = harness.load_json(harness.ROOT, "BENCHMARK.json")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


def test_cells_name_files_that_exist():
    configs = {c["name"]: c for c in BENCH["configs"]}
    for cell in BENCH["workloads"]:
        assert NAME.match(cell["name"]) and len(cell["why"]) <= 200
        assert os.path.isfile(os.path.join(harness.ROOT, configs[cell["config"]]["file"]))
        traffic = harness.load_json(harness.BENCH_DIR, "traffic", cell["traffic"] + ".json")
        importlib.import_module(f"chipbench.runners.{traffic['runner']}")
    assert {c["name"] for c in BENCH["configs"]} == {w["config"] for w in BENCH["workloads"]}
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) <= 1


def test_every_metric_has_a_file_and_a_reader():
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    cells = {w["name"] for w in BENCH["workloads"]}
    assert "setup_s" in e2e
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert NAME.match(m["name"])
        spec = harness.load_json(harness.BENCH_DIR, "metrics", m["name"] + ".json")
        assert hasattr(importlib.import_module(f"chipbench.readers.{spec['reader']}"), "read")
        assert set(m.get("workloads", cells)) <= cells
    for m in BENCH["end_to_end"]:
        assert 0 < m["bound"] <= 0.1 and m["source"] in ("host_clock", "device_trace")
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e
        moved = next(e for e in BENCH["end_to_end"] if e["name"] == m["moves"])
        assert set(m.get("workloads", cells)) <= set(moved.get("workloads", cells))
    for cell in BENCH["workloads"]:
        assert len(harness.metrics_for(BENCH, cell, "end_to_end")) >= 2
        assert len(harness.metrics_for(BENCH, cell, "per_layer")) >= 1


def test_peaks_table_is_keyed_by_device_kind():
    peaks = harness.load_json(harness.BENCH_DIR, "peaks.json")
    assert peaks["source"]
    assert peaks["device_kinds"]["TPU v5 lite"]["bf16_flops_per_s"] == 197e12
