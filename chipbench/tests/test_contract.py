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


def test_a_serving_cell_replays_its_files_arrival_trace():
    """The property, for whatever serving cells there are: a later PR adds its
    own and edits nothing here."""
    serving = 0
    for cell in BENCH["workloads"]:
        traffic = harness.load_json(harness.BENCH_DIR, "traffic", cell["traffic"] + ".json")
        if traffic["runner"].startswith("serve"):
            serving += 1
            assert isinstance(traffic["schedule_seed"], int), cell["name"]
    assert serving >= 1


def test_every_metric_has_a_file_and_a_reader():
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    cells = {w["name"] for w in BENCH["workloads"]}
    assert "setup_s" in e2e
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert NAME.match(m["name"])
        spec = harness.metric_spec(m["name"])
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


def test_a_twin_metric_reads_what_its_original_reads():
    """One quantity under two names, where its cells are judged on different
    end-to-end metrics: the twin's file names the original's, and the entries
    differ in name, ``moves`` and cells alone."""
    by_name = {m["name"]: m for m in BENCH["end_to_end"] + BENCH["per_layer"]}
    twins = 0
    for m in by_name.values():
        raw = harness.load_json(harness.BENCH_DIR, "metrics", m["name"] + ".json")
        if "same_as" not in raw:
            continue
        twins += 1
        base = by_name[raw["same_as"]]
        assert harness.metric_spec(m["name"]) == harness.metric_spec(base["name"])
        assert "reader" in harness.metric_spec(m["name"])
        assert all(m.get(k) == base.get(k) for k in ("unit", "better", "source", "layer")), m["name"]
        assert not set(m["workloads"]) & set(base["workloads"]), m["name"]
    assert twins >= 1


def test_the_expert_cell_reports_the_median_under_its_own_name():
    cell = next(w for w in BENCH["workloads"] if w["name"] == "glm_serve_docqa")
    names = {m["name"] for m in harness.metrics_for(BENCH, cell, "end_to_end")}
    assert {"req_ms_per_token_p50.moe", "setup_s"} <= names and "req_ms_per_token_p50" not in names
    measured = harness.Measured(attempted=3, failed=0, correct=True, values={"setup_s": 1.0},
                                lists={"req_ms_per_token": [3.0, 1.0, 2.0]})
    read = lambda name: harness.read_metric({"name": name}, {"measured": measured})
    assert read("req_ms_per_token_p50.moe") == read("req_ms_per_token_p50") == 2.0
