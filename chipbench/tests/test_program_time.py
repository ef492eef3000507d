"""The reader of the device's program line (``readers/program_time.py``): on
made-up events, on the two recorded chip traces (``data/program_spans``: the
program of before PR 53, private methods' names, spans without arguments;
``data/program_runs``: ``tools/record_program_runs.py`` on a TPU v5 lite, the
programs' own names and spans with ``program``/``seq``), and through ``read``."""

import os

import pytest

from chipbench import harness
from chipbench import trace_reduce as tr
from chipbench.readers import program_time as pt

DATA = os.path.join(os.path.dirname(__file__), "data")
OLD = {"_prefill_impl": "engine.prefill_dispatch", "_join_impl": "engine.join",
       "_step_impl": "engine.step_dispatch"}
SERVING = ["prefill_device_share", "join_device_share", "decode_program_mean_ms",
           "prefill_program_mean_ms", "prefill_queue_delay_mean_ms",
           "device_clock_lead_ms.programs"]


def spec_of(name):
    return harness.metric_spec(name)


@pytest.fixture(scope="module")
def recorded():
    """``name -> (runs, host, busy_s)`` of the two recorded traces."""
    out = {}
    for name, spans in (("program_spans", OLD.values()),
                        ("program_runs", spec_of(SERVING[0])["programs"].values())):
        data = tr.load(os.path.join(DATA, name + ".xplane.pb.gz"))
        out[name] = (*pt.extract(data, set(spans)), tr.reduce(tr.extract(data), 1)["busy_s"])
    return out


def test_every_metric_of_the_reader_shares_one_map_of_programs():
    maps = [spec_of(name)["programs"] for name in SERVING + ["step_program_mean_ms.train"]]
    assert all(m == maps[0] for m in maps)
    assert maps[0] == {"engine_prefill": "engine.prefill_dispatch", "engine_join": "engine.join",
                       "engine_decode": "engine.step_dispatch", "lm_train_step": "train_step"}
    bench = harness.load_json(harness.ROOT, "BENCHMARK.json")
    mine = [m for m in bench["per_layer"] if spec_of(m["name"]).get("reader") == "program_time"]
    assert len(mine) == 13 and bench["per_layer"][-13:] == mine  # appended, after all others
    assert all(m["better"] == "lower" and m["source"] == "device_trace" for m in mine)


def test_the_new_traces_figures(recorded):
    runs, host, busy_s = recorded["program_runs"]
    read = lambda name: pt.figure(spec_of(name), runs, host, busy_s, 1)
    assert [r["program"] for r in runs[0]] == (
        ["engine_prefill", "engine_join"] * 3 + ["engine_decode"] * 5)
    assert read("prefill_device_share") == pytest.approx(16.426963, rel=1e-6)
    assert read("join_device_share") == pytest.approx(27.029573, rel=1e-6)
    assert read("decode_program_mean_ms") == pytest.approx(0.0189146, rel=1e-6)
    assert read("prefill_program_mean_ms") == pytest.approx(0.008197, rel=1e-6)
    assert read("prefill_queue_delay_mean_ms") == pytest.approx(0.0926, rel=1e-6)
    assert read("device_clock_lead_ms.programs") == pytest.approx(0.868584, rel=1e-6)
    assert read("step_program_mean_ms.train") is None  # no train step in a serving trace
    decode = pt.figure({**spec_of("prefill_device_share"), "program": "engine_decode"},
                       runs, host, busy_s, 1)
    # a run holds the gaps between its operations: the three shares pass 100
    assert 100.0 < read("prefill_device_share") + read("join_device_share") + decode < 110.0
    for twin in SERVING:
        assert spec_of(twin + ".moe") == spec_of(twin)


def test_the_old_programs_names_read_nothing_under_the_new(recorded):
    """The parent commit under this benchmark: no run is called
    ``engine_prefill``, every metric is left out of the line, nothing raises."""
    runs, host, busy_s = recorded["program_spans"]
    for name in SERVING + ["step_program_mean_ms.train"]:
        assert pt.figure(spec_of(name), runs, host, busy_s, 1) is None


def test_the_old_trace_read_under_its_own_names(recorded):
    runs, host, busy_s = recorded["program_spans"]
    read = lambda **spec: pt.figure({"programs": OLD, **spec}, runs, host, busy_s, 1)
    means = [read(figure="mean_ms", program=p) * 1e3 for p in OLD]
    assert means == pytest.approx([7.3245, 13.387, 18.786667], rel=1e-6)  # us: 2, 2 and 3 runs
    assert read(figure="matched_share") == 100.0
    assert read(figure="clock_lead_ms") == pytest.approx(0.601709, rel=1e-6)


def run(program, start, dur, flow):
    return {"program": program, "start": start, "end": start + dur, "run_id": flow, "flow": (12, flow)}


def made_up(n_chips=1, window=(0.0, 1000.0)):
    """Two decode runs a chip, launched from two spans on one thread: the
    first span's launch is enqueued from a worker thread (two hops), the
    second from the span's own thread (one)."""
    line, worker = (9, 0), (9, 1)
    spans = {line: pt._nest([(100.0, 200.0, "engine.step_dispatch", {"program": "engine_decode", "seq": 3}),
                             (500.0, 600.0, "engine.step_dispatch", {"program": "engine_decode", "seq": 4})])}
    producers = {(14, 1): (line, 150.0), (14, 2): (line, 550.0)}
    consumers = {worker: pt._nest([(160.0, 190.0, (14, 1))]), line: pt._nest([(560.0, 590.0, (14, 2))])}
    runs = {}
    for chip in range(n_chips):
        producers[(12, 10 * chip + 1)] = (worker, 170.0)
        producers[(12, 10 * chip + 2)] = (line, 570.0)
        runs[chip] = [run("engine_decode", 90.0 + chip, 100.0, 10 * chip + 1),
                      run("engine_decode", 700.0, 400.0, 10 * chip + 2)]
    return runs, (producers, consumers, spans, window)


DECODE = {"engine_decode": "engine.step_dispatch"}


def test_made_up_runs_are_clipped_counted_and_tied():
    runs, host = made_up()
    read = lambda **spec: pt.figure({"programs": DECODE, **spec}, runs, host, 4e-7, 1)
    assert read(figure="device_share", program="engine_decode") == pytest.approx(100.0)  # 100 + 300 of 400
    assert read(figure="mean_ms", program="engine_decode") == pytest.approx(100e-6)  # the second crosses the end
    assert read(figure="matched_share") == 100.0
    assert read(figure="clock_lead_ms") == pytest.approx(10e-6)  # the span opened at 100, its run at 90
    assert read(figure="queue_delay_mean_ms", program="engine_decode") == pytest.approx((-10 + 200) / 2 * 1e-6)
    assert read(figure="mean_ms", program="engine_join") is None
    with pytest.raises(ValueError):
        read(figure="other")


def test_four_chips_are_averaged_and_each_run_is_tied_to_the_one_span():
    runs, host = made_up(n_chips=4)
    read = lambda **spec: pt.figure({"programs": DECODE, **spec}, runs, host, 4e-7, 4)
    assert read(figure="device_share", program="engine_decode") == pytest.approx(100.0)
    assert read(figure="matched_share") == 100.0
    assert read(figure="clock_lead_ms") == pytest.approx(10e-6)  # chip 0's is the earliest
    assert len(pt.matches(runs, host, DECODE)) == 8
    # fewer chips than the trace holds: the first of them
    assert pt.figure({"programs": DECODE, "figure": "mean_ms", "program": "engine_decode"},
                     runs, host, 4e-7, 1) == pytest.approx(100e-6)


def test_no_window_no_runs_no_busy_time_read_nothing():
    runs, host = made_up()
    spec = {"programs": DECODE, "figure": "device_share", "program": "engine_decode"}
    assert pt.figure(spec, runs, (*host[:3], None), 4e-7, 1) is None
    assert pt.figure(spec, {}, host, 4e-7, 1) is None
    assert pt.figure(spec, runs, host, 0.0, 1) is None


def test_read_finds_the_cells_newest_trace_and_loads_it_once(monkeypatch, tmp_path):
    import gzip
    import shutil

    monkeypatch.setattr(harness, "TRACE_DIR", str(tmp_path))
    where = tmp_path / "lm_serve_knee" / "plugins" / "profile" / "recorded"
    where.mkdir(parents=True)
    with gzip.open(os.path.join(DATA, "program_runs.xplane.pb.gz")) as f, \
            open(where / "chip.xplane.pb", "wb") as g:
        shutil.copyfileobj(f, g)
    trace = tr.reduce_file(str(where / "chip.xplane.pb"), 1)
    measured = harness.Measured(attempted=1, failed=0, correct=True, trace=trace)
    ctx = {"measured": measured, "cell": {"name": "lm_serve_knee"}, "device": {"count": 1}}
    loads = []
    real = tr.load
    monkeypatch.setattr(tr, "load", lambda path: (loads.append(path), real(path))[1])
    got = {name: harness.read_metric({"name": name}, ctx) for name in SERVING}
    assert all(v is not None for v in got.values()) and len(loads) == 1
    assert got["decode_program_mean_ms"] == pytest.approx(0.0189146, rel=1e-6)
    assert harness.read_metric({"name": "prefill_device_share.moe"}, ctx) == got["prefill_device_share"]
    # an untraced run reads nothing, whatever lies in the directory; nor does another cell
    bare = {**ctx, "measured": harness.Measured(attempted=1, failed=0, correct=True)}
    bare.pop("program_runs")
    assert harness.read_metric({"name": "prefill_device_share"}, bare) is None
    other = {"measured": measured, "cell": {"name": "glm_serve_docqa"}, "device": {"count": 1}}
    assert harness.read_metric({"name": "prefill_device_share.moe"}, other) is None
