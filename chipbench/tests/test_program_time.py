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
# the figures of engine_prefill and engine_join stand under their ``.moe`` names alone since PR
# 58: every admission of the dense replica rides engine_admit_step, and the un-suffixed four
# read nothing in the two cells they had
SERVING = ["prefill_device_share.moe", "join_device_share.moe", "decode_program_mean_ms",
           "prefill_program_mean_ms.moe", "prefill_queue_delay_mean_ms.moe",
           "device_clock_lead_ms.programs"]
PROGRAMS = {"engine_prefill": "engine.prefill_dispatch", "engine_join": "engine.join",
            "engine_decode": "engine.step_dispatch", "lm_train_step": "train_step",
            "engine_admit_step": "engine.admit_step_dispatch"}


def spec_of(name):
    return harness.metric_spec(name)


@pytest.fixture(scope="module")
def recorded():
    """``name -> (runs, host, busy_s)`` of the two recorded traces."""
    out = {}
    for name, spans in (("program_spans", OLD.values()),
                        ("program_runs", PROGRAMS.values())):
        data = tr.load(os.path.join(DATA, name + ".xplane.pb.gz"))
        out[name] = (*pt.extract(data, set(spans)), tr.reduce(tr.extract(data), 1)["busy_s"])
    return out


def test_every_metric_of_the_reader_shares_one_map_of_programs():
    """Selected by reader, wherever the entries stand.  The map is the files of
    ``programs/`` (a later PR's program is a file more, no edit) and no metric's
    file brings one of its own: one map, so one load of the trace a line."""
    bench = harness.load_json(harness.ROOT, "BENCHMARK.json")
    mine = [m for m in bench["per_layer"] if spec_of(m["name"]).get("reader") == "program_time"]
    assert {m["name"] for m in mine} >= set(SERVING) | {"step_program_mean_ms.train", "admit_step_mfu"}
    assert pt.programs() == PROGRAMS
    assert not any("programs" in spec_of(m["name"]) for m in mine)
    assert all(m["source"] == "device_trace" for m in mine)
    assert [m["name"] for m in mine if m["better"] == "higher"] == ["admit_step_mfu"]


def test_the_new_traces_figures(recorded):
    runs, host, busy_s = recorded["program_runs"]
    read = lambda name: pt.figure(spec_of(name), runs, host, busy_s, 1)
    assert [r["program"] for r in runs[0]] == (
        ["engine_prefill", "engine_join"] * 3 + ["engine_decode"] * 5)
    assert read("prefill_device_share.moe") == pytest.approx(16.426963, rel=1e-6)
    assert read("join_device_share.moe") == pytest.approx(27.029573, rel=1e-6)
    assert read("decode_program_mean_ms") == pytest.approx(0.0189146, rel=1e-6)
    assert read("prefill_program_mean_ms.moe") == pytest.approx(0.008197, rel=1e-6)
    assert read("prefill_queue_delay_mean_ms.moe") == pytest.approx(0.0926, rel=1e-6)
    assert read("device_clock_lead_ms.programs") == pytest.approx(0.868584, rel=1e-6)
    assert read("step_program_mean_ms.train") is None  # no train step in a serving trace
    assert read("admit_step_mfu") is None  # recorded before an admission rode a step
    decode = pt.figure({**spec_of("prefill_device_share.moe"), "program": "engine_decode"},
                       runs, host, busy_s, 1)
    # a run holds the gaps between its operations: the three shares pass 100
    assert 100.0 < read("prefill_device_share.moe") + read("join_device_share.moe") + decode < 110.0
    for twin in ("decode_program_mean_ms", "device_clock_lead_ms.programs"):
        assert spec_of(twin + ".moe") == spec_of(twin)


def test_the_folded_files_read_what_the_unsuffixed_files_read(recorded):
    """PR 58 folded four specifications into their ``.moe`` files and gave every
    file the map that names ``engine_admit_step``: on the recorded trace each
    reads, to the last digit, what the parent's file of the un-suffixed name
    read (figure, program and the parent's four-program map)."""
    runs, host, busy_s = recorded["program_runs"]
    parent_map = {k: v for k, v in PROGRAMS.items() if k != "engine_admit_step"}
    old_runs, old_host = pt.extract(tr.load(os.path.join(DATA, "program_runs.xplane.pb.gz")),
                                    set(parent_map.values()))
    for name, fig, program in (("prefill_device_share.moe", "device_share", "engine_prefill"),
                               ("join_device_share.moe", "device_share", "engine_join"),
                               ("prefill_program_mean_ms.moe", "mean_ms", "engine_prefill"),
                               ("prefill_queue_delay_mean_ms.moe", "queue_delay_mean_ms", "engine_prefill")):
        parent = {"reader": "program_time", "figure": fig, "program": program, "programs": parent_map}
        now = pt.figure(spec_of(name), runs, host, busy_s, 1)
        assert now is not None and now == pt.figure(parent, old_runs, old_host, busy_s, 1)


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


CFG = harness.load_json(harness.BENCH_DIR, "configs", "cerebras-gpt-1.3b.json")
PEAK = 197e12


def admit_trace(admissions, at_share_of_peak=1.0, tied=True):
    """One ``engine_admit_step`` run an admission ``(bucket, tokens, rows)``,
    each as long as a chip at ``at_share_of_peak`` of its peak needs for the
    WHOLE bucket's operations, each launched from its own span."""
    from chipbench import flops

    line, spans, producers, consumers, runs, t = (9, 0), [], {}, [], [], 1000.0
    for i, (bucket, tokens, rows) in enumerate(admissions):
        dur = flops.gpt_admit_step_flops(CFG, 24, bucket, rows) / (PEAK * at_share_of_peak) * 1e9
        spans.append((t, t + 50.0, "engine.admit_step_dispatch",
                      {"program": "engine_admit_step", "seq": i, "bucket": bucket,
                       "tokens": tokens, "rows": rows, "slot": 0}))
        if tied:
            producers[(12, i)] = (line, t + 10.0)
        runs.append(run("engine_admit_step", t + 60.0, dur, i))
        t += 60.0 + dur + 100.0
    return {0: runs}, (producers, {}, {line: pt._nest(spans)}, (0.0, t))


def test_the_admit_steps_mfu_counts_what_was_asked_and_never_the_padding():
    from chipbench import flops

    spec = spec_of("admit_step_mfu")
    assert (spec["flops"], spec["args"]) == ("gpt_admit_step_flops", ["tokens", "rows"])
    read = lambda trace: pt.figure(spec, *trace, 1.0, 1, model=(CFG, 24, PEAK))
    # a chip AT its peak over the whole bucket: a full bucket reads 100, one whose prompt
    # fills 1,025 of 1,984 rows what the real rows' operations are of the bucket's
    assert read(admit_trace([(1984, 1984, 16)])) == pytest.approx(100.0)
    asked = flops.gpt_admit_step_flops(CFG, 24, 1025, 16)
    whole = flops.gpt_admit_step_flops(CFG, 24, 1984, 16)
    assert 48.0 < 100.0 * asked / whole < 52.0
    assert read(admit_trace([(1984, 1025, 16)])) == pytest.approx(100.0 * asked / whole)
    # over runs, operations over seconds: not the mean of the runs' shares
    both = read(admit_trace([(1984, 1984, 16), (1984, 1025, 16)], at_share_of_peak=0.5))
    assert both == pytest.approx(50.0 * (asked + whole) / (2 * whole))
    # whatever the traffic, a program that computes its whole bucket cannot pass the chip
    for tokens in (1, 16, 1024, 1025, 1440, 1984):
        assert 0.0 < read(admit_trace([(1984, tokens, 16)])) <= 100.0 + 1e-9
    # nothing clamps it: a run shorter than its operations need reads over 100
    assert read(admit_trace([(1984, 1984, 16)], at_share_of_peak=2.0)) == pytest.approx(200.0)
    # runs that no span claims are not counted, and under 95% tied there is no reading
    assert read(admit_trace([(1984, 1984, 16)], tied=False)) is None
    runs, host = admit_trace([(1984, 1984, 16)])
    assert pt.figure(spec, runs, (*host[:3], None), 1.0, 1, model=(CFG, 24, PEAK)) is None
    assert pt.figure({**spec, "program": "engine_decode"}, runs, host, 1.0, 1, model=(CFG, 24, PEAK)) is None


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
    assert harness.read_metric({"name": "decode_program_mean_ms.moe"}, ctx) == got["decode_program_mean_ms"]
    # an untraced run reads nothing, whatever lies in the directory; nor does another cell
    bare = {**ctx, "measured": harness.Measured(attempted=1, failed=0, correct=True)}
    bare.pop("program_runs")
    assert harness.read_metric({"name": "prefill_device_share.moe"}, bare) is None
    other = {"measured": measured, "cell": {"name": "glm_serve_docqa"}, "device": {"count": 1}}
    assert harness.read_metric({"name": "prefill_device_share.moe"}, other) is None
