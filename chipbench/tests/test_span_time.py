"""The reader of the program's own spans (``readers/span_time.py``): on made-up
events, on the recorded chip trace ``data/program_spans.xplane.pb.gz``
(``tools/record_program_trace.py`` on a TPU v5 lite: a toy engine replica
serving three requests inside the window), and through the serving runner."""

import os

import pytest
from test_runners import SERVE_TRACED_ON_CPU, last_line, tiny  # noqa: F401 - tiny is a fixture

from chipbench import harness
from chipbench import run as bench_run
from chipbench import trace_reduce as tr
from chipbench.readers import span_time

RECORDED = os.path.join(os.path.dirname(__file__), "data", "program_spans.xplane.pb.gz")
SERVE = ["serve.iteration", "serve.admit", "serve.reply", "engine.submit",
         "engine.prefill_dispatch", "engine.first_token_fetch", "engine.join", "engine.step",
         "engine.step_dispatch", "engine.decode_fetch", "engine.step_host"]
LOOP = ["serve.iteration", "serve.admit", "serve.reply", "engine.submit", "engine.step",
        "engine.step_host"]
DISPATCH = ["engine.step_dispatch", "engine.prefill_dispatch", "engine.join"]
FETCH = ["engine.decode_fetch", "engine.first_token_fetch"]

OP = "%fusion.1 = bf16[8,8]{1,0} fusion(%a)"
# One iteration inside the window, and one that runs past its end.
HOST = {
    "chipbench.trace_window": [(0.0, 1000.0)],
    "serve.iteration": [(100.0, 800.0), (950.0, 200.0)],
    "engine.step": [(200.0, 500.0)],
    "engine.step_dispatch": [(200.0, 100.0)],
    "engine.decode_fetch": [(300.0, 350.0)],
    "engine.step_host": [(650.0, 50.0)],
    "rpc.recv": [(0.0, 1000.0)],  # not of the family: owns nothing
}
BUSY = [(OP, 250.0, 350.0), (OP, 700.0, 100.0)]  # idle 0-250, 600-700, 800-1000
DEVICES = {0: BUSY, 1: [(OP, -50.0, 1100.0)], 2: BUSY, 3: [(OP, 2000.0, 10.0)]}


def share(spans, devices=DEVICES, n=1, host=HOST):
    spec = {"figure": "idle_share", "spans": spans, "among": SERVE}
    return span_time.figure(spec, devices, host, n)


def test_idle_goes_to_the_innermost_open_span_by_overlap():
    # the gap 0-250 is split at 100 and 200, not given whole to its midpoint's owner
    assert share(DISPATCH) == pytest.approx(5.0)        # 200-250
    assert share(FETCH) == pytest.approx(5.0)           # 600-650
    # 100-200 and 800-900 of the iteration itself, 650-700 of step_host, and
    # 950-1000 of the iteration that is clipped to the window
    assert share(LOOP) == pytest.approx(30.0)
    # 0-100 and 900-950 lie in no span of the family: 55% idle, 40 attributed
    assert share(SERVE) == pytest.approx(40.0)


def test_four_chips_are_averaged():
    # chip 1 is busy throughout, chip 3 idle throughout, 0 and 2 as above
    assert share(DISPATCH, n=4) == pytest.approx((5.0 + 0.0 + 5.0 + 10.0) / 4)
    assert share(FETCH, n=4) == pytest.approx((5.0 + 0.0 + 5.0 + 35.0) / 4)
    assert share(SERVE, n=4) == pytest.approx((40.0 + 0.0 + 40.0 + 85.0) / 4)


def test_mean_ms_counts_spans_wholly_inside_the_window():
    mean = lambda names: span_time.figure({"figure": "mean_ms", "spans": names}, DEVICES, HOST, 1)
    assert mean(["serve.iteration"]) == pytest.approx(800e-6)  # the second one crosses the end
    assert mean(["engine.step", "engine.step_host"]) == pytest.approx(275e-6)
    assert mean(["serve.reply"]) is None


def test_a_program_without_spans_reads_nothing():
    bare = {"chipbench.trace_window": [(0.0, 1000.0)], "chipbench.serve.engine_step": [(0.0, 500.0)]}
    assert share(DISPATCH, host=bare) is None
    assert share(DISPATCH, host={}) is None                       # no window span
    assert share(DISPATCH, devices={}) is None                    # no device plane (a CPU)
    with pytest.raises(ValueError):
        span_time.figure({"figure": "other", "spans": []}, DEVICES, HOST, 1)


def test_owners_picks_the_latest_started_across_threads():
    spans = [("a", 0.0, 100.0), ("b", 10.0, 30.0), ("c", 20.0, 50.0)]  # c outlives b: another thread
    assert span_time.owners(spans) == [
        (0.0, 10.0, "a"), (10.0, 20.0, "b"), (20.0, 40.0, "c"), (40.0, 70.0, "c"), (70.0, 100.0, "a")]


@pytest.fixture(scope="module")
def recorded():
    return span_time.extract(tr.load(RECORDED))


def test_recorded_trace_holds_the_program_spans_beside_the_device_events(recorded):
    devices, host = recorded
    assert list(devices) == [0] and len(devices[0]) == 564
    assert {n for n in host if n.startswith(("serve.", "engine."))} == set(SERVE)
    count = lambda name: len(host[name])
    assert count("serve.iteration") == count("engine.step") == 3
    assert count("engine.submit") == 2 and count("serve.reply") == 2
    assert len(host[tr.WINDOW_SPAN]) == 1
    # every child lies inside its parent, on the profiler's clock too
    inside = lambda a, b: any(s <= a[0] and a[0] + a[1] <= s + d for s, d in host[b])
    for child, parent in (("engine.step_dispatch", "engine.step"), ("engine.decode_fetch", "engine.step"),
                          ("engine.step", "serve.iteration"), ("engine.join", "engine.submit"),
                          ("engine.submit", "serve.admit"), ("serve.reply", "serve.iteration")):
        assert all(inside(c, parent) for c in host[child]), child


def test_recorded_trace_figures(recorded):
    devices, host = recorded
    read = lambda name: span_time.figure(
        harness.load_json(harness.BENCH_DIR, "metrics", name + ".json"), devices, host, 1)
    dispatch, fetch, loop = (read(f"idle_host_{k}_share.serve") for k in ("dispatch", "fetch", "loop"))
    assert dispatch == pytest.approx(42.230236, rel=1e-6)
    assert fetch == pytest.approx(30.832153, rel=1e-6)
    assert loop == pytest.approx(12.913898, rel=1e-6)
    # a toy replica's chip is idle 99.5% of the window (trace_reduce's own figure);
    # the three shares account for all of it that lies inside an iteration
    summary = tr.reduce(tr.extract(tr.load(RECORDED)), n_devices=1)
    idle = 100.0 * (1.0 - summary["busy_s"] / summary["window_s"])
    assert idle == pytest.approx(99.526273, rel=1e-6)
    assert dispatch + fetch + loop == pytest.approx(share(SERVE, devices, 1, host))
    assert dispatch + fetch + loop < idle
    mean = span_time.figure({"figure": "mean_ms", "spans": ["engine.step"]}, devices, host, 1)
    assert mean == pytest.approx(1.9152633, rel=1e-6)
    # the trace has none of the trainer's spans: its metrics read nothing
    assert read("dispatch_mean_ms.train") is None
    assert read("idle_host_dispatch_share.train") is None


def test_serving_cell_reports_the_registry_metrics(tiny, capsys):  # noqa: F811
    rc = bench_run.main(["--workload", "lm_serve_steady", "--seed", str(2**31 + 11),
                         "--seconds", "1.5", "--trace", "1"])
    line = last_line(capsys)
    assert rc == 0 and line["correct"] is True and line["failed"] == 0
    # a CPU has no device plane in its trace: the trace readers, span_time among them, return nothing
    assert set(line["metrics"]) >= SERVE_TRACED_ON_CPU
    m = {k: v["value"] for k, v in line["metrics"].items()}
    assert m["first_token_mean_ms"] >= m["queue_wait_mean_ms"] + 0.9 * m["prefill_mean_ms"]
    # until PR 57 a step() held one dispatch and one fetch, and dispatch + fetch <= step held
    # of the means.  Now a step() may dispatch twice (the step ahead, the step that carries this
    # pass's admission), submit dispatches too, and a step() that books two steps waits twice:
    # what still holds call for call is that every fetch lies inside a step()
    assert 0.0 < m["decode_fetch_mean_ms"] <= m["decode_step_mean_ms"]
    assert m["decode_dispatch_mean_ms"] > 0.0
    assert m["iteration_period_mean_ms"] >= m["decode_step_mean_ms"]
