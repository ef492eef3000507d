"""The host monitor (``telemetry/hostmon.py``, docs/TELEMETRY.md "Which of
them stood still"): a tick that woke late lands in the bucket of its lateness
and closes a ``host.tick`` span as long as the stall, a collection is one
``host.gc`` span and one observation, and starting it twice starts it once."""

import gc
import threading
import time

import pytest

from moolib_tpu import telemetry
from moolib_tpu.telemetry import hostmon


def _buckets(name, **labels):
    """Counts by upper edge (``inf`` last) of one histogram series."""
    family = telemetry.get_registry().snapshot()[name]
    edges = family["buckets"] + [float("inf")]
    counts = [0] * len(edges)
    for s in family["series"]:
        if s["labels"] == {k: str(v) for k, v in labels.items()}:
            counts = s["value"]["buckets"]
    return dict(zip(edges, counts))


def _rose(before, after):
    return {edge: after[edge] - before[edge] for edge in after if after[edge] != before[edge]}


@pytest.mark.parametrize("late,edge", [
    (0.0, 0.0001), (0.0003, 0.0005), (0.002, 0.005), (0.004, 0.005), (1.2, 5.0), (200.0, float("inf"))])
def test_a_tick_lands_in_the_bucket_of_its_lateness(late, edge):
    """An injected clock and sleep: the thread asked for ``TICK_SECONDS`` and
    the clock says it got that and ``late`` more.  (A monitor another test
    started may tick beside this one: on time, so only this tick's own bucket
    is held to a count where it is a late one.)"""
    now = [100.0]
    slept = []

    def sleep(seconds):
        slept.append(seconds)
        now[0] += seconds + late

    before = _buckets("host_stall_seconds")
    telemetry.get_tracer().clear()
    assert hostmon.tick(clock=lambda: now[0], sleep=sleep) == pytest.approx(late, abs=1e-9)
    rose = _rose(before, _buckets("host_stall_seconds"))
    assert slept == [hostmon.TICK_SECONDS] == [0.01]
    assert rose.get(edge, 0) >= 1 and (late < 1.0 or rose[edge] == 1)
    assert "host.tick" in {s.name for s in telemetry.get_tracer().spans()}


def test_a_stalled_tick_closes_a_span_as_long_as_the_stall():
    """The real clock: a wake-up 1.2 s late is one ``host.tick`` of 1.21 s in
    the tracer's ring, and one observation between 1 and 5 seconds."""
    before = _buckets("host_stall_seconds")
    telemetry.get_tracer().clear()
    late = hostmon.tick(sleep=lambda seconds: time.sleep(seconds + 1.2))
    assert late == pytest.approx(1.2, abs=0.15)
    assert _rose(before, _buckets("host_stall_seconds")).get(5.0) == 1
    long = [s.dur_ns / 1e9 for s in telemetry.get_tracer().spans()
            if s.name == "host.tick" and s.dur_ns > 1e9]
    assert long == [pytest.approx(1.21, abs=0.15)]


def test_starting_the_monitor_twice_starts_it_once():
    telemetry.ensure_host_monitor()
    telemetry.ensure_host_monitor()
    assert [t.name for t in threading.enumerate()].count("host-monitor") == 1
    assert gc.callbacks.count(hostmon._on_gc) == 1
    monitor = next(t for t in threading.enumerate() if t.name == "host-monitor")
    assert monitor.daemon
    # it ticks: on time on a quiet host, and each tick is a span
    telemetry.get_tracer().clear()
    before = sum(_buckets("host_stall_seconds").values())
    time.sleep(0.2)
    assert sum(_buckets("host_stall_seconds").values()) - before >= 5
    assert [s.name for s in telemetry.get_tracer().spans()].count("host.tick") >= 5


def test_one_collection_is_one_span_and_one_observation():
    """And the hook leaves every callback another module put there (jax keeps
    one of its own), before it and after it."""
    seen = []
    theirs = lambda phase, info: seen.append(phase)
    gc.callbacks.insert(0, theirs)
    try:
        telemetry.ensure_host_monitor()
        others = [cb for cb in gc.callbacks if cb is not hostmon._on_gc]
        gc.disable()  # no collection of the interpreter's own beside this one
        try:
            before = _buckets("host_gc_pause_seconds", generation=2)
            telemetry.get_tracer().clear()
            gc.collect()
            spans = [s for s in telemetry.get_tracer().spans() if s.name == "host.gc"]
        finally:
            gc.enable()
        assert [s.args for s in spans] == [{"generation": 2}]
        rose = _rose(before, _buckets("host_gc_pause_seconds", generation=2))
        assert sum(rose.values()) == 1
        assert seen[:2] == ["start", "stop"] and theirs in gc.callbacks
        assert [cb for cb in gc.callbacks if cb is not hostmon._on_gc] == others
        # the span is the pause: it lies in the bucket the observation went to
        (edge,) = rose
        assert spans[0].dur_ns / 1e9 <= edge + 1e-3
    finally:
        gc.callbacks.remove(theirs)
