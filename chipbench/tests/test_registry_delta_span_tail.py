"""The two readers of what a window held at its worst: ``registry_delta`` on
hand-made snapshots, ``span_tail`` on hand-made ``(devices, host)`` pairs, and
both through ``read`` as the harness calls them."""

import pytest

from chipbench import harness
from chipbench.readers import registry_delta, span_tail

EDGES = [0.001, 0.01, 0.1, 1.0]


def counter(**by_state):
    return {"kind": "counter", "series": [
        {"labels": {"state": k}, "value": v} for k, v in by_state.items()]}


def hist(**by_phase):
    return {"kind": "histogram", "buckets": EDGES, "series": [
        {"labels": {"phase": k}, "value": {"buckets": v, "count": sum(v), "sum": 0.0}}
        for k, v in by_phase.items()]}


BEFORE = {"loop": counter(busy=10.0, empty=2.0),
          "h": hist(iteration=[5, 1, 0, 0, 0], queue=[0, 0, 0, 1, 0])}
AFTER = {"loop": counter(busy=50.0, empty=7.0, blocked=5.0),
         "h": hist(iteration=[9, 4, 1, 0, 0], queue=[0, 0, 0, 1, 0], reply=[0, 0, 0, 0, 2])}


@pytest.mark.parametrize("spec,want", [
    ({"figure": "counter_share", "metric": "loop", "labels": {"state": "empty"}}, 10.0),
    # a series the window saw for the first time counts from zero
    ({"figure": "counter_share", "metric": "loop", "labels": {"state": "blocked"}}, 10.0),
    ({"figure": "counter_share", "metric": "loop"}, 100.0),
    ({"figure": "counter_share", "metric": "loop", "labels": {"state": "other"}}, 0.0),
    ({"figure": "counter_share", "metric": "absent", "labels": {"state": "empty"}}, None),
    # the highest bucket that ROSE: 0.1, though 1.0 holds a count from before the window
    ({"figure": "histogram_longest_le", "metric": "h", "labels": {"phase": "iteration"},
      "scale": 1000.0}, 100.0),
    ({"figure": "histogram_longest_le", "metric": "h", "labels": {"phase": "queue"}}, None),
    # a rise in +Inf reads twice the last edge
    ({"figure": "histogram_longest_le", "metric": "h", "labels": {"phase": "reply"}}, 2.0),
    # no labels: over every series of the family
    ({"figure": "histogram_longest_le", "metric": "h", "scale": 1000.0}, 2000.0),
    ({"figure": "histogram_longest_le", "metric": "absent"}, None),
    ({"figure": "histogram_longest_le", "metric": "loop"}, None),  # not a histogram
])
def test_registry_delta(spec, want):
    m = harness.Measured(attempted=1, failed=0, correct=True,
                         counters_before=BEFORE, counters_after=AFTER)
    got = registry_delta.read(spec, {"measured": m})
    assert got is None if want is None else got == pytest.approx(want)


def test_registry_delta_without_a_window_reads_nothing():
    m = harness.Measured(attempted=1, failed=0, correct=True)  # the train runner's: no snapshots
    spec = {"figure": "counter_share", "metric": "loop", "labels": {"state": "empty"}}
    assert registry_delta.read(spec, {"measured": m}) is None
    same = harness.Measured(attempted=1, failed=0, correct=True,
                            counters_before=AFTER, counters_after=AFTER)
    assert registry_delta.read(spec, {"measured": same}) is None  # nothing rose
    with pytest.raises(ValueError):
        registry_delta.read({"figure": "other", "metric": "loop"}, {"measured": m})


OP = "%fusion.1 = bf16[8,8]{1,0} fusion(%a)"
MS = 1e6  # the trace's clock is in ns
WINDOW = {"chipbench.trace_window": [(10 * MS, 100 * MS)]}  # 10..110 ms


def longest(host, spans, witness=()):
    spec = {"figure": "longest_ms", "spans": spans, "witness": list(witness)}
    return span_tail.longest_ms(spec, {}, {**WINDOW, **host})


def test_longest_is_clipped_to_the_window():
    host = {"serve.iteration": [(12 * MS, 3 * MS), (50 * MS, 7 * MS), (0.0, 15 * MS), (105 * MS, 900 * MS)],
            "host.tick": [(20 * MS, 10 * MS)], "host.gc": [(200 * MS, 40 * MS)]}
    assert longest(host, ["serve.iteration"]) == pytest.approx(7.0)   # 5 of 15 and 5 of 900 lie inside
    assert longest(host, ["serve.iteration", "host.tick"]) == pytest.approx(10.0)
    # a collection after the window is none in it: 0 where the monitor's tick is
    # in the trace, nothing where the program has no monitor
    assert longest(host, ["host.gc"], witness=["host.tick"]) == 0.0
    assert longest(host, ["host.gc"]) is None
    assert longest({}, ["host.gc"], witness=["host.tick"]) is None
    assert span_tail.longest_ms({"spans": ["host.tick"]}, {}, {"host.tick": [(0.0, MS)]}) is None  # no window


def lead(host, ops, spans=("serve.empty",)):
    spec = {"figure": "clock_lead_ms", "spans": list(spans),
            "dispatch": ["engine.prefill_dispatch", "engine.join"]}
    return span_tail.clock_lead_ms(spec, {0: ops}, {**WINDOW, **host})


def test_clock_lead_positive_negative_none():
    # busy to 20, empty spans end at 30 and 40 (one long quiet spell), the host
    # dispatches the prefill at 41.5, the device's next operation starts at 40:
    # its events lie at least 1.5 ms early
    ops = [(OP, 12 * MS, 8 * MS), (OP, 40 * MS, 5 * MS)]
    host = {"serve.empty": [(20 * MS, 10 * MS), (30 * MS, 10 * MS)],
            "engine.prefill_dispatch": [(41.5 * MS, 1 * MS)]}
    assert lead(host, ops) == pytest.approx(1.5)
    # the device follows the host, as clocks that agree have it: 0.3 ms of launch
    late = [(OP, 12 * MS, 8 * MS), (OP, 41.8 * MS, 5 * MS)]
    assert lead(host, late) == pytest.approx(-0.3)
    # the largest over the wake-ups; a second kind of quiet end (a prefill's
    # token is back) is followed by whichever dispatch comes first, here the join
    two = {"serve.empty": [(30 * MS, 10 * MS)], "engine.first_token_fetch": [(60 * MS, 3.5 * MS)],
           "engine.prefill_dispatch": [(41.5 * MS, 1 * MS), (90 * MS, 1 * MS)],
           "engine.join": [(66.2 * MS, 1 * MS)]}
    both = late + [(OP, 64 * MS, 3 * MS)]
    assert lead(two, both, spans=("serve.empty", "engine.first_token_fetch")) == pytest.approx(2.2)
    assert lead(two, both, spans=("serve.empty",)) == pytest.approx(-0.3)


def test_clock_lead_reads_nothing_without_a_wake_up():
    ops = [(OP, 12 * MS, 8 * MS), (OP, 40 * MS, 5 * MS)]
    dispatch = {"engine.prefill_dispatch": [(41.5 * MS, 1 * MS)]}
    assert lead(dispatch, ops) is None                                         # never empty
    assert lead({"serve.empty": [(20 * MS, 15 * MS)]}, ops) is None           # nothing dispatched after
    assert lead({**dispatch, "serve.empty": [(10 * MS, 5 * MS)]}, ops) is None  # the chip busy at its end
    assert lead({**dispatch, "serve.empty": [(1 * MS, 4 * MS)]}, ops) is None   # ends before the window
    assert lead({**dispatch, "serve.empty": [(20 * MS, 10 * MS)]}, ops[:1]) is None  # no operation after
    assert lead({**dispatch, "serve.empty": [(20 * MS, 10 * MS)]}, []) is None       # no device plane
    spec = {"figure": "clock_lead_ms", "spans": ["serve.empty"], "dispatch": []}
    assert span_tail.clock_lead_ms(spec, {0: ops}, {"serve.empty": [(20 * MS, 10 * MS)]}) is None


def test_span_tail_read_uses_the_trace_the_cell_already_loaded():
    m = harness.Measured(attempted=1, failed=0, correct=True, trace={"busy_s": 1.0})
    host = {**WINDOW, "host.tick": [(20 * MS, 10 * MS), (40 * MS, 1500 * MS)]}
    ctx = {"measured": m, "cell": {"name": "no_such_cell"}, "device": {"count": 1},
           "program_trace": ({0: [(OP, 12 * MS, 8 * MS)]}, host)}
    spec = {"figure": "longest_ms", "spans": ["host.tick"]}
    assert span_tail.read(spec, ctx) == pytest.approx(70.0)  # 40..110 of 40..1540
    ctx.pop("program_trace")  # no trace of that cell on disk: nothing to read
    assert span_tail.read(spec, ctx) is None
    m.trace = None  # not a traced run: an older trace may lie there
    assert span_tail.read(spec, {**ctx, "program_trace": ({}, host)}) is None
    with pytest.raises(ValueError):
        span_tail.read({"figure": "other", "spans": []}, ctx)
