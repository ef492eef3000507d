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


BEFORE = {"loop": counter(busy=10.0, empty=2.0), "pad": counter(all=3.0), "still": counter(all=1.0),
          "h": hist(iteration=[5, 1, 0, 0, 0], queue=[0, 0, 0, 1, 0])}
AFTER = {"loop": counter(busy=50.0, empty=7.0, blocked=5.0), "pad": counter(all=8.0), "still": counter(all=1.0),
         "h": hist(iteration=[9, 4, 1, 0, 0], queue=[0, 0, 0, 1, 0], reply=[0, 0, 0, 0, 2])}


@pytest.mark.parametrize("spec,want", [
    ({"figure": "counter_share", "metric": "loop", "labels": {"state": "empty"}}, 10.0),
    # a series the window saw for the first time counts from zero
    ({"figure": "counter_share", "metric": "loop", "labels": {"state": "blocked"}}, 10.0),
    ({"figure": "counter_share", "metric": "loop"}, 100.0),
    ({"figure": "counter_share", "metric": "loop", "labels": {"state": "other"}}, 0.0),
    ({"figure": "counter_share", "metric": "absent", "labels": {"state": "empty"}}, None),
    # a share ACROSS families: blocked rose 5 of the 5 + (40 + 5 + 5) the two families rose
    ({"figure": "family_share", "metric": "pad", "among": ["pad", "loop"]}, 100.0 * 5 / 55),
    ({"figure": "family_share", "metric": "loop", "among": ["pad", "loop"]}, 100.0 * 50 / 55),
    ({"figure": "family_share", "metric": "pad", "among": ["pad"]}, 100.0),
    # a part that the program does not count: no reading, not a share that reads too high
    ({"figure": "family_share", "metric": "pad", "among": ["pad", "absent"]}, None),
    ({"figure": "family_share", "metric": "still", "among": ["still"]}, None),  # none rose
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
    with pytest.raises(ValueError):  # a share of a total it is no part of
        registry_delta.read({"figure": "family_share", "metric": "loop", "among": ["pad"]}, {"measured": m})


def test_the_pad_share_is_the_buckets_empty_positions_of_all_it_was_handed():
    """``prefill_pad_share`` through its own file: 1,984-row buckets around
    prompts of 1,025 and 1,984 tokens, and one prompt of 1,024 in its own."""
    snap = lambda pad, real: {
        "serve_pad_tokens_total": {"kind": "counter", "series": [{"labels": {}, "value": pad}]},
        "serve_engine_prefill_tokens_total": {"kind": "counter", "series": [{"labels": {}, "value": real}]}}
    m = harness.Measured(attempted=3, failed=0, correct=True, counters_before=snap(100.0, 5000.0),
                         counters_after=snap(100.0 + 959, 5000.0 + 1025 + 1984 + 1024))
    got = harness.read_metric({"name": "prefill_pad_share"}, {"measured": m})
    assert got == pytest.approx(100.0 * 959 / (959 + 4033))
    older = harness.Measured(attempted=1, failed=0, correct=True, counters_before={},
                             counters_after={"serve_pad_tokens_total": snap(1.0, 1.0)["serve_pad_tokens_total"]})
    assert harness.read_metric({"name": "prefill_pad_share"}, {"measured": older}) is None


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
