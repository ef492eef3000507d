"""The traffic generator: a serving file's schedule is one arrival trace, the
file's own; ``--seed`` changes the token ids and nothing of the schedule."""

import collections
import glob
import inspect
import os

import numpy as np
import pytest

from chipbench import harness, traffic

MIX = harness.load_json(harness.BENCH_DIR, "traffic", "serve_steady.json")


def counted(schedule):
    return [r for r in schedule if r["counted"]]


def test_same_file_same_schedule_same_seed_same_tokens():
    assert traffic.serve_schedule(MIX, 50.0) == traffic.serve_schedule(dict(MIX), 50.0)
    assert np.array_equal(traffic.prompt_tokens(7, 3, 40, 50257),
                          traffic.prompt_tokens(7, 3, 40, 50257))


@pytest.mark.parametrize("schedule_seed", [0, 1, 12345, 2**31 + 17])
def test_any_trace_same_count_and_multiset(schedule_seed):
    mix = {**MIX, "schedule_seed": schedule_seed}
    base = counted(traffic.serve_schedule(MIX, 50.0))
    got = counted(traffic.serve_schedule(mix, 50.0))
    assert len(got) == len(base) == round(MIX["rate_per_s"] * 50.0)
    key = lambda rs: collections.Counter((r["prompt_len"], r["budget"]) for r in rs)
    assert key(got) == key(base)
    lead = MIX["lead_s"]
    assert all(lead <= r["due_s"] < lead + 50.0 for r in got)
    dues = [r["due_s"] for r in traffic.serve_schedule(mix, 50.0)]
    assert dues == sorted(dues)


SERVING = sorted(
    name for name in (os.path.basename(p)[:-len(".json")] for p in
                      glob.glob(os.path.join(harness.BENCH_DIR, "traffic", "*.json")))
    if harness.load_json(harness.BENCH_DIR, "traffic", name + ".json")["runner"].startswith("serve"))


def test_the_serving_files_are_found():
    assert {"serve_steady", "serve_knee", "serve_docqa"} <= set(SERVING)


@pytest.mark.parametrize("name", SERVING)
def test_any_two_seeds_replay_one_schedule_with_other_tokens(name):
    """The pin that ``test_seed_changes_the_order`` was, turned round: lead,
    window and drain are the file's trace entry for entry, and ``--seed`` is
    no argument of the schedule at all: it reaches the token ids alone."""
    mix = harness.load_json(harness.BENCH_DIR, "traffic", name + ".json")
    assert isinstance(mix["schedule_seed"], int)
    assert list(inspect.signature(traffic.serve_schedule).parameters) == ["traffic", "seconds"]
    a = traffic.serve_schedule(mix, 50.0)
    assert a == traffic.serve_schedule(mix, 50.0)
    assert {r["counted"] for r in a} == {True, False} and not a[0]["counted"] and not a[-1]["counted"]
    r = counted(a)[0]
    tokens = [traffic.prompt_tokens(seed, r["index"], r["prompt_len"], 50257) for seed in (1, 2**31 + 17)]
    assert tokens[0].shape == tokens[1].shape and not np.array_equal(*tokens)


def test_two_schedule_seeds_give_the_multiset_in_another_order_at_other_times():
    a = counted(traffic.serve_schedule(MIX, 50.0))
    b = counted(traffic.serve_schedule({**MIX, "schedule_seed": MIX["schedule_seed"] + 1}, 50.0))
    key = lambda rs: [(r["prompt_len"], r["budget"]) for r in rs]
    assert collections.Counter(key(a)) == collections.Counter(key(b)) and key(a) != key(b)
    assert len(a) == len(b) and all(x["due_s"] != y["due_s"] for x, y in zip(a, b))


def test_a_serving_file_without_a_schedule_seed_is_refused():
    bare = {k: v for k, v in MIX.items() if k != "schedule_seed"}
    with pytest.raises(ValueError, match="schedule_seed"):
        traffic.serve_schedule(bare, 50.0)


def test_serve_knee_is_serve_steady_at_another_rate_on_its_own_trace():
    knee = harness.load_json(harness.BENCH_DIR, "traffic", "serve_knee.json")
    differ = {k for k in set(MIX) | set(knee) if MIX.get(k) != knee.get(k)}
    assert differ <= {"rate_per_s", "schedule_seed", "doc", "drain_limit_s"}
    assert knee["rate_per_s"] == 16.0
    assert sum(r["counted"] for r in traffic.serve_schedule(knee, 50.0)) == 800


def test_lengths_follow_the_file():
    n = 200
    prompts = traffic.quantiles(MIX["prompt_tokens"], n)
    budgets = traffic.quantiles(MIX["budget_tokens"], n)
    assert min(prompts) == 16 and max(prompts) == 512
    assert min(budgets) == 16 and max(budgets) == 256
    assert sorted(prompts)[n // 2] == pytest.approx(128, abs=2)
    assert sorted(budgets)[n // 2] == pytest.approx(64, abs=1)


@pytest.mark.parametrize("cv, var_lo, var_hi", [(0.05, 0.0, 1.0), (1.0, 18.0, 33.0),
                                                 (3.0, 60.0, 1e9)])
def test_arrival_process(cv, var_lo, var_hi):
    """Count, order and range for any cv; the count that falls into the first
    half of the span varies as a binomial's (n/4 = 25) at cv 1 - sorted
    uniform draws, a Poisson process conditioned on its count - far less for
    an even spacing and far more for bursts."""
    rng = np.random.default_rng(0)
    halves = []
    for _ in range(400):
        t = traffic.arrivals({"cv": cv}, 100, 25.0, rng)
        assert len(t) == 100 and np.all(np.diff(t) >= 0) and 0 <= t[0] and t[-1] < 25.0
        halves.append(int((t < 12.5).sum()))
    assert abs(np.mean(halves) - 50) < 3.0
    assert var_lo <= np.var(halves) <= var_hi
