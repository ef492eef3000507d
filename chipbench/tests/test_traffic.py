"""The traffic generator: the seed changes the order of the work, never its
amount."""

import collections

import numpy as np
import pytest

from chipbench import harness, traffic

MIX = harness.load_json(harness.BENCH_DIR, "traffic", "serve_steady.json")


def counted(schedule):
    return [r for r in schedule if r["counted"]]


def test_same_seed_same_schedule():
    a = traffic.serve_schedule(MIX, 3_000_000_001, 50.0)
    b = traffic.serve_schedule(MIX, 3_000_000_001, 50.0)
    assert a == b
    assert np.array_equal(traffic.prompt_tokens(7, 3, 40, 50257),
                          traffic.prompt_tokens(7, 3, 40, 50257))


@pytest.mark.parametrize("seed", [0, 1, 12345, 2**31 + 17])
def test_any_seed_same_count_and_multiset(seed):
    base = counted(traffic.serve_schedule(MIX, 99, 50.0))
    got = counted(traffic.serve_schedule(MIX, seed, 50.0))
    assert len(got) == len(base) == round(MIX["rate_per_s"] * 50.0)
    key = lambda rs: collections.Counter((r["prompt_len"], r["budget"]) for r in rs)
    assert key(got) == key(base)
    lead = MIX["lead_s"]
    assert all(lead <= r["due_s"] < lead + 50.0 for r in got)
    dues = [r["due_s"] for r in traffic.serve_schedule(MIX, seed, 50.0)]
    assert dues == sorted(dues)


def test_seed_changes_the_order():
    a = counted(traffic.serve_schedule(MIX, 1, 50.0))
    b = counted(traffic.serve_schedule(MIX, 2, 50.0))
    assert [(r["prompt_len"], r["budget"]) for r in a] != [(r["prompt_len"], r["budget"]) for r in b]


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
