"""The one traffic generator: a data file of parameters in, a schedule out.

A serving mix (``chipbench/traffic/<name>.json``, ``"runner": "serve"``) names
a rate, an arrival process, and the distributions of prompt length and token
budget.  The schedule it gives has, for EVERY seed:

- the same number of requests due inside the window, ``round(rate * seconds)``;
- the same multiset of (prompt length, budget) pairs: lengths and budgets are
  the distributions' quantiles at ``(i + 0.5) / n``, paired by a permutation
  drawn from the file's ``pairing_seed`` and not from ``--seed``.

Which pair arrives when, and the arrival offsets, are one arrival trace that
belongs to the FILE: both are drawn from its ``schedule_seed`` and from nothing
else, so every run of a cell replays the same schedule, entry for entry, as a
benchmark on a recorded public trace does.  A run's median follows the pattern
of arrivals (how many slots are in use when), and a schedule drawn from
``--seed`` made it a property of the seed: 1.6-3.7% from seed to seed where
one seed repeats to 0.1-0.8% (PERF.md, sections 2 and 6).  ``--seed`` draws the token
ids (:func:`prompt_tokens`) and, in the runners, the weights: every run routes
other tokens through other weights along the same trace.  A file's
``schedule_seed`` is chosen on the chip as the median of five candidates
(``tools/repeat.py --schedule-seeds``; the readings are in PERF.md), so that the trace
stands for its mix and not for a lucky draw.  A serving file without one is
an error.

Requests before the window (``lead_s``, so that the window opens on a system
already in its steady state) and after it (the generator keeps offering while
the window's requests drain) take pairs from the same multiset, in an order
and at times of the same trace; they are sent and not counted.
"""

from __future__ import annotations

import math
from statistics import NormalDist
from typing import Dict, List

import numpy as np


def quantiles(dist: Dict, n: int) -> List[int]:
    """``n`` whole numbers at the quantiles ``(i + 0.5) / n`` of a log-normal
    distribution (``median``, ``sigma``; sigma 0 is a fixed length), clipped
    to ``[min, max]``."""
    z = np.array([NormalDist().inv_cdf((i + 0.5) / n) for i in range(n)])
    vals = float(dist["median"]) * np.exp(float(dist["sigma"]) * z)
    lo, hi = dist.get("min", 1), dist.get("max", math.inf)
    return [int(v) for v in np.clip(np.rint(vals), lo, hi)]


def pairs(traffic: Dict, n: int) -> List[tuple]:
    """The fixed multiset of ``n`` (prompt length, budget) pairs."""
    prompts = quantiles(traffic["prompt_tokens"], n)
    budgets = quantiles(traffic["budget_tokens"], n)
    order = np.random.default_rng(int(traffic["pairing_seed"])).permutation(n)
    return [(prompts[i], budgets[int(j)]) for i, j in enumerate(order)]


def arrivals(spec: Dict, n: int, span_s: float, rng: np.random.Generator) -> np.ndarray:
    """``n`` sorted arrival offsets in ``[0, span_s)``: a renewal process
    conditioned on its count.  ``n + 1`` gaps are drawn gamma-distributed
    with coefficient of variation ``spec["cv"]`` and rescaled to the span.
    ``cv`` 1 gives exponential gaps, whose rescaled sums are distributed as
    ``n`` sorted uniform draws: a Poisson process conditioned on its count.
    ``cv`` above 1 gives bursts, ``cv`` towards 0 an even spacing."""
    if n == 0:
        return np.zeros(0)
    gaps = rng.gamma(1.0 / float(spec["cv"]) ** 2, 1.0, n + 1)
    offsets = np.cumsum(gaps)[:n] * (span_s / gaps.sum())
    return np.minimum(offsets, np.nextafter(span_s, 0.0))  # a vanishing last gap rounds up


def serve_schedule(traffic: Dict, seconds: float) -> List[Dict]:
    """Requests in order of their due time.  Each: ``due_s`` (offset from the
    generator's start), ``prompt_len``, ``budget``, ``counted`` (due inside
    the window) and ``index`` (with ``--seed``, seeds its token ids).  The
    run's seed is no argument: the schedule is the file's."""
    if "schedule_seed" not in traffic:
        raise ValueError("a serving traffic file names its arrival trace: no 'schedule_seed'")
    rate = float(traffic["rate_per_s"])
    lead_s = float(traffic.get("lead_s", 0.0))
    tail_s = float(traffic["drain_limit_s"])
    n = int(round(rate * seconds))
    if n < 1:
        raise ValueError("the window holds no request at this rate")
    multiset = pairs(traffic, n)
    rng = np.random.default_rng([int(traffic["schedule_seed"]), 0x5EED])
    out: List[Dict] = []

    def add(count: int, start: float, span: float, counted: bool) -> None:
        order = rng.permutation(n)
        offs = arrivals(traffic["arrivals"], count, span, rng)
        for k in range(count):
            p, b = multiset[int(order[k % n])]
            out.append({"due_s": start + float(offs[k]), "prompt_len": p,
                        "budget": b, "counted": counted})

    add(int(round(rate * lead_s)), 0.0, lead_s, False)
    add(n, lead_s, seconds, True)
    add(int(round(rate * tail_s)), lead_s + seconds, tail_s, False)
    for i, r in enumerate(out):
        r["index"] = i
    return out


def prompt_tokens(seed: int, index: int, length: int, vocab: int) -> np.ndarray:
    """Token ids of request ``index`` under ``seed`` (ids 0 and 1 are left
    free, as in the program's own examples)."""
    rng = np.random.default_rng([int(seed), 0x70C, int(index)])
    return rng.integers(2, vocab, length).astype(np.int32)
