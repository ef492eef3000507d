"""The training loop's section timer.

The reference has no tracer — only ``debug_info`` dumps and log timings
(SURVEY.md §5.1).  :class:`StepTimer` is cheap wall-clock section timing
with EMA summaries, for the python-side loop (act/learn/reduce shares).
Registry-backed: every section also lands in the telemetry registry
(``loop_section_seconds{section=...}``) and records a host span, so the
loop breakdown exports through Prometheus/Chrome-trace without the loop
doing anything beyond ``timer.section(...)``.  A device trace is the
operator's window in :mod:`moolib_tpu.telemetry.profiling`.
"""

from __future__ import annotations

import contextlib
import time
from collections import defaultdict
from typing import Dict, Iterator, Optional

from .. import telemetry


class StepTimer:
    """EMA section timer for the training loop's python side.

    Each ``section`` observation additionally feeds the process telemetry:
    a ``loop_section_seconds{section=<name>}`` histogram sample in the
    registry and a span in the default tracer.  Pass ``publish=False`` (or
    a private ``registry``/``tracer``) to opt out — e.g. micro-benchmarks
    that would flood the span ring.
    """

    def __init__(
        self,
        alpha: float = 0.05,
        publish: bool = True,
        registry: Optional["telemetry.Registry"] = None,
        tracer: Optional["telemetry.Tracer"] = None,
    ):
        self._alpha = alpha
        self._ema: Dict[str, float] = {}
        self._counts: Dict[str, int] = defaultdict(int)
        self._hist = None
        self._tracer = None
        if publish:
            reg = registry or telemetry.get_registry()
            self._hist = reg.histogram(
                "loop_section_seconds", "train-loop section wall time", ("section",)
            )
            self._tracer = tracer or telemetry.get_tracer()

    @contextlib.contextmanager
    def section(self, name: str, **args) -> Iterator[None]:
        """``args`` go to the section's span as given (and with it to an open
        profiler: ``telemetry.span``)."""
        span = self._tracer.span(name, **args) if self._tracer is not None else None
        if span is not None:
            span.__enter__()
        t0 = time.perf_counter()
        try:
            yield
        finally:
            dt = time.perf_counter() - t0
            if span is not None:
                span.__exit__(None, None, None)
            if self._hist is not None:
                self._hist.observe(dt, section=name)
            prev = self._ema.get(name)
            self._ema[name] = dt if prev is None else (1 - self._alpha) * prev + self._alpha * dt
            self._counts[name] += 1

    def summary(self) -> Dict[str, float]:
        """EMA seconds per section."""
        return dict(self._ema)

    def report(self) -> str:
        total = sum(self._ema.values()) or 1e-9
        parts = [
            f"{k}={v*1e3:.1f}ms({v/total*100:.0f}%)"
            for k, v in sorted(self._ema.items(), key=lambda kv: -kv[1])
        ]
        return " ".join(parts)
