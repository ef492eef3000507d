"""Host monitor: whether the process stood still, and in what.

A serving replica whose chip went idle for two seconds was either waiting on
the device, or not running at all.  The service loop's own spans cannot tell
(the thread that would close them is the one that stood), so two witnesses
run beside it, always on and with nothing to configure:

*A tick.*  A daemon thread asks, under a ``host.tick`` span, to sleep
``TICK_SECONDS`` and observes by how much it woke late into
``host_stall_seconds``.  A thread that only sleeps is late when the process
could not run: the GIL was held, the process was descheduled or stopped.  The
span is as long as the stall, in the tracer's ring and (through the span's
``TraceAnnotation``) in whatever profiler trace is open.

*Collections.*  A ``gc.callbacks`` hook puts every collection under a
``host.gc`` span (args ``generation``) and its pause into
``host_gc_pause_seconds{generation}``.

Read with the service thread's own gap (``serve_phase_seconds{phase=
"iteration"}``, ``serve.iteration``): a thread that waited while the tick was
on time waited for the runtime or the device; both long, the process stood; a
collection as long names the cause (docs/TELEMETRY.md, "Which of them stood
still").  Stdlib only, like the rest of the package.
"""

from __future__ import annotations

import gc
import threading
import time

from .metrics import get_registry
from .tracing import span

__all__ = ["TICK_SECONDS", "ensure_host_monitor", "tick"]

TICK_SECONDS = 0.01

_REG = get_registry()
_M_STALL = _REG.histogram(
    "host_stall_seconds",
    "how late the host monitor's thread woke from a sleep of TICK_SECONDS: "
    "the process could not run (GIL held, descheduled, stopped)",
)
_M_GC = _REG.histogram(
    "host_gc_pause_seconds",
    "pause of one garbage collection, by generation",
    labelnames=("generation",),
)

_lock = threading.Lock()
_thread = None
_collection = None  # (open host.gc span, its start): collections do not nest


def tick(clock=time.monotonic, sleep=time.sleep) -> float:
    """One tick: sleep ``TICK_SECONDS`` under a ``host.tick`` span, observe
    and return the seconds it woke late."""
    with span("host.tick"):
        t0 = clock()
        sleep(TICK_SECONDS)
        late = max(0.0, clock() - t0 - TICK_SECONDS)
    _M_STALL.observe(late)
    return late


def _run() -> None:
    while True:
        tick()


def _on_gc(phase: str, info: dict) -> None:
    global _collection
    if phase == "start":
        active = span("host.gc", generation=info["generation"])
        active.__enter__()
        _collection = active, time.monotonic()
    elif _collection is not None:
        (active, t0), _collection = _collection, None
        pause = time.monotonic() - t0
        active.__exit__(None, None, None)
        _M_GC.observe(pause, generation=info["generation"])


def ensure_host_monitor() -> None:
    """Start the tick and hook the collector, once a process; every serving
    and training loop calls it, and nothing ever stops it (a daemon)."""
    global _thread
    with _lock:
        if _thread is not None:
            return
        gc.callbacks.append(_on_gc)
        _thread = threading.Thread(target=_run, name="host-monitor", daemon=True)
        _thread.start()
