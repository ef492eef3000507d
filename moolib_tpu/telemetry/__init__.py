"""moolib_tpu.telemetry — unified metrics registry + span tracing.

The reference moolib's observability is ``debug_info`` string dumps and log
timings (SURVEY §5.1).  This package replaces that with one idiom used by
every layer of the stack (RPC transport, accumulator, envpool, batcher,
train loops)::

    from moolib_tpu import telemetry

    _REG = telemetry.get_registry()
    _STEPS = _REG.counter("envpool_steps_total", "env steps completed")
    ...
    _STEPS.inc(batch_size)

    with telemetry.span("learn"):
        ...

and exporters that read the registry without the subsystems knowing:
Prometheus text over an opt-in loopback HTTP endpoint, periodic JSONL
snapshots into the run directory, a SIGUSR1 dump handler, and Chrome
trace-event JSON of the recorded host spans (mergeable next to
``jax.profiler`` device traces).  Cohort-wide totals piggyback on the
agents' existing ``GlobalStatsAccumulator`` reduce via
:class:`CohortCounters` — no second wire protocol.

Environment knobs (read by :func:`init_from_env`, which entry points call
once; everything defaults to off):

- ``MOOLIB_TELEMETRY_HTTP_PORT`` — serve ``/metrics`` + ``/trace`` on this
  loopback port (``0`` picks a free port; the chosen one is logged).
- ``MOOLIB_TELEMETRY_DIR`` — run directory for periodic JSONL snapshots
  (``telemetry.jsonl``) and the final host Chrome trace
  (``host_trace.json``).
- ``MOOLIB_TELEMETRY_INTERVAL`` — JSONL snapshot period, seconds
  (default 15).
- ``MOOLIB_TELEMETRY_SIGUSR1`` — ``0`` disables the dump-on-signal
  handler (installed by default when ``init_from_env`` runs on the main
  thread).
- ``MOOLIB_DEVMON_INTERVAL`` / ``MOOLIB_DEVMON_HBM_WARN_FRACTION`` —
  device performance plane knobs (:mod:`moolib_tpu.telemetry.devmon`).

The metric name reference lives in docs/TELEMETRY.md.
"""

from __future__ import annotations

import atexit
import os
import threading
from typing import Optional

from .metrics import (  # noqa: F401
    Counter,
    Gauge,
    Histogram,
    Registry,
    DEFAULT_SIZE_BUCKETS,
    DEFAULT_TIME_BUCKETS,
    get_registry,
)
from .tracing import (  # noqa: F401
    Span,
    TraceContext,
    Tracer,
    attach_context,
    child_span,
    current_context,
    decode_context,
    encode_context,
    get_tracer,
    root_span,
    span,
)
from .exporters import (  # noqa: F401
    JsonlSnapshotter,
    dump_diagnostics,
    install_signal_dump,
    prometheus_text,
    read_snapshot_tail,
    serve_http,
)
from .flightrec import (  # noqa: F401
    FlightRecorder,
    flight_event,
    get_flight_recorder,
)
from .cohort import CohortCounters  # noqa: F401
from .aggregator import CohortAggregator, install_rpc_handlers  # noqa: F401
from . import devmon  # noqa: F401
from . import profiling  # noqa: F401
from .hostmon import ensure_host_monitor  # noqa: F401
from .recovery import (  # noqa: F401
    RECOVERY_BUCKETS,
    RECOVERY_PHASES,
    observe_phase,
    recovery_histogram,
)

__all__ = [
    "CohortAggregator",
    "CohortCounters",
    "install_rpc_handlers",
    "profiling",
    "RECOVERY_BUCKETS",
    "RECOVERY_PHASES",
    "observe_phase",
    "recovery_histogram",
    "Counter",
    "FlightRecorder",
    "Gauge",
    "Histogram",
    "JsonlSnapshotter",
    "Registry",
    "Span",
    "TraceContext",
    "Tracer",
    "attach_context",
    "child_span",
    "current_context",
    "decode_context",
    "devmon",
    "dump_diagnostics",
    "encode_context",
    "ensure_host_monitor",
    "flight_event",
    "flush",
    "get_flight_recorder",
    "get_registry",
    "get_tracer",
    "init_from_env",
    "install_signal_dump",
    "read_snapshot_tail",
    "root_span",
    "shutdown",
    "prometheus_text",
    "serve_http",
    "span",
]

_init_lock = threading.Lock()
_initialized = False
_snapshotter: Optional[JsonlSnapshotter] = None
_http_port: Optional[int] = None


def init_from_env() -> dict:
    """Start the exporters the environment asks for (see module docstring).

    Idempotent — entry points and libraries can all call it; only the first
    call starts anything.  Returns ``{"http_port": int|None, "run_dir":
    str|None}`` for logging."""
    global _initialized, _snapshotter, _http_port
    with _init_lock:
        if _initialized:
            return {"http_port": _http_port, "run_dir": _snapshotter._dir if _snapshotter else None}
        _initialized = True
        # Every failure below degrades to "that exporter is off" with a
        # stderr note — a malformed observability knob must never kill a
        # training entry point at startup.
        run_dir = os.environ.get("MOOLIB_TELEMETRY_DIR") or None
        port_s = os.environ.get("MOOLIB_TELEMETRY_HTTP_PORT")
        if port_s is not None:
            try:
                _http_port = serve_http(int(port_s))
            except (OSError, ValueError) as e:
                _http_port = None
                _warn(f"http exporter disabled ({e!r})")
        if run_dir:
            try:
                interval = float(os.environ.get("MOOLIB_TELEMETRY_INTERVAL", "15"))
            except ValueError as e:
                interval = 15.0
                _warn(f"bad MOOLIB_TELEMETRY_INTERVAL ({e!r}); using 15s")
            try:
                _snapshotter = JsonlSnapshotter(run_dir, interval=interval)
                # Runs shorter than one interval still get their final
                # snapshot + host trace; an earlier explicit shutdown()
                # makes this a no-op.
                atexit.register(shutdown)
            except OSError as e:
                run_dir = None
                _warn(f"jsonl exporter disabled ({e!r})")
        if os.environ.get("MOOLIB_TELEMETRY_SIGUSR1", "1") != "0":
            install_signal_dump(run_dir)
        try:
            # Device performance plane: jax.monitoring compile listeners
            # (only when jax is already in the process) and the optional
            # periodic HBM sampler (MOOLIB_DEVMON_INTERVAL).
            devmon.install_from_env()
        except Exception as e:  # noqa: BLE001 — same degradation contract
            _warn(f"devmon disabled ({e!r})")
        return {"http_port": _http_port, "run_dir": run_dir}


def _warn(msg: str) -> None:
    import sys

    sys.stderr.write(f"moolib_tpu.telemetry: {msg}\n")


def flush() -> None:
    """Write a JSONL snapshot + host trace now, keeping the exporters
    running.  Entry points call this at the end of train() — a second
    train() in the same process keeps its telemetry (shutdown() would
    permanently disable the snapshotter while init_from_env stays latched).
    """
    with _init_lock:
        snap = _snapshotter
    if snap is not None:
        snap.flush()


def shutdown() -> None:
    """Stop the JSONL snapshotter after a final snapshot + host trace.
    Registered atexit by init_from_env; daemon threads die with the
    process either way."""
    global _snapshotter
    with _init_lock:
        snap, _snapshotter = _snapshotter, None
        if snap is not None:
            snap.close()
