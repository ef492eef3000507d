"""Device performance plane: compile, HBM, and MFU accounting.

The cohort observability plane can say *that* a step is slow; this module
says *why*.  Four sub-planes, all publishing through the process metrics
registry (docs/TELEMETRY.md "Device performance plane"):

- **Compile observability** — :func:`install_compile_listeners` subscribes
  to ``jax.monitoring`` (backend compile durations, persistent-cache
  hits/misses) and :func:`instrument_jit` wraps a jitted callable with a
  recompile detector that asks the jit's own cache whether a call compiled
  (O(1) a call; :func:`jit_program` names, jits and wraps in one step, so
  that a program has ONE name from the profiler's program line to the
  counter's label): every *new* abstract input signature increments
  ``jit_compiles_total{fn}``, and a signature change after the first compile
  emits one ``devmon.recompile`` flight event carrying the signature diff
  plus a stderr WARN — the dynamic counterpart of the static
  ``recompile-risk`` lint (docs/ANALYSIS.md).
- **Memory** — :func:`sample_memory` polls ``device.memory_stats()`` into
  ``hbm_bytes_{in_use,peak,limit}{device}`` gauges with high-watermark
  tracking and an OOM-margin warning (``MOOLIB_DEVMON_HBM_WARN_FRACTION``).
  Backends without allocator stats (CPU) fall back to host RSS under
  ``device="host"`` so the gauges populate everywhere.
- **Step cost / MFU** — :func:`step_cost` pulls XLA's counted flops and
  bytes accessed from ``jitted.lower(...).compile().cost_analysis()``
  (cached per abstract signature) and :func:`publish_step` combines it with
  a measured step time into ``step_mfu{fn}`` / ``step_bytes_per_flop{fn}``
  gauges plus a roofline classification (compute- vs memory-bound).  The
  peak FLOP/s and HBM-bandwidth tables live here — the one home for numbers
  ``benchmarks/impala_roofline.py`` and the examples used to hand-maintain.
- **Cohort skew** — lives on
  :meth:`moolib_tpu.telemetry.aggregator.CohortAggregator.step_skew`, which
  fuses per-peer step timings scraped over RPC; this module only documents
  the gauges it publishes.

Everything here is jax-optional at import time: the telemetry package must
stay importable from env workers that never touch jax, so jax imports are
deferred into the functions that need them.
"""

from __future__ import annotations

import functools
import math
import os
import re
import sys
import threading
import time
from collections import Counter
from typing import Any, Dict, List, Optional, Tuple

from . import metrics
from .flightrec import flight_event

__all__ = [
    "StepCost",
    "compile_summary",
    "install_compile_listeners",
    "install_from_env",
    "instrument_jit",
    "jit_program",
    "last_recompile",
    "observe_call",
    "peak_bandwidth",
    "peak_flops",
    "publish_step",
    "reset_for_tests",
    "roofline",
    "sample_memory",
    "start",
    "step_cost",
    "stop",
    "summary_text",
]

_REG = metrics.get_registry()
_M_COMPILES = _REG.counter(
    "jit_compiles_total",
    "distinct abstract input signatures seen per instrumented jit "
    "(each one is an XLA compile)",
    ("fn",),
)
_M_RECOMPILES = _REG.counter(
    "jit_recompiles_total",
    "signature changes after the first compile (each emitted a "
    "devmon.recompile flight event)",
    ("fn",),
)
_M_COMPILE_SECONDS = _REG.histogram(
    "jit_compile_seconds",
    "backend (XLA) compile wall time, from jax.monitoring",
)
_M_CACHE_HITS = _REG.counter(
    "jit_cache_hits_total", "persistent compile-cache hits (jax.monitoring)"
)
_M_CACHE_MISSES = _REG.counter(
    "jit_cache_misses_total", "persistent compile-cache misses (jax.monitoring)"
)
_M_HBM_IN_USE = _REG.gauge(
    "hbm_bytes_in_use", "allocator bytes in use per device (host RSS on CPU)",
    ("device",),
)
_M_HBM_PEAK = _REG.gauge(
    "hbm_bytes_peak", "allocator peak bytes in use per device", ("device",)
)
_M_HBM_LIMIT = _REG.gauge(
    "hbm_bytes_limit", "allocator byte limit per device (host MemTotal on CPU)",
    ("device",),
)
_M_STEP_MFU = _REG.gauge(
    "step_mfu",
    "model FLOPs utilization: XLA-counted flops / step seconds / peak FLOP/s",
    ("fn",),
)
_M_STEP_BPF = _REG.gauge(
    "step_bytes_per_flop",
    "XLA-counted bytes accessed per flop for the step (arithmetic intensity^-1)",
    ("fn",),
)
_M_STEP_FLOPS = _REG.gauge(
    "step_flops", "XLA-counted model flops per step", ("fn",)
)
_M_STEP_BYTES = _REG.gauge(
    "step_bytes_accessed", "XLA-counted bytes accessed per step", ("fn",)
)

# Peak dense (bf16) FLOP/s and HBM bandwidth per chip, from public spec
# sheets.  Substring-matched against ``device.device_kind`` — order matters
# ("v5p" and "v5 lite" before "v5").  These tables are the one home for the
# numbers; bench.py, impala_roofline.py and the benchmarks read them here.
# The CPU backend has no peak (MFU is None there); any other kind missing
# from the tables is an error, never a default.
_PEAK_FLOPS: List[Tuple[str, float]] = [
    ("v6e", 918e12),
    ("v6 lite", 918e12),
    ("v6", 918e12),
    ("v5p", 459e12),
    ("v5 lite", 197e12),
    ("v5e", 197e12),
    ("v5", 459e12),
    ("v4", 275e12),
    ("v3", 123e12),
    ("v2", 45e12),
]
_PEAK_BW: List[Tuple[str, float]] = [
    ("v6e", 1640e9),
    ("v6 lite", 1640e9),
    ("v6", 1640e9),
    ("v5p", 2765e9),
    ("v5 lite", 819e9),
    ("v5e", 819e9),
    ("v5", 2765e9),
    ("v4", 1228e9),
    ("v3", 900e9),
    ("v2", 700e9),
]

_lock = threading.RLock()
# fn name -> {"seen": set, "last": sig, "compiles": int, "recompiles": int,
#             "last_diff": str|None}
_JIT_STATE: Dict[str, Dict[str, Any]] = {}
_COST_CACHE: Dict[Tuple[str, Any], Optional["StepCost"]] = {}
_WATERMARKS: Dict[str, float] = {}  # device label -> peak bytes_in_use seen
_HBM_WARNED: Dict[str, bool] = {}  # device label -> currently above threshold
_LAST_MEMORY: Dict[str, Dict[str, float]] = {}
_listeners_installed = False
_thread: Optional[threading.Thread] = None
_thread_stop = threading.Event()


# --------------------------------------------------------------------- compile
def install_compile_listeners() -> bool:
    """Subscribe to ``jax.monitoring``: backend compile durations feed
    ``jit_compile_seconds``; persistent compile-cache hit/miss events feed
    ``jit_cache_{hits,misses}_total``.  Idempotent; returns False when the
    listeners were already installed (or jax.monitoring is unavailable)."""
    global _listeners_installed
    with _lock:
        if _listeners_installed:
            return False
        try:
            from jax import monitoring  # deferred: telemetry imports without jax
        except Exception:  # noqa: BLE001 — no jax, no compile plane
            return False

        def _on_duration(key: str, dur: float, **kw) -> None:
            if "backend_compile" in key:
                _M_COMPILE_SECONDS.observe(dur)

        def _on_event(key: str, **kw) -> None:
            if key.endswith("cache_hits"):
                _M_CACHE_HITS.inc()
            elif key.endswith("cache_misses"):
                _M_CACHE_MISSES.inc()

        try:
            monitoring.register_event_duration_secs_listener(_on_duration)
            monitoring.register_event_listener(_on_event)
        except Exception:  # noqa: BLE001 — observability must not break startup
            return False
        _listeners_installed = True
        return True


def compile_summary() -> Dict[str, float]:
    """What the compile listeners have counted in this process: seconds
    spent compiling or fetching compiled programs, how many programs, and
    the persistent cache's hits and misses (a miss is an entry written)."""
    hist = _M_COMPILE_SECONDS.labels().get()
    return {
        "compile_s": hist["sum"],
        "compiles": hist["count"],
        "cache_hits": _M_CACHE_HITS.labels().get(),
        "cache_misses": _M_CACHE_MISSES.labels().get(),
    }


def _leaf_sig(x) -> str:
    shape = getattr(x, "shape", None)
    dtype = getattr(x, "dtype", None)
    if shape is not None and dtype is not None:
        return f"{tuple(shape)}/{dtype}"
    return type(x).__name__


def _signature(args, kwargs):
    """Cheap abstract signature of a call: the treedef plus per-leaf
    (shape, dtype) strings — exactly what decides whether jax.jit retraces
    (python-scalar leaves collapse to their type: jit weak-types them, so
    value changes don't recompile and must not count here)."""
    import jax

    leaves, treedef = jax.tree_util.tree_flatten((args, kwargs))
    return (str(treedef), tuple(_leaf_sig(l) for l in leaves))


def _diff_sigs(old, new) -> str:
    """Compact human diff between two signatures, for the flight event."""
    if old[0] != new[0]:
        return f"tree structure changed: {old[0]} -> {new[0]}"
    parts = []
    o, n = old[1], new[1]
    for i in range(max(len(o), len(n))):
        ov = o[i] if i < len(o) else "<absent>"
        nv = n[i] if i < len(n) else "<absent>"
        if ov != nv:
            parts.append(f"leaf[{i}]: {ov} -> {nv}")
    return "; ".join(parts) or "signatures differ"


def _cache_size(fn) -> Optional[int]:
    """How many programs a jit holds, or None for a callable that cannot say
    (anything but ``jax.jit``'s own wrapper)."""
    size = getattr(fn, "_cache_size", None)
    return None if size is None else size()


class _InstrumentedJit:
    """Callable wrapper around a jitted function that tracks abstract input
    signatures.  Attribute access (``lower``, ``_cache_size``, ...) forwards
    to the wrapped jit so AOT paths and tests see the real object.

    A call costs two reads of the jit's cache size and one integer add:
    ``seq`` is how many times this process has called the program, so read
    BEFORE a call it is the number of the dispatch about to be made (what the
    dispatching span hands the profiler, docs/TELEMETRY.md "Device
    programs").  The signature (a flatten of every argument and one string a
    leaf) is computed only when this call compiled: the first call, and then
    whenever the cache grew, from the arguments after the call (a donated
    array keeps its shape and dtype).  A wrapped callable that has no
    ``_cache_size`` pays for the signature at every call, as before."""

    __slots__ = ("_fn", "name", "seq", "_primed", "__weakref__")  # jax keys caches on weak references

    def __init__(self, fn, name: str):
        self._fn = fn
        self.name = name
        self.seq = 0
        self._primed = False

    def __call__(self, *args, **kwargs):
        self.seq += 1
        before = _cache_size(self._fn)
        out = self._fn(*args, **kwargs)
        if not self._primed or before is None or _cache_size(self._fn) != before:
            self._primed = True
            observe_call(self.name, args, kwargs)
        return out

    def __getattr__(self, item):
        return getattr(self._fn, item)


def instrument_jit(fn, name: str):
    """Wrap a jitted callable with the recompile detector (idempotent on
    already-wrapped callables)."""
    if isinstance(fn, _InstrumentedJit):
        return fn
    return _InstrumentedJit(fn, name)


def jit_program(fn, name: str, **jit_kwargs):
    """Name, jit and instrument ``fn``: the one place a device program gets
    its name.  ``name`` (letters, digits and ``_``) is then the same string
    everywhere the program shows: ``jit_<name>(...)`` on the profiler's
    ``XLA Modules`` line and in the compiled module's text, the ``fn`` label
    of ``jit_compiles_total`` and of the recompile flight event, and the
    ``program`` argument of the span that dispatches it (the caller passes
    ``program=jitted.name, seq=jitted.seq`` when it opens that span).
    ``jit_kwargs`` go to ``jax.jit``; argument numbers count ``fn``'s own
    parameters (a bound method's ``self`` is not one)."""
    import jax

    @functools.wraps(fn)
    def program(*args, **kwargs):
        return fn(*args, **kwargs)

    program.__name__ = program.__qualname__ = name
    return instrument_jit(jax.jit(program, **jit_kwargs), name)


def record_signature(name: str, sig) -> bool:
    """Feed one observed call signature to the detector; returns True when
    the signature is new (== an XLA compile).  A new signature after the
    first emits exactly one ``devmon.recompile`` flight event + WARN;
    returning to an already-seen signature is silent (jit serves it from
    cache — no compile happened)."""
    with _lock:
        st = _JIT_STATE.get(name)
        if st is None:
            st = _JIT_STATE[name] = {
                "seen": set(), "last": None, "compiles": 0,
                "recompiles": 0, "last_diff": None,
            }
        fresh = sig not in st["seen"]
        if fresh:
            st["seen"].add(sig)
            st["compiles"] += 1
            recompile = st["last"] is not None
            if recompile:
                st["recompiles"] += 1
                st["last_diff"] = _diff_sigs(st["last"], sig)
        prev_diff = st["last_diff"]
        st["last"] = sig
    if fresh:
        _M_COMPILES.inc(fn=name)
        if recompile:
            _M_RECOMPILES.inc(fn=name)
            flight_event("devmon.recompile", fn=name, diff=prev_diff)
            sys.stderr.write(
                f"moolib_tpu.devmon: WARN recompile of {name!r}: {prev_diff}\n"
            )
    return fresh


def observe_call(name: str, args=(), kwargs=None) -> None:
    """Record one call's abstract signature for ``name`` without wrapping
    the callable — the seam for step functions that are closures rather
    than raw jits (parallel/train.py).  Never raises."""
    try:
        record_signature(name, _signature(args, kwargs or {}))
    except Exception:  # noqa: BLE001 — accounting must never break the step
        pass


def last_recompile(name: str) -> Optional[str]:
    """The most recent signature diff that triggered a recompile of ``name``
    (None when the fn never recompiled)."""
    with _lock:
        st = _JIT_STATE.get(name)
        return st["last_diff"] if st else None


# ---------------------------------------------------------------------- memory
def _host_memory() -> Optional[Dict[str, float]]:
    """RSS + MemTotal fallback for backends without allocator stats."""
    try:
        with open("/proc/self/statm") as f:
            rss_pages = int(f.read().split()[1])
        rss = rss_pages * os.sysconf("SC_PAGE_SIZE")
    except (OSError, ValueError, IndexError):
        return None
    limit = 0.0
    try:
        with open("/proc/meminfo") as f:
            for line in f:
                if line.startswith("MemTotal:"):
                    limit = float(line.split()[1]) * 1024.0
                    break
    except (OSError, ValueError, IndexError):
        pass
    return {"bytes_in_use": float(rss), "bytes_limit": limit}


def _warn_fraction() -> float:
    try:
        return float(os.environ.get("MOOLIB_DEVMON_HBM_WARN_FRACTION", "0.9"))
    except ValueError:
        return 0.9


def sample_memory() -> Dict[str, Dict[str, float]]:
    """One memory sample across ``jax.local_devices()`` into the
    ``hbm_bytes_*`` gauges, with high-watermark tracking and an OOM-margin
    warning: crossing ``MOOLIB_DEVMON_HBM_WARN_FRACTION`` of the limit emits
    a ``devmon.hbm_pressure`` flight event once per excursion (re-armed when
    usage drops back under).  Devices without ``memory_stats()`` (CPU)
    collapse into one host-RSS sample under ``device="host"``."""
    try:
        import jax

        devices = jax.local_devices()
    except Exception:  # noqa: BLE001 — no jax backend, fall through to host
        devices = []
    out: Dict[str, Dict[str, float]] = {}
    for d in devices:
        try:
            ms = d.memory_stats()
        except Exception:  # noqa: BLE001 — per-device stats are best-effort
            ms = None
        if not ms:
            continue
        label = f"{d.platform}:{d.id}"
        out[label] = {
            "bytes_in_use": float(ms.get("bytes_in_use", 0.0)),
            "bytes_peak": float(
                ms.get("peak_bytes_in_use", ms.get("bytes_in_use", 0.0))
            ),
            "bytes_limit": float(ms.get("bytes_limit", 0.0)),
        }
    if not out:
        host = _host_memory()
        if host is not None:
            out["host"] = {
                "bytes_in_use": host["bytes_in_use"],
                "bytes_peak": host["bytes_in_use"],
                "bytes_limit": host["bytes_limit"],
            }
    frac = _warn_fraction()
    for label, row in out.items():
        with _lock:
            wm = max(_WATERMARKS.get(label, 0.0), row["bytes_in_use"],
                     row.get("bytes_peak", 0.0))
            _WATERMARKS[label] = wm
            _LAST_MEMORY[label] = dict(row)
        row["bytes_peak"] = max(row.get("bytes_peak", 0.0), wm)
        _M_HBM_IN_USE.set(row["bytes_in_use"], device=label)
        _M_HBM_PEAK.set(row["bytes_peak"], device=label)
        _M_HBM_LIMIT.set(row["bytes_limit"], device=label)
        limit = row["bytes_limit"]
        if limit > 0:
            over = row["bytes_in_use"] / limit >= frac
            with _lock:
                warned = _HBM_WARNED.get(label, False)
                _HBM_WARNED[label] = over
            if over and not warned:
                flight_event(
                    "devmon.hbm_pressure",
                    device=label,
                    in_use=int(row["bytes_in_use"]),
                    limit=int(limit),
                    fraction=round(row["bytes_in_use"] / limit, 3),
                )
                sys.stderr.write(
                    f"moolib_tpu.devmon: WARN {label} at "
                    f"{row['bytes_in_use'] / limit:.0%} of its memory limit\n"
                )
    return out


# --------------------------------------------------------------- step cost/MFU
# Opcode position only (``... all-reduce(`` / ``all-reduce-start(``): names
# and ``-done`` halves of async pairs must not count twice.
_COLLECTIVES = "all-reduce|all-gather|all-to-all|collective-permute|reduce-scatter"
_COLLECTIVE_RE = re.compile(rf" ({_COLLECTIVES})(?:-start)?\(")
_SYNC_COLLECTIVE_RE = re.compile(rf" = (.*?) (?:{_COLLECTIVES})\(")  # its result's type
_ARRAY_RE = re.compile(r"\b[a-z]+(\d+)\[([\d,]*)\]")  # bits and dims of f32[512,2048]


def sync_collectives(text: str) -> Tuple[int, int]:
    """``(count, bytes)`` of a compiled module's collectives that hold the
    device's operation line from start to end: every collective instruction
    that is neither the ``-start`` / ``-done`` half of a pair nor inside a
    computation an overlapping fusion calls (``calls=%async_collective_fusion.N``,
    or an ``%async-collective-start`` / ``-done`` fusion's).  Bytes are those
    of the collective's own result: an all-reduce and a slice count the
    all-reduce."""
    lines = text.splitlines()
    overlapped = set()
    for line in lines:
        if "calls=%async_collective_fusion" in line or line.lstrip().startswith(
                ("%async-collective-", "ROOT %async-collective-")):
            overlapped.update(re.findall(r"calls=%([\w.\-]+)", line))
    count = nbytes = 0
    inside = None
    for line in lines:
        if line.startswith(("%", "ENTRY ")):
            inside = line.removeprefix("ENTRY ").lstrip("%").split(" ")[0]
        m = None if inside in overlapped else _SYNC_COLLECTIVE_RE.search(line)
        if m is not None:
            count += 1
            nbytes += sum(
                math.prod(int(d) for d in dims.split(",") if d) * ((int(bits) + 7) // 8)
                for bits, dims in _ARRAY_RE.findall(m.group(1)))
    return count, nbytes


class StepCost:
    """What XLA compiled for one step: counted flops and bytes accessed,
    the program's device memory (arguments + outputs + temporaries, minus
    aliased bytes, from ``memory_analysis()``), the aliased bytes themselves
    (outputs that took a donated argument's memory; 0 where a donation could
    not be used), the number of Mosaic kernel call sites
    (``tpu_custom_call``), the collectives by kind, and how many of them
    hold the operation line with how many bytes (:func:`sync_collectives`)."""

    __slots__ = ("flops", "bytes_accessed", "memory_bytes", "donated_bytes",
                 "kernels", "collectives", "sync_collectives")

    def __init__(self, flops: float, bytes_accessed: float,
                 memory_bytes: Optional[int] = None, kernels: int = 0,
                 collectives: Optional[Dict[str, int]] = None,
                 donated_bytes: Optional[int] = None,
                 sync_collectives: Tuple[int, int] = (0, 0)):
        self.flops = float(flops)
        self.bytes_accessed = float(bytes_accessed)
        self.memory_bytes = memory_bytes
        self.donated_bytes = donated_bytes
        self.kernels = kernels
        self.collectives = collectives or {}
        self.sync_collectives = sync_collectives

    @property
    def arithmetic_intensity(self) -> Optional[float]:
        return self.flops / self.bytes_accessed if self.bytes_accessed else None

    def program(self) -> Dict[str, Any]:
        """The compiled program's facts as a JSON-ready dict."""
        return {"memory_bytes": self.memory_bytes, "mosaic_kernels": self.kernels,
                "collectives": dict(self.collectives),
                "sync_collectives": self.sync_collectives[0],
                "sync_collective_bytes": self.sync_collectives[1]}

    def __repr__(self):
        return f"StepCost(flops={self.flops:.3g}, bytes_accessed={self.bytes_accessed:.3g})"


def step_cost(name: str, jitted, *args, **kwargs) -> Optional["StepCost"]:
    """XLA's account of ``jitted(*args, **kwargs)``, cached per abstract
    signature (lowering is pure: donated buffers are NOT consumed).  When
    the step already compiled with these avals the ``.compile()`` here hits
    the persistent compile cache, so calling this after the first real step
    is cheap.  Returns None when the backend counts no flops; a step that
    does not compile raises, as the step itself would."""
    sig = (name, _signature(args, kwargs))
    with _lock:
        if sig in _COST_CACHE:
            return _COST_CACHE[sig]
    compiled = jitted.lower(*args, **kwargs).compile()
    analysis = compiled.cost_analysis()
    if isinstance(analysis, (list, tuple)):
        analysis = analysis[0] if analysis else {}
    cost = None
    flops = float((analysis or {}).get("flops", 0.0))
    if flops > 0:
        mem = compiled.memory_analysis()
        text = compiled.as_text()
        cost = StepCost(
            flops,
            float(analysis.get("bytes accessed", 0.0)),
            memory_bytes=None if mem is None else int(
                mem.argument_size_in_bytes + mem.output_size_in_bytes
                + mem.temp_size_in_bytes - mem.alias_size_in_bytes
            ),
            kernels=text.count("tpu_custom_call"),
            collectives=dict(Counter(_COLLECTIVE_RE.findall(text))),
            donated_bytes=None if mem is None else int(mem.alias_size_in_bytes),
            sync_collectives=sync_collectives(text),
        )
    with _lock:
        _COST_CACHE[sig] = cost
    if cost is not None:
        _M_STEP_FLOPS.set(cost.flops, fn=name)
        _M_STEP_BYTES.set(cost.bytes_accessed, fn=name)
    return cost


def _peak(table: List[Tuple[str, float]], device_kind: str) -> Optional[float]:
    k = device_kind.lower()
    if k == "cpu":
        return None
    for sub, peak in table:
        if sub in k:
            return peak
    raise ValueError(
        f"device kind {device_kind!r} is not in devmon's peak tables; add its "
        "published peak there (an MFU against an assumed peak is not reported)"
    )


def peak_flops(device_kind: str) -> Optional[float]:
    """Peak dense bf16 FLOP/s for a device kind from the spec table; None on
    the CPU backend; ``ValueError`` for any other kind the table lacks."""
    return _peak(_PEAK_FLOPS, device_kind)


def peak_bandwidth(device_kind: str) -> Optional[float]:
    """Peak HBM bytes/s for a device kind (same contract as
    :func:`peak_flops`)."""
    return _peak(_PEAK_BW, device_kind)


def roofline(flops: float, bytes_accessed: float, device_kind: str) -> Dict[str, Any]:
    """Roofline classification for a step: arithmetic intensity vs the
    chip's ridge point (peak_flops / peak_bw).  AI below the ridge means the
    step is HBM-bound; above, compute-bound.  On the CPU backend (no peaks)
    only the arithmetic intensity is reported and ``bound`` is None."""
    pf, pb = peak_flops(device_kind), peak_bandwidth(device_kind)
    out: Dict[str, Any] = {"peak_flops": pf, "peak_bw": pb, "bound": None}
    if not bytes_accessed or not flops:
        return out
    ai = flops / bytes_accessed
    out["arithmetic_intensity_flop_per_byte"] = ai
    if pf is None:
        return out
    ridge = pf / pb
    out["ridge_flop_per_byte"] = ridge
    out["min_step_s_compute"] = flops / pf
    out["min_step_s_memory"] = bytes_accessed / pb
    out["roofline_mfu_ceiling"] = min(1.0, ai / ridge)
    out["bound"] = "memory" if ai < ridge else "compute"
    return out


def publish_step(
    name: str,
    cost: Optional["StepCost"],
    step_seconds: float,
    device_kind: Optional[str] = None,
) -> Optional[Dict[str, Any]]:
    """Combine an XLA step cost with a measured step time into the
    ``step_mfu{fn}`` / ``step_bytes_per_flop{fn}`` gauges plus the roofline
    verdict.  Returns ``{"mfu", "bytes_per_flop", "bound", ...}``, or None
    when there is nothing to publish — degenerate inputs, or the CPU backend,
    which has no peak to hold a step against."""
    if cost is None or step_seconds <= 0 or cost.flops <= 0:
        return None
    if device_kind is None:
        import jax

        device_kind = jax.devices()[0].device_kind
    roof = roofline(cost.flops, cost.bytes_accessed, device_kind)
    bpf = cost.bytes_accessed / cost.flops
    _M_STEP_BPF.set(bpf, fn=name)
    if roof["peak_flops"] is None:
        return None
    mfu = cost.flops / step_seconds / roof["peak_flops"]
    _M_STEP_MFU.set(mfu, fn=name)
    return {
        "mfu": mfu,
        "bytes_per_flop": bpf,
        "bound": roof["bound"],
        "roofline": roof,
    }


# ------------------------------------------------------------------- lifecycle
def start(interval: float) -> bool:
    """Background memory-sampling thread (daemon; one per process)."""
    global _thread
    with _lock:
        if _thread is not None and _thread.is_alive():
            return False
        _thread_stop.clear()

        def _loop():
            while not _thread_stop.wait(interval):
                try:
                    sample_memory()
                except Exception:  # noqa: BLE001 — sampling must never crash the run
                    pass

        _thread = threading.Thread(target=_loop, name="devmon-mem", daemon=True)
        _thread.start()
        return True


def stop() -> None:
    global _thread
    with _lock:
        t, _thread = _thread, None
    if t is not None:
        _thread_stop.set()
        t.join(timeout=1.0)


def install_from_env() -> dict:
    """Wire the device plane per the environment: compile listeners when jax
    is already in the process (env workers that never import jax skip them),
    and the periodic memory sampler when ``MOOLIB_DEVMON_INTERVAL`` > 0.
    Called by :func:`moolib_tpu.telemetry.init_from_env`; idempotent."""
    listeners = False
    if "jax" in sys.modules:
        listeners = install_compile_listeners()
    interval = 0.0
    raw = os.environ.get("MOOLIB_DEVMON_INTERVAL")
    if raw:
        try:
            interval = float(raw)
        except ValueError:
            interval = 0.0
    started = start(interval) if interval > 0 else False
    return {"listeners": listeners, "interval": interval if started else None}


def summary_text() -> str:
    """Devmon section for :func:`~moolib_tpu.telemetry.exporters.dump_diagnostics`:
    per-device HBM watermarks, compile counts, and the last recompile
    signature diff per fn.  Formats already-collected dicts only — safe from
    a signal handler."""
    with _lock:
        jits = {k: dict(v) for k, v in _JIT_STATE.items()}
        marks = dict(_WATERMARKS)
        mem = {k: dict(v) for k, v in _LAST_MEMORY.items()}
    lines = ["--- devmon (device performance plane) ---\n"]
    if marks:
        for label in sorted(marks):
            row = mem.get(label, {})
            lines.append(
                f"memory {label}: watermark={marks[label] / 1e6:.1f}MB"
                f" in_use={row.get('bytes_in_use', 0.0) / 1e6:.1f}MB"
                f" limit={row.get('bytes_limit', 0.0) / 1e6:.1f}MB\n"
            )
    else:
        lines.append("memory: no samples yet\n")
    if jits:
        for name in sorted(jits):
            st = jits[name]
            lines.append(
                f"jit {name}: compiles={st['compiles']}"
                f" recompiles={st['recompiles']}\n"
            )
            if st["last_diff"]:
                lines.append(f"  last recompile: {st['last_diff']}\n")
    else:
        lines.append("jit: no instrumented callables yet\n")
    return "".join(lines)


def reset_for_tests() -> None:
    """Drop detector / cost-cache / watermark state (test isolation only;
    registered metrics reset separately via the registry)."""
    stop()
    with _lock:
        _JIT_STATE.clear()
        _COST_CACHE.clear()
        _WATERMARKS.clear()
        _HBM_WARNED.clear()
        _LAST_MEMORY.clear()
