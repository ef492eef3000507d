"""On-demand ``jax.profiler`` device-trace windows, RPC- or signal-driven.

A device profile is the one observability surface you cannot leave running:
it costs memory and perturbs timing.  This module makes it a *window* you
open remotely on a live process — over the ``__telemetry_profile`` RPC
every scrapable peer defines (:func:`moolib_tpu.telemetry.aggregator
.install_rpc_handlers`), or a local signal toggle — and closes either
explicitly or after a timed duration.

Each window records a ``device_profile`` span in the host tracer when it
closes, with the same ``perf_counter_ns`` clock every other span uses, so a
merged cohort timeline (``scripts/trace_merge.py``) shows exactly which
host-side work the device capture brackets; the returned anchors
(``unix_time_ns``/``perf_counter_ns`` at start) let offline tooling align
the XLA trace the same way.

When a window closes, the trace it has just written is read back and the
stop's reply says what the device ran, by PROGRAM (:func:`summarize`): runs,
device seconds and share of busy time, mean and longest run, and how long
each run waited behind its dispatch.  Only the profiler's program line (one
event a run of a compiled program) and the host's spans are read, never the
operation line, in the thread that called the stop: an RPC handler's or a
timer's, not the loop that feeds the device.

``jax`` is imported lazily inside the start path only — processes that
never profile (env workers, the broker) never pay the import, and a box
without jax degrades to an error dict instead of an exception.
"""

from __future__ import annotations

import bisect
import glob
import os
import re
import signal as _signal
import threading
import time
from typing import Dict, Optional

from . import tracing
from .flightrec import flight_event

__all__ = [
    "start_device_trace",
    "stop_device_trace",
    "profile_status",
    "handle_command",
    "install_signal_toggle",
    "summarize",
]

_lock = threading.Lock()
_active: Optional[dict] = None  # {"logdir", "t0_ns", "unix_ns", "timer", "guard"}

DEFAULT_WINDOW_S = 3.0
# Hard ceiling on any window's lifetime.  The stop is normally an RPC from
# the requester; a requester killed mid-window would otherwise leave the
# profiler armed forever (collecting, costing memory, blocking every later
# start with "profile already active").  MOOLIB_PROFILE_MAX_WINDOW_S
# overrides; <= 0 disables the guard.
DEFAULT_MAX_WINDOW_S = 120.0


def _max_window_s() -> float:
    try:
        return float(
            os.environ.get("MOOLIB_PROFILE_MAX_WINDOW_S", str(DEFAULT_MAX_WINDOW_S))
        )
    except ValueError:
        return DEFAULT_MAX_WINDOW_S


def _arm_guard(logdir: str, max_s: float):
    """Watchdog-fed deadline that force-stops an abandoned window.  Returns
    the guard object to close on a normal stop; None when disabled.  Uses
    the repo Watchdog (lazy import — watchdog.py imports telemetry) with a
    plain daemon Timer as fallback so the ceiling survives either way."""

    def _expire(_section: str, timeout: float) -> None:
        with _lock:
            abandoned = _active is not None and _active["logdir"] == logdir
        if not abandoned:
            return  # the window was stopped (and maybe another opened) in time
        flight_event("profile.abandoned", logdir=logdir, max_window_s=timeout)
        tracing.get_tracer().event(
            "device_profile.abandoned", logdir=logdir, max_window_s=timeout
        )
        stop_device_trace()

    try:
        from ..watchdog import Watchdog

        wd = Watchdog(
            timeout=max_s, on_expire=_expire, name="profile-window", dump=False
        )
        wd.arm("device_profile", max_s)
        return wd
    except Exception:  # noqa: BLE001 — guard must not block the profile itself
        timer = threading.Timer(max_s, _expire, args=("device_profile", max_s))
        timer.daemon = True
        timer.start()
        return timer


def _close_guard(guard) -> None:
    if guard is None:
        return
    try:
        guard.close()  # Watchdog
    except AttributeError:
        guard.cancel()  # Timer fallback


def _default_logdir() -> str:
    base = os.environ.get("MOOLIB_PROFILE_DIR") or os.path.join(
        os.environ.get("TMPDIR", "/tmp"), "moolib_profiles"
    )
    return os.path.join(base, f"pid{os.getpid()}-{int(time.time())}")


def start_device_trace(logdir: Optional[str] = None) -> dict:
    """Open a ``jax.profiler`` trace window.  Returns ``{"ok": True,
    "logdir", "unix_time_ns", "perf_counter_ns"}`` (the anchors match the
    host tracer's clock) or ``{"ok": False, "error"}`` — never raises, so
    the RPC handler can always serialize the answer."""
    global _active
    with _lock:
        if _active is not None:
            return {"ok": False, "error": "profile already active", "logdir": _active["logdir"]}
        logdir = logdir or _default_logdir()
        try:
            import jax

            os.makedirs(logdir, exist_ok=True)
            jax.profiler.start_trace(logdir)
        except ImportError:
            return {"ok": False, "error": "jax unavailable"}
        except Exception as e:  # noqa: BLE001 — report, don't kill the peer
            return {"ok": False, "error": f"start_trace failed: {e}"}
        _active = {
            "logdir": logdir,
            "t0_ns": time.perf_counter_ns(),
            "unix_ns": time.time_ns(),
            "timer": None,
            "guard": None,
        }
        tracing.get_tracer().event("device_profile.start", logdir=logdir)
        anchors = {
            "ok": True,
            "logdir": logdir,
            "unix_time_ns": _active["unix_ns"],
            "perf_counter_ns": _active["t0_ns"],
        }
    # Outside the lock: the guard's expiry path calls stop_device_trace,
    # and Watchdog construction must not run under _lock.
    max_s = _max_window_s()
    if max_s > 0:
        guard = _arm_guard(logdir, max_s)
        with _lock:
            if _active is not None and _active["logdir"] == logdir:
                _active["guard"] = guard
            else:  # stopped already (tiny window) — don't leak the monitor
                _close_guard(guard)
    return anchors


def stop_device_trace() -> dict:
    """Close the active window; records the ``device_profile`` host span
    covering it."""
    global _active
    err = None
    with _lock:
        if _active is None:
            return {"ok": False, "error": "no profile active"}
        state, _active = _active, None
        timer = state.get("timer")
        if timer is not None:
            timer.cancel()
        try:
            import jax

            jax.profiler.stop_trace()
        except ImportError:
            err = {"ok": False, "error": "jax unavailable"}
        except Exception as e:  # noqa: BLE001
            err = {"ok": False, "error": f"stop_trace failed: {e}", "logdir": state["logdir"]}
    # Outside the lock: closing the guard may join its monitor thread, whose
    # expiry path takes _lock.
    _close_guard(state.get("guard"))
    if err is not None:
        return err
    dur_ns = time.perf_counter_ns() - state["t0_ns"]
    summary = {"window_s": dur_ns / 1e9, **summarize(state["logdir"])}
    flight_event("profile.summary", logdir=state["logdir"], **summary)
    tracing.get_tracer().record(
        "device_profile",
        state["t0_ns"],
        dur_ns,
        args={"logdir": state["logdir"], "summary": summary},
    )
    return {"ok": True, "logdir": state["logdir"], "duration_s": dur_ns / 1e9,
            "summary": summary}


# ------------------------------------------------- what the window's device ran
_DEVICE_PLANE = re.compile(r"^/device:\w+:(\d+)$")
_RUN = re.compile(r"^jit_(\w+)\(\d+\)$")  # a run of a program on the "XLA Modules" line


def _nested(events):
    """One thread's ``(start, end, ...)`` events in time order, and for each
    the index of the event around it (-1: none).  A thread's events nest."""
    events.sort(key=lambda e: (e[0], -e[1]))
    around, open_ = [], []
    for i, ev in enumerate(events):
        while open_ and events[open_[-1]][1] <= ev[0]:
            open_.pop()
        around.append(open_[-1] if open_ else -1)
        open_.append(i)
    return events, around


def _open_at(nested, t):
    """Index of the innermost of one thread's events open at ``t``, or -1."""
    if nested is None:
        return -1
    events, around = nested
    i = bisect.bisect_right(events, t, key=lambda e: e[0]) - 1
    while i >= 0 and events[i][1] <= t:
        i = around[i]
    return i


def summarize(logdir: str, programs: Optional[Dict[str, str]] = None) -> dict:
    """What the newest trace under ``logdir`` says the device ran, by program
    (``devmon.jit_program`` names them; docs/TELEMETRY.md "Device programs").

    Each run on a chip's ``XLA Modules`` line is tied to the host span that
    dispatched it by the launch's own flow events (``_pt``/``_p`` produce,
    ``_ct``/``_c`` consume), walked back from the run to the event the Python
    thread produced inside the span; nothing is matched by nearness in time,
    and a run whose walk ends elsewhere (dispatched before the window opened)
    counts as unmatched.  A dispatch span is one opened with a ``program``
    argument (its outermost, where spans of one launch nest), and it must name
    the run's program, with a ``seq`` above the last matched run's; for a
    trace of spans without arguments pass ``programs``, program -> span name.

    Returns ``{"busy_s", "programs": {name: {"runs", "device_s", "busy_share",
    "mean_ms", "max_ms", "matched", "queue_delay_mean_ms",
    "queue_delay_max_ms", "by_rows"}}, "matched_share", "clock_lead_ms"}``:
    ``busy_s`` the union of the runs (mean over chips), a queue delay the run's
    device start minus its span's start, ``by_rows`` the same split by the
    spans' ``rows`` (the decode step's row count), and ``clock_lead_ms`` the
    largest span start minus run start over the matched runs, 0 at the least
    (no run starts before its dispatch began: the rest is the two clocks'
    disagreement); the figures of the match are ``None`` when under 95% of the
    runs have one.  ``{"programs": {}}`` where the trace has no program line
    (the CPU backend) or cannot be read; never raises."""
    try:
        return _summarize(logdir, programs)
    except Exception as e:  # noqa: BLE001 — the window closed; the summary is a courtesy
        return {"programs": {}, "error": f"{type(e).__name__}: {e}"}


def _summarize(logdir: str, programs: Optional[Dict[str, str]]) -> dict:
    paths = sorted(glob.glob(os.path.join(logdir, "plugins/profile/*/*.xplane.pb")))
    if not paths:
        return {"programs": {}}
    from jax.profiler import ProfileData

    runs: Dict[int, list] = {}  # chip -> [(start, end, program, flow)]
    made: Dict[tuple, tuple] = {}  # flow -> (line, start) of the event that produced it
    taken: Dict[tuple, list] = {}  # line -> [(start, end, flow)] of the events that consume one
    spans: Dict[tuple, list] = {}  # line -> [(start, end, program, args)] of the dispatch spans
    by_span = {span: program for program, span in (programs or {}).items()}
    for p, plane in enumerate(ProfileData.from_file(paths[-1]).planes):
        chip = _DEVICE_PLANE.match(plane.name)
        for l, line in enumerate(plane.lines):
            if chip and line.name != "XLA Modules":
                continue
            for ev in line.events:
                name = ev.name
                if name.startswith("$"):  # the Python tracer's own events
                    continue
                t0 = float(ev.start_ns)
                t1 = t0 + float(ev.duration_ns)
                st = dict(ev.stats)
                if chip:
                    m = _RUN.match(name)
                    if m:
                        runs.setdefault(int(chip[1]), []).append(
                            (t0, t1, m[1], (st.get("_ct"), st.get("_c"))))
                    continue
                program = by_span.get(name) if programs else st.get("program")
                if program is not None:
                    spans.setdefault((p, l), []).append((t0, t1, program, st))
                    continue
                if "_p" in st:
                    made[(st.get("_pt"), st["_p"])] = ((p, l), t0)
                if "_c" in st:
                    taken.setdefault((p, l), []).append((t0, t1, (st.get("_ct"), st["_c"])))
    if not runs:
        return {"programs": {}}
    dispatched = {ev[2] for evs in spans.values() for ev in evs}  # programs owed a match
    taken = {line: _nested(evs) for line, evs in taken.items()}
    spans = {line: _nested(evs) for line, evs in spans.items()}

    def cause(flow):
        """The dispatch span at the head of the launch that ``flow`` ends."""
        for _ in range(16):  # a v5 lite's chain has three hops
            if flow not in made:
                return None
            line, t = made[flow]
            i = _open_at(spans.get(line), t)
            if i >= 0:
                events, around = spans[line]
                # spans of one launch nest (engine.state_write in engine.join): the outermost
                while around[i] >= 0 and all(events[around[i]][3].get(k) == events[i][3].get(k)
                                             for k in ("program", "seq")):
                    i = around[i]
                return events[i]
            i = _open_at(taken.get(line), t)
            if i < 0:
                return None
            flow = taken[line][0][i][2]
        return None

    out: Dict[str, dict] = {}
    busy_ns, owed, lead = 0.0, 0, 0.0
    for _chip, chip_runs in sorted(runs.items()):
        chip_runs.sort()
        at, last_seq = float("-inf"), {}
        for t0, t1, program, flow in chip_runs:
            busy_ns += max(0.0, t1 - max(t0, at))
            at = max(at, t1)
            row = out.setdefault(program, {"runs": 0, "device_ns": 0.0, "max_ns": 0.0,
                                           "delays": [], "by_rows": {}})
            row["runs"] += 1
            row["device_ns"] += t1 - t0
            row["max_ns"] = max(row["max_ns"], t1 - t0)
            if program not in dispatched:
                continue
            owed += 1
            span = cause(flow)
            seq = None if span is None else span[3].get("seq")
            if span is None or span[2] != program or (
                    seq is not None and seq <= last_seq.get(program, -1)):
                continue  # unmatched: reported as such, never guessed
            last_seq[program] = seq
            lead = max(lead, span[0] - t0)
            row["delays"].append(t0 - span[0])
            if "rows" in span[3]:
                by = row["by_rows"].setdefault(span[3]["rows"], [0, 0.0])
                by[0] += 1
                by[1] += t1 - t0
    matched = sum(len(row["delays"]) for row in out.values())
    enough = owed > 0 and matched >= 0.95 * owed
    for row in out.values():
        delays, device_ns, n = row.pop("delays"), row.pop("device_ns"), row["runs"]
        row.update(
            device_s=device_ns / len(runs) / 1e9, busy_share=100.0 * device_ns / busy_ns,
            mean_ms=device_ns / n / 1e6, max_ms=row.pop("max_ns") / 1e6, matched=len(delays),
            queue_delay_mean_ms=sum(delays) / len(delays) / 1e6 if delays and enough else None,
            queue_delay_max_ms=max(delays) / 1e6 if delays and enough else None,
            by_rows={rows: {"runs": k, "mean_ms": ns / k / 1e6}
                     for rows, (k, ns) in sorted(row["by_rows"].items())})
    return {"busy_s": busy_ns / len(runs) / 1e9, "programs": out,
            "matched_share": 100.0 * matched / owed if owed else None,
            "clock_lead_ms": lead / 1e6 if enough else None}


def profile_status() -> dict:
    with _lock:
        if _active is None:
            return {"active": False}
        return {"active": True, "logdir": _active["logdir"]}


def handle_command(
    action: str, logdir: Optional[str] = None, seconds: Optional[float] = None
) -> dict:
    """The ``__telemetry_profile`` RPC surface:

    - ``"start"`` — open a window (until an explicit stop).
    - ``"stop"`` — close it.
    - ``"status"`` — is one open, and where.
    - ``"window"`` — open and auto-close after ``seconds``
      (default :data:`DEFAULT_WINDOW_S`); the follow-up stop runs on a
      daemon timer, so the requesting client doesn't have to stay alive.
    """
    if action == "start":
        return start_device_trace(logdir)
    if action == "stop":
        return stop_device_trace()
    if action == "status":
        return profile_status()
    if action == "window":
        res = start_device_trace(logdir)
        if not res.get("ok"):
            return res
        delay = DEFAULT_WINDOW_S if seconds is None else max(0.1, float(seconds))
        timer = threading.Timer(delay, stop_device_trace)
        timer.daemon = True
        with _lock:
            if _active is not None:
                _active["timer"] = timer
        timer.start()
        res["window_s"] = delay
        return res
    return {"ok": False, "error": f"unknown action {action!r}"}


def install_signal_toggle(
    signum: int = _signal.SIGUSR2, logdir: Optional[str] = None
) -> bool:
    """Toggle a device-trace window on ``signum`` (default SIGUSR2 — the
    SIGUSR1 slot belongs to the diagnostics dump).  Main thread only;
    returns False when the handler could not be installed."""

    def _toggle(sig, frame):
        if profile_status()["active"]:
            # The stop writes the trace and reads it back (``summarize``): on
            # a thread of its own, not between two bytecodes of the main one.
            threading.Thread(target=stop_device_trace, name="profile-stop", daemon=True).start()
        else:
            start_device_trace(logdir)

    try:
        _signal.signal(signum, _toggle)
    except (ValueError, OSError):
        return False
    return True
