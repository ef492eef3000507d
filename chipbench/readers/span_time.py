"""A figure of the PROGRAM's own spans in the traced window.

The program's tracer writes each of its spans into the profiler's trace
(``moolib_tpu/telemetry/tracing.py``), on the device events' clock.
``trace_reduce.extract`` keeps only the benchmark's ``chipbench.*`` spans, so
this reader loads the cell's newest trace itself and takes the host events
whose names the metric's file lists:

``{"figure": "idle_share", "spans": [...], "among": [...]}``
    percent of the window, mean over the chips, in which the chip ran no
    operation and the innermost open span of ``among`` (the latest started, on
    any host thread) was one of ``spans``.  ``among`` is the whole family of
    spans, the same in every metric of a cell, so that every idle instant goes
    to at most one of them and nesting is resolved the same way.  A gap is
    split where the owner changes: each span gets the part it overlaps.
``{"figure": "mean_ms", "spans": [...]}``
    mean duration, in ms, of the spans of those names that lie wholly inside
    the window.

The window is the benchmark's ``chipbench.trace_window`` span.  A program
that records no such spans (an older commit) gives ``None``: the metric is
left out of the line.
"""

import glob
import os

from chipbench import harness
from chipbench import trace_reduce as tr


def extract(data):
    """``ProfileData`` -> the operations of each device plane, and every host
    event as ``name -> [(start_ns, duration_ns)]``."""
    devices, host = {}, {}
    for plane in data.planes:
        m = tr.DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m and line.name == tr.OPS_LINE:
                devices[int(m[1])] = [(ev.name, float(ev.start_ns), float(ev.duration_ns))
                                      for ev in line.events]
            elif not m:
                for ev in line.events:
                    host.setdefault(ev.name, []).append(
                        (float(ev.start_ns), float(ev.duration_ns)))
    return devices, host


def owners(spans):
    """``[(name, start, duration)]`` -> disjoint ``[(lo, hi, name)]`` in time
    order: over each stretch, the open span that started last."""
    points = sorted({p for _n, s, d in spans for p in (s, s + d)})
    order = sorted(spans, key=lambda e: e[1])
    out, open_, i = [], [], 0
    for lo, hi in zip(points, points[1:]):
        while i < len(order) and order[i][1] <= lo:
            open_.append(order[i])
            i += 1
        open_ = [e for e in open_ if e[1] + e[2] > lo]
        if open_:
            out.append((lo, hi, open_[-1][0]))
    return out


def idle_by_owner(events, owned, lo, hi):
    """Nanoseconds of ``lo..hi`` in which one chip ran none of ``events``,
    by the name that owned them."""
    out, i = {}, 0
    for a, b in tr._gaps(tr.busy_intervals(tr._clip(events, lo, hi)), lo, hi):
        while i < len(owned) and owned[i][1] <= a:
            i += 1
        j = i
        while j < len(owned) and owned[j][0] < b:
            s, e, name = owned[j]
            out[name] = out.get(name, 0.0) + min(e, b) - max(s, a)
            j += 1
    return out


def figure(spec, devices, host, n_devices):
    window = host.get(tr.WINDOW_SPAN)
    devices = [evs for _k, evs in sorted(devices.items())[:n_devices] if evs]
    if not window or not devices:
        return None
    lo, hi = window[0][0], window[0][0] + window[0][1]
    named = lambda names: [(n, s, d) for n in names for s, d in host.get(n, ())]
    if spec["figure"] == "mean_ms":
        inside = [d for _n, s, d in named(spec["spans"]) if s >= lo and s + d <= hi]
        return sum(inside) / len(inside) / 1e6 if inside else None
    if spec["figure"] == "idle_share":
        among = tr._clip(named(spec["among"]), lo, hi)
        if not among:
            return None
        owned = owners(among)
        idle = [idle_by_owner(evs, owned, lo, hi) for evs in devices]
        mine = sum(ns for by in idle for name, ns in by.items() if name in spec["spans"])
        return 100.0 * mine / len(devices) / (hi - lo)
    raise ValueError(f"unknown span figure {spec['figure']!r}")


def read(spec, ctx):
    if ctx["measured"].trace is None:  # not a traced run: an older trace may lie there
        return None
    paths = sorted(glob.glob(os.path.join(
        harness.TRACE_DIR, ctx["cell"]["name"], "plugins/profile/*/*.xplane.pb")))
    if not paths:
        return None
    if "program_trace" not in ctx:  # the cell's metrics share one ctx: load once
        ctx["program_trace"] = extract(tr.load(paths[-1]))
    return figure(spec, *ctx["program_trace"], n_devices=ctx["device"]["count"])
