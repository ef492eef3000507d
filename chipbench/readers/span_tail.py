"""What the traced tail held at its worst: figures of the PROGRAM's spans in
the traced window that are neither a mean nor a share (``span_time`` has
those).  The trace is the cell's newest, found and loaded as ``span_time``
finds and loads it (once a line: the cell's metrics share one ``ctx``).

``{"figure": "longest_ms", "spans": [...], "witness": [...]}``
    the longest, in ms, of the spans of those names that overlap the
    benchmark's ``chipbench.trace_window``, each clipped to it.  With none:
    0 where a span of ``witness`` is in the trace (the program was watching
    and nothing happened: no collection in the tail), else ``None`` (an older
    program, and the metric is left out of the line).
``{"figure": "clock_lead_ms", "spans": [...], "dispatch": [...]}``
    a lower bound, in ms, on how far device events LEAD host spans in this
    trace.  ``spans`` are those at whose end the device has nothing in
    flight (the engine was empty; a prefill's token has come back);
    ``dispatch`` is every span in which the host hands the device work.  For
    each span of ``spans`` that ends inside the window with chip 0 idle at
    its end: ``h`` = start of the first ``dispatch`` span at or after that
    end, ``d`` = start of the first device operation after the chip went
    idle.  A device cannot start what the host has not dispatched, so ``h -
    d`` above 0 is how much the two clocks disagree, at the least (the launch
    takes time too).  The figure is the largest over the window's wake-ups;
    ``None`` with none.  Reported only: ``span_time`` is not corrected by it.
"""

import bisect

from chipbench import trace_reduce as tr
from chipbench.readers import span_time


def _window(host):
    window = host.get(tr.WINDOW_SPAN)
    return (window[0][0], window[0][0] + window[0][1]) if window else None


def longest_ms(spec, devices, host):
    window = _window(host)
    if window is None:
        return None
    named = [(n, s, d) for n in spec["spans"] for s, d in host.get(n, ())]
    clipped = tr._clip(named, *window)
    if clipped:
        return max(d for _n, _s, d in clipped) / 1e6
    return 0.0 if any(host.get(n) for n in spec.get("witness", ())) else None


def clock_lead_ms(spec, devices, host):
    window = _window(host)
    if window is None or not devices or not devices[min(devices)]:
        return None
    lo, hi = window
    busy = tr.busy_intervals(devices[min(devices)])
    busy_starts = [a for a, _b in busy]
    dispatches = sorted(s for n in spec["dispatch"] for s, _d in host.get(n, ()))
    leads = []
    for name in spec["spans"]:
        for start, dur in host.get(name, ()):
            end = start + dur
            k = bisect.bisect_right(busy_starts, end)  # busy[k] is the first to start after end
            j = bisect.bisect_left(dispatches, end)
            idle = k == 0 or busy[k - 1][1] <= end
            if lo <= end <= hi and idle and k < len(busy) and j < len(dispatches):
                leads.append((dispatches[j] - busy_starts[k]) / 1e6)
    return max(leads) if leads else None


FIGURES = {"longest_ms": longest_ms, "clock_lead_ms": clock_lead_ms}


def read(spec, ctx):
    if spec["figure"] not in FIGURES:
        raise ValueError(f"unknown span figure {spec['figure']!r}")
    # span_time's own read finds the cell's newest trace and leaves it in ctx;
    # a mean over no span reads nothing, whatever the trace holds
    span_time.read({"figure": "mean_ms", "spans": []}, ctx)
    if ctx["measured"].trace is None or "program_trace" not in ctx:
        return None
    return FIGURES[spec["figure"]](spec, *ctx["program_trace"])
