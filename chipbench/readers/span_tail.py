"""What the traced tail held at its worst: a figure of the PROGRAM's spans in
the traced window that is neither a mean nor a share (``span_time`` has
those).  The trace is the cell's newest, found and loaded as ``span_time``
finds and loads it (once a line: the cell's metrics share one ``ctx``).

``{"figure": "longest_ms", "spans": [...], "witness": [...]}``
    the longest, in ms, of the spans of those names that overlap the
    benchmark's ``chipbench.trace_window``, each clipped to it.  With none:
    0 where a span of ``witness`` is in the trace (the program was watching
    and nothing happened: no collection in the tail), else ``None`` (an older
    program, and the metric is left out of the line).
"""

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


FIGURES = {"longest_ms": longest_ms}


def read(spec, ctx):
    if spec["figure"] not in FIGURES:
        raise ValueError(f"unknown span figure {spec['figure']!r}")
    # span_time's own read finds the cell's newest trace and leaves it in ctx;
    # a mean over no span reads nothing, whatever the trace holds
    span_time.read({"figure": "mean_ms", "spans": []}, ctx)
    if ctx["measured"].trace is None or "program_trace" not in ctx:
        return None
    return FIGURES[spec["figure"]](spec, *ctx["program_trace"])
