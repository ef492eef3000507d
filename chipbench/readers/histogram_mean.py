"""Mean of what one of the program's histograms observed inside the window:
(sum after - sum before) / (count after - count before), times ``scale``.
``{"metric": "serve_phase_seconds", "labels": {"phase": "queue"}}``."""

from . import series


def read(spec, ctx):
    m = ctx["measured"]
    after = series(m.counters_after, spec["metric"], spec.get("labels"))
    if after is None:
        return None
    before = series(m.counters_before, spec["metric"], spec.get("labels")) or {"sum": 0.0, "count": 0}
    n = after["count"] - before["count"]
    if n <= 0:
        return None
    return (after["sum"] - before["sum"]) / n * spec.get("scale", 1.0)
