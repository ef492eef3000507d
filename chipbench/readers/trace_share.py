"""Share of device busy time spent in the operations whose HLO text matches
``pattern``, in percent, from the traced window."""

from chipbench import trace_reduce


def read(spec, ctx):
    trace = ctx["measured"].trace
    if not trace or not trace["busy_s"]:
        return None
    return 100.0 * trace_reduce.pattern_seconds(trace, spec["pattern"]) / trace["busy_s"]
