"""A kernel's share of its memory roofline, in percent: the bytes one call
must move (``chipbench/kernel_bytes.py``, fed the mean of one of the
program's histograms INSIDE the traced window: the runner's
``values["trace_mean.<histogram>"]``, so that bytes and seconds are read on
one clock) over the chip's HBM peak
(``chipbench/hbm_peaks.json``), over the seconds one call took in the traced
window (self time of the operations whose HLO text matches ``pattern``,
divided by their number over ``events_per_call``: an expert layer is two
grouped matmuls).

``{"pattern": ..., "events_per_call": 2, "bytes": "moe_expert_matmul",
"metric": "serve_engine_experts_touched"}``.  Nothing to read (no trace, no
such operation, no such histogram: an older program) gives ``None``."""

import re

from chipbench import harness, kernel_bytes



def read(spec, ctx):
    trace = ctx["measured"].trace
    kinds = harness.load_json(harness.BENCH_DIR, "hbm_peaks.json")["device_kinds"]
    peak = kinds.get(ctx["device"]["kind"])
    mean = ctx["measured"].values.get("trace_mean." + spec["metric"])
    if not trace or peak is None or mean is None:
        return None
    rx = re.compile(spec["pattern"])
    seconds = [s for text, s in trace["op_seconds"] if rx.search(text)]
    calls = len(seconds) / spec.get("events_per_call", 1)
    if not calls or not sum(seconds):
        return None
    need = getattr(kernel_bytes, spec["bytes"])(ctx["config"], ctx["traffic"], mean)
    return 100.0 * need / peak["hbm_bytes_per_s"] / (sum(seconds) / calls)
