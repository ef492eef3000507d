"""``kernel_roofline`` for byte functions kept in another module of the
benchmark: a kernel's share of its memory roofline, in percent.  The spec is
``kernel_roofline``'s with ``"module"``, the module under ``chipbench/`` that
holds the function ``"bytes"`` names:

``{"module": "kernel_bytes_hybrid", "bytes": "kda_decode", "pattern": ...,
"events_per_call": 1, "metric": "serve_engine_state_live_slots"}``.

Bytes from the mean of the program's histogram INSIDE the traced window (the
runner's ``values["trace_mean.<histogram>"]``) over the chip's HBM peak
(``chipbench/hbm_peaks.json``), over the seconds one call took in the same
window (self time of the operations whose HLO text matches ``pattern``, over
their number over ``events_per_call``).  Nothing to read (no trace, no such
operation, no such histogram: an older program) gives ``None``."""

import importlib
import re

from chipbench import harness


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
    count = getattr(importlib.import_module("chipbench." + spec["module"]), spec["bytes"])
    return 100.0 * count(ctx["config"], ctx["traffic"], mean) / peak["hbm_bytes_per_s"] / (
        sum(seconds) / calls)
