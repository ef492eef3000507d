"""A kernel's share of the chip's HBM bandwidth, in percent, where the bytes
follow from what the program counted for a call and from the matched call's
own shape (``kernel_flops_roofline``'s twin for a kernel that is judged
against memory, whose calls differ in size): ``metric`` names the program's
histogram of what a call really worked on (here a prompt's real positions: the
call's shape holds its bucket, padding included), read as its mean INSIDE the
traced window (the runner's ``values["trace_mean.<histogram>"]``); ``pattern``
matches the operation's HLO text at its result, its groups are whole numbers
(here the channels); ``chipbench/<module>.<bytes>(config, mean, *groups)``
counts one call, and is linear in the mean, so that the mean over the window's
calls gives their sum.  The bytes of every matched call of the traced window
over the peak of ``hbm_peaks.json`` over their self time.

``{"module": "kernel_bytes_ssm", "bytes": "ssm_prefill", "metric":
"serve_engine_scan_prefill_positions", "pattern":
"^%?ssm_prefill[\\w.]* = \\(f32\\[\\d+,(\\d+)\\]"}``.  Nothing to read (no
trace, no such operation, no such histogram: an older program; no peak on
record) gives ``None``."""

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
    count = getattr(importlib.import_module("chipbench." + spec["module"]), spec["bytes"])
    moved = seconds = 0.0
    for text, s in trace["op_seconds"]:
        m = rx.search(text)
        if m:
            moved += count(ctx["config"], mean, *(int(g) for g in m.groups()))
            seconds += s
    if not seconds:
        return None
    return 100.0 * moved / seconds / peak["hbm_bytes_per_s"]
