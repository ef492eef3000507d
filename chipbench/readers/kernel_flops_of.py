"""A kernel's share of the chip's peak of operations, in percent, where the
operations follow from what the program counted for a call
(``kernel_flops_roofline``'s twin for a kernel whose calls' shapes hold a
bucket and not the work: ``kernel_bytes_roofline`` over the other peak):
``metric`` names the program's histogram of what a call really worked on (a
prompt's real positions), read as its mean INSIDE the traced window (the
runner's ``values["trace_mean.<histogram>"]``);
``chipbench/<module>.<flops>(config, mean)`` counts one call, and is linear in
the mean, so that the mean over the window's calls gives their sum.  The
operations of every matched call of the traced window over the peak of
``peaks.json`` over their self time.

``{"module": "flops_ssd", "flops": "ssd_prefill", "metric":
"serve_engine_scan_prefill_positions", "pattern": "^%?ssd_prefill[\\w.]* = "}``.
Nothing to read (no trace, no such operation, no such histogram: an older
program; no peak on record) gives ``None``."""

import importlib
import re


def read(spec, ctx):
    trace = ctx["measured"].trace
    peak = ctx["peaks"]["device_kinds"].get(ctx["device"]["kind"])
    mean = ctx["measured"].values.get("trace_mean." + spec["metric"])
    if not trace or peak is None or mean is None:
        return None
    rx = re.compile(spec["pattern"])
    seconds = [s for text, s in trace["op_seconds"] if rx.search(text)]
    if not sum(seconds):
        return None
    count = getattr(importlib.import_module("chipbench." + spec["module"]), spec["flops"])
    return 100.0 * len(seconds) * count(ctx["config"], mean) / sum(seconds) / peak["bf16_flops_per_s"]
