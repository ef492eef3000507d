"""A kernel's share of the chip's peak of operations, in percent, where the
operations follow from the matched call's own shape: ``pattern`` matches the
operation's HLO text at its result, group 1 the number of heads and group 2
the sequence length;
``chipbench/<module>.<flops>(config, heads, length)`` counts one call.  The
operations of every matched call of the traced window over the peak of
``peaks.json`` over their self time.

``{"module": "flops_window", "flops": "windowed_attention", "pattern":
"^%?flash_attention[\\w.]* = \\(?bf16\\[(72),(\\d+),128\\]"}``.  Nothing to read
(no trace, no such operation: an older program; no peak on record) gives
``None``."""

import importlib
import re


def read(spec, ctx):
    trace = ctx["measured"].trace
    peak = ctx["peaks"]["device_kinds"].get(ctx["device"]["kind"])
    if not trace or peak is None:
        return None
    rx = re.compile(spec["pattern"])
    count = getattr(importlib.import_module("chipbench." + spec["module"]), spec["flops"])
    flops = seconds = 0.0
    for text, s in trace["op_seconds"]:
        m = rx.search(text)
        if m:
            flops += count(ctx["config"], int(m.group(1)), int(m.group(2)))
            seconds += s
    if not seconds:
        return None
    return 100.0 * flops / seconds / peak["bf16_flops_per_s"]
