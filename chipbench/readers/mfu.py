"""Model FLOP/s utilisation in percent: operations per token, from the
function in ``chipbench/flops.py`` that ``spec["flops"]`` names, times tokens
per second over chips times the peak of ``peaks.json``.  A device kind that
the table lacks is an error."""

from chipbench import flops


def read(spec, ctx):
    values = ctx["measured"].values
    tokens, seconds = values.get("tokens"), values.get("window_s")
    if not tokens or not seconds:
        return None
    peak = ctx["peaks"]["device_kinds"][ctx["device"]["kind"]]["bf16_flops_per_s"]
    per_token = getattr(flops, spec["flops"])(
        ctx["config"], int(values["n_layer"]), int(values["seq_len"]))
    return 100.0 * per_token * tokens / seconds / (ctx["device"]["count"] * peak)
