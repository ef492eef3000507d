"""``values[num] / values[den] * scale``: a rate over the whole window."""


def read(spec, ctx):
    values = ctx["measured"].values
    num, den = values.get(spec["num"]), values.get(spec["den"])
    if num is None or not den:
        return None
    return num / den * spec.get("scale", 1.0)
