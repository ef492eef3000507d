"""A quantile over ALL entries of a series the runner recorded (one entry a
request or a step), by linear interpolation between the sorted entries:
``{"list": ..., "q": 0.9}``; q = 0.5 is the median."""


def read(spec, ctx):
    xs = sorted(ctx["measured"].lists.get(spec["list"]) or ())
    if len(xs) < 2:
        return None
    pos = spec["q"] * (len(xs) - 1)
    lo = int(pos)
    hi = min(lo + 1, len(xs) - 1)
    return (xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)) * spec.get("scale", 1.0)
