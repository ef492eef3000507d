"""Mean of a gauge the runner sampled through the window: ``{"gauge": name}``."""


def read(spec, ctx):
    xs = ctx["measured"].samples.get(spec["gauge"])
    if not xs:
        return None
    return sum(xs) / len(xs) * spec.get("scale", 1.0)
