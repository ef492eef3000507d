"""A value the runner measured itself, times ``scale``: ``{"key": ...}``."""


def read(spec, ctx):
    v = ctx["measured"].values.get(spec["key"])
    return None if v is None else v * spec.get("scale", 1.0)
