"""A figure of the reduced trace: ``idle_share`` (1 - busy / window) or
``collective_exposed_share`` (collective self time / window), in percent."""


def read(spec, ctx):
    trace = ctx["measured"].trace
    if not trace or not trace["window_s"]:
        return None
    if spec["figure"] == "idle_share":
        return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
    if spec["figure"] == "collective_exposed_share":
        return 100.0 * trace["collective_exposed_s"] / trace["window_s"]
    raise ValueError(f"unknown trace figure {spec['figure']!r}")
