"""``memory_stats()["peak_bytes_in_use"]`` of the fullest chip, in GB (1e9)."""


def read(spec, ctx):
    peak = ctx["device"]["memory_peak_bytes"]
    return peak / 1e9 if peak else None
