"""Readers: one module per kind, ``read(spec, ctx) -> float | None``.

``spec`` is the metric's own file (``chipbench/metrics/<name>.json``); ``ctx``
holds ``measured`` (a ``harness.Measured``), ``config``, ``traffic``, ``cell``,
``device`` and ``peaks``.  A reader that finds nothing to read returns
``None`` and the harness leaves the metric out of the line.
"""


def series(snapshot, name, labels):
    """The value of one series of a ``Registry.snapshot()``, or ``None``."""
    family = snapshot.get(name)
    if family is None:
        return None
    for s in family["series"]:
        if all(s["labels"].get(k) == v for k, v in (labels or {}).items()):
            return s["value"]
    return None
