"""Three figures of the program's registry over the measured window
(``counters_before`` / ``counters_after``, as ``histogram_mean`` reads them).
None is a mean: a share says where a total went, and a level says how bad the
worst moment was, which a mean over 7,000 observations hides.

``{"figure": "counter_share", "metric": name, "labels": {...}}``
    the rise, over the window, of the series the labels select, over the
    rise of every series of the family, in percent.  ``None`` where the
    family is not there (an older program) or none of it rose.
``{"figure": "family_share", "metric": name, "among": [name, ...]}``
    a share ACROSS families, where the program counts the parts of one total
    under several names: the rise of every series of ``metric`` over the rise
    of every series of every family of ``among`` (which names ``metric``
    too), in percent.  ``None`` where a family of ``among`` is not there (a
    part is not counted: the share would read too high) or none of them rose.
``{"figure": "histogram_longest_le", "metric": name, "labels": {...},
"scale": s}``
    the upper edge, times ``scale``, of the highest bucket whose count rose
    in the window, over every series the labels select (no labels: the whole
    family).  The edges stand in the snapshot's family under ``buckets``, the
    counts in each series' ``value["buckets"]``, one longer for +Inf; a rise
    in +Inf reads TWICE the last edge (there is no edge to report, and the
    reading must still stand above the last).  ``None`` where none rose.
    With the registry's default time buckets and ``scale`` 1000 the readings
    are 0.1, 0.5, 1, 5, 10, 50, 100, 500, 1000, 5000, 30000, 120000, 240000
    ms: a level, not a value, so it needs no threshold, and a stall of a
    second cannot hide in it.
"""


def selected(snapshot, name, labels):
    """Every series of the family the labels select, by its label set."""
    family = snapshot.get(name) or {"series": []}
    return {tuple(sorted(s["labels"].items())): s["value"] for s in family["series"]
            if all(s["labels"].get(k) == v for k, v in (labels or {}).items())}


def counter_share(spec, before, after):
    was = selected(before, spec["metric"], None)
    rises = {key: value - was.get(key, 0.0)
             for key, value in selected(after, spec["metric"], None).items()}
    total = sum(rises.values())
    mine = selected(after, spec["metric"], spec.get("labels"))
    return 100.0 * sum(rises[key] for key in mine) / total if total > 0 else None


def family_share(spec, before, after):
    if spec["metric"] not in spec["among"]:
        raise ValueError(f"a share of its own total: {spec['metric']!r} is not among {spec['among']}")
    if any(name not in after for name in spec["among"]):
        return None
    rise = lambda name: (sum(selected(after, name, None).values())
                         - sum(selected(before, name, None).values()))
    total = sum(rise(name) for name in spec["among"])
    return 100.0 * rise(spec["metric"]) / total if total > 0 else None


def histogram_longest_le(spec, before, after):
    edges = (after.get(spec["metric"]) or {}).get("buckets")
    if not edges:
        return None
    was = selected(before, spec["metric"], spec.get("labels"))
    highest = -1
    for key, value in selected(after, spec["metric"], spec.get("labels")).items():
        old = was[key]["buckets"] if key in was else [0] * len(value["buckets"])
        rose = [i for i, (a, b) in enumerate(zip(value["buckets"], old)) if a > b]
        highest = max([highest] + rose)
    if highest < 0:
        return None
    edge = edges[highest] if highest < len(edges) else 2.0 * edges[-1]
    return edge * spec.get("scale", 1.0)


FIGURES = {"counter_share": counter_share, "family_share": family_share,
           "histogram_longest_le": histogram_longest_le}


def read(spec, ctx):
    m = ctx["measured"]
    if spec["figure"] not in FIGURES:
        raise ValueError(f"unknown registry figure {spec['figure']!r}")
    return FIGURES[spec["figure"]](spec, m.counters_before, m.counters_after)
