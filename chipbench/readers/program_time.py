"""Figures of the device's PROGRAM line in the traced window.

Beside ``XLA Ops`` (one event an operation, what ``trace_reduce`` reads) a
chip's plane has a line ``XLA Modules`` with ONE event for each run of a
compiled program, named ``jit_<program>(<fingerprint>)``: where the program
gives its programs names (``moolib_tpu/telemetry/devmon.py:jit_program``) a
run's device time needs no pattern over the shapes of results.  This reader
loads the cell's newest trace as ``span_time`` does, takes that line of each
chip and clips to the benchmark's ``chipbench.trace_window``.  The metric's
file says which program and which figure:

``{"figure": "device_share", "program": P}``
    seconds of P's runs, clipped to the window, over the window's busy seconds
    (``trace_reduce``'s union of operations), percent, mean over the chips.  A
    program's run holds the gaps between its operations, so the programs'
    shares may sum past 100.
``{"figure": "mean_ms", "program": P}``
    mean device duration, in ms, of the runs of P that lie wholly inside the
    window, over all chips.
``{"figure": "queue_delay_mean_ms", "program": P}``
    over P's MATCHED runs that start inside the window: device start minus
    the start of the host span that dispatched the run, mean, in ms.
``{"figure": "clock_lead_ms"}``
    over ALL matched runs that start inside the window: the largest of span
    start minus run start, in ms, 0 at the least.  No run starts before its
    own dispatch began, so what is above 0 is how far the device events' clock
    leads the host spans' in this trace, at the least.

``{"figure": "mfu", "program": P, "flops": F, "args": [...]}``
    over P's MATCHED runs that lie wholly inside the window: the operations
    the runs WERE ASKED for, over their device seconds, over the chip's peak
    (``peaks.json``), in percent.  A run's operations are
    ``chipbench.flops.F(config, n_layer, **{a: the span's own argument a})``:
    what the dispatching span hands the profiler says what the launch was
    for (a prompt's REAL tokens, the rows stepped), whatever shape the program
    padded it to.  ``None`` with no such run, or when under 95% of P's runs
    inside the window are tied (the rest would go uncounted).  Nothing clamps
    it: operations counted too high, or seconds that leave work out, must
    show as a reading over 100.

``programs/<program>.json`` names, for each program, the span that dispatches
it (``engine_prefill``: ``engine.prefill_dispatch``, ...): one map for every
metric of this reader, so one load of the trace a line.  A run is tied to
its span by what the trace itself records of the launch, never by nearness in
time: the run consumes a flow (``_ct``/``_c``) that a host event produced
(``_pt``/``_p``: ``DoEnqueueProgram``); that event lies inside one that
consumes the flow of the event before it, and so on back to the one the Python
thread produced INSIDE the dispatch span (on a TPU v5 lite: run <-
``DoEnqueueProgram`` in ``...=>IssueSequencedEvent`` <- ``tpu::System::Execute``
in ``PJRT_LoadedExecutable_Execute`` <- ``... linkage`` in the span).  "Inside"
is containment on one thread's line.  The walk knows no event's name, only
the flows and the names of the dispatch spans.  The span's own arguments are
the cross-check: a span that says ``program`` must say the run's, and ``seq``
(the count of the program's dispatches) must rise with the runs.  A run whose
walk ends anywhere else is unmatched (its dispatch came before the profiler
started, as a step in flight's does), and a figure that needs the match reads
``None`` when under 95% of the window's runs have one.

A program without such names (an older commit: ``jit__prefill_impl``) gives
no run of ``P`` and every figure reads ``None``: the metric is left out of the
line.  Nothing of the program under test is imported here.
"""

import bisect
import glob
import os
import re

from chipbench import flops, harness
from chipbench import trace_reduce as tr

MODULES_LINE = "XLA Modules"
RUN_NAME = re.compile(r"^jit_(\w+)\(\d+\)$")
MATCHED_AT_LEAST = 0.95
_WALK_AT_MOST = 16  # hops from a run back to its span; a v5 lite's chain has 3


def _stats(ev):
    return {k: v for k, v in ev.stats}


def extract(data, span_names):
    """``ProfileData`` -> ``runs`` ``{chip: [run]}`` in time order, each a dict
    of ``program``, ``start``, ``end`` (ns), ``run_id`` and ``flow``; and the
    host's side ``(producers, consumers, spans, window)``: ``(type, id) ->
    (line, start)`` of the events that hand a flow on, ``line -> [(start, end,
    flow)]`` of those that take one up, ``line -> [(start, end, name, args)]``
    of the dispatch spans, both nested (``_nest``), and the window span's
    ``(lo, hi)`` or ``None``."""
    runs, producers, consumers, spans, window = {}, {}, {}, {}, None
    for p, plane in enumerate(data.planes):
        m = tr.DEVICE_PLANE.match(plane.name)
        for l, line in enumerate(plane.lines):
            if m:
                if line.name != MODULES_LINE:
                    continue
                for ev in line.events:
                    name = RUN_NAME.match(ev.name)
                    if name:
                        st = _stats(ev)
                        runs.setdefault(int(m[1]), []).append({
                            "program": name[1], "start": float(ev.start_ns),
                            "end": float(ev.start_ns) + float(ev.duration_ns),
                            "run_id": st.get("run_id"), "flow": (st.get("_ct"), st.get("_c"))})
                continue
            for ev in line.events:
                name = ev.name
                if name.startswith("$"):  # the Python tracer's calls: no flow, no span
                    continue
                start = float(ev.start_ns)
                end = start + float(ev.duration_ns)
                if name == tr.WINDOW_SPAN:
                    window = window or (start, end)
                elif name in span_names:
                    spans.setdefault((p, l), []).append((start, end, name, _stats(ev)))
                    continue
                st = _stats(ev)
                if "_p" in st:
                    producers[(st.get("_pt"), st["_p"])] = ((p, l), start)
                if "_c" in st:
                    consumers.setdefault((p, l), []).append((start, end, (st.get("_ct"), st["_c"])))
    for chip_runs in runs.values():
        chip_runs.sort(key=lambda r: r["start"])
    nested = lambda by_line: {line: _nest(events) for line, events in by_line.items()}
    return runs, (producers, nested(consumers), nested(spans), window)


def _nest(events):
    """One thread's events ``(start, end, ...)`` -> ``(events, parents)``: in
    time order, and for each the index of the event that encloses it, -1 for
    none (a thread's events nest properly)."""
    events = sorted(events, key=lambda e: (e[0], -e[1]))
    parents, open_ = [], []
    for i, ev in enumerate(events):
        while open_ and events[open_[-1]][1] <= ev[0]:
            open_.pop()
        parents.append(open_[-1] if open_ else -1)
        open_.append(i)
    return events, parents


def _innermost(nested, at):
    """Of one thread's nested events, the one that started last among those
    open at ``at``; ``None`` with none."""
    if nested is None:
        return None
    events, parents = nested
    i = bisect.bisect_right(events, at, key=lambda e: e[0]) - 1
    while i >= 0 and events[i][1] <= at:
        i = parents[i]
    return events[i] if i >= 0 else None


def dispatch_span(run, producers, consumers, spans):
    """The dispatch span whose launch became ``run``, as ``(start, end, name,
    args)``, or ``None``: from the run's flow back, producer by producer."""
    flow = run["flow"]
    for _ in range(_WALK_AT_MOST):
        producer = producers.get(flow)
        if producer is None:
            return None
        line, at = producer
        span = _innermost(spans.get(line), at)
        if span is not None:
            return span
        consumer = _innermost(consumers.get(line), at)
        if consumer is None:
            return None
        flow = consumer[2]
    return None


def matches(runs, host, programs):
    """``[(run, span or None)]`` over the runs of ``programs`` on every chip,
    a chip's in time order.  A span counts only if it is the one that
    dispatches the run's program, says so where it says anything, and its
    ``seq`` is above that of the chip's last matched run of the program."""
    producers, consumers, spans, _window = host
    out = []
    for _chip, chip_runs in sorted(runs.items()):
        last_seq = {}
        for run in chip_runs:
            program = run["program"]
            if program not in programs:
                continue
            span = dispatch_span(run, producers, consumers, spans)
            if span is not None:
                args = span[3]
                seq = args.get("seq")
                if (span[2] != programs[program] or args.get("program", program) != program
                        or (seq is not None and seq <= last_seq.get(program, -1))):
                    span = None
                elif seq is not None:
                    last_seq[program] = seq
            out.append((run, span))
    return out


def programs():
    """The one map every metric of this reader shares: ``programs/<program>.json``
    names the span that dispatches the program.  A later PR's program is one
    file more.  (A ``spec`` with a ``programs`` of its own is a test's.)"""
    return {os.path.basename(path)[:-len(".json")]: harness.load_json(path)["span"]
            for path in sorted(glob.glob(os.path.join(harness.BENCH_DIR, "programs", "*.json")))}


def figure(spec, runs, host, busy_s, n_devices, model=None):
    """``model``: ``(config, n_layer, peak operations a second)``, for the
    one figure that counts operations."""
    window = host[3]
    runs = dict(sorted(runs.items())[:n_devices])
    if window is None or not runs:
        return None
    lo, hi = window
    kind, program = spec["figure"], spec.get("program")
    mine = [r for chip_runs in runs.values() for r in chip_runs if r["program"] == program]
    if kind == "device_share":
        if not mine or not busy_s:
            return None
        clipped = sum(max(0.0, min(r["end"], hi) - max(r["start"], lo)) for r in mine)
        return 100.0 * clipped / len(runs) / (busy_s * 1e9)
    if kind == "mean_ms":
        inside = [r["end"] - r["start"] for r in mine if r["start"] >= lo and r["end"] <= hi]
        return sum(inside) / len(inside) / 1e6 if inside else None
    if kind in ("queue_delay_mean_ms", "clock_lead_ms", "matched_share", "mfu"):
        tied = matches(runs, host, spec.get("programs") or programs())
        if kind == "mfu":  # P's runs wholly inside the window: operations asked over seconds taken
            pairs = [(r, s) for r, s in tied
                     if r["program"] == program and r["start"] >= lo and r["end"] <= hi]
        else:
            pairs = [(r, s) for r, s in tied if lo <= r["start"] < hi]
        matched = [(r, s) for r, s in pairs if s is not None]
        if kind == "matched_share":
            return 100.0 * len(matched) / len(pairs) if pairs else None
        if not matched or len(matched) < MATCHED_AT_LEAST * len(pairs):
            return None
        if kind == "clock_lead_ms":
            return max(0.0, max(s[0] - r["start"] for r, s in matched)) / 1e6
        if kind == "mfu":
            config, n_layer, peak = model
            count = getattr(flops, spec["flops"])
            asked = sum(count(config, n_layer, **{a: s[3][a] for a in spec["args"]}) for _r, s in matched)
            return 100.0 * asked / (sum(r["end"] - r["start"] for r, _s in matched) / 1e9) / peak
        delays = [r["start"] - s[0] for r, s in matched if r["program"] == program]
        return sum(delays) / len(delays) / 1e6 if delays else None
    raise ValueError(f"unknown program figure {kind!r}")


def read(spec, ctx):
    trace = ctx["measured"].trace
    if trace is None:  # not a traced run: an older trace may lie there
        return None
    paths = sorted(glob.glob(os.path.join(
        harness.TRACE_DIR, ctx["cell"]["name"], "plugins/profile/*/*.xplane.pb")))
    if not paths:
        return None
    if "program_runs" not in ctx:  # the cell's metrics share one ctx and one map: one load
        ctx["program_runs"] = extract(tr.load(paths[-1]), set(programs().values()))
    model = None
    if spec["figure"] == "mfu":
        config = ctx["config"]
        model = (config, config["uses"][ctx["traffic"]["use"]]["n_layer"],
                 ctx["peaks"]["device_kinds"][ctx["device"]["kind"]]["bf16_flops_per_s"])
    return figure(spec, *ctx["program_runs"], busy_s=trace["busy_s"],
                  n_devices=ctx["device"]["count"], model=model)
