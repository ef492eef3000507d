"""From a profiler trace (``.xplane.pb``) to the numbers the metrics read.

What the TPU's trace looks like (TPU v5 lite, jax 0.9; see
``tests/data/sample.xplane.pb.gz``): one plane ``/device:TPU:<n>`` per chip,
whose line ``XLA Ops`` holds one event per executed HLO operation, named by
its HLO text (``%fusion.12 = bf16[32,2048]{...} fusion(...)``), with control
flow (``%while``) as an event that spans its body's events.  The host plane
``/host:CPU`` has one line per thread; ``jax.profiler.TraceAnnotation`` spans
sit on the line of the thread that opened them, on the device events' clock.

The reduction:

- busy time of a chip is the union of its operations' intervals, clipped to
  the window; idle share is one minus busy over the window.  The window is
  the benchmark's own ``chipbench.trace_window`` span where there is one,
  else the extent of the device events;
- an operation's time is its SELF time (its interval minus its children's),
  so a loop and its body are not counted twice;
- operations are grouped by kind, type and shape of their result
  (``convert_f32_2048_16_16_128_``), because the program gives them no
  stable names yet; a metric selects operations by a pattern on the HLO text;
- collective time counts as exposed: on the chip's one in-order operation
  line a collective's ``-start`` returns at once and its ``-done`` (or a
  synchronous collective, or a fusion that calls one: a reduce-scatter on
  this compiler) holds the line for as long as nothing else can run, and
  the part that is hidden never appears on that line;
- each idle gap is given to the innermost of the benchmark's own spans
  (``chipbench.*``) that was open at its midpoint, on any host thread, or to
  ``_no_benchmark_span_``; gaps are also counted by length (``idle_gap_sizes``:
  upper edge in seconds or none, gaps per chip, seconds per chip), which tells a pause
  between two programs from the many short ones inside a program.
"""

from __future__ import annotations

import gzip
import math
import re
from typing import Dict, Iterable, List, Optional, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
SPAN_PREFIX = "chipbench."
WINDOW_SPAN = SPAN_PREFIX + "trace_window"
_COLLECTIVES = r"(all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all)"
# The operation itself, or a fusion that calls one: this compiler runs a
# reduce-scatter as ``fusion(...), kind=kCustom, calls=%all-reduce-scatter.4``
# (an all-reduce and a slice).  A fusion that only overlaps one
# (``calls=%async_collective_fusion.7``) is compute and is not taken.
COLLECTIVE = re.compile(rf"\b{_COLLECTIVES}(-start|-done)?\(|\bcalls=%?{_COLLECTIVES}")
_HLO = re.compile(r"^%?(?P<name>[\w.\-]+) = (?P<result>.*)$")
_SHAPE = re.compile(r"([a-z]+\d*)\[([\d,]*)\]")
_LAYOUT = re.compile(r"\{[^{}]*\}")
GAP_CLASSES_S = (1e-5, 1e-4, 1e-3, 1e-2)  # idle gaps are counted by length, in these classes

Event = Tuple[str, float, float]  # text, start_ns, duration_ns


def op_key(text: str) -> str:
    """``%convert.3 = f32[2048,16,16,128]{...} convert(...)`` ->
    ``convert_f32_2048_16_16_128_``.  An operation with several results (a
    fusion that writes a matmul's product and a few reductions beside it) is
    named by its largest, the first of them on a tie; a text of another form
    stands for itself."""
    m = _HLO.match(text)
    result = _LAYOUT.sub("", m["result"]) if m else ""
    if result.startswith("("):
        result = result[1:result.find(")")]
    else:
        result = result.split(" ", 1)[0]
    shapes = _SHAPE.findall(result)
    if not shapes:
        return re.sub(r"[^\w]+", "_", text)[:60]
    size = lambda dims: math.prod(int(d) for d in dims.split(",") if d)
    dtype, dims = max(shapes, key=lambda s: size(s[1]))
    base = re.sub(r"[.\d]+$", "", m["name"]).replace("-", "_")
    return f"{base}_{dtype}_{dims.replace(',', '_')}_"


def extract(data) -> Dict:
    """``jax.profiler.ProfileData`` -> plain lists: the operations of each
    device plane and every one of the benchmark's spans."""
    devices: Dict[int, List[Event]] = {}
    spans: List[Event] = []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m and line.name == OPS_LINE:
                devices[int(m[1])] = [
                    (ev.name, float(ev.start_ns), float(ev.duration_ns))
                    for ev in line.events]
            elif not m:
                spans.extend(
                    (ev.name, float(ev.start_ns), float(ev.duration_ns))
                    for ev in line.events if ev.name.startswith(SPAN_PREFIX))
    return {"devices": devices, "spans": spans}


def load(path: str):
    from jax.profiler import ProfileData

    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            return ProfileData.from_serialized_xspace(f.read())
    return ProfileData.from_file(path)


def _clip(events: Iterable[Event], lo: float, hi: float) -> List[Event]:
    out = []
    for text, start, dur in events:
        s, e = max(start, lo), min(start + dur, hi)
        if e > s:
            out.append((text, s, e - s))
    return out


def self_times(events: List[Event]) -> List[Tuple[str, float]]:
    """(text, self ns) per event: its duration minus that of the events
    nested directly inside it."""
    order = sorted(events, key=lambda e: (e[1], -e[2]))
    out: List[List] = []
    stack: List[Tuple[int, float]] = []  # index into out, end
    for text, start, dur in order:
        while stack and start >= stack[-1][1]:
            stack.pop()
        if stack:
            out[stack[-1][0]][1] -= dur
        out.append([text, dur])
        stack.append((len(out) - 1, start + dur))
    return [(t, max(d, 0.0)) for t, d in out]


def busy_intervals(events: List[Event]) -> List[Tuple[float, float]]:
    merged: List[List[float]] = []
    for _t, start, dur in sorted(events, key=lambda e: e[1]):
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], start + dur)
        else:
            merged.append([start, start + dur])
    return [(a, b) for a, b in merged]


def _gaps(busy: List[Tuple[float, float]], lo: float, hi: float):
    at = lo
    for a, b in busy:
        if a > at:
            yield at, a
        at = max(at, b)
    if hi > at:
        yield at, hi


def _owner(spans: List[Event], t: float) -> str:
    best: Optional[Event] = None
    for sp in spans:
        if sp[1] <= t < sp[1] + sp[2] and (best is None or sp[1] >= best[1]):
            best = sp
    return best[0][len(SPAN_PREFIX):] if best else "_no_benchmark_span_"


def reduce(extracted: Dict, n_devices: int) -> Optional[Dict]:
    """The summary the readers use; ``None`` when no operation ran on a device."""
    devices = {k: v for k, v in sorted(extracted["devices"].items())[:n_devices] if v}
    if not devices:
        return None
    spans = extracted["spans"]
    window = [s for s in spans if s[0] == WINDOW_SPAN]
    if window:
        lo, hi = window[0][1], window[0][1] + window[0][2]
    else:
        lo = min(e[1] for evs in devices.values() for e in evs)
        hi = max(e[1] + e[2] for evs in devices.values() for e in evs)
    inner = [s for s in spans if s[0] != WINDOW_SPAN]
    busy_ns = 0.0
    by_key: Dict[str, float] = {}
    by_text: List[Tuple[str, float]] = []
    collective_ns = 0.0
    gap_ns: Dict[str, float] = {}
    gap_sizes = [[0, 0.0] for _ in range(len(GAP_CLASSES_S) + 1)]  # count, seconds
    for events in devices.values():
        events = _clip(events, lo, hi)
        busy = busy_intervals(events)
        busy_ns += sum(b - a for a, b in busy)
        for text, ns in self_times(events):
            key = op_key(text)
            by_key[key] = by_key.get(key, 0.0) + ns
            by_text.append((text, ns))
            if COLLECTIVE.search(text):
                collective_ns += ns
        for a, b in _gaps(busy, lo, hi):
            name = _owner(inner, (a + b) / 2)
            gap_ns[name] = gap_ns.get(name, 0.0) + (b - a)
            size = gap_sizes[sum((b - a) / 1e9 >= edge for edge in GAP_CLASSES_S)]
            size[0] += 1
            size[1] += (b - a) / 1e9
    n = len(devices)
    rank = lambda d: [[k, v / n / 1e9] for k, v in sorted(d.items(), key=lambda kv: -kv[1])]
    return {
        "devices": n,
        "window_s": (hi - lo) / 1e9,
        "busy_s": busy_ns / n / 1e9,
        "collective_exposed_s": collective_ns / n / 1e9,
        "top_ops": rank(by_key),
        "idle_gaps": rank(gap_ns),
        "idle_gap_sizes": [[edge, c / n, sec / n] for edge, (c, sec)
                           in zip(GAP_CLASSES_S + (None,), gap_sizes)],
        "op_seconds": [(t, ns / n / 1e9) for t, ns in by_text],
    }


def pattern_seconds(summary: Dict, pattern: str) -> float:
    """Self seconds (mean over chips) of the operations whose HLO text matches."""
    rx = re.compile(pattern)
    return sum(s for text, s in summary["op_seconds"] if rx.search(text))


def reduce_file(path: str, n_devices: int) -> Optional[Dict]:
    return reduce(extract(load(path)), n_devices)
