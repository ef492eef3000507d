"""What a cell's newest trace holds, by PROGRAM: the builder's reading beside
the result line (run it in the same chip call as the traced run, which leaves
the trace under ``.chipbench_trace/<cell>``).

    python3 chipbench/run.py --workload lm_serve_knee --seed 7 --seconds 50 --trace 1
    python3 chipbench/tools/program_runs.py lm_serve_knee          # or a path to an .xplane.pb(.gz)

Prints one JSON line ``PROGRAM_RUNS``: for each program of the metrics' own
``programs`` map that ran, its runs inside the window, ``device_share`` (of the
window's busy seconds), ``mean_ms``, ``queue_delay_mean_ms``; the sum of the
shares; ``matched_share`` (percent of the window's runs tied to their dispatch
span) and ``clock_lead_ms``; every figure as ``readers/program_time.py`` reads
it, so the decode program's share, which has no metric of its own, is here too.
``tail_span_mean_ms`` holds the mean of the host's ``serve.iteration``,
``engine.step`` and ``engine.decode_fetch`` spans INSIDE the same window
(``readers/span_time.py``): the registry's ``iteration_period_mean_ms`` and
``decode_step_mean_ms`` are means over the 50 s BEFORE it, at another occupancy.
"""

import glob
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main(where: str, chips: int = 1) -> int:
    from chipbench import harness
    from chipbench import trace_reduce as tr
    from chipbench.readers import program_time, span_time

    path = where
    if not os.path.isfile(path):
        paths = sorted(glob.glob(os.path.join(
            harness.TRACE_DIR, where, "plugins/profile/*/*.xplane.pb")))
        if not paths:
            print(f"program_runs: no trace of {where!r}", file=sys.stderr)
            return 1
        path = paths[-1]
    programs = program_time.programs()
    data = tr.load(path)
    runs, host = program_time.extract(data, set(programs.values()))
    busy_s = tr.reduce(tr.extract(data), chips)["busy_s"]
    read = lambda **spec: program_time.figure(
        {"programs": programs, **spec}, runs, host, busy_s, chips)
    lo, hi = host[3]
    out = {"window_s": (hi - lo) / 1e9, "busy_s": busy_s, "programs": {}}
    for program in programs:
        inside = sum(lo <= r["start"] and r["end"] <= hi and r["program"] == program
                     for chip_runs in runs.values() for r in chip_runs)
        if inside:
            out["programs"][program] = {"runs_inside": inside, **{
                fig: read(figure=fig, program=program)
                for fig in ("device_share", "mean_ms", "queue_delay_mean_ms")}}
    devices, spans = span_time.extract(data)
    out["tail_span_mean_ms"] = {
        name: span_time.figure({"figure": "mean_ms", "spans": [name]}, devices, spans, chips)
        for name in ("serve.iteration", "engine.step", "engine.decode_fetch", "train_step")}
    out["share_sum"] = sum(p["device_share"] for p in out["programs"].values())
    out["matched_share"] = read(figure="matched_share")
    out["clock_lead_ms"] = read(figure="clock_lead_ms")
    print("PROGRAM_RUNS", json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1], *(int(a) for a in sys.argv[2:3])))
