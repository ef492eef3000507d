"""Runs of one cell, one after another, for a builder on the chip machine:

    python3 chipbench/tools/repeat.py --workload lm_serve_knee --seeds 11 12 13 \\
        [--schedule-seeds 4001 4002 4003] [--seconds 50] [--trace 0] [--label knee_sets]

Each run is the benchmark's own command in a process of its own (this one stays
off jax, so the chip is the child's).  The result lines go to
``chiprun_out/<label>.jsonl`` with the wall seconds of the whole process, and
for every metric the median and the spread ``(Q3 - Q1) / median`` of
``statistics.quantiles(values, n=4)`` are printed at the end.

``--schedule-seeds`` is how a serving file's arrival trace was chosen: run i
replays the trace of candidate i, and the candidate whose
``req_ms_per_token_p50`` is the median of the five is the one the file keeps
(PERF.md, section 2).  The committed file is never written: a candidate runs
from a scratch tree under ``.bench_checkout/`` that links to everything of
this checkout but ``chipbench/traffic``, of which it holds a copy with the
candidate's ``schedule_seed``; a run that is cut leaves the checkout as it was.
"""

import argparse
import json
import os
import re
import shutil
import statistics
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from chipbench import harness  # noqa: E402 - imports no jax until a run asks for the device

SCHEDULE_SEED = re.compile(r'("schedule_seed":\s*)\d+')


def spread(values):
    if len(values) < 2:
        return None
    q1, _q2, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def link_all_but(src: str, dst: str, *leave_out: str) -> None:
    os.makedirs(dst)
    for name in os.listdir(src):
        if name not in leave_out:
            os.symlink(os.path.join(src, name), os.path.join(dst, name))


def candidate_tree(traffic_name: str, schedule_seed: int) -> str:
    """A scratch root whose ``chipbench/traffic/<traffic_name>.json`` carries
    ``schedule_seed``; everything else is this checkout's, by symbolic link
    (``run.py`` takes its root from the path it was started by)."""
    root = os.path.join(ROOT, ".bench_checkout", f"schedule_{schedule_seed}")
    shutil.rmtree(root, ignore_errors=True)
    link_all_but(ROOT, root, "chipbench", ".bench_checkout")
    link_all_but(harness.BENCH_DIR, os.path.join(root, "chipbench"), "traffic")
    shutil.copytree(os.path.join(harness.BENCH_DIR, "traffic"),
                    os.path.join(root, "chipbench", "traffic"))
    path = os.path.join(root, "chipbench", "traffic", traffic_name + ".json")
    with open(path) as f:
        text = f.read()
    with open(path, "w") as f:
        f.write(SCHEDULE_SEED.sub(rf"\g<1>{schedule_seed}", text, count=1))
    return root


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", type=int, nargs="+", required=True)
    p.add_argument("--schedule-seeds", type=int, nargs="+")
    p.add_argument("--seconds", type=float, default=50.0)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--label")
    args = p.parse_args(argv)
    if args.schedule_seeds and len(args.schedule_seeds) != len(args.seeds):
        p.error("one schedule seed a run")
    cell = next(w for w in harness.load_json(ROOT, "BENCHMARK.json")["workloads"]
                if w["name"] == args.workload)
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    out_path = os.path.join(out_dir, (args.label or args.workload) + ".jsonl")
    lines = []
    for i, seed in enumerate(args.seeds):
        schedule_seed = args.schedule_seeds[i] if args.schedule_seeds else None
        root = ROOT if schedule_seed is None else candidate_tree(cell["traffic"], schedule_seed)
        t0 = time.monotonic()
        run = subprocess.run(
            [sys.executable, os.path.join(root, "chipbench", "run.py"),
             "--workload", args.workload, "--seed", str(seed),
             "--seconds", str(args.seconds), "--trace", str(args.trace)],
            stdout=subprocess.PIPE, text=True, cwd=root)
        wall_s = time.monotonic() - t0
        text = run.stdout.strip().splitlines()
        line = json.loads(text[-1]) if run.returncode == 0 and text else {"error": text[-3:]}
        line.update(seed=seed, schedule_seed=schedule_seed, rc=run.returncode,
                    wall_s=round(wall_s, 1), trace=args.trace,
                    notes=[t for t in text[:-1] if t.startswith(("SETUP", "NOTES"))])
        lines.append(line)
        with open(out_path, "a") as f:
            f.write(json.dumps(line) + "\n")
        print(json.dumps({k: v for k, v in line.items() if k not in ("notes", "breakdown")}),
              flush=True)
        if schedule_seed is not None:
            shutil.rmtree(root, ignore_errors=True)
    good = [ln for ln in lines if ln.get("correct") and ln.get("failed") == 0]
    print(f"{len(good)} of {len(lines)} runs correct with none failed")
    for name in sorted({m for ln in good for m in ln["metrics"]}):
        values = [ln["metrics"][name]["value"] for ln in good if name in ln["metrics"]]
        print(f"{name}: n {len(values)} median {statistics.median(values):.6g} "
              f"spread {spread(values)} values {[round(v, 4) for v in values]}")
    return 0 if len(good) == len(lines) else 1


if __name__ == "__main__":
    sys.exit(main())
