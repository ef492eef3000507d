"""The benchmark's one command.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Finds the cell in ``BENCHMARK.json``, its configuration and traffic files by
name, the traffic's runner in ``chipbench/runners/``, and each metric's reader
through ``chipbench/metrics/<name>.json``.  Prints the result as the last line
of standard output; exits non-zero, with no result, where jax finds no TPU or
too few chips.  See ``chipbench/README.md``.
"""

import time

_T_START = time.monotonic()

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from chipbench import harness  # noqa: E402


def parse(argv):
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--seconds", type=float, default=10.0)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return p.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    bench = harness.load_json(ROOT, "BENCHMARK.json")
    cell = next((w for w in bench["workloads"] if w["name"] == args.workload), None)
    if cell is None:
        print(f"no workload {args.workload!r} in BENCHMARK.json", file=sys.stderr)
        return 2
    config_entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    config = harness.load_json(ROOT, config_entry["file"])
    traffic = harness.load_json(harness.BENCH_DIR, "traffic", cell["traffic"] + ".json")
    harness.place_compile_cache()
    setup = harness.Setup(_T_START)
    try:
        with setup.phase("imports"):
            runner = importlib.import_module(f"chipbench.runners.{traffic['runner']}")
            devices = harness.require_accelerator(cell["chips"])
    except harness.NoAccelerator as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 3
    measured = runner.run(cell=cell, config=config, traffic=traffic, seed=args.seed,
                          seconds=args.seconds, traced=bool(args.trace),
                          devices=devices, setup=setup)
    line = harness.result_line(bench, cell, measured, devices, config, traffic,
                               traced=bool(args.trace))
    if measured.trace is not None:
        measured.notes["idle_gap_sizes"] = measured.trace["idle_gap_sizes"]
    harness.say("NOTES", measured.notes)
    # each number that decided ``correct`` beside its limit: the result's last key, and the
    # last lines of standard error (what the driver's record keeps of a run that is not correct)
    line["compared"] = {name: {"value": value, "limit": limit}
                        for name, (value, limit) in measured.notes.get("compared", {}).items()}
    for name, pair in line["compared"].items():
        print(f"chipbench: compared {name} = {pair['value']} (limit {pair['limit']})", file=sys.stderr)
    print(f"chipbench: correct = {line['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
