"""One cell from a scratch tree whose copy of a benchmark file is changed, for
a builder on the chip machine: what ``repeat.py --schedule-seeds`` does for a
trace, for any key of the cell's traffic or configuration file.

    python3 chipbench/tools/variant.py --workload brumby_serve_statebound \\
        --traffic rate_per_s=1.5 --seeds 11 [--seconds 50] [--label sweep]      # a knee sweep's point
    python3 chipbench/tools/variant.py --workload brumby_serve_statebound \\
        --config model=chipbench.tests.planted_faults_retention:NoDecay \\
        --seeds 12 --seconds 5 --label planted                                   # a planted fault

The committed files are never written: a run starts from
``.bench_checkout/variant/`` (``repeat.py``'s links; ``configs`` and
``traffic`` copied, the named keys replaced by their JSON values, or by the
text itself where it is no JSON).  One line a run goes to
``chiprun_out/<label>.jsonl``, with what was changed, every ``reference_*`` note
of the reference check and the engine's counters; the exit code is 0."""

import argparse
import json
import os
import shutil
import subprocess
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import repeat  # noqa: E402 - the sibling tool; imports no jax

ROOT, harness = repeat.ROOT, repeat.harness


def _value(text: str):
    try:
        return json.loads(text)
    except ValueError:
        return text


def variant_tree(changes) -> str:
    """``changes``: {file under chipbench/: {key: value}}."""
    root = os.path.join(ROOT, ".bench_checkout", "variant")
    shutil.rmtree(root, ignore_errors=True)
    repeat.link_all_but(ROOT, root, "chipbench", ".bench_checkout")
    repeat.link_all_but(harness.BENCH_DIR, os.path.join(root, "chipbench"), "configs", "traffic")
    for name in ("configs", "traffic"):
        shutil.copytree(os.path.join(harness.BENCH_DIR, name), os.path.join(root, "chipbench", name))
    for rel, keys in changes.items():
        path = os.path.join(root, "chipbench", rel)
        data = harness.load_json(path)
        data.update(keys)
        with open(path, "w") as f:
            json.dump(data, f)
    return root


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--traffic", nargs="*", default=[], metavar="KEY=VALUE")
    p.add_argument("--config", nargs="*", default=[], metavar="KEY=VALUE")
    p.add_argument("--seeds", type=int, nargs="+", required=True)
    p.add_argument("--seconds", type=float, default=50.0)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--label", default="variant")
    args = p.parse_args(argv)
    bench = harness.load_json(ROOT, "BENCHMARK.json")
    cell = next(w for w in bench["workloads"] if w["name"] == args.workload)
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    pairs = lambda items: {k: _value(v) for k, v in (item.split("=", 1) for item in items)}
    changed = {"traffic": pairs(args.traffic), "config": pairs(args.config)}
    root = variant_tree({
        os.path.join("traffic", cell["traffic"] + ".json"): changed["traffic"],
        os.path.relpath(os.path.join(ROOT, entry["file"]), harness.BENCH_DIR): changed["config"]})
    out_path = os.path.join(ROOT, "chiprun_out", args.label + ".jsonl")
    os.makedirs(os.path.dirname(out_path), exist_ok=True)
    for seed in args.seeds:
        t0 = time.monotonic()
        run = subprocess.run(
            [sys.executable, os.path.join(root, "chipbench", "run.py"),
             "--workload", args.workload, "--seed", str(seed),
             "--seconds", str(args.seconds), "--trace", str(args.trace)],
            stdout=subprocess.PIPE, text=True, cwd=root)
        text = run.stdout.strip().splitlines()
        line = json.loads(text[-1]) if run.returncode == 0 and text else {"error": text[-3:]}
        notes = next((json.loads(t[len("NOTES "):]) for t in text if t.startswith("NOTES ")), {})
        line.update(seed=seed, changed=changed, rc=run.returncode,
                    wall_s=round(time.monotonic() - t0, 1),
                    **{k: v for k, v in notes.items() if k.startswith("reference_")},
                    phases=notes.get("phases"), engine=notes.get("engine"))
        line.pop("breakdown", None)
        with open(out_path, "a") as f:
            f.write(json.dumps(line) + "\n")
        print(json.dumps({k: line.get(k) for k in (
            "seed", "changed", "rc", "correct", "failed", "attempted", "metrics",
            "reference_not_argmax_share", "wall_s")}), flush=True)
    shutil.rmtree(root, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
