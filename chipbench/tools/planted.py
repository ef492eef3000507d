"""One cell with a fault planted in its model, through the benchmark's own
command, for a builder on the chip machine:

    python3 chipbench/tools/planted.py --workload solar_serve_longgen \\
        --fault Bf16State --seeds 11 12 [--seconds 5] [--label planted_bf16]

The committed files are never written: the run starts from a scratch tree
under ``.bench_checkout/`` (``repeat.py``'s, by symbolic links) whose copy of
the configuration's file names ``chipbench.tests.planted_faults:<fault>`` as
its ``"model"``.  The result lines go to ``chiprun_out/<label>.jsonl`` with the
share the reference check read; the exit code is 0 if every run ended
``correct: false`` (the limit refused the fault), 1 if one passed it."""

import argparse
import json
import os
import shutil
import subprocess
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import repeat  # noqa: E402 - the sibling tool; imports no jax

ROOT, harness = repeat.ROOT, repeat.harness


def planted_tree(config_file: str, fault: str) -> str:
    root = os.path.join(ROOT, ".bench_checkout", f"planted_{fault}")
    shutil.rmtree(root, ignore_errors=True)
    repeat.link_all_but(ROOT, root, "chipbench", ".bench_checkout")
    repeat.link_all_but(harness.BENCH_DIR, os.path.join(root, "chipbench"), "configs")
    shutil.copytree(os.path.join(harness.BENCH_DIR, "configs"),
                    os.path.join(root, "chipbench", "configs"))
    path = os.path.join(root, config_file)
    config = harness.load_json(path)
    config["model"] = f"chipbench.tests.planted_faults:{fault}"
    with open(path, "w") as f:
        json.dump(config, f)
    return root


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--fault", required=True)
    p.add_argument("--seeds", type=int, nargs="+", required=True)
    p.add_argument("--seconds", type=float, default=5.0)
    p.add_argument("--label")
    args = p.parse_args(argv)
    bench = harness.load_json(ROOT, "BENCHMARK.json")
    cell = next(w for w in bench["workloads"] if w["name"] == args.workload)
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    root = planted_tree(entry["file"], args.fault)
    out_path = os.path.join(ROOT, "chiprun_out", (args.label or "planted_" + args.fault) + ".jsonl")
    os.makedirs(os.path.dirname(out_path), exist_ok=True)
    refused = 0
    for seed in args.seeds:
        run = subprocess.run(
            [sys.executable, os.path.join(root, "chipbench", "run.py"),
             "--workload", args.workload, "--seed", str(seed),
             "--seconds", str(args.seconds), "--trace", "0"],
            stdout=subprocess.PIPE, text=True, cwd=root)
        text = run.stdout.strip().splitlines()
        line = json.loads(text[-1]) if run.returncode == 0 and text else {"error": text[-3:]}
        notes = next((json.loads(t[len("NOTES "):]) for t in text if t.startswith("NOTES ")), {})
        line.update(seed=seed, fault=args.fault, rc=run.returncode,
                    reference_not_argmax_share=notes.get("reference_not_argmax_share"),
                    reference_tokens_checked=notes.get("reference_tokens_checked"))
        line.pop("breakdown", None)
        refused += line.get("correct") is False and line.get("failed") == 0
        with open(out_path, "a") as f:
            f.write(json.dumps(line) + "\n")
        print(json.dumps({k: line.get(k) for k in (
            "seed", "fault", "rc", "correct", "failed", "reference_not_argmax_share",
            "reference_tokens_checked")}), flush=True)
    shutil.rmtree(root, ignore_errors=True)
    print(f"{refused} of {len(args.seeds)} runs refused by the cell's limit alone")
    return 0 if refused == len(args.seeds) else 1


if __name__ == "__main__":
    sys.exit(main())
