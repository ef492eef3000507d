"""The two readings a dense serving cell's limits are set from, on the chip at
the cell's own size, in one process, for a builder:

    python3 chipbench/tools/gap_control.py lm_serve_longprompt --seeds 11 12 13

The engine is built and warmed once, as the runner builds it.  For each seed
the weights are made as the runner makes them and installed, and the runner's
own ``checked_requests`` sends the cell's checked requests and fillers through the
engine with every slot in use.  The LOWER reading is the program's: the gaps of
the tokens it served (``runners/serve.py``'s ``reference_rows`` and
``gap_sigma``).  The UPPER is the control's: at each position of the same
prompts and served tokens, the gap of the token that ``reference/gpt_control.py``
(the reference in the program's place, every product's operands at float8
e4m3's 3 bits of mantissa, the precision below the configuration's bfloat16)
puts first.  Both lists go through the runner's own
``compare_gaps`` and ``harness.within``: the program has to come out correct
and the control not, on every seed.  No window is measured.  One line a seed
goes to ``--out`` (``chiprun_out/gap_control.jsonl``) with every gap of both (so that a
limit can be set from the readings); the last line printed says on how many
seeds each verdict came out as it must.
"""

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


MANTISSA_BITS = 3  # float8 e4m3


def read_seed(engine, model, config, traffic, seed):
    """``(program's gaps, control's gaps)`` of one seed's weights, over the
    tokens that the cell's ``correct`` checks."""
    import jax
    import jax.numpy as jnp

    from chipbench import harness
    from chipbench.reference import gpt_control
    from chipbench.runners import serve

    engine.set_params(None)  # the last seed's weights go before these come: one copy fits beside the pool
    params = jax.jit(model.init)(
        jax.random.key(harness.fold_seed(seed)), jnp.zeros((1, 8), jnp.int32))
    engine.set_params(params)
    n_layer = config["uses"][traffic["use"]]["n_layer"]
    program, control = [], []
    for prompt, emitted in serve.checked_requests(engine, config, traffic, seed):
        ref = serve.reference_rows(params, config, traffic, prompt, emitted)
        program += serve.gap_sigma(ref, emitted)
        control += serve.gap_sigma(ref, gpt_control.first_tokens(
            params, prompt, emitted, n_layer, config["n_head"], MANTISSA_BITS))
    return program, control


def main(argv=None) -> int:
    from chipbench import harness

    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("workload")
    p.add_argument("--seeds", type=int, nargs="+", required=True)
    p.add_argument("--out", default=os.path.join(ROOT, "chiprun_out", "gap_control.jsonl"))
    args = p.parse_args(argv)
    bench = harness.load_json(ROOT, "BENCHMARK.json")
    cell = next(w for w in bench["workloads"] if w["name"] == args.workload)
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    config = harness.load_json(ROOT, entry["file"])
    traffic = harness.load_json(harness.BENCH_DIR, "traffic", cell["traffic"] + ".json")
    harness.place_compile_cache()
    harness.require_accelerator(cell["chips"])

    import jax
    import jax.numpy as jnp

    from chipbench.runners import serve
    from moolib_tpu.engine import ContinuousBatchingEngine

    model = serve.build_model(config, traffic)
    engine = ContinuousBatchingEngine(
        model, jax.jit(model.init)(jax.random.key(0), jnp.zeros((1, 8), jnp.int32)),
        slots=traffic["slots"], block_size=traffic["block_size"],
        max_seq_len=traffic["positions_per_slot"],
        max_prompt_len=traffic["prompt_tokens"]["max"],
        min_prompt_len=serve.min_prompt_len(traffic))
    engine.warmup()
    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    as_it_must = {"program_correct": 0, "control_not_correct": 0}
    for seed in args.seeds:
        program, control = read_seed(engine, model, config, traffic, seed)
        verdicts = {name: serve.compare_gaps(gaps, config, traffic)
                    for name, gaps in (("program", program), ("control", control))}
        as_it_must["program_correct"] += harness.within(verdicts["program"])
        as_it_must["control_not_correct"] += not harness.within(verdicts["control"])
        line = {"workload": args.workload, "seed": seed, "mantissa_bits": MANTISSA_BITS,
                "tokens": len(program), "compared": verdicts,
                "correct": {name: harness.within(v) for name, v in verdicts.items()}}
        with open(args.out, "a") as f:
            f.write(json.dumps({**line, "program_gaps": program, "control_gaps": control}) + "\n")
        print(json.dumps(line), flush=True)
    print(json.dumps({"seeds": len(args.seeds), **as_it_must}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
