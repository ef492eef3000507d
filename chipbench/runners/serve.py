"""Runner ``serve``: one engine replica on the chip, as ``lm_serve --engine``
builds it (``ContinuousBatchingEngine`` + ``EngineService`` under a
``ServeReplica``, reached over the program's own RPC), under open-loop load
from ``chipbench/loadgen.py`` in a process of its own.

The model is the configuration's, not ``lm_serve.make_model``'s (which fixes
rotary positions, float32 and a toy width): ``TransformerLM`` with learned
positions and bfloat16 compute, weights made on the device by one jitted init
from the seed.  Warm-up is the engine's own (every prefill bucket up to the
longest prompt the traffic file allows, every join block count, the decode
step).  A compilation inside the window makes the run incorrect.

Correctness, outside the window: a few seeded requests that cross many blocks
of the pool go through the engine's public ``submit`` / ``step`` / ``retire``
together with fillers that keep every other slot in use, and every token they
emit must be, in the plain float32 reference's logits for the same sequence, within
``serve_gap_sigma`` standard deviations of that row's maximum.  Greedy tokens
themselves flip on rounding when weights are random; the reference's logit at
the emitted token does not.  A traffic file with ``reference_limits`` of its
own has the fillers' tokens checked as well and holds two numbers, the largest
gap and the mean gap, to that file's limits: over some hundreds of tokens the
mean separates bfloat16's near ties from a lower precision's on every seed,
where the largest gap of a few dozen tokens does not (PERF.md section 4).
"""

from __future__ import annotations

import asyncio
import json
import os
import socket
import subprocess
import sys
import threading
import time
from typing import Dict, List

from chipbench import harness, readers
from chipbench import traffic as traffic_mod
from chipbench.reference import gpt

REPLICA = "chipbench_replica"


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def build_model(config: Dict, traffic: Dict):
    from moolib_tpu.models.transformer import TransformerLM

    use = config["uses"][traffic["use"]]
    return TransformerLM(
        vocab_size=config["vocab_size"], d_model=config["n_embd"],
        num_heads=config["n_head"], num_layers=use["n_layer"],
        max_len=config["n_positions"], attention=use["attention"],
        pos_embedding=use["position"])


def served_batch(engine, config, traffic, seed):
    """Run the file's ``reference_requests`` through the engine's public
    ``submit`` / ``step`` / ``retire`` together with its ``reference_fillers``
    (requests that keep the other slots and their share of the pool in use,
    spread between the checked ones): ``(prompt, emitted tokens, checked)`` of
    every request, in the order they finished."""
    fill = traffic["reference_fillers"]
    batch = [(fill["prompt_tokens"], fill["budget_tokens"], False)] * fill["count"]
    stride = fill["count"] // len(traffic["reference_requests"]) + 1
    for i, (plen, budget) in enumerate(traffic["reference_requests"]):
        batch.insert(i * stride, (plen, budget, True))
    live, done = {}, []
    for k, (plen, budget, check) in enumerate(batch):
        prompt = traffic_mod.prompt_tokens(seed, 10 ** 6 + k, plen, config["vocab_size"])
        slot, emitted = engine.submit(prompt, budget)
        if slot is None:
            done.append((prompt, emitted, check))
        else:
            live[slot] = (prompt, check)
    while live:
        _emissions, finished = engine.step()
        for slot in finished:
            prompt, check = live.pop(slot)
            done.append((prompt, engine.retire(slot), check))
    return done


def reference_rows(params, config, traffic, prompt, emitted):
    """The float32 reference's logits at every position where a request that
    was sent ``prompt`` and answered ``emitted`` chose a token."""
    import jax.numpy as jnp
    import numpy as np

    seq = np.concatenate([prompt, np.asarray(emitted, np.int32)])
    rows = jnp.arange(len(prompt) - 1, len(seq) - 1)
    return np.asarray(gpt.logits(params, jnp.asarray(seq[:-1]), config["uses"][traffic["use"]]["n_layer"],
                                 config["n_head"], rows=rows))


def gap_sigma(ref, tokens) -> List[float]:
    """(reference max - reference logit of the token) / reference std, a position."""
    import numpy as np

    chosen = ref[np.arange(len(tokens)), np.asarray(tokens)]
    return ((ref.max(-1) - chosen) / ref.std(-1)).tolist()


def checked_requests(engine, config, traffic, seed):
    """``(prompt, emitted tokens)`` of the requests of :func:`served_batch`
    whose tokens are held to the reference: the file's ``reference_requests``,
    and where it has ``reference_limits`` of its own the fillers too."""
    return [(prompt, emitted) for prompt, emitted, check in served_batch(engine, config, traffic, seed)
            if check or "reference_limits" in traffic]


def reference_gaps(engine, params, config, traffic, seed) -> List[float]:
    """The gap of every token that a checked request emitted (module docstring)."""
    return [g for prompt, emitted in checked_requests(engine, config, traffic, seed)
            for g in gap_sigma(reference_rows(params, config, traffic, prompt, emitted), emitted)]


def compare_gaps(gaps: List[float], config, traffic) -> Dict[str, list]:
    """Each number of the reference check beside its limit: the largest gap
    under the configuration's ``serve_gap_sigma``, or, where the traffic file
    brings ``reference_limits`` (readings and control in PERF.md), the largest
    and the mean gap under that file's own."""
    limits = traffic.get("reference_limits") or {"gap_sigma_max": config["tolerance"]["serve_gap_sigma"]}
    read = {"gap_sigma_max": max(gaps), "gap_sigma_mean": sum(gaps) / len(gaps)} if gaps else {}
    return {"reference_" + name: [read.get(name), limit] for name, limit in limits.items()}


def min_prompt_len(traffic) -> int:
    """The shortest prompt the cell sends, checked requests and fillers with
    the counted ones: warm-up compiles no bucket below its bucket."""
    return min([traffic["prompt_tokens"]["min"], traffic["reference_fillers"]["prompt_tokens"]]
               + [plen for plen, _budget in traffic["reference_requests"]])


def _spanned(fn, name):
    def wrapped(*a, **kw):
        with harness.span(name):
            return fn(*a, **kw)
    return wrapped


def run(*, cell, config, traffic, seed, seconds, traced, devices, setup) -> harness.Measured:
    import jax
    import jax.numpy as jnp

    with setup.phase("program_imports"):
        from moolib_tpu import telemetry
        from moolib_tpu.engine import ContinuousBatchingEngine, EngineService
        from moolib_tpu.rpc import Rpc
        from moolib_tpu.serving import ServeReplica

    compiles = harness.CompileCounter()
    registry = telemetry.get_registry()
    lead_s = float(traffic.get("lead_s", 0.0))
    drain_s = float(traffic["drain_limit_s"])
    trace_s = float(traffic["trace_seconds"]) if traced else 0.0

    with setup.phase("rpc_listen_generator_start"):
        address = f"127.0.0.1:{_free_port()}"
        rpc = Rpc()
        rpc.set_name(REPLICA)
        rpc.listen(address)
        child = subprocess.Popen(
            [sys.executable, os.path.join(harness.BENCH_DIR, "loadgen.py"), address, REPLICA],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True,
            env={**os.environ, "JAX_PLATFORMS": "cpu"})
    replica = None
    try:
        schedule = traffic_mod.serve_schedule(traffic, seconds)
        child.stdin.write(json.dumps({
            "schedule": schedule, "seed": seed, "vocab": config["vocab_size"],
            "deadline_s": drain_s + seconds + lead_s,
            "min_s": lead_s + seconds + trace_s,
            "stop_s": lead_s + seconds + max(drain_s, trace_s + 1.0)}) + "\n")
        child.stdin.flush()
        with setup.phase("init_weights"):
            model = build_model(config, traffic)
            params = jax.jit(model.init)(
                jax.random.key(harness.fold_seed(seed)), jnp.zeros((1, 8), jnp.int32))
            jax.block_until_ready(params)
        with setup.phase("engine_build_warmup"):
            engine = ContinuousBatchingEngine(
                model, params, slots=traffic["slots"], block_size=traffic["block_size"],
                max_seq_len=traffic["positions_per_slot"],
                max_prompt_len=traffic["prompt_tokens"]["max"],
                min_prompt_len=min_prompt_len(traffic))
            engine.warmup()
        with setup.phase("reference_check"):
            gaps = reference_gaps(engine, params, config, traffic, seed)
        if traced:
            engine.step = _spanned(engine.step, "serve.engine_step")
            engine.submit = _spanned(engine.submit, "serve.engine_submit")
        service = EngineService(rpc, engine, name="generate",
                                max_queue=traffic["max_queue"],
                                default_max_new=traffic["budget_tokens"]["median"])
        replica = ServeReplica(rpc, None, params, name="generate", service=service)
        with setup.phase("wait_generator"):
            if child.stdout.readline().strip() != "READY":
                raise RuntimeError("the load generator did not come up")
        t0 = time.monotonic() + 0.5
        child.stdin.write(json.dumps({"t0": t0}) + "\n")
        child.stdin.flush()
        setup.phases["lead_in"] = t0 + lead_s - time.monotonic()  # offered, not counted
        setup_s = t0 + lead_s - setup.t_start
        harness.say("SETUP", setup.report(setup_s, compiles))

        state: Dict = {"samples": []}
        tracer = harness.TraceWindow(cell["name"]) if traced else None

        def control():
            try:
                time.sleep(max(0.0, t0 + lead_s - time.monotonic()))
                state["before"] = registry.snapshot()
                state["compiles_before"] = compiles.snapshot()
                end = t0 + lead_s + seconds
                while time.monotonic() < end:
                    if traced:  # sampled only where the per-layer metrics are reported
                        occ = registry.snapshot().get("serve_engine_slot_occupancy")
                        if occ and occ["series"]:
                            state["samples"].append(occ["series"][0]["value"])
                    time.sleep(0.05)
                state["after"] = registry.snapshot()
                if tracer is not None:
                    tracer.start()
                    with harness.span("trace_window"):
                        time.sleep(trace_s)
                    tracer.stop()
                line = child.stdout.readline()
                state["result"] = json.loads(line[len("RESULT "):])
                state["compiles_after"] = compiles.snapshot()
            except Exception as e:  # noqa: BLE001 - surfaces below as a failed run
                state["error"] = repr(e)
            finally:
                # EngineService.close() empties the slot table; from another
                # thread that races the iteration in flight.  Run it on the
                # service loop's own thread, between two iterations.
                loop.call_soon_threadsafe(replica.close)

        loop = asyncio.new_event_loop()
        controller = threading.Thread(target=control, name="chipbench-control", daemon=True)
        controller.start()
        try:
            with harness.span("serve.service_loop"):
                loop.run_until_complete(replica.loop())
        finally:
            loop.close()
        controller.join()
        if "error" in state:
            raise RuntimeError(f"control thread failed: {state['error']}")
    finally:
        try:
            child.stdin.write("\n")
            child.stdin.flush()
            child.wait(timeout=20)
        except Exception:  # noqa: BLE001
            pass
        if child.poll() is None:
            child.kill()
            child.wait()
        if replica is not None:
            replica.close()
        rpc.close()

    records = state["result"]["records"]
    deadline = t0 + lead_s + seconds + drain_s
    ms_per_token, lateness, failed = [], [], 0
    for r in records:
        good = r["ok"] and r["done"] is not None and r["done"] <= deadline
        failed += 0 if good else 1
        end = r["done"] if good else deadline
        ms_per_token.append((end - r["due"]) * 1e3 / r["budget"])
        lateness.append((r["sent"] - r["due"]) * 1e3)
    attempted = sum(1 for r in schedule if r["counted"])
    failed += attempted - len(records)  # never sent: the generator was stopped
    in_window = state["compiles_after"]["programs"] - state["compiles_before"]["programs"]
    compared = {**compare_gaps(gaps, config, traffic),
                "failed": [failed, 0], "compiles_in_window": [in_window, 0]}
    correct = harness.within(compared)
    slow = sorted(zip(ms_per_token, records), key=lambda p: -p[0])[:10]
    phases = {}
    for after in state["after"].get("serve_phase_seconds", {"series": []})["series"]:
        phase = after["labels"].get("phase")
        before = readers.series(state["before"], "serve_phase_seconds", {"phase": phase})
        before = before or {"count": 0, "sum": 0.0, "buckets": [0] * len(after["value"]["buckets"])}
        n = after["value"]["count"] - before["count"]
        delta = [a - b for a, b in zip(after["value"]["buckets"], before["buckets"])]
        phases[phase] = {"count": n, "buckets": delta,
                         "mean_ms": (after["value"]["sum"] - before["sum"]) * 1e3 / n if n else None}
    measured = harness.Measured(
        attempted=attempted, failed=failed, correct=correct,
        values={"setup_s": setup_s},
        lists={"req_ms_per_token": ms_per_token, "gen_lateness_ms": lateness},
        counters_before=state["before"], counters_after=state["after"],
        samples={"serve_engine_slot_occupancy": state["samples"]},
        notes={"compared": compared, "reference_gap_sigma_max": max(gaps) if gaps else None,
               "reference_gap_sigma_mean": sum(gaps) / len(gaps) if gaps else None,
               "reference_tokens_checked": len(gaps), "compiles_in_window": in_window, "compiles_at_end": state["compiles_after"],
               "ms_per_token_max": max(ms_per_token), "lateness_max_ms": max(lateness),
               "phases": phases, "client": state["result"]["client"],
               "sent_total": state["result"]["sent_total"],
               "service": {k: v for k, v in service.stats().items() if k != "engine"},
               "engine": engine.stats(),
               "slowest": [{"ms_per_token": round(m, 2), "prompt_len": r["prompt_len"],
                            "budget": r["budget"], "due_s": round(r["due"] - t0, 2),
                            "late_ms": round((r["sent"] - r["due"]) * 1e3, 2),
                            "ok": r["ok"], "error": r.get("error")} for m, r in slow]},
    )
    if tracer is not None:
        measured.trace = tracer.reduce(len(devices))
    return measured
