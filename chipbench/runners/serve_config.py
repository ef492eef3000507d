"""Runner ``serve_config``: runner ``serve``'s replica and load (one engine
replica as ``lm_serve --engine`` builds it, under open-loop load from
``chipbench/loadgen.py``), for a model that the program builds from the
configuration's own file: ``"model": "<module>:<class>"`` names the program's
class, and its ``from_config(config, max_len=..., **uses)`` is the function
``lm_serve --engine --config`` calls (``glm-4.7-flash.json``:
``moolib_tpu.models.latent_moe:LatentMoELM``).  The next architecture's cell
is a configuration, a reference, a traffic file and entries: no runner.

What differs from ``serve``: the model and its reference come from the
configuration (``"reference"`` names the module under ``chipbench/reference``
whose ``logits(params, tokens, config, rows=...)`` is compared); weights are
bfloat16, made on the device by one jitted init from the seed; the engine
learns the traffic's shortest prompt beside its longest, so that warm-up
compiles only the prefill buckets the mix reaches.  The program's modules, the
configuration's model first, are imported BEFORE the runner listens or starts
the generator: on a program that lacks one the cell ends at once, non-zero,
with no child left behind.

Correctness, outside the window: the file's ``reference_requests`` go through
``submit`` / ``step`` / ``retire`` with fillers in every other slot, and every
token of all of them (752 here) is looked up in the reference's float32 logits
for its own sequence.  Of those tokens at most ``serve_not_argmax_share`` may
be another token than the reference's largest logit.  Why a share and not
``serve``'s largest gap: with routed experts a near tie in a router's scores
sends a token to another expert in bfloat16 than in float32, and that one
token's logits then move by up to two standard deviations whatever the
precision of the rest, so the largest gap of a run says nothing; how OFTEN
the argmax moves does follow the precision (the configuration's file has the
readings).  The largest and the mean gap are reported in ``NOTES``.
"""

from __future__ import annotations

import asyncio
import importlib
import json
import os
import subprocess
import sys
import threading
import time
from typing import Dict

from chipbench import harness, readers
from chipbench import traffic as traffic_mod
from chipbench.runners.serve import REPLICA, _free_port, _spanned


def load_model(config: Dict):
    """The program's class that the configuration's file names under
    ``"model"``, as ``<module>:<class>``."""
    module, _, name = config["model"].partition(":")
    return getattr(importlib.import_module(module), name)


def reference_gaps(engine, params, config, traffic, seed):
    """Run the file's ``reference_requests`` through the engine together with
    its ``reference_fillers`` (spread between them, so that every slot is in
    use) and return, for every token emitted, (reference max - reference
    logit of the token) / reference std at that position: two lists, the
    checked requests' tokens and the fillers'."""
    import jax.numpy as jnp
    import numpy as np

    ref = importlib.import_module(
        config["reference"][:-len(".py")].replace("/", "."))
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
    gaps = {True: [], False: []}
    for prompt, emitted, check in done:
        seq = np.concatenate([prompt, np.asarray(emitted, np.int32)])
        rows = jnp.arange(len(prompt) - 1, len(seq) - 1)
        want = np.asarray(ref.logits(params, jnp.asarray(seq[:-1]), config, rows=rows))
        chosen = want[np.arange(len(emitted)), np.asarray(emitted)]
        gaps[check].extend(((want.max(-1) - chosen) / want.std(-1)).tolist())
    return gaps[True], gaps[False]


def _trace_means(state) -> Dict[str, float]:
    """``trace_mean.<histogram>``: the mean of what each of the program's
    label-free histograms observed INSIDE the traced window, so that a metric
    which divides a count by the trace's device seconds (a roofline share)
    reads both on one clock: the measured window's own mean belongs to other
    steps, and after a stall differs from the traced tail's by a factor."""
    out = {}
    before, after = state.get("trace_before"), state.get("trace_after")
    for name, family in (after or {}).items():
        new = readers.series(after, name, None)
        if not isinstance(new, dict) or "sum" not in new or family["series"][0]["labels"]:
            continue
        old = readers.series(before or {}, name, None) or {"sum": 0.0, "count": 0}
        n = new["count"] - old["count"]
        if n > 0:
            out["trace_mean." + name] = (new["sum"] - old["sum"]) / n
    return out


def run(*, cell, config, traffic, seed, seconds, traced, devices, setup) -> harness.Measured:
    import jax

    with setup.phase("program_imports"):
        model_class = load_model(config)  # first: absent on an older program
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
            model = model_class.from_config(
                config, max_len=traffic["positions_per_slot"],
                **config["uses"][traffic["use"]])
            params = jax.jit(model.init)(jax.random.key(harness.fold_seed(seed)))
            jax.block_until_ready(params)
        with setup.phase("engine_build_warmup"):
            engine = ContinuousBatchingEngine(
                model, params, slots=traffic["slots"], block_size=traffic["block_size"],
                max_seq_len=traffic["positions_per_slot"],
                max_prompt_len=traffic["prompt_tokens"]["max"],
                min_prompt_len=min(
                    [traffic["prompt_tokens"]["min"], traffic["reference_fillers"]["prompt_tokens"]]
                    + [p for p, _b in traffic["reference_requests"]]))
            engine.warmup()
        with setup.phase("reference_check"):
            checked, fillers = reference_gaps(engine, params, config, traffic, seed)
            gaps = checked + fillers
            not_argmax = sum(g > 0 for g in gaps) / max(1, len(gaps))
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
                        state["trace_before"] = registry.snapshot()
                        time.sleep(trace_s)
                        state["trace_after"] = registry.snapshot()
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
    tol = config["tolerance"]["serve_not_argmax_share"]
    compared = {"reference_not_argmax_share": [not_argmax if gaps else None, tol],
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
        values={"setup_s": setup_s, **_trace_means(state)},
        lists={"req_ms_per_token": ms_per_token, "gen_lateness_ms": lateness},
        counters_before=state["before"], counters_after=state["after"],
        samples={"serve_engine_slot_occupancy": state["samples"]},
        notes={"compared": compared, "reference_not_argmax_share": not_argmax,
               "reference_gap_sigma_max": max(gaps) if gaps else None,
               "reference_gap_sigma_max_long": max(checked) if checked else None,
               "reference_gap_sigma_mean": sum(gaps) / max(1, len(gaps)),
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
