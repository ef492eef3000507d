"""Record one small trace of the program's NAMED programs and their dispatch
spans for chipbench/tests (run it on the chip), and ask a live replica what an
operator asks it.

    python3 chipbench/tools/record_program_runs.py chiprun_out/program_runs

``record_program_trace.py``'s toy replica (1 layer, d=128, 4 slots) behind
``EngineService`` over loopback.  Three things, in one process:

1. after a warm round, three requests with budgets 3, 4 and 6 are served inside
   a ``chipbench.trace_window`` span while the profiler records (no Python
   tracer, no HLO protos: the file stays small).  The file committed as
   ``chipbench/tests/data/program_runs.xplane.pb.gz`` was made so on a TPU v5
   lite; what ``readers/program_time.py`` reads from it is printed (``FIGURE``),
   and each run with the span it was tied to (``RUN``).
2. an operator's window on the live replica: ``profiling.handle_command``
   ``start``, a round of eight requests, ``stop``; the reply's per-program
   summary is printed (``SUMMARY``), then the summary is computed once more on
   its own and the longest ``host.tick`` span that overlapped it printed
   (``SUMMARY_HOST``): what the call costs the process it is made in.
3. what a span costs, in ns, with and without arguments, profiler closed and
   open (``SPAN_NS``).
"""

import asyncio
import glob
import gzip
import json
import os
import shutil
import socket
import sys
import threading
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def span_ns(telemetry, n=20000):
    """ns a span: bare, and opened with three scalar arguments."""
    def timed(**args):
        t0 = time.perf_counter_ns()
        for _ in range(n):
            with telemetry.span("chipbench.cost", **args):
                pass
        return (time.perf_counter_ns() - t0) / n
    return {"bare": timed(), "three_args": timed(program="engine_decode", seq=7, rows=128)}


def main(out_dir: str) -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np

    if jax.devices()[0].platform != "tpu":
        print("record_program_runs: no TPU", file=sys.stderr)
        return 1
    from chipbench import harness
    from chipbench import trace_reduce as tr
    from chipbench.readers import program_time
    from moolib_tpu import telemetry
    from moolib_tpu.engine import ContinuousBatchingEngine, EngineService
    from moolib_tpu.models.transformer import TransformerLM
    from moolib_tpu.rpc import Rpc
    from moolib_tpu.telemetry import profiling

    model = TransformerLM(vocab_size=256, d_model=128, num_heads=4, num_layers=1,
                          max_len=128, attention="dense", dtype=jnp.bfloat16,
                          pos_embedding="learned")
    params = jax.jit(model.init)(jax.random.key(0), jnp.zeros((1, 8), jnp.int32))
    engine = ContinuousBatchingEngine(model, params, slots=4, block_size=16,
                                      max_seq_len=128, max_prompt_len=32)
    engine.warmup()
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        address = f"127.0.0.1:{s.getsockname()[1]}"
    server, client = Rpc(), Rpc()
    server.set_name("toy_replica")
    server.listen(address)
    client.set_name("toy_client")
    client.connect(address)
    service = EngineService(server, engine, name="generate")
    loop = asyncio.new_event_loop()
    served = threading.Thread(target=lambda: loop.run_until_complete(service.loop()), daemon=True)
    served.start()
    rng = np.random.default_rng(0)

    def round_of(budgets):
        futures = [client.async_("toy_replica", "generate",
                                 rng.integers(1, 256, 20).astype(np.int32), b) for b in budgets]
        return [np.asarray(f.result(120)) for f in futures]

    try:
        round_of([3, 4, 6])  # warm: the RPC path, every shape
        cost = {"closed": span_ns(telemetry)}
        os.makedirs(out_dir, exist_ok=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.enable_hlo_proto = False
        jax.profiler.start_trace(out_dir, profiler_options=options)
        with jax.profiler.TraceAnnotation(tr.WINDOW_SPAN):
            outs = round_of([3, 4, 6])
        jax.profiler.stop_trace()
        print("TOKENS", [o.tolist()[-6:] for o in outs])

        window_dir = os.path.join(out_dir, "operator")
        print("START", json.dumps(profiling.handle_command("start", logdir=window_dir)))
        round_of([6, 8, 9, 12, 7, 10, 11, 12])
        cost["open"] = span_ns(telemetry, n=2000)
        reply = profiling.handle_command("stop")
        print("SUMMARY", json.dumps(reply))
        t0 = time.perf_counter_ns()
        profiling.summarize(window_dir)
        t1 = time.perf_counter_ns()
        ticks = [s.dur_ns for s in telemetry.get_tracer().spans()
                 if s.name == "host.tick" and s.start_ns < t1 and s.start_ns + s.dur_ns > t0]
        print("SUMMARY_HOST", json.dumps({
            "summarize_ms": (t1 - t0) / 1e6, "host_ticks": len(ticks),
            "host_tick_longest_ms": max(ticks, default=0) / 1e6}))
        print("SPAN_NS", json.dumps(cost))
    finally:
        loop.call_soon_threadsafe(service.close)
        served.join(timeout=30)
        client.close()
        server.close()
    path = sorted(glob.glob(os.path.join(out_dir, "plugins/profile/*/*.xplane.pb")))[-1]
    packed = os.path.join(out_dir, "program_runs.xplane.pb.gz")
    with open(path, "rb") as f, gzip.open(packed, "wb") as g:
        shutil.copyfileobj(f, g)
    print("BYTES", os.path.getsize(path), "gz", os.path.getsize(packed))
    data = tr.load(packed)
    programs = harness.load_json(
        harness.BENCH_DIR, "metrics", "prefill_device_share.json")["programs"]
    runs, host = program_time.extract(data, set(programs.values()))
    for run, span in program_time.matches(runs, host, programs):
        print("RUN", run["program"], run["run_id"], round(run["end"] - run["start"]),
              span and (span[2], round(run["start"] - span[0]), span[3]))
    busy_s = tr.reduce(tr.extract(data), 1)["busy_s"]
    for fig in ("device_share", "mean_ms", "queue_delay_mean_ms"):
        for program in programs:
            spec = {"figure": fig, "program": program, "programs": programs}
            print("FIGURE", fig, program, program_time.figure(spec, runs, host, busy_s, 1))
    for fig in ("clock_lead_ms", "matched_share"):
        print("FIGURE", fig, program_time.figure(
            {"figure": fig, "programs": programs}, runs, host, busy_s, 1))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
