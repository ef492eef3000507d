"""Record one small trace of the program's own spans for chipbench/tests (run
it on the chip).

    python3 chipbench/tools/record_program_trace.py chiprun_out/program_trace

A toy engine replica (1 layer, d=128, 4 slots) behind ``EngineService``,
reached over the program's RPC on loopback as the serving cell reaches its
own; after a warm round, two requests with budgets 3 and 4 are served (three
decode iterations) inside a ``chipbench.trace_window`` span while the profiler
records, without the Python tracer and the HLO protos so that the file stays
small.  The file committed as ``chipbench/tests/data/program_spans.xplane.pb.gz``
was made by this script on a TPU v5 lite.  It prints what the trace holds, what
``readers/span_time.py`` reads from it, and how far the end of each
``engine.decode_fetch`` span (host) lies behind the end of the step's last
operation (device): the two clocks' offset plus the copy to the host.
"""

import asyncio
import glob
import gzip
import os
import shutil
import socket
import sys
import threading

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main(out_dir: str) -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np

    if jax.devices()[0].platform != "tpu":
        print("record_program_trace: no TPU", file=sys.stderr)
        return 1
    from chipbench import harness
    from chipbench import trace_reduce as tr
    from chipbench.readers import span_time
    from moolib_tpu.engine import ContinuousBatchingEngine, EngineService
    from moolib_tpu.models.transformer import TransformerLM
    from moolib_tpu.rpc import Rpc

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
        round_of([3, 4])  # warm: the RPC path, every shape
        os.makedirs(out_dir, exist_ok=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.enable_hlo_proto = False
        jax.profiler.start_trace(out_dir, profiler_options=options)
        with jax.profiler.TraceAnnotation(tr.WINDOW_SPAN):
            outs = round_of([3, 4])
        jax.profiler.stop_trace()
    finally:
        loop.call_soon_threadsafe(service.close)
        served.join(timeout=30)
        client.close()
        server.close()
    print("TOKENS", [o.tolist()[-6:] for o in outs])
    path = sorted(glob.glob(os.path.join(out_dir, "plugins/profile/*/*.xplane.pb")))[-1]
    packed = os.path.join(out_dir, "program_spans.xplane.pb.gz")
    with open(path, "rb") as f, gzip.open(packed, "wb") as g:
        shutil.copyfileobj(f, g)
    print("BYTES", os.path.getsize(path), "gz", os.path.getsize(packed))
    data = tr.load(packed)
    for plane in data.planes:
        print("PLANE", plane.name, [(line.name, len(list(line.events))) for line in plane.lines])
    devices, host = span_time.extract(data)
    family = harness.load_json(  # the serving cell's family of spans, as its metrics list it
        harness.BENCH_DIR, "metrics", "idle_host_loop_share.serve.json")["among"]
    lo = host[tr.WINDOW_SPAN][0][0]
    print("WINDOW_NS", host[tr.WINDOW_SPAN][0][1])
    for name in family:
        print("SPAN", name, [(round(s - lo), round(d)) for s, d in host.get(name, ())])
    busy = tr.busy_intervals(devices[0])
    print("DEVICE_BUSY_NS", round(sum(b - a for a, b in busy)), "intervals", len(busy))
    for fig, spans in (("idle_share", family), ("idle_share", ["engine.decode_fetch"]),
                       ("mean_ms", ["engine.step"])):
        spec = {"figure": fig, "spans": spans, "among": family}
        print("FIGURE", fig, spans[:2], span_time.figure(spec, devices, host, 1))
    ends = sorted(s + d for _t, s, d in devices[0])
    for s, d in host.get("engine.decode_fetch", ()):
        before = [e for e in ends if e <= s + d]
        if before:
            print("FETCH_END_AFTER_LAST_OP_NS", round(s + d - before[-1]))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
