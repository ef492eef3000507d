"""LM serving under load: latency percentiles + throughput per config.

VERDICT round-3 ask #8.  Drives ``lm_serve`` (a real server process behind
the RPC dynamic-batching queue) with N concurrent closed-loop clients and
reports p50/p99 request latency, requests/s, and generated tokens/s — with
dynamic batching on vs off, and a GQA ``kv_heads`` sweep.  The reference's
inference batching (``src/moolib.cc:1007-1178``) never had a latency number;
this is it.

One JSON line per config:
    {"clients": 8, "dynamic_batching": true, "kv_heads": 4, "p50_ms": ...,
     "p99_ms": ..., "requests_per_s": ..., "tokens_per_s": ...}

``--qps`` switches to the sustained-load mode for the resilient serving
plane (``moolib_tpu/serving.py``): the batch-1 two-stage-readiness baseline
row still runs first (unchanged config, so the record keeps its control),
then a broker + replica-mode server comes up and paced clients hold each
target QPS for the window, reporting p50/p99 **and the admission reject
rate** — the number the old closed-loop rows cannot see (a closed loop
self-throttles instead of overrunning admission).  One JSON line per
target:
    {"metric": "serve_qps", "qps_target": 50, "p50_ms": ..., "p99_ms": ...,
     "achieved_qps": ..., "reject_rate": ..., ...}
"""

from __future__ import annotations

import argparse
import json
import os
import re
import socket
import subprocess
import sys
import threading
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)  # run as `python benchmarks/serve_bench.py` directly


def _server_platform(log_path: str) -> str:
    """The server's jax platform, parsed from its startup line — rows carry
    it so a CPU row can never be read as a chip result."""
    try:
        with open(log_path) as f:
            m = re.search(r"\[platform=(\w+)\]", f.read())
        return m.group(1) if m else "unknown"
    except OSError:
        return "unknown"


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    p = s.getsockname()[1]
    s.close()
    return p


def _await_line(log_path: str, server, marker: str, timeout: float,
                fail_msg: str) -> None:
    """Poll the server log until ``marker`` appears, the server dies, or
    ``timeout`` expires (raising ``fail_msg``)."""
    deadline = time.time() + timeout
    while time.time() < deadline:
        with open(log_path) as f:
            if marker in f.read():
                return
        if server.poll() is not None:
            raise RuntimeError(f"server died: {open(log_path).read()[-2000:]}")
        time.sleep(0.2)
    raise RuntimeError(fail_msg)


def run_config(args, dynamic: bool, kv_heads: int, batch_size: int):
    port = _free_port()
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(
        os.environ,
        PYTHONPATH=root + os.pathsep + os.environ.get("PYTHONPATH", ""),
    )
    cmd = [
        sys.executable, "-m", "moolib_tpu.examples.lm_serve",
        "--listen", f"127.0.0.1:{port}",
        "--vocab", str(args.vocab),
        "--seq_len", str(args.seq_len),
        "--d_model", str(args.d_model),
        "--layers", str(args.layers),
        "--heads", str(args.heads),
        "--kv_heads", str(kv_heads),
        "--batch_size", str(batch_size),
        "--max_new_tokens", str(args.max_new_tokens),
    ]
    if not dynamic:
        cmd.append("--no_dynamic_batching")
    # Log to a file, not a pipe: the server outlives the bench window and a
    # full pipe would wedge it mid-measurement.
    log_path = f"/tmp/serve_bench_{port}.log"
    with open(log_path, "w") as log:
        # Own session: if serve_bench itself is SIGTERMed, killpg below
        # still reaps the server — an orphaned forever-serving
        # process would hold the chip and starve every later bench.
        server = subprocess.Popen(cmd, stdout=log, stderr=subprocess.STDOUT,
                                  text=True, env=env, cwd=root,
                                  start_new_session=True)
    try:
        # Two-stage readiness: the server prints its "precompiling" line as
        # soon as it is alive with args parsed — that line gates "server
        # never came up" on a tight bound.  The "serving" line then gets
        # the GENEROUS bound: bucket pre-compiles of a large model
        # legitimately take minutes, and conflating the two turns slow
        # compiles into spurious startup failures.
        _await_line(log_path, server, "precompiling", args.startup_timeout,
                    "server never came up")
        _await_line(log_path, server, "serving", args.ready_timeout,
                    f"server never finished pre-compiling within "
                    f"{args.ready_timeout:.0f}s")

        import numpy as np

        from moolib_tpu import Rpc

        rpc = Rpc()
        rpc.set_name("bench_client")
        rpc.set_timeout(120)
        rpc.connect(f"127.0.0.1:{port}")
        rng = np.random.default_rng(0)
        prompt = rng.integers(2, args.vocab, args.seq_len).astype(np.int32)
        # Warm: first call compiles the generate step server-side.
        rpc.sync("lm_server", "generate", prompt)
        stats0 = rpc.sync("lm_server", "generate_stats")

        latencies: list = []
        failures: list = []
        lock = threading.Lock()
        stop = time.time() + args.seconds

        def client_loop(seed):
            r = np.random.default_rng(seed)
            while time.time() < stop:
                p = r.integers(2, args.vocab, args.seq_len).astype(np.int32)
                t0 = time.perf_counter()
                try:
                    out = rpc.sync("lm_server", "generate", p)
                    if len(out) != args.seq_len + args.max_new_tokens:
                        raise RuntimeError(f"bad output length {len(out)}")
                except Exception as e:  # noqa: BLE001 — a dead client thread
                    # would silently skew the closed-loop percentiles
                    with lock:
                        failures.append(str(e))
                    return
                dt = time.perf_counter() - t0
                with lock:
                    latencies.append(dt)

        threads = [
            threading.Thread(target=client_loop, args=(i,), daemon=True)
            for i in range(args.clients)
        ]
        t0 = time.time()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        wall = time.time() - t0
        stats1 = rpc.sync("lm_server", "generate_stats")
        rpc.close()
        if failures or not latencies:
            raise RuntimeError(
                f"{len(failures)}/{args.clients} clients failed "
                f"({len(latencies)} requests completed): "
                + "; ".join(failures[:3])
            )
        lat = np.sort(np.asarray(latencies))
        # Queue service-quality deltas over the measurement window: how full
        # the dynamic batches actually ran and how long requests sat queued
        # before service — the data that makes the batching crossover
        # legible instead of asserted (VERDICT r4 weak #6).
        d = {k: stats1[k] - stats0[k] for k in ("items", "takes", "wait_s_sum")}
        takes = max(1, int(d["takes"]))
        row = {
            "platform": _server_platform(log_path),
            "clients": args.clients,
            "dynamic_batching": dynamic,
            "kv_heads": kv_heads,
            "batch_size": batch_size if dynamic else 1,
            "requests": int(lat.size),
            "p50_ms": round(float(np.percentile(lat, 50)) * 1e3, 1),
            "p99_ms": round(float(np.percentile(lat, 99)) * 1e3, 1),
            "requests_per_s": round(lat.size / wall, 1),
            "tokens_per_s": round(lat.size * args.max_new_tokens / wall, 1),
            "avg_batch_fill": round(d["items"] / takes, 2),
            "avg_queue_wait_ms": round(d["wait_s_sum"] / max(1, d["items"]) * 1e3, 2),
            # Cumulative since server start (maxima are not window-diffable;
            # includes the one warm-up call).
            "server_max_queue_wait_ms": round(float(stats1["wait_s_max"]) * 1e3, 2),
            "server_max_queue_depth": int(stats1["depth_max"]),
        }
        print(json.dumps(row), flush=True)
        return row
    finally:
        import signal

        try:
            os.killpg(server.pid, signal.SIGKILL)
        except (OSError, ProcessLookupError):
            server.kill()
        server.wait()
        try:
            os.unlink(log_path)
        except OSError:
            pass


_PHASES = ("admission", "queue", "batch_assembly", "device", "reply")


def _phase_totals(rpc, replica):
    """Per-phase ``(sum_s, count)`` of the server's ``serve_phase_seconds``
    histogram, pulled over the ``__telemetry_snapshot`` RPC every scrapable
    peer defines.  ``None`` when the server predates the endpoint — the
    breakdown row is additive, never a bench failure."""
    try:
        snap = rpc.sync(replica, "__telemetry_snapshot")
    except Exception:  # noqa: BLE001
        return None
    fam = (snap.get("metrics") or {}).get("serve_phase_seconds") or {}
    out = {}
    for s in fam.get("series", ()):
        ph = (s.get("labels") or {}).get("phase")
        v = s.get("value") or {}
        if ph:
            out[ph] = (float(v.get("sum", 0.0)), int(v.get("count", 0)))
    return out


def run_qps(args, engine: bool = False):
    """Sustained-QPS rows against a replica-mode server (admission control
    on): paced arrivals, per-request deadline, typed rejects counted.

    ``engine=True`` serves through ``lm_serve --engine`` (continuous
    batching over the paged KV cache) — the A/B arm.  With
    ``--mixed_tokens`` each request draws its own generation budget, the
    workload where batch-synchronous decode convoys short requests behind
    long ones.  Returns the row dicts for the A/B gate."""
    import numpy as np

    from moolib_tpu import Broker
    from moolib_tpu.serving import ServeClient, is_overload_error

    broker_port = _free_port()
    broker = Broker()
    broker.set_name("broker")
    broker.listen(f"127.0.0.1:{broker_port}")
    stop_pump = threading.Event()

    def pump():
        while not stop_pump.is_set():
            broker.update()
            stop_pump.wait(0.05)

    threading.Thread(target=pump, daemon=True).start()

    port = _free_port()
    env = dict(
        os.environ,
        PYTHONPATH=ROOT + os.pathsep + os.environ.get("PYTHONPATH", ""),
    )
    cmd = [
        sys.executable, "-m", "moolib_tpu.examples.lm_serve",
        "--listen", f"127.0.0.1:{port}",
        "--broker", f"127.0.0.1:{broker_port}",
        "--vocab", str(args.vocab),
        "--seq_len", str(args.seq_len),
        "--d_model", str(args.d_model),
        "--layers", str(args.layers),
        "--heads", str(args.heads),
        "--kv_heads", str(args.heads),
        "--batch_size", str(args.batch_sizes[0]),
        "--max_new_tokens", str(args.max_new_tokens),
        "--max_queue", str(args.max_queue),
    ]
    if engine:
        cmd += ["--engine", "--slots", str(args.batch_sizes[0]),
                "--block_size", str(args.block_size)]
    log_path = f"/tmp/serve_bench_qps_{port}.log"
    with open(log_path, "w") as log:
        server = subprocess.Popen(cmd, stdout=log, stderr=subprocess.STDOUT,
                                  text=True, env=env, cwd=ROOT,
                                  start_new_session=True)
    client = None
    try:
        _await_line(log_path, server, "precompiling", args.startup_timeout,
                    "server never came up")
        _await_line(log_path, server, "serving", args.ready_timeout,
                    f"server never finished pre-compiling within "
                    f"{args.ready_timeout:.0f}s")
        platform = _server_platform(log_path)
        client = ServeClient(broker=f"127.0.0.1:{broker_port}",
                             deadline_s=args.deadline_s)
        client.wait_for_replicas(1, timeout=30.0)
        rng = np.random.default_rng(0)
        prompt = rng.integers(2, args.vocab, args.seq_len).astype(np.int32)
        # Duplicates in --mixed_tokens weight the draw (8 8 32 256 = half
        # the requests short); the latency buckets key on distinct values.
        mixed = sorted(args.mixed_tokens or ())
        distinct = sorted(set(mixed))
        # Warm + prime the server's service-time EMA — one call per decode
        # budget, so the baseline arm's per-budget jit compiles land before
        # the measured window (the engine arm compiled everything at
        # warmup; these are no-ops there).
        if mixed:
            for mt in distinct:
                client.call(prompt, mt)
        else:
            client.call(prompt)
        replica = client.replicas()[0]
        phases0 = _phase_totals(client._rpc, replica)

        rows = []
        for q in args.qps:
            latencies: list = []
            lat_by_mt: dict = {mt: [] for mt in distinct}
            outcomes = {"ok": 0, "reject": 0, "deadline": 0, "error": 0,
                        "tokens": 0}
            lock = threading.Lock()
            pending = []

            def on_done(fut, t0, mt):
                dt = time.perf_counter() - t0
                exc = fut.exception()
                with lock:
                    if exc is None:
                        outcomes["ok"] += 1
                        # Real generated tokens, counted client-side from
                        # the reply length (budget minus any early EOS).
                        outcomes["tokens"] += (
                            len(fut.result()) - args.seq_len
                        )
                        latencies.append(dt)
                        if mt in lat_by_mt:
                            lat_by_mt[mt].append(dt)
                    elif is_overload_error(exc):
                        outcomes["reject"] += 1
                    elif "deadline" in str(exc).lower():
                        outcomes["deadline"] += 1
                    else:
                        outcomes["error"] += 1

            interval = 1.0 / q
            n = max(1, int(args.seconds * q))
            t_start = time.perf_counter()
            for i in range(n):
                # Paced (open-loop) arrivals: a slow server sees the real
                # offered load and must shed it through admission, not
                # through a self-throttling client.
                target = t_start + i * interval
                delay = target - time.perf_counter()
                if delay > 0:
                    time.sleep(delay)
                p = rng.integers(2, args.vocab, args.seq_len).astype(np.int32)
                mt = int(rng.choice(mixed)) if mixed else None
                t0 = time.perf_counter()
                fut = client.submit(p) if mt is None else client.submit(p, mt)
                fut.add_done_callback(
                    lambda f, t0=t0, mt=mt: on_done(f, t0, mt)
                )
                pending.append(fut)
            for fut in pending:
                try:
                    fut.result(args.deadline_s + 10.0)
                except Exception:  # noqa: BLE001 — classified in on_done
                    pass
            wall = time.perf_counter() - t_start

            def _pct(xs, p):
                return (round(float(np.percentile(np.asarray(xs), p)) * 1e3, 1)
                        if xs else None)

            with lock:
                lat = sorted(latencies)
                row = {
                    "metric": "serve_qps",
                    "platform": platform,
                    "engine": engine,
                    "qps_target": q,
                    "deadline_s": args.deadline_s,
                    "requests": n,
                    "ok": outcomes["ok"],
                    "rejects": outcomes["reject"],
                    "deadline_errors": outcomes["deadline"],
                    "errors": outcomes["error"],
                    "reject_rate": round(outcomes["reject"] / n, 4),
                    "achieved_qps": round(outcomes["ok"] / wall, 1),
                    "tokens_per_s": round(outcomes["tokens"] / wall, 1),
                    "wall_s": round(wall, 2),
                    "p50_ms": _pct(lat, 50),
                    "p99_ms": _pct(lat, 99),
                }
                if mixed:
                    # Convoy visibility: short requests' tail latency is
                    # where batch-synchronous decode pays (a short request
                    # steps to its batch's longest budget).
                    row["mixed_tokens"] = mixed
                    row["p50_ms_short"] = _pct(lat_by_mt[distinct[0]], 50)
                    row["p99_ms_short"] = _pct(lat_by_mt[distinct[0]], 99)
                    row["p99_ms_long"] = _pct(lat_by_mt[distinct[-1]], 99)
            rows.append(row)
            print(json.dumps(row), flush=True)
        # Where did the latency go?  Per-phase means over the whole QPS
        # sweep, from the server's serve_phase_seconds histogram deltas
        # (admission -> queue -> batch_assembly -> device -> reply).
        phases1 = _phase_totals(client._rpc, replica)
        if phases0 is not None and phases1 is not None:
            breakdown = {}
            for ph in _PHASES:
                s0, c0 = phases0.get(ph, (0.0, 0))
                s1, c1 = phases1.get(ph, (0.0, 0))
                dc = c1 - c0
                breakdown[ph] = {
                    "count": dc,
                    "mean_ms": (round((s1 - s0) / dc * 1e3, 3)
                                if dc > 0 else None),
                }
            print(json.dumps({
                "metric": "serve_phase_breakdown",
                "platform": platform,
                "engine": engine,
                "phases": breakdown,
            }), flush=True)
        return rows
    finally:
        import signal

        if client is not None:
            client.close()
        stop_pump.set()
        broker.close()
        try:
            os.killpg(server.pid, signal.SIGKILL)
        except (OSError, ProcessLookupError):
            server.kill()
        server.wait()
        try:
            os.unlink(log_path)
        except OSError:
            pass


def main(argv=None):
    # This process is the load generator.  A chip belongs to one process at
    # a time and that process is the server child: whatever jax does here
    # (the package imports it) stays on the host.
    import jax

    jax.config.update("jax_platforms", "cpu")
    p = argparse.ArgumentParser()
    p.add_argument("--clients", type=int, default=8)
    p.add_argument("--seconds", type=float, default=10.0, help="load window per config")
    p.add_argument("--vocab", type=int, default=256)
    p.add_argument("--seq_len", type=int, default=16)
    p.add_argument("--d_model", type=int, default=128)
    p.add_argument("--layers", type=int, default=2)
    p.add_argument("--heads", type=int, default=4)
    p.add_argument("--kv_heads", type=int, nargs="+", default=[4, 1],
                   help="GQA sweep (heads value = plain MHA)")
    p.add_argument("--max_new_tokens", type=int, default=16)
    p.add_argument("--batch_sizes", type=int, nargs="+", default=[16],
                   help="dynamic-batching cap sweep (crossover search); the "
                   "kv_heads sweep runs at the first value")
    p.add_argument("--startup_timeout", type=float, default=90.0,
                   help="deadline for the server's 'precompiling' proof-of-"
                   "life line (args parsed, jax imported); only THIS "
                   "expiring means 'server never came up'")
    p.add_argument("--qps", type=float, nargs="+", default=None,
                   help="sustained-QPS mode: paced open-loop load at each "
                   "target against a replica-mode server (admission control "
                   "on); reports p50/p99 + reject rate per target")
    p.add_argument("--deadline_s", type=float, default=5.0,
                   help="per-request deadline in --qps mode (drives both "
                   "client retries and server admission)")
    p.add_argument("--max_queue", type=int, default=128,
                   help="server admission queue bound in --qps mode")
    p.add_argument("--ready_timeout", type=float, default=420.0,
                   help="deadline from proof-of-life to the 'serving' line; "
                   "bucketed serving pre-compiles every power-of-2 bucket "
                   "before readiness, and each bucket's prefill+decode "
                   "compile can take a while at full model size")
    p.add_argument("--engine", action="store_true",
                   help="A/B in --qps mode: run the baseline replica arm, "
                   "then the continuous-batching engine arm (lm_serve "
                   "--engine), and print a serve_engine_ab comparison row")
    p.add_argument("--mixed_tokens", type=int, nargs="+", default=None,
                   help="per-request generation budgets drawn uniformly "
                   "(e.g. 8 32 256) — the mixed-length workload where "
                   "batch-synchronous decode convoys short requests")
    p.add_argument("--block_size", type=int, default=16,
                   help="KV block size for the engine arm")
    p.add_argument("--check", action="store_true",
                   help="with --engine: exit non-zero unless the engine arm "
                   "sustains >= check_ratio x baseline tokens/s with zero "
                   "errors in both arms (rejects are allowed — that is "
                   "admission working)")
    p.add_argument("--check_ratio", type=float, default=1.0,
                   help="tokens/s floor for --check, as a multiple of the "
                   "baseline arm")
    args = p.parse_args(argv)

    cfg = (
        f"# lm_serve load: d={args.d_model} L={args.layers} H={args.heads} "
        f"T={args.seq_len}+{args.max_new_tokens} clients={args.clients} "
        f"window={args.seconds}s"
    )
    print(cfg, flush=True)
    if args.qps:
        if args.engine:
            # Engine A/B: the same paced mixed-budget load against the
            # baseline replica arm, then the continuous-batching engine.
            # Same broker machinery, same admission contract — only the
            # service loop differs, so the delta IS the engine.
            base_rows = run_qps(args, engine=False)
            eng_rows = run_qps(args, engine=True)

            def _agg(rows):
                ok = sum(r["ok"] for r in rows)
                err = sum(r["errors"] + r["deadline_errors"] for r in rows)
                tps = sum(r["tokens_per_s"] * r["wall_s"] for r in rows)
                wall = sum(r["wall_s"] for r in rows)
                p99s = [r.get("p99_ms_short") for r in rows
                        if r.get("p99_ms_short") is not None]
                return {
                    "ok": ok, "errors": err,
                    "tokens_per_s": round(tps / max(wall, 1e-9), 1),
                    "p99_ms_short_worst": max(p99s) if p99s else None,
                }
            base, eng = _agg(base_rows), _agg(eng_rows)
            speedup = (round(eng["tokens_per_s"] / base["tokens_per_s"], 2)
                       if base["tokens_per_s"] else None)
            print(json.dumps({
                "metric": "serve_engine_ab",
                "qps_targets": args.qps,
                "mixed_tokens": sorted(args.mixed_tokens or ()),
                "baseline": base,
                "engine": eng,
                "tokens_per_s_speedup": speedup,
            }), flush=True)
            if args.check:
                problems = []
                if base["errors"] or eng["errors"]:
                    problems.append(
                        f"hard errors (baseline={base['errors']}, "
                        f"engine={eng['errors']})"
                    )
                if eng["tokens_per_s"] < args.check_ratio * base["tokens_per_s"]:
                    problems.append(
                        f"engine {eng['tokens_per_s']} tok/s < "
                        f"{args.check_ratio} x baseline "
                        f"{base['tokens_per_s']} tok/s"
                    )
                if problems:
                    raise SystemExit("serve_engine_ab CHECK FAILED: "
                                     + "; ".join(problems))
                print("# serve_engine_ab check passed", flush=True)
            return
        # The batch-1 two-stage-readiness baseline stays the first row (the
        # control a time limit must never truncate away), then the
        # sustained-QPS rows run against the resilient plane.
        run_config(args, dynamic=False, kv_heads=args.heads, batch_size=1)
        run_qps(args)
        return
    ok: set = set()
    # (dynamic, kv_heads, batch_size): the batch-1 BASELINE runs first
    # (the crossover's control row must never be the one a time limit
    # truncates away), then the GQA sweep at the
    # first batch size, then the batch-size sweep at the MHA config.
    configs = [(False, args.heads, 1)]
    configs += [(True, kv, args.batch_sizes[0]) for kv in args.kv_heads]
    if args.heads not in args.kv_heads:
        # The batch-size sweep needs its reference point at the first cap.
        configs.append((True, args.heads, args.batch_sizes[0]))
    configs += [(True, args.heads, b) for b in args.batch_sizes[1:]]
    for dynamic, kv, bs in configs:
        attempts = 0
        while True:
            attempts += 1
            try:
                run_config(args, dynamic=dynamic, kv_heads=kv, batch_size=bs)
                ok.add((dynamic, kv, bs))
                break
            except Exception as e:  # noqa: BLE001 — one bad config must not
                # abort the rest of the sweep.  A startup no-show gets ONE
                # retry: a transient port clash must not cost a whole re-run.
                if "never came up" in str(e) and attempts == 1:
                    print(f"# config dynamic={dynamic} kv={kv} bs={bs} "
                          f"startup no-show; retrying once", flush=True)
                    continue
                print(f"# config dynamic={dynamic} kv={kv} bs={bs} FAILED: {e}",
                      flush=True)
                break
    # The exit code insists on exactly the rows the sweep exists to
    # compare: the headline batched config and the batch-1 control.
    crossover = {(True, args.heads, args.batch_sizes[0]), (False, args.heads, 1)}
    missing = crossover - ok
    if missing:
        raise SystemExit(
            f"{len(configs) - len(ok)}/{len(configs)} serve configs failed, "
            f"including the crossover pair {sorted(missing)}"
        )


if __name__ == "__main__":
    main()
