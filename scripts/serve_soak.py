#!/usr/bin/env python
"""Serving-plane soak: replica SIGKILL + live hot-swap under sustained load.

Drives the resilient serving plane (``moolib_tpu/serving.py``;
docs/RESILIENCE.md "Serving") end to end with real processes:

1. **Formation**: this script hosts the Broker and an in-process
   :class:`~moolib_tpu.serving.ModelPublisher` ("pusher"), then spawns two
   ``moolib_tpu.examples.lm_serve`` replica subprocesses (``--broker`` +
   ``--publisher``).  Both must print the two-stage readiness lines and be
   discovered by a broker-polling :class:`~moolib_tpu.serving.ServeClient`.
2. **Sustained load**: paced open-loop requests at a target QPS for the
   whole window; every future is awaited, every outcome classified.
3. **Replica SIGKILL mid-stream**: at a seeded time (middle half of the
   window, :meth:`FaultPlan.replica_kill_time`), a seeded victim is
   SIGKILLed (:meth:`FaultPlan.replica_kill`) — no drain, no leave.  The
   gate is the plane's headline claim: **zero lost requests** — every
   in-flight future completes on the survivor (latency, not loss).
4. **Live hot-swap**: the pusher publishes a new model version while load
   continues; the survivor must install it between service iterations
   (``hot_swaps >= 1``, ``serve_swap_seconds`` recorded) and the swap must
   cause **no admission rejects** (rejects delta over the swap window = 0).

Exit 0 only when every gate holds; the JSON verdict goes to ``--out`` or stdout.

``--engine`` serves every replica through the continuous-batching engine
(``lm_serve --engine``) under the same kill + hot-swap gates — the engine
inherits the resilience contract, so the soak must not care which service
loop answered.  ``--swing`` runs the QPS-elasticity phase instead (see
:func:`run_swing`): calm -> 5x surge -> quiet offered load against an
autoscaled fleet, gated on a ``serve_wait`` grow, a ``serve_idle``
graceful shrink, and zero lost requests.

Usage::

    python scripts/serve_soak.py --smoke                  # ~1 min CI profile
    python scripts/serve_soak.py --seed 7 --out /tmp/serve_soak.json
    python scripts/serve_soak.py --smoke --engine         # engine arm
    python scripts/serve_soak.py --smoke --swing          # elasticity swing
"""

from __future__ import annotations

import argparse
import json
import os
import signal
import socket
import subprocess
import sys
import threading
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

T0 = time.monotonic()


def log(msg: str) -> None:
    print(f"[serve_soak +{time.monotonic() - T0:6.1f}s] {msg}", flush=True)


def free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def await_line(log_path: str, proc, marker: str, timeout: float) -> None:
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        try:
            with open(log_path) as f:
                if marker in f.read():
                    return
        except OSError:
            pass
        if proc.poll() is not None:
            raise RuntimeError(
                f"replica died before '{marker}': "
                + open(log_path).read()[-2000:]
            )
        time.sleep(0.2)
    raise RuntimeError(f"'{marker}' not seen within {timeout:.0f}s")


def spawn_replica(name: str, port: int, broker_addr: str, flags) -> tuple:
    env = dict(
        os.environ,
        PYTHONPATH=ROOT + os.pathsep + os.environ.get("PYTHONPATH", ""),
        JAX_PLATFORMS=os.environ.get("JAX_PLATFORMS", "cpu"),
    )
    cmd = [
        sys.executable, "-m", "moolib_tpu.examples.lm_serve",
        "--listen", f"127.0.0.1:{port}",
        "--broker", broker_addr,
        "--name", name,
        "--publisher", "pusher",
        "--vocab", str(flags.vocab),
        "--seq_len", str(flags.seq_len),
        "--d_model", str(flags.d_model),
        "--layers", str(flags.layers),
        "--heads", str(flags.heads),
        "--batch_size", str(flags.batch_size),
        "--max_new_tokens", str(flags.max_new_tokens),
        "--max_queue", str(flags.max_queue),
        "--seed", str(flags.seed),
    ]
    if flags.engine:
        cmd.append("--engine")
    log_path = f"/tmp/serve_soak_{name}.log"
    with open(log_path, "w") as lf:
        proc = subprocess.Popen(cmd, stdout=lf, stderr=subprocess.STDOUT,
                                text=True, env=env, cwd=ROOT,
                                start_new_session=True)
    return proc, log_path


def run_swing(flags) -> int:
    """QPS-elasticity swing (ISSUE 12 satellite): a one-replica engine
    fleet under a 5x offered-load swing, supervised by the autoscaler's
    serving rules end to end with real processes.

    Phases: **calm** (qps_low, one replica keeps up) -> **surge** (5 x
    qps_low, the replica saturates, ``serve_queue_wait_s`` climbs, the
    policy grows a second replica) -> **quiet** (back to qps_low, the
    fleet drains, sustained idle shrinks it back via the localdir
    decommission flag — a graceful leave, not a kill).

    ``--service_delay_ms`` pins per-iteration service time, so "one
    replica saturates under the surge but two do not" holds on any host
    instead of depending on CPU speed.  Gates: a ``serve_wait`` grow
    fired during the surge, a ``serve_idle`` shrink brought the fleet
    back to one, the decommissioned replica exited cleanly, and zero
    requests were lost (admission rejects are the plane working).
    """
    import shutil
    import tempfile

    import numpy as np

    from moolib_tpu import Broker
    from moolib_tpu.autoscaler import (
        Autoscaler,
        AutoscalePolicy,
        SubprocessFleet,
    )
    from moolib_tpu.serving import ServeClient, is_overload_error

    qps_low = flags.qps if flags.qps is not None else 2.0
    qps_high = 5.0 * qps_low
    calm_s = 8.0 if flags.smoke else 15.0
    surge_s = 35.0 if flags.smoke else 60.0
    quiet_s = 25.0 if flags.smoke else 45.0
    log(f"swing: qps {qps_low} -> {qps_high} -> {qps_low} "
        f"({calm_s}/{surge_s}/{quiet_s}s), service_delay="
        f"{flags.service_delay_ms}ms")

    broker_addr = f"127.0.0.1:{free_port()}"
    broker = Broker()
    broker.set_name("broker")
    broker.listen(broker_addr)
    stop_pump = threading.Event()

    def pump():
        while not stop_pump.is_set():
            broker.update()
            stop_pump.wait(0.05)

    threading.Thread(target=pump, daemon=True).start()
    base_dir = tempfile.mkdtemp(prefix="serve_swing_")

    def spawn(name: str, localdir: str) -> subprocess.Popen:
        env = dict(
            os.environ,
            PYTHONPATH=ROOT + os.pathsep + os.environ.get("PYTHONPATH", ""),
            JAX_PLATFORMS=os.environ.get("JAX_PLATFORMS", "cpu"),
            MOOLIB_TELEMETRY_DIR=localdir,
            MOOLIB_TELEMETRY_INTERVAL="1",
        )
        cmd = [
            sys.executable, "-m", "moolib_tpu.examples.lm_serve",
            "--listen", f"127.0.0.1:{free_port()}",
            "--broker", broker_addr,
            "--name", name,
            "--localdir", localdir,
            "--engine",
            # Capacity pin: one replica serves ~slots/(max_new x delay)
            # req/s (4/(4 x 0.15) ~ 6.7 with defaults), so the 5x surge
            # (10 req/s) saturates one replica and two absorb it.
            "--slots", str(max(1, flags.batch_size // 2)),
            "--vocab", str(flags.vocab),
            "--seq_len", str(flags.seq_len),
            "--d_model", str(flags.d_model),
            "--layers", str(flags.layers),
            "--heads", str(flags.heads),
            "--batch_size", str(flags.batch_size),
            "--max_new_tokens", str(flags.max_new_tokens),
            "--max_queue", str(flags.max_queue),
            "--service_delay_ms", str(flags.service_delay_ms),
            "--seed", str(flags.seed),
        ]
        lf = open(os.path.join(localdir, "replica.log"), "ab")
        return subprocess.Popen(cmd, stdout=lf, stderr=subprocess.STDOUT,
                                env=env, cwd=ROOT, start_new_session=True)

    fleet = SubprocessFleet(spawn, base_dir, name_prefix="swing")
    policy = AutoscalePolicy(
        min_peers=1, max_peers=2, cooldown_s=5.0,
        serve_wait_grow_s=0.4, serve_wait_polls=2,
        # The quiet trickle still lands ~qps_low answers/s fleet-wide;
        # idle means "at or below the calm rate with a cold queue".
        serve_idle_qps=max(0.1, qps_low), serve_idle_occupancy=0.5,
        serve_idle_polls=3,
    )
    scaler = Autoscaler(policy, fleet, poll_interval=1.0)
    result = {
        "soak": "serve_swing", "seed": flags.seed, "smoke": flags.smoke,
        "qps_low": qps_low, "qps_high": qps_high,
        "service_delay_ms": flags.service_delay_ms,
    }
    client = None
    try:
        fleet.grow()
        client = ServeClient(broker=broker_addr, deadline_s=flags.deadline_s,
                             attempt_timeout=2.0, max_attempts=8)
        client.wait_for_replicas(1, timeout=flags.ready_timeout)
        rng = np.random.default_rng(flags.seed)
        warm = rng.integers(2, flags.vocab, flags.seq_len).astype(np.int32)
        client.call(warm)

        outcomes = {"ok": 0, "reject": 0, "deadline": 0, "error": 0}
        error_samples: list = []
        lock = threading.Lock()
        pending = []

        def on_done(fut):
            exc = fut.exception()
            with lock:
                if exc is None:
                    outcomes["ok"] += 1
                elif is_overload_error(exc):
                    outcomes["reject"] += 1
                elif "deadline" in str(exc).lower():
                    outcomes["deadline"] += 1
                else:
                    outcomes["error"] += 1
                    if len(error_samples) < 5:
                        error_samples.append(str(exc)[:300])

        phase_cohorts = {}
        for label, q, dur in (("calm", qps_low, calm_s),
                              ("surge", qps_high, surge_s),
                              ("quiet", qps_low, quiet_s)):
            log(f"phase {label}: qps={q} for {dur}s (cohort={fleet.size()})")
            interval = 1.0 / q
            n = max(1, int(dur * q))
            t0p = time.monotonic()
            peak = fleet.size()
            for i in range(n):
                target = t0p + i * interval
                # Supervise while pacing: the scaler self-limits to its
                # poll interval, so calling it every beat is free.
                while True:
                    scaler.step()
                    now = time.monotonic()
                    if now >= target:
                        break
                    time.sleep(min(0.1, target - now))
                p = rng.integers(2, flags.vocab,
                                 flags.seq_len).astype(np.int32)
                fut = client.submit(p)
                fut.add_done_callback(on_done)
                pending.append(fut)
                peak = max(peak, fleet.size())
            phase_cohorts[label] = {"end": fleet.size(), "peak": peak}
        # Post-quiet grace: keep supervising until the idle shrink lands
        # and the decommissioned replica actually exits.
        deadline = time.monotonic() + 30.0
        shrunk = False
        while time.monotonic() < deadline:
            scaler.step()
            fleet.reap()
            shrunk = (any(e["action"] == "shrink" for e in scaler.events)
                      and fleet.size() <= 1)
            if shrunk:
                break
            time.sleep(0.25)
        unfinished = 0
        for fut in pending:
            try:
                fut.result(flags.deadline_s + 10.0)
            except TimeoutError:
                unfinished += 1
            except Exception:  # noqa: BLE001 — classified in on_done
                pass
        lost = outcomes["deadline"] + outcomes["error"] + unfinished
        grow_reasons = [e["reason"] for e in scaler.events
                        if e["action"] == "grow"]
        shrink_reasons = [e["reason"] for e in scaler.events
                          if e["action"] == "shrink"]
        result.update(
            requests=len(pending),
            ok=outcomes["ok"], rejects=outcomes["reject"],
            deadline_errors=outcomes["deadline"], errors=outcomes["error"],
            unfinished_futures=unfinished, lost_requests=lost,
            error_samples=error_samples,
            phase_cohorts=phase_cohorts,
            scale_events=[{k: e[k] for k in ("action", "peer", "reason")}
                          for e in scaler.events],
        )
        gates = {
            "grew_on_surge_wait": "serve_wait" in grow_reasons,
            "fleet_reached_two": phase_cohorts["surge"]["peak"] >= 2,
            "shrank_back_on_idle": shrunk
                                   and "serve_idle" in shrink_reasons,
            "zero_lost_requests": lost == 0,
        }
        result["gates"] = gates
        result["pass"] = all(gates.values())
    except Exception as e:  # noqa: BLE001 — the verdict must always be written
        log(f"FAILED: {e}")
        result["pass"] = False
        result["failure"] = str(e)
    finally:
        if client is not None:
            client.close()
        stop_pump.set()
        broker.close()
        fleet.terminate_all()
        shutil.rmtree(base_dir, ignore_errors=True)

    payload = json.dumps(result, indent=1)
    if flags.out:
        with open(flags.out, "w") as f:
            f.write(payload + "\n")
        log(f"verdict -> {flags.out}")
    print(payload)
    if result.get("pass"):
        log("PASS: fleet grew under the surge, shrank back when idle, "
            "zero lost requests")
        return 0
    log("FAIL")
    return 1


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--smoke", action="store_true",
                    help="CI profile: short window, small load")
    ap.add_argument("--window_s", type=float, default=None,
                    help="load window (default 20 smoke / 60 full)")
    ap.add_argument("--qps", type=float, default=None,
                    help="offered load (default 30 smoke / 50 full)")
    ap.add_argument("--deadline_s", type=float, default=15.0)
    ap.add_argument("--vocab", type=int, default=64)
    ap.add_argument("--seq_len", type=int, default=8)
    ap.add_argument("--d_model", type=int, default=32)
    ap.add_argument("--layers", type=int, default=1)
    ap.add_argument("--heads", type=int, default=2)
    ap.add_argument("--batch_size", type=int, default=8)
    ap.add_argument("--max_new_tokens", type=int, default=4)
    ap.add_argument("--max_queue", type=int, default=256)
    ap.add_argument("--ready_timeout", type=float, default=300.0)
    ap.add_argument("--out", default=None, help="write the JSON verdict here")
    ap.add_argument("--engine", action="store_true",
                    help="replicas serve through the continuous-batching "
                    "engine (lm_serve --engine); same gates")
    ap.add_argument("--swing", action="store_true",
                    help="run the QPS-elasticity load-swing phase instead "
                    "of the kill+swap soak: calm -> 5x surge -> quiet, "
                    "gated on autoscaler grow/shrink + zero lost requests "
                    "(--qps sets the calm rate, default 2)")
    ap.add_argument("--service_delay_ms", type=float, default=150.0,
                    help="swing only: per-iteration service delay handed to "
                    "lm_serve so one replica deterministically saturates "
                    "under the surge")
    flags = ap.parse_args(argv)
    if flags.swing:
        return run_swing(flags)
    if flags.window_s is None:
        flags.window_s = 20.0 if flags.smoke else 60.0
    if flags.qps is None:
        flags.qps = 30.0 if flags.smoke else 50.0

    import numpy as np

    from moolib_tpu import Broker, Rpc
    from moolib_tpu.serving import ModelPublisher, ServeClient, is_overload_error
    from moolib_tpu.testing.faults import FaultPlan

    # The payload a hot-swap installs must be REAL weights for the replicas'
    # model geometry — the plane will faithfully install whatever the
    # publisher announces, and a garbage pytree turns every later request
    # into a step_fn error.  Build the same model the replicas build (same
    # flags, same seed) and perturb it so the swap is observable.
    import jax
    import jax.numpy as jnp

    from moolib_tpu.examples.lm_serve import make_model

    # The harness computes on the host: an accelerator belongs to one
    # process, and that is a replica child (which gets the caller's
    # JAX_PLATFORMS through its environment), never this parent.
    jax.config.update("jax_platforms", "cpu")
    model = make_model(flags)
    rng0 = np.random.default_rng(flags.seed)
    toks = jnp.asarray(
        rng0.integers(0, flags.vocab, (1, flags.seq_len), dtype=np.int32)
    )
    base_params = model.init(jax.random.key(flags.seed), toks)
    swap_params = jax.device_get(
        jax.tree.map(lambda x: x * (1.0 + 1e-3), base_params)
    )

    plan = FaultPlan(flags.seed)
    kill_t = plan.replica_kill_time(flags.window_s)
    swap_t = round(flags.window_s * 0.8, 3)
    log(f"seed={flags.seed} window={flags.window_s}s qps={flags.qps} "
        f"kill@{kill_t}s swap@{swap_t}s")

    broker_addr = f"127.0.0.1:{free_port()}"
    broker = Broker()
    broker.set_name("broker")
    broker.listen(broker_addr)
    stop_pump = threading.Event()

    def pump():
        while not stop_pump.is_set():
            broker.update()
            stop_pump.wait(0.05)

    threading.Thread(target=pump, daemon=True).start()

    pusher_rpc = Rpc()
    pusher_rpc.set_name("pusher")
    pusher_rpc.listen("127.0.0.1:0")
    pusher_rpc.connect(broker_addr)
    pusher = ModelPublisher(pusher_rpc, name="model")

    replicas = [
        spawn_replica("rep0", free_port(), broker_addr, flags),
        spawn_replica("rep1", free_port(), broker_addr, flags),
    ]
    result = {
        "soak": "serve", "seed": flags.seed, "smoke": flags.smoke,
        "window_s": flags.window_s, "qps": flags.qps,
        "replicas": 2, "plan_actions": [],
    }
    client = None
    try:
        for (proc, lp), name in zip(replicas, ("rep0", "rep1")):
            await_line(lp, proc, "serving", flags.ready_timeout)
            log(f"{name} serving")
        client = ServeClient(broker=broker_addr, deadline_s=flags.deadline_s,
                             attempt_timeout=1.0, max_attempts=8)
        client.wait_for_replicas(2, timeout=30.0)
        log(f"discovered replicas: {client.replicas()}")

        rng = np.random.default_rng(flags.seed)
        warm = rng.integers(2, flags.vocab, flags.seq_len).astype(np.int32)
        client.call(warm)

        latencies: list = []
        outcomes = {"ok": 0, "reject": 0, "deadline": 0, "error": 0}
        error_samples: list = []
        lock = threading.Lock()
        pending = []

        def on_done(fut, t0):
            dt = time.monotonic() - t0
            exc = fut.exception()
            with lock:
                if exc is None:
                    outcomes["ok"] += 1
                    latencies.append(dt)
                elif is_overload_error(exc):
                    outcomes["reject"] += 1
                elif "deadline" in str(exc).lower():
                    outcomes["deadline"] += 1
                else:
                    outcomes["error"] += 1
                    if len(error_samples) < 5:
                        error_samples.append(str(exc)[:300])

        # One seeded schedule, three actors: paced arrivals, the SIGKILL,
        # and the publish all run off the same monotonic clock.
        interval = 1.0 / flags.qps
        n = max(1, int(flags.window_s * flags.qps))
        killed = None
        swap = {"published": False, "rejects_before": None, "version": 2}
        survivor = None
        t_start = time.monotonic()
        for i in range(n):
            target = t_start + i * interval
            now = time.monotonic()
            if now < target:
                time.sleep(target - now)
            t_rel = time.monotonic() - t_start
            if killed is None and t_rel >= kill_t:
                victim = plan.replica_kill([p for p, _lp in replicas])
                survivor = ("rep0", "rep1")[1 - victim]
                killed = {"victim": f"rep{victim}", "t": round(t_rel, 3),
                          "pid": replicas[victim][0].pid}
                log(f"SIGKILLed rep{victim} (pid {killed['pid']}) "
                    f"at +{t_rel:.1f}s; survivor={survivor}")
            if not swap["published"] and t_rel >= swap_t:
                stats = pusher_rpc.sync(survivor or "rep0", "generate_stats")
                swap["rejects_before"] = stats["admission_rejects"]
                pusher.publish(swap_params, version=swap["version"])
                swap["published"] = True
                log(f"published model version {swap['version']} at +{t_rel:.1f}s")
            p = rng.integers(2, flags.vocab, flags.seq_len).astype(np.int32)
            t0 = time.monotonic()
            fut = client.submit(p)
            fut.add_done_callback(lambda f, t0=t0: on_done(f, t0))
            pending.append(fut)
        log(f"offered {n} requests; awaiting completions")
        unfinished = 0
        for fut in pending:
            try:
                fut.result(flags.deadline_s + 10.0)
            except TimeoutError:
                unfinished += 1  # a future that never resolved = lost
            except Exception:  # noqa: BLE001 — classified in on_done
                pass

        # Survivor's post-swap accounting: the swap must have landed, with
        # its duration recorded, and caused no admission rejects.
        deadline = time.monotonic() + 20.0
        st = None
        while time.monotonic() < deadline:
            st = pusher_rpc.sync(survivor or "rep1", "generate_stats")
            if st["model_version"] == swap["version"]:
                break
            time.sleep(0.25)
        lat = sorted(latencies)
        lost = outcomes["deadline"] + outcomes["error"] + unfinished
        result.update(
            requests=n,
            ok=outcomes["ok"],
            rejects=outcomes["reject"],
            deadline_errors=outcomes["deadline"],
            errors=outcomes["error"],
            unfinished_futures=unfinished,
            lost_requests=lost,
            error_samples=error_samples,
            p50_ms=round(lat[len(lat) // 2] * 1e3, 1) if lat else None,
            p99_ms=round(lat[int(len(lat) * 0.99)] * 1e3, 1) if lat else None,
            kill=killed,
            survivor=survivor,
            swap={
                "version": swap["version"],
                "hot_swaps": st["hot_swaps"],
                "serve_swap_seconds": st["last_swap_seconds"],
                "rejects_during_swap":
                    st["admission_rejects"] - (swap["rejects_before"] or 0),
            },
            client_stats=client.stats(),
            plan_actions=[list(a) for a in plan.actions],
        )
        gates = {
            "zero_lost_requests": lost == 0,
            "all_futures_completed": unfinished == 0,
            "replica_killed_mid_stream": killed is not None,
            "hot_swap_completed": st["model_version"] == swap["version"]
                                  and st["hot_swaps"] >= 1,
            "swap_seconds_recorded": st["last_swap_seconds"] is not None,
            "no_swap_rejects":
                st["admission_rejects"] - (swap["rejects_before"] or 0) == 0,
        }
        result["gates"] = gates
        result["pass"] = all(gates.values())
    except Exception as e:  # noqa: BLE001 — the verdict must always be written
        log(f"FAILED: {e}")
        result["pass"] = False
        result["failure"] = str(e)
    finally:
        if client is not None:
            client.close()
        pusher.close()
        pusher_rpc.close()
        stop_pump.set()
        broker.close()
        for proc, lp in replicas:
            try:
                os.killpg(proc.pid, signal.SIGKILL)
            except (OSError, ProcessLookupError):
                proc.kill()
            proc.wait()
            try:
                os.unlink(lp)
            except OSError:
                pass

    payload = json.dumps(result, indent=1)
    if flags.out:
        with open(flags.out, "w") as f:
            f.write(payload + "\n")
        log(f"verdict -> {flags.out}")
    print(payload)
    if result.get("pass"):
        log("PASS: zero lost requests, failover + hot-swap held under load")
        return 0
    log("FAIL")
    return 1


if __name__ == "__main__":
    raise SystemExit(main())
