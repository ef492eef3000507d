#!/usr/bin/env python
"""Preemption-hardened autoscaling soak: the elastic fleet under Poisson kills.

Drives the full supervision stack (docs/RESILIENCE.md "Autoscaling") against
the real elastic LM trainer:

1. **Formation**: this script hosts the Broker and runs an
   :class:`moolib_tpu.autoscaler.Autoscaler` over a
   :class:`~moolib_tpu.autoscaler.SubprocessFleet` of
   ``moolib_tpu.examples.lm`` workers.  The ``below_min`` rule grows the
   cohort from zero to the target size; every worker must print its
   ``recovered:`` line (contributing, model-synced).
2. **Poisson preemption**: a seeded
   :meth:`~moolib_tpu.testing.FaultPlan.poisson_kills` schedule SIGKILLs a
   random live worker at each arrival (no drain, no leave — a real
   preemption).  The autoscaler must respawn and the replacement must be
   contributing again within ``--recovery_bound_s``; each miss counts as an
   ``unrecovered_kill`` and the soak FAILS on any.
3. **Graceful decommission**: one explicit ``fleet.shrink()`` drops the
   decommission flag; the victim drains and announces ``__broker_leave``.
   The broker's membership must exclude the victim within 1 s of the
   victim's exit — sub-second because of the explicit leave, where
   ping-eviction alone would burn the full ``--evict_s`` of silence first.
   The autoscaler then grows the cohort back to target.
4. **Invariants**, checked over every worker log at the end: zero
   ``vbatch_violation`` lines (the virtual batch stayed semantically stable
   across every resize) and the final cohort back at the target size.

Exit 0 only when all four hold; the JSON verdict goes to ``--out`` or stdout.

Usage::

    python scripts/autoscale_soak.py --smoke                 # ~3 min CI profile
    python scripts/autoscale_soak.py --seed 7 --out /tmp/autoscale_soak.json
"""

from __future__ import annotations

import argparse
import json
import os
import socket
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

T0 = time.monotonic()


def log(msg: str) -> None:
    print(f"[autoscale_soak +{time.monotonic() - T0:6.1f}s] {msg}", flush=True)


def free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def worker_log_has(peer_dir: str, needle: str) -> bool:
    try:
        with open(os.path.join(peer_dir, "worker.log")) as f:
            return needle in f.read()
    except OSError:
        return False


def count_in_logs(fleet_dir: str, needle: str) -> int:
    n = 0
    for name in sorted(os.listdir(fleet_dir)) if os.path.isdir(fleet_dir) else []:
        try:
            with open(os.path.join(fleet_dir, name, "worker.log")) as f:
                n += f.read().count(needle)
        except OSError:
            continue
    return n


def dump_worker_tails(fleet_dir: str, n: int = 1500) -> None:
    for name in sorted(os.listdir(fleet_dir)) if os.path.isdir(fleet_dir) else []:
        path = os.path.join(fleet_dir, name, "worker.log")
        try:
            with open(path) as f:
                sys.stderr.write(f"--- tail of {path} ---\n{f.read()[-n:]}\n")
        except OSError:
            continue


class Soak:
    def __init__(self, flags):
        from moolib_tpu import Broker, autoscaler
        from moolib_tpu.testing import FaultPlan

        self.flags = flags
        self.result = {
            "metric": "autoscale_soak",
            "ok": False,
            "failure": None,
            "seed": flags.seed,
            "target_peers": flags.target_peers,
            "evict_s": flags.evict_s,
            "recovery_bound_s": flags.recovery_bound_s,
            "kills": 0,
            "kill_times_s": [],
            "recovery_s": [],
            "unrecovered_kills": 0,
            "graceful_leave_s": None,
            "decommission_drain_s": None,
            "vbatch_violations": None,
            "scale_events": [],
            "final_cohort": None,
        }
        self.fleet_dir = os.path.join(flags.workdir, "fleet")
        port = free_port()
        addr = f"127.0.0.1:{port}"
        self.broker = Broker()
        self.broker.set_name("broker")
        # Modest eviction window: preemption recovery pays it, and the
        # graceful-leave check below proves decommissions DON'T.
        self.broker.set_timeout(flags.evict_s)
        self.broker.listen(addr)
        worker_args = [
            "--vocab", "16", "--seq_len", "16", "--batch_size", "2",
            "--d_model", "16", "--layers", "1", "--heads", "1",
            "--steps", "1000000",  # run until decommissioned/terminated
            "--virtual_batch_size", str(flags.virtual_batch_size),
            "--log_interval", "5", "--watchdog", "180",
        ]
        self.fleet = autoscaler.SubprocessFleet(
            autoscaler.example_spawn(
                addr, self.fleet_dir, "moolib_tpu.examples.lm", worker_args
            ),
            self.fleet_dir,
        )
        # min == target: every preemption/decommission makes the cohort
        # below_min, which is exactly what pulls it back to size.
        self.policy = autoscaler.AutoscalePolicy(
            flags.target_peers, flags.target_peers + 1,
            cooldown_s=flags.cooldown_s,
        )
        self.scaler = autoscaler.Autoscaler(
            self.policy, self.fleet, poll_interval=flags.poll_s
        )
        self.plan = FaultPlan(flags.seed)

    # ------------------------------------------------------------- plumbing
    def members(self):
        g = self.broker._groups.get("lm")
        return list(g.active_members) if g is not None else []

    def tick(self, seconds: float = 0.05) -> None:
        self.broker.update()
        self.scaler.step()
        time.sleep(seconds)

    def wait(self, pred, bound_s: float, what: str):
        deadline = time.monotonic() + bound_s
        while time.monotonic() < deadline:
            v = pred()
            if v:
                return v
            self.tick()
        raise SystemExit(f"FAIL: deadline ({bound_s:.0f}s) expired while {what}")

    def peer_dirs(self):
        return {name: os.path.join(self.fleet_dir, name)
                for name in self.fleet.peers()}

    def recovered_peers(self):
        return {name for name, d in self.peer_dirs().items()
                if worker_log_has(d, "recovered:")}

    # --------------------------------------------------------------- phases
    def form_cohort(self) -> None:
        flags = self.flags
        log(f"phase 1: forming cohort of {flags.target_peers} "
            f"(below_min grows from zero)")
        self.wait(
            lambda: len(self.members()) >= flags.target_peers
            and len(self.recovered_peers()) >= flags.target_peers,
            flags.phase_deadline, "forming the initial cohort",
        )
        log(f"phase 1 OK: members={self.members()}")

    def poisson_phase(self) -> None:
        flags = self.flags
        schedule = self.plan.poisson_kills(flags.kill_rate, flags.kill_window_s)
        schedule = schedule[: flags.max_kills] or [flags.kill_window_s / 2]
        log(f"phase 2: Poisson preemptions at {schedule} "
            f"(rate={flags.kill_rate}/s over {flags.kill_window_s:.0f}s)")
        t_phase = time.monotonic()
        for t_kill in schedule:
            while time.monotonic() - t_phase < t_kill:
                self.tick()
            # A kill while the previous recovery is still in flight would
            # make per-kill recovery accounting ambiguous; wait out the
            # current rejoin first (the Poisson time is a lower bound).
            self.wait(
                lambda: len(self.members()) >= flags.target_peers,
                flags.recovery_bound_s + flags.phase_deadline,
                "waiting for cohort before next kill",
            )
            before = set(self.fleet.peers())
            victim = self.pick_victim()
            assert self.fleet.kill(victim), f"kill({victim}) found no live peer"
            t0 = time.monotonic()
            self.result["kills"] += 1
            self.result["kill_times_s"].append(round(time.monotonic() - T0, 1))
            log(f"SIGKILLed {victim} (preemption); waiting for replacement")

            def replacement_contributing():
                fresh = set(self.fleet.peers()) - before
                return any(
                    worker_log_has(os.path.join(self.fleet_dir, n), "recovered:")
                    for n in fresh
                ) and len(self.members()) >= flags.target_peers

            try:
                self.wait(replacement_contributing, flags.recovery_bound_s,
                          f"recovering from the {victim} preemption")
            except SystemExit:
                self.result["unrecovered_kills"] += 1
                log(f"UNRECOVERED kill of {victim} "
                    f"(bound {flags.recovery_bound_s:.0f}s)")
                continue
            took = time.monotonic() - t0
            self.result["recovery_s"].append(round(took, 1))
            log(f"recovered in {took:.1f}s (evict {flags.evict_s:.0f}s of that)")
        if self.result["unrecovered_kills"]:
            raise SystemExit(
                f"FAIL: {self.result['unrecovered_kills']} unrecovered kills"
            )
        log(f"phase 2 OK: {self.result['kills']} kills, "
            f"recoveries {self.result['recovery_s']}")

    def pick_victim(self) -> str:
        live = [n for n in self.fleet.peers()
                if n in self.members()]
        assert live, "no live member to preempt"
        return self.plan.rng("victim").choice(sorted(live))

    def decommission_phase(self) -> None:
        flags = self.flags
        log("phase 3: graceful decommission (drain + __broker_leave)")
        t_flag = time.monotonic()
        victim = self.fleet.shrink()
        assert victim is not None, "nothing to decommission"
        proc = self.fleet._peers[victim]["proc"]
        t_exit = t_gone = None
        deadline = time.monotonic() + flags.phase_deadline
        while time.monotonic() < deadline and (t_exit is None or t_gone is None):
            if t_exit is None and proc.poll() is not None:
                t_exit = time.monotonic()
            if t_gone is None and victim not in self.members():
                t_gone = time.monotonic()
            self.broker.update()  # membership only; no autoscale races here
            time.sleep(0.005)
        if t_exit is None or t_gone is None:
            raise SystemExit(f"FAIL: decommission of {victim} never completed "
                             f"(exit={t_exit}, membership={t_gone})")
        # The leave RPC lands BEFORE the worker exits, so membership drops
        # no later than ~the exit.  Eviction alone would need evict_s more.
        leave_lag = max(0.0, t_gone - t_exit)
        self.result["graceful_leave_s"] = round(leave_lag, 3)
        self.result["decommission_drain_s"] = round(t_gone - t_flag, 1)
        log(f"decommissioned {victim}: drain+leave {t_gone - t_flag:.1f}s, "
            f"membership lag after exit {leave_lag:.3f}s "
            f"(eviction would be {flags.evict_s:.0f}s)")
        if leave_lag >= 1.0:
            raise SystemExit(
                f"FAIL: graceful leave took {leave_lag:.2f}s — that is the "
                f"ping-eviction path, not __broker_leave"
            )
        # below_min pulls the cohort back to target.
        self.wait(lambda: len(self.members()) >= flags.target_peers,
                  flags.phase_deadline, "regrowing after the decommission")
        log(f"phase 3 OK: cohort back at {len(self.members())}")

    def finish(self) -> None:
        self.result["vbatch_violations"] = count_in_logs(
            self.fleet_dir, "vbatch_violation"
        )
        self.result["final_cohort"] = len(self.members())
        self.result["scale_events"] = [
            {k: (round(v, 1) if isinstance(v, float) else v)
             for k, v in e.items()}
            for e in self.scaler.events
        ]
        if self.result["vbatch_violations"]:
            raise SystemExit(
                f"FAIL: {self.result['vbatch_violations']} vbatch violations "
                f"— the virtual batch did not survive a resize"
            )
        self.result["ok"] = True

    def close(self) -> None:
        self.fleet.terminate_all()
        self.broker.close()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description="autoscaling soak under Poisson preemption")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--smoke", action="store_true",
                    help="~3 min CI profile (1 kill, small windows)")
    ap.add_argument("--target_peers", type=int, default=2)
    ap.add_argument("--virtual_batch_size", type=int, default=8)
    ap.add_argument("--evict_s", type=float, default=10.0,
                    help="broker ping-eviction timeout (preemptions pay it; "
                    "graceful decommissions must not)")
    ap.add_argument("--recovery_bound_s", type=float, default=None,
                    help="kill-to-contributing SLO for the respawned peer "
                    "(default 90 smoke / 120 full)")
    ap.add_argument("--kill_rate", type=float, default=None,
                    help="Poisson preemption rate, kills/s (default ~1 kill "
                    "per window smoke, 3 per window full)")
    ap.add_argument("--kill_window_s", type=float, default=None)
    ap.add_argument("--max_kills", type=int, default=None)
    ap.add_argument("--cooldown_s", type=float, default=2.0)
    ap.add_argument("--poll_s", type=float, default=0.5)
    ap.add_argument("--phase_deadline", type=float, default=None)
    ap.add_argument("--workdir", default=None)
    ap.add_argument("--out", default=None, help="write the JSON verdict here")
    flags = ap.parse_args(argv)
    if flags.recovery_bound_s is None:
        flags.recovery_bound_s = 90.0 if flags.smoke else 120.0
    if flags.kill_window_s is None:
        flags.kill_window_s = 20.0 if flags.smoke else 120.0
    if flags.kill_rate is None:
        flags.kill_rate = (1.0 if flags.smoke else 3.0) / flags.kill_window_s
    if flags.max_kills is None:
        flags.max_kills = 1 if flags.smoke else 4
    if flags.phase_deadline is None:
        flags.phase_deadline = 180.0 if flags.smoke else 420.0

    import tempfile

    flags.workdir = flags.workdir or tempfile.mkdtemp(prefix="autoscale_soak_")
    # Respawned workers skip XLA compilation through the persistent compile
    # cache every entry point shares (utils/compile_cache.py), so the
    # recovery bound budgets eviction + rejoin + model sync, not compiles.
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    log(f"seed={flags.seed} target={flags.target_peers} workdir={flags.workdir}")
    soak = Soak(flags)
    try:
        soak.form_cohort()
        soak.poisson_phase()
        soak.decommission_phase()
        soak.finish()
    except (SystemExit, AssertionError) as e:
        soak.result["failure"] = str(e)
        dump_worker_tails(soak.fleet_dir)
        raise
    finally:
        soak.close()
        payload = json.dumps(soak.result, indent=1)
        if flags.out:
            with open(flags.out, "w") as f:
                f.write(payload + "\n")
        print(payload, flush=True)
    log("AUTOSCALE SOAK OK")
    return 0


if __name__ == "__main__":
    sys.exit(main())
