#!/usr/bin/env python
"""Seeded chaos soak: drive the real stack through its fault domains.

Exercises the supervision story end to end with a deterministic
:class:`moolib_tpu.testing.FaultPlan` (docs/RESILIENCE.md):

1. **EnvPool supervision** (in-process): SIGKILL a worker mid-step; the
   pending ``EnvStepperFuture`` must complete on the respawn and
   ``envpool_worker_restarts`` must move.
2. **2-peer elastic LM run under RPC chaos**: peer A hosts the broker,
   checkpoints, and runs with a watchdog; peer B joins; seeded frame
   drop/dup is injected into both via ``MOOLIB_FAULTS``.  Peer B is
   SIGKILLed mid-run; A must still reach its target step count.
3. **Forced kill + corrupt checkpoint + relaunch**: A is relaunched
   open-ended, SIGKILLed once fresh checkpoints land, the newest
   checkpoint is truncated, and a final relaunch must resume from the
   newest *intact* checkpoint (step-counter continuity in the logs) and
   reach its target.
4. **Distributed checkpoints under mid-write host loss**: a 2-peer
   ``--shard_grads`` cohort snapshots into one shared directory; peer B
   is SIGKILLed *mid-shard-write* (a write-delay fault widens the
   window).  No torn checkpoint may ever be eligible, the 1-host
   relaunch must resume from the newest *committed* cohort manifest
   with step continuity (an elastic M<N restore), and the measured
   per-capture ``checkpoint_stall_seconds`` must stay under 10% of the
   mean step time (async capture is non-stalling).

Exit code 0 only when every phase holds.  A wedged child is killed by its
own ``--watchdog`` (non-zero exit) or by this script's phase deadline —
either way the soak fails loudly instead of hanging CI.

Usage::

    python scripts/chaos_soak.py --smoke        # ~60 s CI profile
    python scripts/chaos_soak.py --seed 7       # longer default soak
"""

from __future__ import annotations

import argparse
import os
import re
import signal
import socket
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def log(msg: str) -> None:
    print(f"[chaos_soak +{time.monotonic() - T0:6.1f}s] {msg}", flush=True)


def free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port




def child_env(faults: str = "", extra_env=None) -> dict:
    env = dict(
        os.environ,
        PYTHONPATH=ROOT + os.pathsep + os.environ.get("PYTHONPATH", ""),
        JAX_PLATFORMS="cpu",
    )
    # Respawned/relaunched children skip recompilation through the
    # persistent compile cache every entry point shares
    # (utils/compile_cache.py) — the recovery bound below budgets model
    # re-sync, not XLA compile time.
    if faults:
        env["MOOLIB_FAULTS"] = faults
    else:
        env.pop("MOOLIB_FAULTS", None)
    if extra_env:
        env.update(extra_env)
    return env


def spawn_lm(args, log_path, faults="", extra_env=None):
    with open(log_path, "w") as f:
        return subprocess.Popen(
            [sys.executable, "-m", "moolib_tpu.examples.lm"] + args,
            stdout=f, stderr=subprocess.STDOUT,
            env=child_env(faults, extra_env), cwd=ROOT,
            start_new_session=True,
        )


def kill_tree(proc) -> None:
    if proc.poll() is None:
        try:
            os.killpg(proc.pid, signal.SIGKILL)
        except OSError:
            pass
        proc.wait()


def logged_steps(log_path: str):
    """All ``step=K`` values printed so far, in order."""
    try:
        with open(log_path) as f:
            return [int(m.group(1)) for m in re.finditer(r"^step=(\d+)", f.read(), re.M)]
    except OSError:
        return []


def resumed_step(log_path: str):
    try:
        with open(log_path) as f:
            m = re.search(r"resumed from checkpoint step (\d+)", f.read())
        return int(m.group(1)) if m else None
    except OSError:
        return None


def wait_for(pred, deadline: float, what: str, procs=()):
    while time.monotonic() < deadline:
        v = pred()
        if v:
            return v
        for p in procs:
            if p.poll() not in (None, 0):
                raise SystemExit(f"FAIL: child died (rc={p.returncode}) while {what}")
        time.sleep(0.25)
    raise SystemExit(f"FAIL: deadline expired while {what}")


def dump_tail(path: str, n: int = 2000) -> None:
    try:
        with open(path) as f:
            sys.stderr.write(f"--- tail of {path} ---\n{f.read()[-n:]}\n")
    except OSError:
        pass


# --------------------------------------------------------------------- phases
def phase_envpool(plan) -> None:
    """Kill a worker mid-step; the same future must complete on respawn."""
    import numpy as np

    from moolib_tpu import EnvPool, telemetry

    log("phase 1: envpool worker kill -> respawn")
    pool = EnvPool(_SlowEnv, num_processes=2, batch_size=4, num_batches=1)
    try:
        fut = pool.step(0, np.zeros(4, np.int64))
        time.sleep(0.1)  # ensure the step is in flight
        idx = plan.kill_envpool_worker(pool)
        out = fut.result()  # must complete without raising
        assert (out["state"][:, 0] == 7.0).all(), out["state"][:, 0]
        out = pool.step(0, np.zeros(4, np.int64)).result()  # next step fine too
        assert (out["state"][:, 0] == 7.0).all()
        restarts = telemetry.get_registry().counter_values().get(
            "envpool_worker_restarts", 0.0
        )
        assert restarts >= 1.0, f"no restart recorded ({restarts})"
        log(f"phase 1 OK (killed worker {idx}; restarts={restarts:.0f})")
    finally:
        pool.close()


class _SlowEnv:
    """0.4 s steps: wide window to land the kill mid-step."""

    def reset(self):
        import numpy as np

        return np.zeros(2, np.float32)

    def step(self, action):
        import numpy as np

        time.sleep(0.4)
        return np.full(2, 7.0, np.float32), 1.0, False, {}


def lm_args(flags, steps, ckpt_dir, port=None, connect=None, watchdog=120.0,
            name=None):
    args = [
        "--seq_len", "16", "--batch_size", "2", "--d_model", "16",
        "--layers", "1", "--heads", "1", "--vocab", "16",
        "--log_interval", "10", "--steps", str(steps),
        "--checkpoint_interval", str(flags.checkpoint_interval),
        "--watchdog", str(watchdog),
    ]
    if ckpt_dir:
        args += ["--checkpoint_dir", ckpt_dir]
    if port is not None:
        args += ["--address", f"127.0.0.1:{port}"]
    if connect is not None:
        args += ["--connect", f"127.0.0.1:{connect}"]
    if name:
        args += ["--local_name", name]
    return args


def recovered_line(log_path: str):
    """The one-shot per-phase recovery breakdown a rejoining peer prints
    once it is contributing again (``recovered: {...}``), or None."""
    try:
        with open(log_path) as f:
            m = re.search(r"^recovered: (\{.*\})", f.read(), re.M)
        return m.group(1) if m else None
    except OSError:
        return None


def phase_cohort(flags, plan, workdir: str) -> int:
    """2-peer elastic lm under RPC chaos; peer B dies mid-run and is
    RESPAWNED: the rejoiner must be contributing again (its ``recovered:``
    per-phase line) within ``--recovery_bound_s`` — the warm-rejoin SLO —
    and A must still reach its target step count.  Returns that target."""
    log("phase 2: 2-peer elastic lm; kill + respawn peer B mid-run")
    port = free_port()
    ckpt_dir = os.path.join(workdir, "ckpt")
    faults = f"seed={plan.seed},rpc_drop={flags.rpc_drop},rpc_dup={flags.rpc_dup}"
    a_log = os.path.join(workdir, "peerA.log")
    b_log = os.path.join(workdir, "peerB.log")
    b2_log = os.path.join(workdir, "peerB_respawn.log")
    # A's target is stretched: it must outlive B's kill AND the respawned
    # B's whole recovery (jax start + rejoin + model sync + first step) so
    # the broker it hosts stays up while the recovery bound is measured.
    target = flags.steps * 3
    a = spawn_lm(lm_args(flags, target, ckpt_dir, port=port, name="peerA"),
                 a_log, faults)
    b = spawn_lm(lm_args(flags, target, None, connect=port, name="peerB"),
                 b_log, faults)
    b2 = None
    deadline = time.monotonic() + flags.phase_deadline
    try:
        # Let the cohort make some progress, then kill B.
        wait_for(lambda: logged_steps(a_log) and logged_steps(a_log)[-1] >= flags.steps // 3,
                 deadline, "waiting for early progress", procs=(a,))
        if b.poll() is None:
            plan.kill_process(b)
            log(f"killed peer B (pid {b.pid}) at step "
                f"~{logged_steps(a_log)[-1]} of {target}")
        # Respawn B; its rejoin is SLO-gated: kill-to-contributing must fit
        # --recovery_bound_s (compile cache + chunked model sync do the
        # heavy lifting; docs/RESILIENCE.md "Recovery budget").
        t_respawn = time.monotonic()
        b2 = spawn_lm(lm_args(flags, target, None, connect=port, name="peerB2"),
                      b2_log, faults)
        rec_deadline = min(deadline, t_respawn + flags.recovery_bound_s)
        rec = wait_for(lambda: recovered_line(b2_log), rec_deadline,
                       f"waiting for respawned peer B to recover "
                       f"(bound {flags.recovery_bound_s:.0f}s)", procs=(a, b2))
        took = time.monotonic() - t_respawn
        log(f"respawned peer B contributing after {took:.1f}s "
            f"(bound {flags.recovery_bound_s:.0f}s): {rec}")
        rc = a.wait(timeout=max(5.0, deadline - time.monotonic()))
        if rc != 0:
            dump_tail(a_log)
            raise SystemExit(f"FAIL: peer A exited rc={rc}")
        steps = logged_steps(a_log)
        assert steps and steps[-1] >= target - 10, steps[-10:]
        log(f"phase 2 OK (peer A reached step {steps[-1]}/{target}; "
            f"B recovered in {took:.1f}s)")
        return target
    except subprocess.TimeoutExpired:
        dump_tail(a_log)
        raise SystemExit("FAIL: peer A never finished (watchdog should have fired)")
    finally:
        kill_tree(a)
        kill_tree(b)
        if b2 is not None:
            kill_tree(b2)


def phase_kill_resume(flags, plan, workdir: str, reached: int) -> None:
    """SIGKILL the leader once fresh checkpoints land, truncate the newest,
    and assert the relaunch resumes from the newest INTACT one."""
    from moolib_tpu.checkpoint import Checkpointer

    log("phase 3: forced kill, checkpoint truncation, resume")
    port = free_port()
    ckpt_dir = os.path.join(workdir, "ckpt")
    a_log = os.path.join(workdir, "peerA_openended.log")
    # Open-ended relaunch (huge target): resumes from phase 2's final
    # checkpoint, keeps training and checkpointing until we kill it.
    a = spawn_lm(lm_args(flags, reached + 1_000_000, ckpt_dir, port=port,
                         name="peerA"), a_log)
    deadline = time.monotonic() + flags.phase_deadline
    ck = Checkpointer(ckpt_dir)
    try:
        wait_for(lambda: (ck.latest_step() or 0) > reached, deadline,
                 "waiting for a post-resume checkpoint", procs=(a,))
        plan.kill_process(a)  # forced kill: no finally-block save
        a.wait()
        log(f"killed open-ended peer A (pid {a.pid}) at checkpoint "
            f"step {ck.latest_step()}")
    finally:
        kill_tree(a)
    assert resumed_step(a_log), "open-ended run did not resume from checkpoint"

    victim = plan.truncate_checkpoint(ckpt_dir)
    log(f"truncated newest checkpoint payload: {victim}")
    expect_resume = ck.latest_intact_step()
    assert expect_resume is not None, "no intact checkpoint left"

    final_log = os.path.join(workdir, "peerA_final.log")
    target = expect_resume + 30
    a = spawn_lm(lm_args(flags, target, ckpt_dir, port=free_port(),
                         name="peerA"), final_log)
    try:
        rc = a.wait(timeout=flags.phase_deadline)
    except subprocess.TimeoutExpired:
        dump_tail(final_log)
        raise SystemExit("FAIL: resumed run never finished")
    finally:
        kill_tree(a)
    if rc != 0:
        dump_tail(final_log)
        raise SystemExit(f"FAIL: resumed run exited rc={rc}")
    got = resumed_step(final_log)
    steps = logged_steps(final_log)
    assert got == expect_resume, (
        f"resumed from {got}, expected newest intact {expect_resume}"
    )
    # Step-counter continuity: the first logged step continues past the
    # resume point (no restart from zero), and the target was reached.
    assert steps and steps[0] >= got and steps[-1] >= target - 10, steps
    log(f"phase 3 OK (resumed from intact step {got}, reached {steps[-1]})")


def _ckpt_async_stats(log_path: str):
    """The exit-line capture stats a distributed-checkpoint run prints
    (``ckpt_async: captures=.. commits=.. stall_s=.. write_s=.. train_s=..
    steps=..``), as a dict, or None."""
    try:
        with open(log_path) as f:
            m = re.search(
                r"^ckpt_async: captures=(\d+) commits=(\d+) stall_s=([\d.]+) "
                r"write_s=([\d.]+) train_s=([\d.]+) steps=(\d+)",
                f.read(), re.M,
            )
    except OSError:
        return None
    if not m:
        return None
    keys = ("captures", "commits", "stall_s", "write_s", "train_s", "steps")
    return {k: float(m.group(i + 1)) for i, k in enumerate(keys)}


def phase_ckpt_distributed(flags, plan, workdir: str) -> None:
    """2-peer sharded cohort writing DISTRIBUTED checkpoints into one shared
    directory; peer B is SIGKILLed mid-shard-write (write-delay fault widens
    the window).  The invariants (ISSUE 17):

    - the torn step dir is never eligible: every ``step_<N>/`` the relaunch
      can select holds a committed ``cohort_manifest.json``;
    - the relaunched (now 1-host) cohort resumes from the newest COMMITTED
      snapshot with step-counter continuity — an elastic M<N restore;
    - async capture is non-stalling: the measured ``checkpoint_stall_seconds``
      per capture stays under 10% of the mean step time."""
    from moolib_tpu.checkpoint import DistributedCheckpointer

    log("phase 4: distributed checkpoints; kill peer B mid-shard-write")
    port = free_port()
    dckpt_dir = os.path.join(workdir, "dckpt")
    a_log = os.path.join(workdir, "dpeerA.log")
    b_log = os.path.join(workdir, "dpeerB.log")
    target = flags.steps * 2
    shard_args = ["--shard_grads"]
    a = spawn_lm(shard_args + lm_args(flags, target, dckpt_dir, port=port,
                                      name="dpeerA"), a_log)
    # The victim's shard writes dawdle between staging and rename
    # (MOOLIB_CKPT_WRITE_DELAY) so the mid-write kill window is wide enough
    # to hit deterministically.
    b = spawn_lm(shard_args + lm_args(flags, target, dckpt_dir, connect=port,
                                      name="dpeerB"),
                 b_log, extra_env={"MOOLIB_CKPT_WRITE_DELAY": "0.4"})
    ck = DistributedCheckpointer(dckpt_dir)
    deadline = time.monotonic() + flags.phase_deadline
    try:
        # First committed cohort snapshot, then catch the next shard write
        # in flight and kill B under it.
        wait_for(lambda: ck.latest_committed_step() is not None, deadline,
                 "waiting for the first committed cohort checkpoint",
                 procs=(a, b))
        victim_tmp = plan.kill_mid_shard_write(
            b, dckpt_dir, timeout=max(5.0, deadline - time.monotonic())
        )
        if victim_tmp is None:
            raise SystemExit("FAIL: no shard write observed to kill under")
        log(f"killed peer B (pid {b.pid}) mid-shard-write: {victim_tmp}")
        # A absorbs the loss (cohort shrinks to 1, checkpointing continues)
        # and must still reach its target.
        rc = a.wait(timeout=max(5.0, deadline - time.monotonic()))
        if rc != 0:
            dump_tail(a_log)
            raise SystemExit(f"FAIL: peer A exited rc={rc}")
    except subprocess.TimeoutExpired:
        dump_tail(a_log)
        raise SystemExit("FAIL: peer A never finished after mid-write kill")
    finally:
        kill_tree(a)
        kill_tree(b)

    committed = ck.committed_steps()
    assert committed, "no committed distributed checkpoint survived"
    expect_resume = committed[-1]
    # Zero eligible torn checkpoints: everything restore can select is
    # committed, and every torn/uncommitted husk is verifiably NOT.
    torn = [
        name for name in os.listdir(dckpt_dir)
        if name.startswith("step_") and not name.endswith(".tmp")
        and not os.path.exists(
            os.path.join(dckpt_dir, name, "cohort_manifest.json"))
    ]
    for name in torn:
        assert int(name[len("step_"):]) not in committed
    log(f"committed steps {committed}; torn/uncommitted dirs ignored: {torn}")

    # Non-stalling capture, measured: per-capture stall < 10% of step time.
    s = _ckpt_async_stats(a_log)
    assert s and s["captures"] >= 1, f"no capture stats in peer A log: {s}"
    step_time = s["train_s"] / max(s["steps"], 1.0)
    stall = s["stall_s"] / s["captures"]
    assert stall < 0.10 * step_time, (
        f"async capture stalls the step: {stall:.4f}s/capture vs "
        f"10% of {step_time:.4f}s step"
    )
    log(f"capture stall {stall * 1e3:.2f}ms vs step {step_time * 1e3:.1f}ms "
        f"({s['captures']:.0f} captures, {s['commits']:.0f} commits)")

    # Elastic M<N restore: the 2-host checkpoint restores onto a 1-host
    # cohort from the newest COMMITTED step, with step continuity.
    final_log = os.path.join(workdir, "dpeerA_final.log")
    final_target = expect_resume + 30
    a = spawn_lm(shard_args + lm_args(flags, final_target, dckpt_dir,
                                      port=free_port(), name="dpeerA"),
                 final_log)
    try:
        rc = a.wait(timeout=flags.phase_deadline)
    except subprocess.TimeoutExpired:
        dump_tail(final_log)
        raise SystemExit("FAIL: distributed-resume run never finished")
    finally:
        kill_tree(a)
    if rc != 0:
        dump_tail(final_log)
        raise SystemExit(f"FAIL: distributed-resume run exited rc={rc}")
    got = resumed_step(final_log)
    steps = logged_steps(final_log)
    assert got == expect_resume, (
        f"resumed from {got}, expected newest committed {expect_resume}"
    )
    assert steps and steps[0] >= got and steps[-1] >= final_target - 10, steps
    log(f"phase 4 OK (resumed 1-host from committed step {got} of a 2-host "
        f"cohort, reached {steps[-1]})")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description="seeded chaos soak")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--smoke", action="store_true",
                    help="~60 s CI profile (small step targets, tight deadlines)")
    ap.add_argument("--steps", type=int, default=None,
                    help="phase-2 target optimizer steps (default 60 smoke / 300 full)")
    ap.add_argument("--checkpoint_interval", type=float, default=1.0)
    ap.add_argument("--rpc_drop", type=float, default=0.02)
    ap.add_argument("--rpc_dup", type=float, default=0.01)
    ap.add_argument("--recovery_bound_s", type=float, default=None,
                    help="respawned-peer rejoin SLO: kill-to-contributing "
                    "seconds (default 60 smoke / 90 full; "
                    "docs/RESILIENCE.md recovery budget)")
    ap.add_argument("--phase_deadline", type=float, default=None,
                    help="per-phase wall deadline, seconds")
    ap.add_argument("--workdir", default=None)
    flags = ap.parse_args(argv)
    if flags.steps is None:
        flags.steps = 60 if flags.smoke else 300
    if flags.phase_deadline is None:
        flags.phase_deadline = 150.0 if flags.smoke else 600.0
    if flags.recovery_bound_s is None:
        flags.recovery_bound_s = 60.0 if flags.smoke else 90.0

    import tempfile

    from moolib_tpu.testing import FaultPlan

    workdir = flags.workdir or tempfile.mkdtemp(prefix="chaos_soak_")
    plan = FaultPlan(flags.seed)
    log(f"seed={flags.seed} workdir={workdir} steps={flags.steps} "
        f"recovery_bound={flags.recovery_bound_s:.0f}s")
    phase_envpool(plan)
    reached = phase_cohort(flags, plan, workdir)
    phase_kill_resume(flags, plan, workdir, reached)
    phase_ckpt_distributed(flags, plan, workdir)
    log(f"CHAOS SOAK OK (fault log: {plan.actions})")
    return 0


T0 = time.monotonic()

if __name__ == "__main__":
    sys.exit(main())
