"""Collect the CPU-side microbenchmarks into one committed artifact.

VERDICT round-1 ask #9: commit RPC/codec/allreduce numbers each round so perf
regressions stay visible between rounds even when the TPU is unavailable.
Writes ``BENCH_LOCAL.json`` at the repo root:

    python benchmarks/run_local.py

Caveat recorded in the artifact: this box has one CPU core, so call-rate
numbers are noisy (thread-handoff order inverts under load); bandwidth
numbers are the trustworthy ones.
"""

from __future__ import annotations

import json
import os
import platform
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(cmd, timeout=600, extra_env=None):
    t0 = time.time()
    # Children import moolib_tpu by path: make the repo root importable and
    # pin the CPU backend (this harness collects the CPU plumbing rows).
    env = dict(
        os.environ,
        PYTHONPATH=ROOT + os.pathsep + os.environ.get("PYTHONPATH", ""),
        JAX_PLATFORMS="cpu",
        **(extra_env or {}),
    )
    # Capture via temp FILES, not pipes: jax's plugin discovery can fork a
    # daemon that inherits the pipe fds, and communicate() then blocks on
    # pipe EOF long after the benchmark itself exited.
    import tempfile

    with tempfile.TemporaryFile("w+") as out_f, tempfile.TemporaryFile("w+") as err_f:
        try:
            proc = subprocess.run(
                cmd, cwd=ROOT, stdout=out_f, stderr=err_f, text=True,
                timeout=timeout, env=env,
            )
            rc = proc.returncode
        except subprocess.TimeoutExpired:
            return {"cmd": " ".join(cmd[1:]), "rc": -1, "error": f"timeout {timeout}s"}
        out_f.seek(0)
        err_f.seek(0)
        return {
            "cmd": " ".join(cmd[1:]),
            "rc": rc,
            "seconds": round(time.time() - t0, 1),
            "stdout": out_f.read().strip().splitlines(),
            "stderr": err_f.read().strip().splitlines()[-5:] if rc else [],
        }


def _run_multiproc_allreduce(py, world=3, timeout=420):
    """The reference's env-var multi-node pattern
    (``test/test_multinode_allreduce.cc:155-181``) on loopback: one OS
    process per rank, rank 0 hosts the broker and its table is the record.
    Proves the WORLD_SIZE/RANK/BROKER_ADDR mode works end to end and that
    the cross-process wire-load numbers match the in-process invariant test
    (ring busiest peer ~2(n-1)/n payloads vs the tree's ~2)."""
    import socket

    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    env = dict(
        os.environ,
        PYTHONPATH=ROOT + os.pathsep + os.environ.get("PYTHONPATH", ""),
        JAX_PLATFORMS="cpu",
        WORLD_SIZE=str(world),
        BROKER_ADDR=f"127.0.0.1:{port}",
    )
    cmd = [py, "benchmarks/allreduce_bench.py", "rpc", "--iters", "3",
           "--sizes", "100000", "1000000", "2621440"]
    cmd_note = " ".join(cmd[1:]) + f"  (WORLD_SIZE={world}, one process per rank)"
    t0 = time.time()
    import tempfile

    files = [tempfile.TemporaryFile("w+") for _ in range(world)]
    procs = [
        subprocess.Popen(cmd, cwd=ROOT, stdout=files[r], stderr=subprocess.STDOUT,
                         text=True, env=dict(env, RANK=str(r)))
        for r in range(world)
    ]
    def rank_tails():
        tails = []
        for r, f in enumerate(files):
            f.seek(0)
            tails += [f"rank{r}: {line}" for line in f.read().strip().splitlines()[-5:]]
        return tails

    deadline = t0 + timeout  # ONE shared budget, not per-rank
    rcs = []
    try:
        for p in procs:
            rcs.append(p.wait(timeout=max(0.0, deadline - time.time())))
    except subprocess.TimeoutExpired:
        for p in procs:
            if p.poll() is None:
                p.kill()
        # The most expensive failure must stay debuggable: keep the tails.
        return {"cmd": cmd_note, "rc": -1,
                "seconds": round(time.time() - t0, 1),
                "error": f"timeout {timeout}s", "stderr": rank_tails()}
    files[0].seek(0)
    out = {
        "cmd": cmd_note,
        # Signal deaths are NEGATIVE returncodes; max() would mask them.
        "rc": next((r for r in rcs if r != 0), 0),
        "seconds": round(time.time() - t0, 1),
        "stdout": files[0].read().strip().splitlines(),
    }
    if out["rc"] != 0:
        # The failure cause usually lives in a non-zero rank's output.
        out["stderr"] = rank_tails()
    return out


def main():
    env_note = {
        "host": platform.node(),
        "cpus": os.cpu_count(),
        "when": time.strftime("%Y-%m-%d %H:%M:%S"),
        "caveat": "single-core box: rates are noisy, bandwidths are meaningful",
    }
    py = sys.executable
    # 8 virtual host devices: a 1-device "psum" is a memcpy, not a
    # collective — the 8-way mesh row at least pays cross-device traffic.
    ici_env = {
        "XLA_FLAGS": (
            os.environ.get("XLA_FLAGS", "")
            + " --xla_force_host_platform_device_count=8"
        ).strip()
    }
    ici = _run([py, "benchmarks/allreduce_bench.py", "ici"], timeout=240, extra_env=ici_env)
    results = {
        "env": env_note,
        "rpc": _run([py, "benchmarks/rpc_bench.py", "--backend", "both"]),
        "allreduce_rpc": _run([py, "benchmarks/allreduce_bench.py", "rpc"]),
        "allreduce_ici": ici,
        "envpool": _run([py, "benchmarks/envpool_bench.py"]),
        # Atari geometry (84x84x4 x 128 x 2 buffers): the reference flagship
        # actor shape — shm->host MB/s is the row that matters.
        "envpool_atari": _run(
            [py, "benchmarks/envpool_bench.py", "--env", "synthetic",
             "--batch_size", "128", "--num_processes", "8", "--steps", "50"]
        ),
        # Whole-agent smoke row (small scale; the reference-scale number is
        # the TPU battery's job — one CPU core can't feed the flagship shape).
        "agent_small": _run(
            [py, "benchmarks/agent_bench.py", "--scale", "small"], timeout=900
        ),
        # R2D2 learner-update plumbing row (tiny shapes; the paper-geometry
        # chip row is the battery's r2d2_bench step).
        "r2d2_small": _run(
            [py, "benchmarks/r2d2_bench.py"], timeout=900,
            extra_env={"MOOLIB_ALLOW_CPU": "1", "MOOLIB_R2D2_T": "8",
                       "MOOLIB_R2D2_B": "4"},
        ),
        # Serving under load: p50/p99 + tokens/s, dynamic batching on/off,
        # GQA sweep (VERDICT r3 ask #8).
        # --batch_sizes sweeps the cap: the crossover vs batch-1 is visible
        # in avg_batch_fill + req/s (cap 4 beats batching-off on this box).
        "serve": _run(
            [py, "benchmarks/serve_bench.py", "--seconds", "6", "--clients", "8",
             "--batch_sizes", "16", "4"],
            timeout=900,
        ),
    }
    results["allreduce_rpc_multiproc"] = _run_multiproc_allreduce(py)
    out = os.path.join(ROOT, "BENCH_LOCAL.json")
    with open(out, "w") as f:
        json.dump(results, f, indent=1)
    print(f"wrote {out}")
    for k, v in results.items():
        if isinstance(v, dict) and "rc" in v:
            print(f"  {k}: rc={v['rc']} ({v.get('seconds', '?')}s)")


if __name__ == "__main__":
    main()
