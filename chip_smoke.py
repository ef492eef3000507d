#!/usr/bin/env python3
"""Does the system still start on the chip?  The quickest end-to-end proof.

Drives the three main paths once each, through the command lines a user
would type, at the full width of the models the repo ships (random weights
from a seed, depth and run length cut to a smoke's size):

- **agent** — ``examples.vtrace.experiment``: the IMPALA agent at the
  reference Atari geometry (84x84x4 uint8 frames, ImpalaNet 16/32/32 in bf16,
  unroll 20, batch 32), two EnvPool actor batches in worker processes, the
  in-process broker and a one-peer Accumulator cohort, device rollout buffers.
- **lm** — ``examples.lm``: the d=1024 / 12-layer / 8x128-head / vocab 32,768
  LM at T=2048 with the Pallas flash kernels, a few AdamW steps on the copy
  task; then one forward+backward check of the kernel against dense attention
  at that head shape (``tests/test_flash_attention_tpu.py``, its tolerances).
- **serve** — one ``examples.lm_serve --engine`` replica of the same model
  answering prompts of different lengths and budgets through ``ServeClient``,
  then one batch-synchronous replica (``generate()``) answering the same
  prompts: greedy tokens must be identical.

``--chips 4`` runs the sharded LM paths and what they are compared with, and
nothing else: ``--mesh dp=4`` against ``--mesh dp=1`` (same seed and global
batch, per-step losses within ``LOSS_RTOL``), and ``--mesh dp=2,sp=2
--attention ring`` at T=4096.

Rules the script keeps, because a chip belongs to one process at a time:
this parent never imports jax (nor the package, which does); every phase is
ONE child process that owns the chip and is gone, with its whole process
group, before the next starts; the serve client is a child pinned to the CPU.
A phase that fails, times out, or computed on a platform other than the
expected one ends the run with a non-zero exit; nothing is carried past it.

Each phase prints one ``PHASE {json}`` line (wall and compile seconds, steps
or requests done, persistent compile-cache hits and misses, which native
libraries were built).  The LAST line of stdout is exactly
``{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}``.
Children's full output goes to ``chiprun_out/chip_smoke/<phase>.log``.
The compile cache is wherever ``JAX_COMPILATION_CACHE_DIR`` says, else
``<repo>/.jax_cache`` (``moolib_tpu/utils/compile_cache.py``): a second run
in the same place starts warm.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import re
import signal
import socket
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
LOG_DIR = os.path.join(ROOT, "chiprun_out", "chip_smoke")
# moolib_tpu.examples.common.REPORT_PREFIX — spelled out, not imported: the
# package imports jax, and this process must stay off it.
REPORT_PREFIX = "RUN_REPORT "
BUDGET_S = 1150.0  # the whole run, compilation included, ends inside 1200 s
# dp=4 vs dp=1 run the same math in bf16 with another reduction order (the
# gradient all-reduce, per-shard batch means), and the difference compounds
# through the updates.  Ten steps on four v5e chips came within 1.7e-5.
LOSS_RTOL = 1e-3

_LM_WIDTH = dict(vocab=32768, d_model=1024, layers=12, heads=8, learning_rate=3e-4)
FULL = dict(
    # What only a chip gives: Mosaic custom calls in the compiled LM step
    # (interpret mode leaves none) and allocator statistics from every device
    # (the cpu backend reports one host figure).
    min_mosaic_kernels=1,
    devices_reporting_memory=4,
    agent=dict(unroll_length=20, batch_size=32, actor_batches=2, env_processes=4,
               total_steps=8000, min_sgd_steps=5),
    # Batch from memory_analysis() of this step compiled for a described v5e
    # (arguments + outputs + temporaries, nothing donated): B=4 is 8.5 GB of
    # 16 GB, B=8 is 13.7 GB and leaves no headroom.
    lm=dict(_LM_WIDTH, seq_len=2048, batch_size=4, steps=20),
    # (prompt length, token budget) per request; all in flight at once on
    # the engine, so slots join and retire at different steps.
    serve=dict(_LM_WIDTH, seq_len=64, max_new_tokens=16, slots=8,
               requests=[(5, 4), (17, 9), (33, 16), (64, 12), (17, 16), (40, 1)],
               # The latent-attention + dropless-experts preset
               # (models.latent_moe.tiny_config) beside it: what the chip adds
               # to the CPU tests is the Mosaic lowering of the latent cache
               # layout and of the grouped matmul.  Each emitted token must
               # lie within this many standard deviations of the float32
               # reference's largest logit (bfloat16 on the chip picks a near
               # tie; a wrong row or expert picks a token several down).
               latent_gap_sigma=0.5),
    mesh=dict(_LM_WIDTH, seq_len=2048, batch_size=4, steps=10,
              ring_seq_len=4096, ring_steps=5),
)


class PhaseFailed(Exception):
    pass


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _group_alive(pgid: int) -> bool:
    """Does the process group still hold a process that could hold the chip?
    Read from /proc: a zombie nobody reaps would answer ``killpg(pgid, 0)``."""
    for pid in os.listdir("/proc"):
        if not pid.isdigit():
            continue
        try:
            with open(f"/proc/{pid}/stat") as f:
                state, _ppid, pgrp = f.read().rsplit(")", 1)[1].split()[:3]
        except OSError:
            continue  # gone between listdir and open
        if int(pgrp) == pgid and state != "Z":
            return True
    return False


class Child:
    """One child process in its own process group, output to a log file."""

    def __init__(self, name: str, argv, log_dir: str, env=None):
        os.makedirs(log_dir, exist_ok=True)
        self.name = name
        self.log_path = os.path.join(log_dir, f"{name}.log")
        self._log = open(self.log_path, "wb")
        self.proc = subprocess.Popen(
            argv, stdout=self._log, stderr=subprocess.STDOUT, cwd=ROOT,
            env=dict(os.environ, PYTHONUNBUFFERED="1", **(env or {})),
            start_new_session=True,
        )

    def output(self) -> str:
        with open(self.log_path, errors="replace") as f:
            return f.read()

    def reap(self) -> None:
        """End the whole process group (EnvPool workers, a serving loop) and
        make sure nothing of it is left to hold the chip."""
        pgid = self.proc.pid
        for sig in (signal.SIGTERM, signal.SIGKILL):
            if not _group_alive(pgid):
                break
            try:
                os.killpg(pgid, sig)
            except ProcessLookupError:
                break
            end = time.monotonic() + 10.0
            # poll() also collects our own child, which is otherwise a zombie
            while (self.proc.poll() is None or _group_alive(pgid)) \
                    and time.monotonic() < end:
                time.sleep(0.1)
        self._log.close()
        if _group_alive(pgid):
            raise PhaseFailed(f"{self.name}: process group {pgid} outlived SIGKILL")

    def fail(self, why: str) -> PhaseFailed:
        tail = "\n".join(self.output().splitlines()[-40:])
        return PhaseFailed(f"{self.name}: {why}\n--- end of {self.log_path} ---\n{tail}")

    def report(self) -> dict:
        """The child's last RUN_REPORT line (examples.common.print_report)."""
        for line in reversed(self.output().splitlines()):
            if line.startswith(REPORT_PREFIX):
                return json.loads(line[len(REPORT_PREFIX):])
        raise self.fail("printed no RUN_REPORT line")


class Smoke:
    """The phase runner: children one after the other, facts per phase, one
    verdict.  ``expect_platform`` is what every child must have computed on."""

    def __init__(self, sizes: dict, expect_platform: str = "tpu",
                 expect_count: int = 1, log_dir: str = LOG_DIR):
        self.sizes = sizes
        self.expect_platform = expect_platform
        self.expect_count = expect_count
        self.log_dir = log_dir
        self.deadline = time.monotonic() + BUDGET_S
        self.device = None  # as the first child reported it

    # ------------------------------------------------------------ children
    def _timeout(self, cap: float) -> float:
        left = self.deadline - time.monotonic()
        if left <= 0:
            raise PhaseFailed("out of time: the run's budget is spent")
        return min(cap, left)

    def start(self, name: str, argv, env=None) -> Child:
        """Start a child; whoever does reaps it in a ``finally``."""
        return Child(name, argv, self.log_dir, env)

    def run(self, name: str, argv, *, timeout: float, env=None) -> Child:
        """Run a child to its end; non-zero exit or time-out fails the phase."""
        child = self.start(name, argv, env)
        try:
            try:
                rc = child.proc.wait(timeout=self._timeout(timeout))
            except subprocess.TimeoutExpired:
                raise child.fail(f"still running after {timeout:.0f} s") from None
            if rc != 0:
                raise child.fail(f"exit code {rc}")
        finally:
            child.reap()
        return child

    def wait_ready(self, child: Child, *, timeout: float) -> dict:
        """Block until a serving child prints its readiness RUN_REPORT."""
        end = time.monotonic() + self._timeout(timeout)
        while time.monotonic() < end:
            if child.proc.poll() is not None:
                raise child.fail(f"exited with {child.proc.returncode} before serving")
            if REPORT_PREFIX in child.output():
                return child.report()
            time.sleep(0.5)
        raise child.fail(f"not serving after {timeout:.0f} s")

    # -------------------------------------------------------------- checks
    def check_device(self, name: str, report: dict) -> None:
        dev = report["device"]
        if dev["platform"] != self.expect_platform:
            raise PhaseFailed(
                f"{name}: computed on platform {dev['platform']!r}, "
                f"expected {self.expect_platform!r}"
            )
        if dev["count"] != self.expect_count:
            raise PhaseFailed(
                f"{name}: {dev['count']} device(s), expected {self.expect_count}"
            )
        if self.device is None:
            self.device = dev
        elif dev != self.device:
            raise PhaseFailed(f"{name}: device {dev} differs from {self.device}")

    def facts(self, name: str, report: dict, **more) -> dict:
        """The phase's printed line, from the child's own report."""
        self.check_device(name, report)
        return {
            "phase": name,
            "wall_s": report["wall_s"],
            "compile_s": round(report["compile_s"], 2),
            "compiles": report["compiles"],
            "cache_hits": report["cache_hits"],
            "cache_misses": report["cache_misses"],
            "cache_dir": report["cache_dir"],
            "native_built": report["native"],
            **more,
        }


def _require(cond, name: str, what: str) -> None:
    if not cond:
        raise PhaseFailed(f"{name}: {what}")


def _finite(x) -> bool:
    return isinstance(x, (int, float)) and math.isfinite(x)


# ------------------------------------------------------------------- phases
def phase_device(s: Smoke) -> dict:
    """Is there a chip at all?  Asked first so its absence costs seconds."""
    code = (
        "import jax, json; d = jax.devices(); print(%r + json.dumps({'device': "
        "{'platform': d[0].platform, 'kind': d[0].device_kind, 'count': len(d)}}))"
        % REPORT_PREFIX
    )
    child = s.run("device", [sys.executable, "-c", code], timeout=180)
    report = child.report()
    s.check_device("device", report)
    return {"phase": "device", **report["device"]}


def phase_agent(s: Smoke) -> dict:
    z = s.sizes["agent"]
    child = s.run("agent", [
        sys.executable, "-m", "moolib_tpu.examples.vtrace.experiment",
        "--env", "synthetic",
        "--unroll_length", str(z["unroll_length"]),
        "--batch_size", str(z["batch_size"]),
        "--virtual_batch_size", str(z["batch_size"]),
        "--actor_batch_size", str(z["batch_size"]),
        "--num_actor_batches", str(z["actor_batches"]),
        "--num_env_processes", str(z["env_processes"]),
        "--total_steps", str(z["total_steps"]),
        "--address", f"127.0.0.1:{_free_port()}",
        "--log_interval", "2",
    ], timeout=600)
    report = child.report()
    r = report["result"]
    _require(r["sgd_steps"] >= z["min_sgd_steps"], "agent",
             f"{r['sgd_steps']} SGD steps applied, need {z['min_sgd_steps']}")
    _require(_finite(r["loss"]), "agent", f"loss is {r['loss']!r}")
    for what in ("param_placement", "batch_placement"):
        _require(r[what]["platforms"] == [s.expect_platform], "agent",
                 f"{what} is {r[what]}, not on {s.expect_platform!r} alone")
    return s.facts("agent", report, sgd_steps=r["sgd_steps"], env_frames=r["steps"],
                   loss=r["loss"])


def _lm_argv(z: dict, mesh: str, attention: str, seq_len: int, steps: int) -> list:
    return [
        sys.executable, "-m", "moolib_tpu.examples.lm",
        "--vocab", str(z["vocab"]), "--d_model", str(z["d_model"]),
        "--layers", str(z["layers"]), "--heads", str(z["heads"]),
        "--seq_len", str(seq_len), "--batch_size", str(z["batch_size"]),
        "--attention", attention, "--mesh", mesh,
        "--steps", str(steps), "--log_interval", "1",
        "--learning_rate", str(z["learning_rate"]),
    ]


def _check_lm(s: Smoke, name: str, report: dict, steps: int) -> list:
    r = report["result"]
    losses = [loss for _, loss in r["losses"]]
    _require(r["steps"] == steps and len(losses) == steps, name,
             f"{r['steps']} steps, {len(losses)} losses, expected {steps}")
    _require(all(_finite(x) for x in losses), name, f"losses not finite: {losses}")
    _require(r["program"]["mosaic_kernels"] >= s.sizes["min_mosaic_kernels"], name,
             f"the compiled step holds {r['program']['mosaic_kernels']} Mosaic "
             "kernels (tpu_custom_call): interpret mode, or no kernel at all")
    _require(r["flash_dense_reroutes"] == 0, name,
             "flash attention was rerouted to the dense path")
    return losses


def phase_lm(s: Smoke) -> dict:
    z = s.sizes["lm"]
    child = s.run("lm", _lm_argv(z, "", "flash", z["seq_len"], z["steps"]), timeout=600)
    report = child.report()
    losses = _check_lm(s, "lm", report, z["steps"])
    _require(losses[-1] < losses[0], "lm",
             f"loss did not fall: {losses[0]} -> {losses[-1]}")
    r = report["result"]
    _require(r["param_placement"]["platforms"] == [s.expect_platform], "lm",
             f"parameters on {r['param_placement']}")
    (mem,) = report["memory"].values()
    if mem["bytes_limit"]:
        _require(mem["bytes_peak"] < 0.9 * mem["bytes_limit"], "lm",
                 f"peak memory {mem['bytes_peak']:.3g} B leaves under a tenth "
                 f"of {mem['bytes_limit']:.3g} B")
    return s.facts(
        "lm", report, steps=r["steps"], first_loss=losses[0], last_loss=losses[-1],
        program=r["program"], peak_bytes=mem["bytes_peak"],
    )


def phase_flash_check(s: Smoke) -> dict:
    """The kernel against dense attention, forward and backward, at the LM's
    head shape: the repo's own on-chip test, with its tolerances.  It skips
    where jax has no accelerator, and a skip is not a pass."""
    t0 = time.monotonic()
    child = s.run("flash_check", [
        sys.executable, "-m", "pytest", "-q", "-p", "no:cacheprovider",
        "tests/test_flash_attention_tpu.py", "-k", "lm_head_shape",
    ], timeout=300, env={"JAX_PLATFORMS": s.expect_platform})
    summary = child.output().strip().splitlines()[-1]
    passed = re.search(r"(\d+) passed", summary)
    _require(passed and not re.search(r"skipped|failed|error", summary),
             "flash_check", f"pytest said: {summary}")
    return {"phase": "flash_check", "wall_s": round(time.monotonic() - t0, 1),
            "cases_passed": int(passed.group(1))}


def _serve_argv(z: dict, port: int, name: str, *extra) -> list:
    return [
        sys.executable, "-m", "moolib_tpu.examples.lm_serve",
        "--listen", f"127.0.0.1:{port}", "--name", name,
        "--vocab", str(z["vocab"]), "--d_model", str(z["d_model"]),
        "--layers", str(z["layers"]), "--heads", str(z["heads"]),
        "--seq_len", str(z["seq_len"]),
        "--max_new_tokens", str(z["max_new_tokens"]),
        "--seed", "0", *extra,
    ]


def _serve_arm(s: Smoke, name: str, argv: list, port: int, budgets: bool):
    """Start one replica, send it the requests from a CPU-pinned client,
    reap it.  Returns (readiness report, per-request continuations)."""
    z = s.sizes["serve"]
    replica = s.start(name, argv)
    try:
        ready = s.wait_ready(replica, timeout=600)
        s.check_device(name, ready)
        spec = dict(address=f"127.0.0.1:{port}", replica=name, vocab=z["vocab"],
                    requests=z["requests"], budgets=budgets)
        client = s.run(
            f"{name}_client",
            [sys.executable, os.path.abspath(__file__), "--client", json.dumps(spec)],
            timeout=300, env={"JAX_PLATFORMS": "cpu"},
        )
        _require(replica.proc.poll() is None, name, "the replica died while serving")
        return ready, client.report()["tokens"]
    finally:
        replica.reap()


def phase_serve(s: Smoke) -> dict:
    z = s.sizes["serve"]
    port = _free_port()
    ready, engine_tokens = _serve_arm(
        s, "serve_engine",
        _serve_argv(z, port, "serve_engine", "--engine", "--slots", str(z["slots"])),
        port, budgets=True,
    )
    # The reference arm: generate() behind the batch-synchronous loop, one
    # request at a time, every request decoded to the full default budget.
    port = _free_port()
    _, ref_tokens = _serve_arm(
        s, "serve_reference",
        _serve_argv(z, port, "serve_reference", "--no_dynamic_batching"),
        port, budgets=False,
    )
    matched = 0
    for i, ((length, budget), got, ref) in enumerate(
            zip(z["requests"], engine_tokens, ref_tokens)):
        _require(len(got) == budget, "serve",
                 f"request {i}: {len(got)} tokens for a budget of {budget}")
        _require(got == ref[:budget], "serve",
                 f"request {i} (prompt {length}, budget {budget}): engine "
                 f"{got} != generate() {ref[:budget]}")
        matched += budget
    latent = _serve_latent(s)
    return s.facts("serve", ready, requests=len(z["requests"]), tokens_matched=matched,
                   **latent)


def _serve_latent(s: Smoke) -> dict:
    """The tiny latent-attention preset through ``lm_serve --engine
    --config``: the same requests, each token checked by the client against
    the plain float32 reference (computed on the CPU from the same seed)."""
    z = s.sizes["serve"]
    config = os.path.join(s.log_dir, "latent_moe_tiny.json")
    s.run(
        "serve_latent_preset",
        [sys.executable, "-c",
         "import json; from moolib_tpu.models.latent_moe import tiny_config; "
         f"json.dump(tiny_config(), open({config!r}, 'w'))"],
        timeout=120, env={"JAX_PLATFORMS": "cpu"})
    port = _free_port()
    name = "serve_latent"
    replica = s.start(name, [
        sys.executable, "-m", "moolib_tpu.examples.lm_serve",
        "--listen", f"127.0.0.1:{port}", "--name", name, "--engine",
        "--config", config, "--slots", str(z["slots"]),
        "--seq_len", str(z["seq_len"]), "--max_new_tokens", str(z["max_new_tokens"]),
        "--seed", "0"])
    try:
        ready = s.wait_ready(replica, timeout=600)
        s.check_device(name, ready)
        spec = dict(address=f"127.0.0.1:{port}", replica=name, requests=z["requests"],
                    budgets=True, config=config, seed=0)
        client = s.run(
            f"{name}_client",
            [sys.executable, os.path.abspath(__file__), "--client", json.dumps(spec)],
            timeout=300, env={"JAX_PLATFORMS": "cpu"})
        _require(replica.proc.poll() is None, name, "the replica died while serving")
        report = client.report()
    finally:
        replica.reap()
    for (length, budget), got in zip(z["requests"], report["tokens"]):
        _require(len(got) == budget, name,
                 f"prompt {length}: {len(got)} tokens for a budget of {budget}")
    _require(report["gap_sigma_max"] <= z["latent_gap_sigma"], name,
             f"a token lies {report['gap_sigma_max']:.3f} standard deviations under "
             f"the reference's largest logit (limit {z['latent_gap_sigma']})")
    return {"latent_tokens_checked": sum(b for _, b in z["requests"]),
            "latent_gap_sigma_max": round(report["gap_sigma_max"], 4)}


def client_main(spec: dict) -> None:
    """The serve client (a CPU-pinned child of the smoke): prompts from a
    seed, through ``ServeClient``, continuations out as a RUN_REPORT."""
    import numpy as np

    from moolib_tpu.rpc import Rpc
    from moolib_tpu.serving import ServeClient

    rpc = Rpc()
    rpc.set_name("smoke_client")
    rpc.connect(spec["address"])
    client = ServeClient(
        rpc, fn="generate", replicas=[spec["replica"]], deadline_s=240.0,
        attempt_timeout=240.0, max_attempts=1, metadata=spec["budgets"],
    )
    config = None
    if spec.get("config"):
        with open(spec["config"]) as f:
            config = json.load(f)
    rng = np.random.default_rng(1)
    vocab = config["vocab_size"] if config else spec["vocab"]
    prompts = [rng.integers(2, vocab, length).astype(np.int32)
               for length, _ in spec["requests"]]
    try:
        if spec["budgets"]:
            futures = [client.submit(p, budget)
                       for p, (_, budget) in zip(prompts, spec["requests"])]
            outs = [np.asarray(f.result(245.0)) for f in futures]
        else:
            outs = [np.asarray(client.call(p)) for p in prompts]
    finally:
        client.close()
        rpc.close()
    tokens = [out[len(p):].tolist() for p, out in zip(prompts, outs)]
    report = {"tokens": tokens}
    if config:
        report["gap_sigma_max"] = _latent_gap(config, spec["seed"], outs, prompts)
    print(REPORT_PREFIX + json.dumps(report), flush=True)


def _latent_gap(config: dict, seed: int, outs, prompts) -> float:
    """On the CPU: the replica's weights again from its seed, the plain
    reference's logits for every sequence, and the largest (reference
    maximum - reference logit of the emitted token) / deviation."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from chipbench.reference import glm_moe_lite
    from moolib_tpu.models.latent_moe import LatentMoELM

    params = jax.jit(LatentMoELM.from_config(config).init)(jax.random.key(seed))
    worst = 0.0
    for out, prompt in zip(outs, prompts):
        want = np.asarray(glm_moe_lite.logits(params, jnp.asarray(out[:-1]), config))
        want = want[len(prompt) - 1:]
        emitted = out[len(prompt):]
        gap = (want.max(-1) - want[np.arange(len(emitted)), emitted]) / want.std(-1)
        worst = max(worst, float(gap.max()))
    return worst


# ------------------------------------------------------- four-chip phases
def _check_spread(s: Smoke, name: str, report: dict) -> None:
    r = report["result"]
    for what in ("param_placement", "batch_placement"):
        _require(r[what]["devices_per_array"] == s.expect_count
                 and r[what]["platforms"] == [s.expect_platform], name,
                 f"{what} is {r[what]}, not on {s.expect_count} "
                 f"{s.expect_platform} devices")
    in_use = {k: v["bytes_in_use"] for k, v in report["memory"].items()}
    _require(len(in_use) == s.sizes["devices_reporting_memory"]
             and all(in_use.values()), name, f"bytes_in_use per device: {in_use}")


def phase_mesh_dp(s: Smoke) -> dict:
    z = s.sizes["mesh"]
    n = s.expect_count
    runs = {}
    for mesh in (f"dp={n}", "dp=1"):
        name = "lm_" + mesh.replace("=", "")
        child = s.run(name, _lm_argv(z, mesh, "flash", z["seq_len"], z["steps"]),
                      timeout=500)
        report = child.report()
        runs[mesh] = (report, _check_lm(s, name, report, z["steps"]))
    report, losses = runs[f"dp={n}"]
    _check_spread(s, f"lm_dp{n}", report)
    _, ref = runs["dp=1"]
    worst = max(abs(a - b) / abs(b) for a, b in zip(losses, ref))
    _require(worst <= LOSS_RTOL, "mesh_dp",
             f"dp={n} losses {losses} vs dp=1 {ref}: relative gap {worst:.3g} "
             f"> {LOSS_RTOL}")
    _require(report["result"]["program"]["collectives"], "mesh_dp",
             "the sharded step holds no collective")
    return s.facts(
        "mesh_dp", report, steps=z["steps"], losses_dp=losses, losses_dp1=ref,
        worst_relative_gap=worst, program=report["result"]["program"],
        bytes_peak={k: v["bytes_peak"] for k, v in report["memory"].items()},
    )


def phase_mesh_ring(s: Smoke) -> dict:
    z = s.sizes["mesh"]
    n = s.expect_count
    mesh = f"dp={n // 2},sp=2"
    child = s.run("lm_ring", _lm_argv(z, mesh, "ring", z["ring_seq_len"], z["ring_steps"]),
                  timeout=500)
    report = child.report()
    losses = _check_lm(s, "lm_ring", report, z["ring_steps"])
    _check_spread(s, "lm_ring", report)
    program = report["result"]["program"]
    _require(program["collectives"].get("collective-permute"), "lm_ring",
             f"no collective-permute in the ring step: {program}")
    return s.facts(
        "mesh_ring", report, mesh=mesh, steps=z["ring_steps"], losses=losses,
        program=program,
        bytes_peak={k: v["bytes_peak"] for k, v in report["memory"].items()},
    )


ONE_CHIP = (phase_device, phase_agent, phase_lm, phase_flash_check, phase_serve)
FOUR_CHIPS = (phase_device, phase_mesh_dp, phase_mesh_ring)


def smoke(phases, sizes: dict, expect_platform: str = "tpu", expect_count: int = 1,
          log_dir: str = LOG_DIR) -> int:
    """Run ``phases`` in order.  Prints one PHASE line each and, only if all
    passed, the verdict as the last line.  Returns the exit code."""
    if os.environ.get("MOOLIB_TPU_FLASH_BWD"):
        print("FAILED: MOOLIB_TPU_FLASH_BWD is a test oracle; unset it",
              file=sys.stderr)
        return 1
    s = Smoke(sizes, expect_platform, expect_count, log_dir)
    started = time.monotonic()
    try:
        for phase in phases:
            t0 = time.monotonic()
            facts = phase(s)
            facts["phase_wall_s"] = round(time.monotonic() - t0, 1)
            print("PHASE " + json.dumps(facts), flush=True)
    except PhaseFailed as e:
        print(f"FAILED after {time.monotonic() - started:.0f} s: {e}",
              file=sys.stderr, flush=True)
        return 1
    print(f"all {len(phases)} phases passed in {time.monotonic() - started:.0f} s",
          flush=True)
    print(json.dumps({"ok": True, "device": s.device}), flush=True)
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: the sharded LM paths on a four-chip host, and "
                    "what they are compared with; no single-chip phase")
    ap.add_argument("--client", help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    if args.client:
        client_main(json.loads(args.client))
        return 0
    phases = FOUR_CHIPS if args.chips == 4 else ONE_CHIP
    return smoke(phases, FULL, expect_count=args.chips)


if __name__ == "__main__":
    sys.exit(main())
