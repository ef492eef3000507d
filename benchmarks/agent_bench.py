"""Whole-agent IMPALA throughput: act + env stepping + learn, overlapped.

``bench.py`` times the learner step alone, but the reference's headline is
whole-agent SPS — the flagship loop with EnvPool
actors, batched inference, and the learner sharing one chip
(``/root/reference/examples/vtrace/experiment.py`` act/learn overlap).

Since the device-resident actor pipeline landed (docs/DESIGN.md "Actor data
plane"), this is an A/B: by default BOTH rollout modes run in one
invocation — the legacy host-batcher path first, then the device-rollout
path — and each prints one JSON row:

    {"metric": "impala_agent_sps", "rollout": "legacy"|"device"|"jax",
     "value": ..., "steady_sps": ..., "host_boundary_bytes_per_frame": ...}

``--rollout all`` (or ``jax``) adds the zero-crossing arm: ``--env_backend
jax`` runs the pure-JAX env family jitted into the unroll scan itself
(docs/DESIGN.md §4c, the Podracer "Anakin" layout), so the whole
act-frame pipeline is one dispatch per unroll and
``host_boundary_bytes_per_frame`` must read exactly 0 — enforced by
``--check``.  That arm uses its own larger env batch (its operating point:
with the env on device, batch size costs no host bytes).

``host_boundary_bytes_per_frame`` comes from the actor-path telemetry
counters (``actor_h2d/d2h_bytes_total``, ``batcher_h2d/d2h_bytes_total``
over ``actor_frames_total``), read as per-run deltas — the one-crossing
uint8 contract as a counted fact, not a narrative.

Scales:

- ``--scale reference``: the reference config (synthetic Atari geometry,
  actor_batch 128 x 2 buffers, unroll 20, learner batch 32) for the chip —
  there the learner is fast and per-dispatch RTT dominates acting, the
  regime the device pipeline exists for.
- ``--scale small``: the CPU smoke ``scripts/ci.sh`` runs.  Uses the
  ``catch_flat`` MLP env so per-frame model FLOPs are negligible and
  whole-agent SPS measures the actor data plane itself (on a CPU box the
  conv learner would otherwise drown the actor plane it is probing);
  long unrolls + virtual batching keep the shared learner/allreduce floor
  amortized the same way in both modes.

``--check`` (the ci.sh smoke gate) exits non-zero unless every mode that
ran reports steady_sps > 0.
"""

from __future__ import annotations

import argparse
import json
import sys
import time


def _run_mode(cfg: dict, total: int, device_rollout: bool, port: int,
              env_backend: str = "envpool"):
    """One train() run; returns (result, bytes_per_frame, seconds) with the
    boundary bytes read as telemetry deltas so back-to-back runs in one
    process don't double-count."""
    from moolib_tpu import telemetry
    from moolib_tpu.examples.vtrace import experiment

    t0 = time.time()
    reg = telemetry.get_registry()
    before = reg.counter_values()
    flags = experiment.make_flags([
        "--env", cfg["env"],
        "--env_backend", env_backend,
        "--total_steps", str(total),
        "--actor_batch_size", str(cfg["actor_batch_size"]),
        "--num_actor_batches", str(cfg["num_actor_batches"]),
        "--batch_size", str(cfg["batch_size"]),
        "--virtual_batch_size", str(cfg["virtual_batch_size"]),
        "--unroll_length", str(cfg["unroll_length"]),
        "--num_env_processes", str(cfg["num_env_processes"]),
        "--log_interval", str(cfg.get("log_interval", 10)),
        "--stats_interval", "5",
        "--device_rollout", "true" if device_rollout else "false",
        # Distinct broker port per mode: the second run must not race the
        # first run's closing listener.
        "--address", f"127.0.0.1:{port}",
        "--quiet",
    ])
    out = experiment.train(flags)
    after = reg.counter_values()
    delta = {k: after.get(k, 0.0) - before.get(k, 0.0) for k in after}
    frames = delta.get("actor_frames_total", 0.0)
    boundary = (
        delta.get("actor_h2d_bytes_total", 0.0)
        + delta.get("actor_d2h_bytes_total", 0.0)
        + delta.get("batcher_h2d_bytes_total", 0.0)
        + delta.get("batcher_d2h_bytes_total", 0.0)
    )
    bpf = round(boundary / frames, 1) if frames else None
    return out, bpf, time.time() - t0


def _probe_rtt():
    """Per-dispatch device round-trip floor in ms: every act() pays one
    dispatch + scalar fetch, the lower bound on a host-stepped actor's
    frame time.  Median of ten."""
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: x + 1)
    x = jnp.zeros((), jnp.int32)
    float(f(x))  # compile
    rtts = []
    for _ in range(10):
        t = time.perf_counter()
        float(f(x))
        rtts.append(time.perf_counter() - t)
    return sorted(rtts)[len(rtts) // 2] * 1e3


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--scale", default="reference", choices=["reference", "small"])
    p.add_argument("--total_steps", type=int, default=None, help="override step budget")
    p.add_argument(
        "--rollout", default="both",
        choices=["both", "all", "device", "legacy", "jax"],
        help="which actor data plane(s) to measure; 'both' runs legacy "
        "then device in one process (A/B on identical config); 'all' adds "
        "the jitted on-device env arm ('jax', Anakin plane) as a third row",
    )
    p.add_argument(
        "--check", action="store_true",
        help="smoke gate (ci.sh): exit non-zero unless every mode that ran "
        "reports steady_sps > 0 (and, for the jax arm, a measured "
        "host_boundary_bytes_per_frame of exactly 0)",
    )
    args = p.parse_args(argv)

    if args.scale == "reference":
        cfg = dict(env="synthetic", actor_batch_size=128, num_actor_batches=2,
                   batch_size=32, virtual_batch_size=32, unroll_length=20,
                   num_env_processes=8, log_interval=10)
        frames_per_batch = cfg["batch_size"] * cfg["unroll_length"]
        total = args.total_steps or max(
            24 * frames_per_batch,
            cfg["actor_batch_size"] * cfg["unroll_length"] * 6,
        )
    else:
        # Actor-plane regime (see module docstring): MLP env, long unrolls,
        # virtual batching.  log_interval 1 s so the steady-state window has
        # samples even on a fast box.
        cfg = dict(env="catch_flat", actor_batch_size=16, num_actor_batches=2,
                   batch_size=16, virtual_batch_size=64, unroll_length=40,
                   num_env_processes=2, log_interval=1)
        total = args.total_steps or 96_000

    # The jax arm ("Anakin") jits the env itself into the unroll dispatch, so
    # its natural operating point is a much larger env batch than the
    # host-actor arms can feed — it gets its own config (always the catch
    # MLP geometry: that is the env family with a pure-JAX twin).  Frames
    # never cross the host boundary, so the headline pairs a bigger SPS with
    # a measured 0.0 bytes/frame rather than a smaller nonzero one.
    jax_cfg = dict(env="catch_flat", actor_batch_size=256, num_actor_batches=2,
                   batch_size=128, virtual_batch_size=512, unroll_length=40,
                   num_env_processes=2, log_interval=1)
    jax_total = args.total_steps or 1_500_000

    modes = {"both": ("legacy", "device"), "all": ("legacy", "device", "jax"),
             "device": ("device",), "legacy": ("legacy",),
             "jax": ("jax",)}[args.rollout]
    rows = []
    for i, mode in enumerate(modes):
        mode_cfg = jax_cfg if mode == "jax" else cfg
        out, bpf, dt = _run_mode(
            mode_cfg, jax_total if mode == "jax" else total,
            device_rollout=(mode != "legacy"), port=4431 + 2 * i,
            env_backend="jax" if mode == "jax" else "envpool",
        )
        rows.append((mode, mode_cfg, out, bpf, dt))

    import jax

    dev = jax.devices()[0]
    rtt_ms = _probe_rtt()
    ok = True
    by_mode = {}
    for mode, cfg, out, bpf, dt in rows:
        row = {
            "metric": "impala_agent_sps",
            "rollout": mode,
            "value": round(out["sps"], 1),
            "steady_sps": out.get("steady_sps"),
            "mfu": out.get("mfu"),
            "host_boundary_bytes_per_frame": bpf,
            "act_rtt_floor_ms": round(rtt_ms, 2),
            "unit": "env_frames/s",
            "scale": args.scale,
            "steps": out["steps"],
            "sgd_steps": out["sgd_steps"],
            "seconds": round(dt, 1),
            "platform": dev.platform,
            "device_kind": dev.device_kind,
            "device_count": len(jax.devices()),
            "config": (
                f"{cfg['env']}, actor_batch {cfg['actor_batch_size']}"
                f"x{cfg['num_actor_batches']}, T={cfg['unroll_length']}, "
                f"B={cfg['batch_size']}, vbs={cfg['virtual_batch_size']}, "
                + ("env jitted into the unroll scan (Anakin), "
                   "act+learn overlapped on one device"
                   if mode == "jax"
                   else "act+step+learn overlapped on one device")
            ),
            "baseline": (
                "reference flagship loop examples/vtrace/experiment.py + "
                "config.yaml:23-65 (no published number; real-time actor "
                "floor 2*128 envs * 60 fps = 15360 frames/s)"
            ),
        }
        print(json.dumps(row))
        by_mode[mode] = row
        if not (row["steady_sps"] and row["steady_sps"] > 0):
            ok = False
        if mode == "jax" and row["host_boundary_bytes_per_frame"] != 0:
            # The zero-crossing contract is the arm's whole point; a nonzero
            # reading means a host staging path leaked back in.
            ok = False
    if "legacy" in by_mode and "device" in by_mode:
        leg, dev_row = by_mode["legacy"], by_mode["device"]
        summary = {
            "metric": "impala_agent_rollout_ab",
            "scale": args.scale,
            "steady_speedup": (
                round(dev_row["steady_sps"] / leg["steady_sps"], 2)
                if leg["steady_sps"] and dev_row["steady_sps"] else None
            ),
            "bytes_per_frame_reduction": (
                round(leg["host_boundary_bytes_per_frame"]
                      / dev_row["host_boundary_bytes_per_frame"], 2)
                if leg["host_boundary_bytes_per_frame"]
                and dev_row["host_boundary_bytes_per_frame"] else None
            ),
        }
        print(json.dumps(summary))
    if "jax" in by_mode and "device" in by_mode:
        jx, dev_row = by_mode["jax"], by_mode["device"]
        print(json.dumps({
            "metric": "impala_agent_jax_vs_device",
            "scale": args.scale,
            "steady_speedup": (
                round(jx["steady_sps"] / dev_row["steady_sps"], 2)
                if dev_row["steady_sps"] and jx["steady_sps"] else None
            ),
            "jax_bytes_per_frame": jx["host_boundary_bytes_per_frame"],
        }))
    if args.check and not ok:
        print("agent_bench --check: a rollout mode is missing steady_sps > 0",
              file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
