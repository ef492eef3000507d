"""R2D2 replay-plane A/B: host store vs device-resident store, one invocation.

The seed bench timed only the jitted learner update; the thing PR-20
rebuilt is everything *around* it — the prioritized store.  So this bench
drives the full learner-side replay cycle per arm at one shared config
(same synthetic trajectories, same seeds, same donated update jit
geometry):

    add -> prioritized sample -> time-major batch -> update -> priority
    write-back

across three arms:

- ``host``     — in-process :class:`moolib_tpu.replay.ReplayBuffer`
  (numpy sum-tree, host stacking, host->device staging per batch);
- ``host_rpc`` — the legacy deployment shape: ``ReplayServer`` /
  ``ReplayClient`` over a same-host ipc loopback (the "host-side
  pickle-RPC store" ROADMAP item 5 names);
- ``device``   — :class:`moolib_tpu.replay.DeviceReplayShard`: sum-tree
  and ring on chip, donated fixed-shape insert/sample, TD errors consumed
  without visiting the host.

Emits one ``{"metric": "r2d2_learner_sps", "arm": ...}`` JSON row per arm
plus an ``r2d2_replay_ab`` summary carrying the device/host speedups, the
device-vs-numpy priority bit-exactness verdict, and the measured
write-once memfd ingest bytes (publish bytes counted once per host, with
two consumer shards attached).  ``--check`` turns the summary into a
smoke gate: every arm > 0 SPS, priorities bit-exact, ingest write-once.

    MOOLIB_ALLOW_CPU=1 python benchmarks/r2d2_bench.py --check
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def make_items(rng, n, T, obs_dim, core_size):
    """Synthetic per-env sequence items shaped like the r2d2 example's
    (state/done/action/reward + stored initial LSTM state)."""
    return [
        {
            "state": rng.normal(size=(T + 1, obs_dim)).astype(np.float32),
            "done": rng.random(T + 1) < 0.01,
            "action": rng.integers(0, 2, size=T + 1).astype(np.int32),
            "reward": rng.normal(size=T + 1).astype(np.float32),
            "core": (
                np.zeros(core_size, np.float32),
                np.zeros(core_size, np.float32),
            ),
        }
        for _ in range(n)
    ]


def check_priority_bitexact(ops: int = 200) -> bool:
    """Drive a seeded add/update schedule through the device shard and the
    numpy ``SumTree`` reference (f32, fed through the shard's own compiled
    priority transform) and compare the trees exactly."""
    from moolib_tpu.replay import DeviceReplayShard, SumTree

    shard = DeviceReplayShard(128, seed=7, name="r2d2_bench_check")
    ref = SumTree(128, dtype=np.float32)
    rng = np.random.default_rng(7)

    def tf(p):
        return np.asarray(shard.priority_transform(np.asarray(p, np.float32)))

    for op in range(ops):
        if op % 2 == 0:
            items = [{"x": rng.normal(size=4).astype(np.float32)} for _ in range(8)]
            prios = (rng.random(8) * 2).astype(np.float32)
            idxs = shard.add(items, prios)
            ref.set(np.asarray(idxs), tf(prios))
        elif len(shard) >= 16:
            idxs = rng.choice(len(shard), size=16, replace=False)
            prios = (rng.random(16) * 3).astype(np.float32)
            shard.update_priorities(idxs.astype(np.int32), prios)
            ref.set(idxs, tf(prios))
            shard.sample(16)
    return bool(np.array_equal(np.asarray(shard.tree), ref.tree))


def measure_ingest_write_once(consumers: int = 2, publishes: int = 4):
    """One publisher, N same-process consumer shards over ipc: the memfd
    multicast writes the payload once per host.  Returns the measured
    byte accounting from ``replay_bytes_total``."""
    from moolib_tpu import Rpc
    from moolib_tpu.replay import (
        DeviceReplayShard,
        ReplayPublisher,
        ReplayShardService,
    )
    from moolib_tpu.replay.host import payload_bytes
    from moolib_tpu.telemetry import metrics

    hub = Rpc()
    hub.set_name("r2d2b-pub")
    hub.listen(":0")
    addr = next(a for a in hub._listen_addrs if a.startswith("ipc://"))
    rng = np.random.default_rng(0)
    # 32 items x [21, 512] f32 ~ 1.4 MB: over the memfd multicast floor.
    items = [
        {"state": rng.normal(size=(21, 512)).astype(np.float32)}
        for _ in range(32)
    ]
    per_publish = payload_bytes(items)

    spokes, services = [], []
    try:
        for i in range(consumers):
            r = Rpc()
            r.set_name(f"r2d2b-shard{i}")
            services.append(
                ReplayShardService(
                    r,
                    "replay",
                    DeviceReplayShard(256, name=f"r2d2b_ing{i}"),
                    shard_index=i,
                    num_shards=consumers,
                )
            )
            r.connect(addr)
            spokes.append(r)
        pub = ReplayPublisher(
            hub, [f"r2d2b-shard{i}" for i in range(consumers)], "replay"
        )
        deadline = time.time() + 10
        while not pub.multicast_ready() and time.time() < deadline:
            time.sleep(0.01)
        multicast = pub.multicast_ready()

        def counter(direction):
            vals = metrics.get_registry().counter_values()
            return vals.get(f'replay_bytes_total{{direction="{direction}"}}', 0.0)

        out0, in0 = counter("ingest_out"), counter("ingest_in")
        for _ in range(publishes):
            pub.publish(items).result(20)
        out_bytes = counter("ingest_out") - out0
        in_bytes = counter("ingest_in") - in0
        for s in services:
            s.drain()
        return {
            "consumers": consumers,
            "publishes": publishes,
            "payload_bytes": per_publish * publishes,
            "ingest_out_bytes": int(out_bytes),
            "ingest_in_bytes": int(in_bytes),
            "multicast": bool(multicast),
            "write_once": out_bytes == per_publish * publishes,
        }
    finally:
        for r in spokes:
            r.close()
        hub.close()


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--check", action="store_true",
                    help="smoke gate: nonzero exit unless every arm runs, "
                    "priorities are bit-exact, and ingest is write-once")
    ap.add_argument("--arms", default="host,host_rpc,device",
                    help="comma-separated arm subset")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import optax

    from moolib_tpu import Rpc
    from moolib_tpu.examples.r2d2 import td_loss
    from moolib_tpu.models.qnet import RecurrentQNet
    from moolib_tpu.replay import (
        DeviceReplayShard,
        ReplayBuffer,
        ReplayClient,
        ReplayServer,
    )
    from moolib_tpu.utils import init_compile_cache

    init_compile_cache()
    if jax.default_backend() == "cpu" and os.environ.get("MOOLIB_ALLOW_CPU") != "1":
        raise SystemExit(
            "r2d2_bench needs an accelerator backend "
            "(MOOLIB_ALLOW_CPU=1 for a labeled plumbing-proof run)"
        )
    dev = jax.devices()[0]

    # Replay-plane geometry (smoke-shrinkable via the same env knobs the
    # seed bench used): T x learn_batch sequences through the learner per
    # cycle, n_envs items inserted per cycle.  The model is deliberately
    # small — this bench times the replay plane, and the T-length LSTM
    # scan is a fixed sequential cost every arm pays identically.
    T = int(os.environ.get("MOOLIB_R2D2_T", 10))
    B = int(os.environ.get("MOOLIB_R2D2_B", 320))
    n_envs = int(os.environ.get("MOOLIB_R2D2_ENVS", 16))
    obs_dim = int(os.environ.get("MOOLIB_R2D2_OBS", 64))
    core_size, capacity = 16, 1024
    model = RecurrentQNet(
        num_actions=2, hidden_size=32, core_size=core_size, encoder="mlp"
    )

    rng = np.random.default_rng(0)
    params0 = model.init(
        jax.random.key(0),
        {
            "state": jnp.zeros((1, B, obs_dim), jnp.float32),
            "done": jnp.zeros((1, B), bool),
            "action": jnp.zeros((1, B), jnp.int32),
            "reward": jnp.zeros((1, B), jnp.float32),
        },
        model.initial_state(B),
    )
    n_params = sum(x.size for x in jax.tree_util.tree_leaves(params0))
    opt = optax.chain(optax.clip_by_global_norm(40.0), optax.adam(1e-3))
    target_params = jax.tree_util.tree_map(jnp.copy, params0)

    from functools import partial

    @partial(jax.jit, donate_argnums=(0, 1))
    def update(p, s, tp, b):
        (loss, prio), g = jax.value_and_grad(
            lambda p_: td_loss(p_, tp, model, b, 0.997), has_aux=True
        )(p)
        up, s = opt.update(g, s, p)
        return optax.apply_updates(p, up), s, loss, prio

    # Pre-generated rotating item pool: identical insert traffic per arm.
    pool = [make_items(rng, n_envs, T, obs_dim, core_size) for _ in range(8)]

    def time_arm(arm):
        rpcs = []
        if arm == "host":
            store = ReplayBuffer(capacity, seed=1)
        elif arm == "device":
            store = DeviceReplayShard(capacity, seed=1, name=f"r2d2b_{arm}")
        elif arm == "host_rpc":
            srv, cli = Rpc(), Rpc()
            srv.set_name("r2d2b-replay-srv")
            cli.set_name("r2d2b-learner")
            cli.set_timeout(30)
            ReplayServer(srv, "replay", ReplayBuffer(capacity, seed=1))
            srv.listen(":0")
            addr = next(a for a in srv._listen_addrs if a.startswith("ipc://"))
            cli.connect(addr)
            store = ReplayClient(cli, "r2d2b-replay-srv", "replay")
            rpcs = [cli, srv]
        else:
            raise SystemExit(f"unknown arm {arm!r}")

        state = {
            "p": jax.tree_util.tree_map(jnp.copy, params0),
            "s": opt.init(params0),
            "i": 0,
        }
        # Warm the store past one learn batch of sequences.
        for k in range(max(2, (2 * B) // n_envs + 1)):
            store.add(pool[k % len(pool)])

        def step():
            store.add(pool[state["i"] % len(pool)])
            state["i"] += 1
            batch_items, idxs, weights = store.sample(B)
            if arm == "device":
                batch = {
                    k: jnp.swapaxes(batch_items[k], 0, 1)
                    for k in ("state", "done", "action", "reward")
                }
                batch["core"] = tuple(batch_items["core"])
                batch["is_weight"] = weights
            else:
                batch = {
                    k: jnp.asarray(np.swapaxes(np.asarray(batch_items[k]), 0, 1))
                    for k in ("state", "done", "action", "reward")
                }
                batch["core"] = tuple(jnp.asarray(c) for c in batch_items["core"])
                batch["is_weight"] = jnp.asarray(weights)
            state["p"], state["s"], loss, prio = update(
                state["p"], state["s"], target_params, batch
            )
            if arm == "device":
                store.update_priorities(idxs, prio)
            else:
                store.update_priorities(np.asarray(idxs), np.asarray(prio))
            return loss

        def run(iters):
            t0 = time.perf_counter()
            for _ in range(iters):
                loss = step()
            jax.block_until_ready(loss)
            return (time.perf_counter() - t0) / iters

        try:
            run(2)  # compile + warm
            sec = run(8)
        finally:
            for r in rpcs:
                r.close()
        return sec

    arms = [a.strip() for a in args.arms.split(",") if a.strip()]
    results = {}
    for arm in arms:
        sec = time_arm(arm)
        frames = B * T
        results[arm] = frames / sec
        print(json.dumps({
            "metric": "r2d2_learner_sps",
            "arm": arm,
            "value": round(frames / sec, 1),
            "unit": "env_frames/s",
            "step_ms": round(sec * 1e3, 2),
            "updates_per_s": round(1.0 / sec, 2),
            "params": n_params,
            "platform": dev.platform,
            "device_kind": dev.device_kind,
            "config": (
                f"replay-plane cycle (add+sample+update+prio writeback): "
                f"{B} sequences x T={T}, obs[{obs_dim}] f32, {n_envs} items "
                f"inserted/cycle, capacity {capacity}, mlp RecurrentQNet, "
                f"clip+adam"
            ),
        }), flush=True)

    bitexact = check_priority_bitexact()
    ingest = measure_ingest_write_once()
    summary = {
        "metric": "r2d2_replay_ab",
        "sps": {k: round(v, 1) for k, v in results.items()},
        "speedup_vs_host": (
            round(results["device"] / results["host"], 2)
            if "device" in results and "host" in results else None
        ),
        "speedup_vs_host_rpc": (
            round(results["device"] / results["host_rpc"], 2)
            if "device" in results and "host_rpc" in results else None
        ),
        "priorities_bitexact": bitexact,
        "ingest": ingest,
        "platform": dev.platform,
    }
    print(json.dumps(summary), flush=True)

    if args.check:
        problems = []
        for arm in arms:
            if not results.get(arm, 0) > 0:
                problems.append(f"arm {arm} produced no throughput")
        if not bitexact:
            problems.append("device priorities diverged from the numpy reference")
        if not ingest["write_once"]:
            problems.append(
                f"ingest bytes {ingest['ingest_out_bytes']} != payload "
                f"{ingest['payload_bytes']} (write-once violated)"
            )
        if problems:
            print("r2d2_bench --check FAILED: " + "; ".join(problems),
                  file=sys.stderr)
            return 1
        print("r2d2_bench --check OK", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
