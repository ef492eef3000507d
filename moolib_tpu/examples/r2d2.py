"""R2D2-style recurrent Q-learning with distributed prioritized replay.

Covers the agent family the reference's users build on top of moolib
("R2D2 / recurrent PPO with LSTM policy + prioritized replay RPC",
BASELINE.json configs): EnvPool actors collect fixed-length sequences with
stored initial LSTM states, push them (with initial TD-error priorities)
into a replay store — the device-resident
:class:`moolib_tpu.replay.DeviceReplayShard` by default
(``--device_replay false`` for the legacy host
:class:`~moolib_tpu.replay.ReplayBuffer`), or served over RPC with
``--replay_peer`` for a distributed actor fleet — and the learner samples
prioritized sequence batches, replays them through the recurrent
Q-network (double-Q with a target network), and writes updated priorities
back (on the device path the TD errors never visit the host).

Run: ``python -m moolib_tpu.examples.r2d2 --total_steps 60000``
"""

from __future__ import annotations

import argparse
import time
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import optax

from .. import EnvPool, utils
from ..envs import CartPoleEnv
from ..models.qnet import RecurrentQNet
from ..replay import ReplayBuffer, ReplayClient, ReplayServer
from .common import finalize_flags


def make_flags(argv=None):
    p = argparse.ArgumentParser(description="moolib_tpu R2D2 (recurrent DQN + PER)")
    p.add_argument("--total_steps", type=int, default=100_000)
    p.add_argument("--batch_size", type=int, default=16, help="envs")
    p.add_argument("--seq_length", type=int, default=20)
    p.add_argument("--learn_batch", type=int, default=32, help="sequences per update")
    p.add_argument("--replay_capacity", type=int, default=4096)
    p.add_argument("--min_replay", type=int, default=200)
    p.add_argument("--learning_rate", type=float, default=1e-3)
    p.add_argument("--discounting", type=float, default=0.997)
    p.add_argument("--target_update_interval", type=int, default=100)
    p.add_argument("--eps_start", type=float, default=1.0)
    p.add_argument("--eps_end", type=float, default=0.05)
    p.add_argument("--eps_decay_steps", type=int, default=30_000)
    p.add_argument("--num_processes", type=int, default=2)
    p.add_argument("--replay_peer", default=None, help="remote replay server peer name")
    p.add_argument(
        "--device_replay",
        type=_bool_flag,
        default=True,
        help="device-resident replay shard (sum-tree + ring on chip); "
        "`--device_replay false` keeps the legacy host ReplayBuffer",
    )
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--log_interval", type=float, default=5.0)
    p.add_argument("--quiet", action="store_true")
    return finalize_flags(p, argv)


def _bool_flag(v) -> bool:
    """argparse-friendly bool: ``--device_replay false`` works (store_true
    can't express an =false override)."""
    return str(v).strip().lower() not in ("0", "false", "no", "off", "")


def td_loss(params, target_params, model, batch, discounting):
    """Sequence double-Q loss; returns (loss, per-sequence TD errors)."""
    init = tuple(batch["core"]) if "core" in batch else ()
    out, _ = model.apply(params, batch, init)
    q = out["q"][:-1]  # [T, B, A]
    tq_out, _ = model.apply(target_params, batch, init)
    target_q = tq_out["q"]  # [T+1, B, A]
    online_next = out["q"][1:]

    actions = batch["action"][:-1]
    rewards = batch["reward"][1:]
    notdone = (~batch["done"][1:]).astype(jnp.float32)
    q_taken = jnp.take_along_axis(q, actions[..., None], axis=-1).squeeze(-1)
    # Double-Q: argmax online, evaluate target.
    next_action = jnp.argmax(online_next, axis=-1)
    next_q = jnp.take_along_axis(target_q[1:], next_action[..., None], axis=-1).squeeze(-1)
    targets = rewards + discounting * notdone * jax.lax.stop_gradient(next_q)
    td = targets - q_taken
    weights = batch.get("is_weight")
    per_elem = 0.5 * td**2
    if weights is not None:
        per_elem = per_elem * weights[None, :]
    loss = jnp.mean(per_elem)
    # R2D2 priority: eta*max + (1-eta)*mean of |td| over the sequence.
    abs_td = jnp.abs(td)
    prio = 0.9 * abs_td.max(axis=0) + 0.1 * abs_td.mean(axis=0)
    return loss, jax.lax.stop_gradient(prio)


def train(flags, on_stats=None) -> dict:
    utils.init_compile_cache()  # before the first jit (utils/compile_cache.py)
    envs = EnvPool(
        partial(CartPoleEnv, max_episode_steps=200),
        num_processes=flags.num_processes,
        batch_size=flags.batch_size,
        num_batches=1,
    )
    model = RecurrentQNet(num_actions=2)
    B, T = flags.batch_size, flags.seq_length
    rng = jax.random.key(flags.seed)

    def dummy(t, b):
        return {
            "state": jnp.zeros((t, b, 4), jnp.float32),
            "done": jnp.zeros((t, b), bool),
            "action": jnp.zeros((t, b), jnp.int32),
            "reward": jnp.zeros((t, b), jnp.float32),
        }

    rng, init_rng = jax.random.split(rng)
    params = model.init(init_rng, dummy(1, B), model.initial_state(B))
    target_params = params
    opt = optax.chain(optax.clip_by_global_norm(40.0), optax.adam(flags.learning_rate))
    opt_state = opt.init(params)

    @jax.jit
    def act_step(params, inputs, core_state, rng_key, eps):
        out, new_core = model.apply(params, inputs, core_state)
        greedy = jnp.argmax(out["q"][0], axis=-1)
        rand = jax.random.randint(rng_key, greedy.shape, 0, model.num_actions)
        explore = jax.random.uniform(jax.random.fold_in(rng_key, 1), greedy.shape) < eps
        return jnp.where(explore, rand, greedy).astype(jnp.int32), new_core

    grad_fn = jax.jit(
        jax.value_and_grad(
            partial(td_loss, model=model, discounting=flags.discounting), has_aux=True
        )
    )

    device_store = bool(flags.device_replay) and not flags.replay_peer
    if flags.replay_peer:
        from .. import Rpc

        rpc = Rpc()
        rpc.set_name(f"r2d2-actor-{flags.seed}")
        rpc.connect(flags.replay_peer)
        replay = ReplayClient(rpc, "replay-server", "replay")
    elif device_store:
        from ..replay import DeviceReplayShard

        replay = DeviceReplayShard(
            flags.replay_capacity, seed=flags.seed, name="r2d2_replay"
        )
    else:
        replay = ReplayBuffer(flags.replay_capacity, seed=flags.seed)

    stats = {"steps": 0, "episodes": 0, "sgd_steps": 0, "loss": 0.0, "eps": 1.0}
    replay_warm = False
    window_returns: list = []
    episode_return = np.zeros(B)

    core_state = model.initial_state(B)
    action = np.zeros(B, np.int64)
    seq: list = []
    start = time.time()
    last_log = time.time()

    def epsilon():
        f = min(1.0, stats["steps"] / flags.eps_decay_steps)
        return flags.eps_start + f * (flags.eps_end - flags.eps_start)

    try:
        while stats["steps"] < flags.total_steps:
            obs = envs.step(0, action).result()
            reward = np.array(obs["reward"], np.float32, copy=True)
            done = np.array(obs["done"], copy=True)
            episode_return += reward
            for i in np.nonzero(done)[0]:
                window_returns.append(episode_return[i])
                stats["episodes"] += 1
                episode_return[i] = 0.0
            stats["steps"] += B

            inputs = {
                "state": jnp.asarray(np.array(obs["state"], np.float32, copy=True))[None],
                "done": jnp.asarray(done)[None],
            }
            rng, akey = jax.random.split(rng)
            core_before = core_state
            new_action, core_state = act_step(
                params, inputs, core_state, akey, epsilon()
            )
            seq.append(
                {
                    "state": np.asarray(inputs["state"][0]),
                    "done": done,
                    "action": np.asarray(new_action),
                    "reward": reward,
                    "core": core_before,
                }
            )
            action = np.asarray(new_action)

            if len(seq) >= T + 1:
                # Split the [T+1, B] window into B per-env sequences.
                stacked = {
                    k: np.stack([s[k] for s in seq]) for k in seq[0] if k != "core"
                }
                core0 = seq[0]["core"]
                items = []
                for b in range(B):
                    item = {k: v[:, b] for k, v in stacked.items()}
                    item["core"] = tuple(np.asarray(c[b]) for c in core0)
                    items.append(item)
                replay.add(items)
                seq = seq[-1:]

            # Latch once past min_replay: the ring never shrinks, and in
            # remote mode size() is a blocking RPC we must not pay per step.
            if not replay_warm:
                replay_warm = replay.size() >= flags.min_replay
            if replay_warm:
                batch_items, idxs, weights = replay.sample(flags.learn_batch)
                if device_store:
                    # Device arrays stay on device: [N, T+1, ...] ->
                    # time-major without a host hop.
                    batch = {
                        k: jnp.swapaxes(batch_items[k], 0, 1)
                        for k in ("state", "done", "action", "reward")
                    }
                    batch["core"] = tuple(batch_items["core"])
                    batch["is_weight"] = weights
                else:
                    # batch leaves: [N, T+1, ...] -> time-major [T+1, N, ...]
                    batch = {
                        "state": jnp.asarray(np.swapaxes(np.asarray(batch_items["state"]), 0, 1)),
                        "done": jnp.asarray(np.swapaxes(np.asarray(batch_items["done"]), 0, 1)),
                        "action": jnp.asarray(np.swapaxes(np.asarray(batch_items["action"]), 0, 1)),
                        "reward": jnp.asarray(np.swapaxes(np.asarray(batch_items["reward"]), 0, 1)),
                        # core was nest-stacked: already a tuple of [N, H] arrays.
                        "core": tuple(jnp.asarray(c) for c in batch_items["core"]),
                        "is_weight": jnp.asarray(weights),
                    }
                (loss, prio), grads = grad_fn(params, target_params, batch=batch)
                updates, opt_state = opt.update(grads, opt_state, params)
                params = optax.apply_updates(params, updates)
                if device_store:
                    # Priority write-back consumes the device TD errors
                    # without realizing them on host.
                    replay.update_priorities(idxs, prio)
                else:
                    replay.update_priorities(np.asarray(idxs), np.asarray(prio))
                stats["loss"] = float(loss)
                stats["sgd_steps"] += 1
                if stats["sgd_steps"] % flags.target_update_interval == 0:
                    target_params = params

            if time.time() - last_log > flags.log_interval:
                last_log = time.time()
                stats["eps"] = epsilon()
                ret = float(np.mean(window_returns[-50:])) if window_returns else 0.0
                sps = stats["steps"] / max(time.time() - start, 1e-6)
                if not flags.quiet:
                    print(
                        f"steps={stats['steps']} sps={sps:.0f} return={ret:.1f} "
                        f"sgd={stats['sgd_steps']} loss={stats['loss']:.4f} "
                        f"eps={stats['eps']:.2f}",
                        flush=True,
                    )
                if on_stats is not None:
                    on_stats(dict(stats))
    finally:
        envs.close()
    stats["mean_episode_return"] = (
        float(np.mean(window_returns[-50:])) if window_returns else 0.0
    )
    stats["window_returns"] = window_returns
    return stats


def serve_replay(argv=None):
    """Run a standalone replay server: ``python -m moolib_tpu.examples.r2d2 serve``."""
    from .. import Rpc

    p = argparse.ArgumentParser()
    p.add_argument("--address", default="0.0.0.0:4441")
    p.add_argument("--capacity", type=int, default=100_000)
    p.add_argument("--device", type=_bool_flag, default=False,
                   help="serve a device-resident shard (memfd ingest + "
                   "cohort sampling endpoints) instead of the host buffer")
    p.add_argument("--shard_index", type=int, default=0)
    p.add_argument("--num_shards", type=int, default=1)
    args = p.parse_args(argv)
    rpc = Rpc()
    rpc.set_name("replay-server")
    if args.device:
        from ..replay import DeviceReplayShard, ReplayShardService

        shard = DeviceReplayShard(args.capacity, name="replay_srv")
        ReplayShardService(rpc, "replay", shard,
                           shard_index=args.shard_index,
                           num_shards=args.num_shards)
    else:
        ReplayServer(rpc, "replay", ReplayBuffer(args.capacity))
    rpc.listen(args.address)
    print(f"replay server on {args.address}")
    while True:
        time.sleep(1)


def main(argv=None):
    import sys

    argv = sys.argv[1:] if argv is None else argv
    if argv and argv[0] == "serve":
        serve_replay(argv[1:])
    else:
        train(make_flags(argv))


if __name__ == "__main__":
    main()
