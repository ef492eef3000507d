"""A2C on CartPole: the minimum end-to-end slice of the framework.

Counterpart of the reference's single-file agent (``examples/a2c.py``): an
EnvPool of CartPole environments, an in-process Broker, and an Accumulator in
standalone mode drive the full wants/has protocol — n-step returns, policy
gradient + baseline + entropy loss — with the jax twist that acting and
learning are two jitted functions and the optimizer is optax.

Run: ``python -m moolib_tpu.examples.a2c --total_steps 100000``
Multi-peer: start a broker (``python -m moolib_tpu.broker``), then several
``--connect host:port --no_standalone_broker`` processes; peers share
gradients elastically exactly like the reference.
"""

from __future__ import annotations

import argparse
import time
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
import optax

from .. import Accumulator, Broker, EnvPool, telemetry, utils
from ..envs import CartPoleEnv
from ..models import ActorCriticNet
from ..ops import discounted_returns, entropy_loss, softmax_cross_entropy
from ..utils.profiling import StepTimer
from ..watchdog import Watchdog
from .common import finalize_flags


def a2c_loss(params, model, batch, initial_core_state, discounting):
    """Policy-gradient + baseline + entropy loss over a [T+1, B] unroll
    (reference loss structure, ``examples/a2c.py:121-164``)."""
    outputs, _ = model.apply(params, batch, initial_core_state)
    logits = outputs["policy_logits"][:-1]  # [T, B, A]
    values = outputs["baseline"]  # [T+1, B]
    actions = batch["action"][:-1]  # action[t] is taken *from* state t
    rewards = batch["reward"][1:]  # reward[t+1] results from action[t]
    done = batch["done"][1:]
    discounts = (~done).astype(jnp.float32) * discounting
    returns = discounted_returns(rewards, discounts, jax.lax.stop_gradient(values[-1]))
    adv = returns - values[:-1]
    pg_loss = jnp.mean(softmax_cross_entropy(logits, actions) * jax.lax.stop_gradient(adv))
    baseline_loss = 0.5 * jnp.mean(adv**2)
    ent_loss = entropy_loss(logits)
    # Reference cost weighting (examples/a2c.py:24-25).
    total = pg_loss + 0.005 * baseline_loss + 0.0006 * ent_loss
    return total, {
        "pg_loss": pg_loss,
        "baseline_loss": baseline_loss,
        "entropy_loss": ent_loss,
    }


def make_flags(argv=None):
    p = argparse.ArgumentParser(description="moolib_tpu A2C on CartPole")
    p.add_argument("--total_steps", type=int, default=100_000)
    p.add_argument("--batch_size", type=int, default=2, help="envs per peer")
    p.add_argument("--rollout_length", type=int, default=64)
    p.add_argument("--num_processes", type=int, default=2)
    p.add_argument("--learning_rate", type=float, default=1e-3)
    p.add_argument("--discounting", type=float, default=0.99)
    p.add_argument("--virtual_batch_size", type=int, default=None)
    p.add_argument("--address", default="127.0.0.1:4431")
    p.add_argument("--connect", default=None, help="broker address (no in-process broker)")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--log_interval", type=float, default=2.0)
    p.add_argument("--no_lstm", action="store_true")
    p.add_argument("--quiet", action="store_true")
    p.add_argument("--watchdog", type=float, default=0.0,
                   help="deadman seconds per loop section (0 = off); expiry "
                   "dumps telemetry + thread stacks and raises "
                   "WatchdogTimeout (docs/RESILIENCE.md)")
    return finalize_flags(p, argv)


def train(flags, on_stats=None) -> dict:
    """Full training loop; returns final stats (for the integration test)."""
    # Before the first jit: restarts skip recompilation via the persistent
    # cache (utils/compile_cache.py).
    utils.init_compile_cache()
    # Opt-in exporters (MOOLIB_TELEMETRY_* env knobs, docs/TELEMETRY.md).
    telemetry.init_from_env()
    # kill -USR2 toggles an on-demand jax.profiler device-trace window.
    telemetry.profiling.install_signal_toggle()
    from ..testing import faults as _faults

    _faults.install_from_env()  # opt-in chaos (MOOLIB_FAULTS; no-op unset)
    # EnvPool must fork before jax spins up device state (same constraint the
    # reference solves with its early fork server, src/env.cc:149-169).
    envs = EnvPool(
        # 200-step cap = CartPole-v0, the reference's task (examples/a2c.py:117).
        # seed=None: OS entropy per env — a fixed seed would correlate the
        # whole batch. flags.seed still seeds the model/policy.
        partial(CartPoleEnv, max_episode_steps=200),
        num_processes=flags.num_processes,
        batch_size=flags.batch_size,
        num_batches=1,
    )

    model = ActorCriticNet(num_actions=2, use_lstm=not flags.no_lstm)
    B, T = flags.batch_size, flags.rollout_length
    rng = jax.random.key(flags.seed)

    def dummy_inputs(t, b):
        return {
            "state": jnp.zeros((t, b, 4), jnp.float32),
            "reward": jnp.zeros((t, b), jnp.float32),
            "done": jnp.zeros((t, b), bool),
            "prev_action": jnp.zeros((t, b), jnp.int32),
            "action": jnp.zeros((t, b), jnp.int32),
        }

    rng, init_rng = jax.random.split(rng)
    params = model.init(init_rng, dummy_inputs(1, B), model.initial_state(B))

    # Reference optimizer settings (examples/a2c.py:22-27,182-184).
    opt = optax.chain(
        optax.clip_by_global_norm(100.0),
        optax.adam(flags.learning_rate, b1=0.0, b2=0.99, eps=3e-7),
    )
    opt_state = opt.init(params)

    @jax.jit
    def act_step(params, inputs, core_state, rng_key):
        out, core_state = model.apply(params, inputs, core_state, sample_rng=rng_key)
        return out["action"][0], core_state

    grad_fn = jax.jit(
        jax.value_and_grad(
            partial(a2c_loss, model=model, discounting=flags.discounting), has_aux=True
        )
    )
    # Recompile detector (telemetry.devmon): flags shape churn in either jit.
    act_step = telemetry.devmon.instrument_jit(act_step, "a2c.act_step")
    grad_fn = telemetry.devmon.instrument_jit(grad_fn, "a2c.grad")

    broker: Optional[Broker] = None
    if flags.connect is None:
        broker = Broker()
        broker.set_name("broker")
        broker.listen(flags.address)
        broker_addr = flags.address
    else:
        broker_addr = flags.connect

    accumulator = Accumulator("a2c", params, buffers=None)
    accumulator.listen("127.0.0.1:0")
    if flags.virtual_batch_size:
        accumulator.set_virtual_batch_size(flags.virtual_batch_size)
    accumulator.connect(broker_addr)

    stats = {
        "mean_episode_return": 0.0,
        "episodes": 0,
        "steps": 0,
        "sgd_steps": 0,
        "pg_loss": 0.0,
        "entropy_loss": 0.0,
    }
    window_returns: list = []
    episode_return = np.zeros(B, np.float64)

    core_state = model.initial_state(B)
    action = jnp.zeros((B,), jnp.int32)
    prev_action = action
    steps_collected = []
    # Latest learn-step aux, kept as DEVICE scalars: fetched in one
    # device_get at the log tick instead of a float() learner-stream sync
    # on every SGD step.
    pending_aux = None
    last_log = time.time()
    start = time.time()
    # Loop-phase breakdown: sections export as loop_section_seconds{section=}
    # histograms + host spans (registry-backed StepTimer).
    timer = StepTimer()
    # Per-section deadman (--watchdog seconds; disabled at 0): a wedged env
    # step / learn step dumps diagnostics and raises instead of hanging.
    wd = Watchdog(timeout=flags.watchdog, name="a2c")

    try:
        while stats["steps"] < flags.total_steps:
            if broker is not None:
                broker.update()
            accumulator.update()

            if not accumulator.connected():
                time.sleep(0.05)
                continue

            if accumulator.wants_state():
                accumulator.set_state({"opt_state": opt_state, "steps": stats["steps"]})
            if accumulator.has_new_state():
                st = accumulator.state()
                if st is not None:
                    opt_state = st["opt_state"]
                    params = accumulator.parameters()
                    if not flags.quiet:
                        print(
                            f"received model version={accumulator.model_version()} "
                            f"from leader {accumulator.get_leader()}",
                            flush=True,
                        )

            # --- act -----------------------------------------------------
            with timer.section("env_step"), wd.section("env_step"):
                obs = envs.step(0, np.asarray(action)).result()
            reward = np.asarray(obs["reward"])
            done = np.asarray(obs["done"])
            episode_return += reward
            for i in np.nonzero(done)[0]:
                window_returns.append(episode_return[i])
                stats["episodes"] += 1
                episode_return[i] = 0.0
            stats["steps"] += B

            inputs = {
                "state": jnp.asarray(obs["state"])[None],
                "reward": jnp.asarray(reward, jnp.float32)[None],
                "done": jnp.asarray(done)[None],
                "prev_action": prev_action[None],
            }
            rng, act_rng = jax.random.split(rng)
            core_before = core_state  # LSTM state *entering* this step
            with timer.section("act"), wd.section("act"):
                new_action, new_core = act_step(params, inputs, core_state, act_rng)
            # result() returns zero-copy shm views valid only until the next
            # step on this batch index (same contract as the reference's
            # from_blob tensors) — copy anything we keep for the unroll.
            # Each step also records the LSTM state *entering* it so the
            # buffer can be trimmed at any boundary.
            steps_collected.append(
                {
                    "state": np.array(obs["state"], np.float32, copy=True),
                    "reward": np.array(reward, np.float32, copy=True),
                    "done": done.copy(),
                    "prev_action": np.asarray(prev_action),
                    "action": np.asarray(new_action),
                    "core": core_before,
                }
            )
            # While a reduction is in flight the learn branch can't consume;
            # keep only the freshest T+1 steps so the jitted unroll length
            # stays fixed (no per-length recompiles).
            if len(steps_collected) > T + 1:
                steps_collected = steps_collected[-(T + 1) :]
            prev_action = new_action
            action = new_action
            core_state = new_core

            # --- learn ---------------------------------------------------
            if accumulator.has_gradients():
                with timer.section("apply"), wd.section("apply"):
                    grads = accumulator.gradients()
                    updates, opt_state = opt.update(grads, opt_state, params)
                    params = optax.apply_updates(params, updates)
                    accumulator.set_parameters(params)
                    accumulator.zero_gradients()
                stats["sgd_steps"] += 1
            elif len(steps_collected) >= T + 1 and accumulator.wants_gradients():
                with timer.section("learn"), wd.section("learn"):
                    batch = {
                        k: jnp.asarray(np.stack([s[k] for s in steps_collected]))
                        for k in steps_collected[0]
                        if k != "core"
                    }
                    (loss, aux), grads = grad_fn(
                        params, batch=batch, initial_core_state=steps_collected[0]["core"]
                    )
                    pending_aux = (aux["pg_loss"], aux["entropy_loss"])
                    # Device grads straight in: the Accumulator's staging
                    # overlaps the per-leaf D2H (PR 4) — device_get here
                    # would block on the whole tree first.
                    accumulator.reduce_gradients(B, grads)
                # Carry the last step into the next unroll (overlap of 1);
                # it still records the LSTM state that entered it.
                steps_collected = steps_collected[-1:]

            if time.time() - last_log > flags.log_interval:
                last_log = time.time()
                if pending_aux is not None:
                    pg_v, ent_v = jax.device_get(pending_aux)
                    stats["pg_loss"] = float(pg_v)
                    stats["entropy_loss"] = float(ent_v)
                    pending_aux = None
                if window_returns:
                    stats["mean_episode_return"] = float(np.mean(window_returns[-100:]))
                sps = stats["steps"] / max(time.time() - start, 1e-6)
                if not flags.quiet:
                    print(
                        f"steps={stats['steps']} sps={sps:.0f} "
                        f"return={stats['mean_episode_return']:.1f} "
                        f"episodes={stats['episodes']} sgd={stats['sgd_steps']} "
                        f"pg={stats['pg_loss']:.3f} ent={stats['entropy_loss']:.3f} "
                        f"[{timer.report()}]",
                        flush=True,
                    )
                if on_stats is not None:
                    on_stats(dict(stats))
        if pending_aux is not None:  # tail flush so the returned stats are fresh
            pg_v, ent_v = jax.device_get(pending_aux)
            stats["pg_loss"] = float(pg_v)
            stats["entropy_loss"] = float(ent_v)
            pending_aux = None
    finally:
        wd.close()
        envs.close()
        accumulator.close()
        if broker is not None:
            broker.close()
        telemetry.flush()  # final JSONL snapshot + host trace, if enabled
    if window_returns:
        stats["mean_episode_return"] = float(np.mean(window_returns[-100:]))
    stats["window_returns"] = window_returns
    return stats


def main(argv=None):
    train(make_flags(argv))


if __name__ == "__main__":
    main()
