"""IMPALA (V-trace) distributed agent — the flagship example.

Counterpart of the reference's ``examples/vtrace/experiment.py`` with the
same loop priority order (``:364-529``):

1. pump group/accumulator; serve/consume state sync
2. stats allreduce on an interval; leader-only checkpointing
3. if gradients are ready: optimizer step + ``zero_gradients``
4. elif a learner batch is ready and the cohort wants gradients:
   forward + v-trace loss + backward → ``reduce_gradients``
5. else act: round-robin over double-buffered actor batches — EnvPool step,
   jitted inference, time-batching into [T+1, B] unrolls, learner batch
   assembly by concatenation along the batch dim

TPU design: acting and learning are two jitted functions on the same chip
(the reference's CUDA stream games become XLA async dispatch); the learner
step can optionally shard over a mesh (``--mesh dp=N``) in which case the
batch is split over ``dp`` and XLA all-reduces gradients over ICI *inside*
the step, with the Accumulator handling only cross-host elasticity.

Run: ``python -m moolib_tpu.examples.vtrace.experiment --env catch``
"""

from __future__ import annotations

import argparse
import os
import pickle
import time
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
import optax

from ... import Accumulator, Batcher, Broker, EnvPool, Group, Rpc, rollout, telemetry, utils
from ...envs import CartPoleEnv, CatchEnv, SyntheticAtariEnv
from ...models import ActorCriticNet, ImpalaNet
from ...ops import entropy_loss, softmax_cross_entropy, vtrace
from ...utils.profiling import StepTimer
from ...watchdog import Watchdog
from .. import common


def make_flags(argv=None):
    p = argparse.ArgumentParser(description="moolib_tpu IMPALA (vtrace)")
    p.add_argument(
        "--env",
        default="catch",
        help="catch | catch_flat | pixel_catch | cartpole | synthetic | "
        "atari:<Game> (needs ale_py) | gym:<gymnasium id> (Discrete actions)",
    )
    p.add_argument("--total_steps", type=int, default=500_000)
    p.add_argument("--actor_batch_size", type=int, default=32)
    p.add_argument("--num_actor_batches", type=int, default=2)
    p.add_argument("--unroll_length", type=int, default=20)
    p.add_argument("--batch_size", type=int, default=8, help="learner batch (unrolls)")
    p.add_argument("--virtual_batch_size", type=int, default=8)
    p.add_argument("--num_env_processes", type=int, default=4)
    p.add_argument("--learning_rate", type=float, default=1e-3)
    p.add_argument("--discounting", type=float, default=0.99)
    p.add_argument("--entropy_cost", type=float, default=0.01)
    p.add_argument("--baseline_cost", type=float, default=0.5)
    p.add_argument("--grad_norm_clipping", type=float, default=40.0)
    p.add_argument("--use_lstm", action="store_true")
    p.add_argument("--address", default="127.0.0.1:4431")
    p.add_argument("--connect", default=None, help="external broker address")
    p.add_argument(
        "--broker_addrs", default=None,
        help="comma-separated broker addresses (primary + hot standbys, "
        "docs/RESILIENCE.md 'Broker failover'): when the list contains "
        "--address this peer hosts the primary and replicates to the "
        "others; otherwise it joins with failover across the list "
        "(--connect stays the single-address alias)")
    p.add_argument("--local_name", default=None)
    p.add_argument("--train_id", default="impala")
    p.add_argument("--checkpoint", default=None)
    p.add_argument("--checkpoint_interval", type=float, default=600.0)
    p.add_argument(
        "--checkpoint_dir", default=None,
        help="distributed checkpoint plane for --shard_grads cohorts "
        "(docs/RESILIENCE.md 'Distributed checkpoints'): a SHARED "
        "directory where every host writes its shard of the snapshot and "
        "the leader two-phase-commits the cohort manifest; restore "
        "re-cuts shards onto the restart cohort size")
    p.add_argument("--stats_interval", type=float, default=2.0)
    p.add_argument("--log_interval", type=float, default=5.0)
    p.add_argument("--device", default=None, help="jax device str, e.g. 'tpu:0'")
    p.add_argument(
        "--ici",
        action="store_true",
        help="reduce gradients over the ICI data plane (XLA psum across the "
        "jax.distributed process set) instead of the RPC tree; the RPC stack "
        "still handles election/model sync/elasticity (SURVEY §7 stage 5)",
    )
    p.add_argument(
        "--coordinator",
        default=None,
        help="jax.distributed coordinator address for multi-host (host:port); "
        "requires --num_processes and --process_id",
    )
    p.add_argument("--num_processes", type=int, default=None)
    p.add_argument("--process_id", type=int, default=None)
    p.add_argument(
        "--mesh",
        default=None,
        help='device mesh for the learner step, e.g. "dp=2,tp=2": the batch '
        "shards over dp, params TP-shard over tp (+FSDP over dp for big "
        "leaves), and XLA all-reduces gradients over ICI inside the jitted "
        "step; the Accumulator then only reduces across hosts",
    )
    p.add_argument(
        "--wire_dtype",
        default=None,
        choices=[None, "bf16", "int8"],
        help="compress gradient allreduce payloads (bf16: 2x, int8+EF: 4x)",
    )
    p.add_argument(
        "--shard_grads",
        action="store_true",
        help="hierarchical reduce plane (DESIGN.md §6d): with --mesh the "
        "jitted step already psums grads over in-mesh dp; this additionally "
        "makes the Accumulator's inter-host rounds sharded — each host "
        "reduce-scatters a disjoint 1/N slice of the flat payload, cutting "
        "contributed bytes to (N-1)/N.  Composes with --actor_mesh/Sebulba "
        "and wire compression; every cohort peer must pass it",
    )
    p.add_argument(
        "--chunked",
        action="store_true",
        help="force gradient rounds over the chunked ring allreduce "
        "(Group.ring_auto would keep a same-host cohort on the tree)",
    )
    p.add_argument(
        "--overlap_grads",
        action="store_true",
        help="latency-hiding gradient pipeline (DESIGN.md §6e): the learner "
        "step runs as a two-jit backward schedule and gradients stream "
        "into the inter-host allreduce bucket-by-bucket while the head of "
        "backward is still computing.  Bit-identical results; streaming "
        "launch engages when --virtual_batch_size 0 (with vbatch the "
        "stream is consumed but buckets wait for the accumulation "
        "barrier).  Unmeshed learner only (with --mesh the in-jit psum "
        "already overlaps over ICI)",
    )
    p.add_argument(
        "--trace_dir",
        default=None,
        help="capture a jax profiler trace of the first learner steps here",
    )
    p.add_argument(
        "--localdir",
        default=None,
        help="write stats rows to <localdir>/logs.tsv with latest symlink + "
        "metadata.json (reference examples/common/record.py)",
    )
    p.add_argument(
        "--wandb",
        action="store_true",
        help="log stats to wandb when the package is installed (gated no-op "
        "otherwise — reference experiment.py:269-276 opt-in)",
    )
    p.add_argument(
        "--device_rollout",
        type=_bool_flag,
        default=True,
        help="device-resident actor pipeline (docs/DESIGN.md 'Actor data "
        "plane'): on-chip [T+1, B] rollout buffers written by a fused act "
        "step, uint8 single-crossing obs upload, async action fetch, "
        "on-device learner batch assembly.  --device_rollout=false keeps "
        "the legacy host-batcher path (bit-exact trajectories, 3 float32 "
        "host-boundary crossings per frame)",
    )
    p.add_argument(
        "--env_backend",
        default="envpool",
        choices=["envpool", "jax"],
        help="envpool: host envs in worker processes (the EnvPool plane); "
        "jax: pure-JAX on-device envs (envs.jax_envs) fused into the rollout "
        "— the Podracer 'Anakin' architecture, zero host-boundary bytes per "
        "frame.  jax supports --env catch_flat/catch and catch_proc",
    )
    p.add_argument(
        "--actor_mesh",
        type=int,
        default=0,
        help="Sebulba split (requires --mesh and --env_backend jax): carve "
        "the first N mesh devices into a dedicated actor submesh running "
        "the fused rollout; the remainder is the learner mesh and completed "
        "unrolls hop between them device-to-device through the Batcher "
        "(batcher_d2d_bytes_total)",
    )
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--quiet", action="store_true")
    p.add_argument(
        "--batcher_max_outstanding", type=int, default=None,
        help="bound the learn batcher's ready queue: actor-side assembly "
        "blocks once this many completed batches await the learner "
        "(Sebulba-seam flow control; default None = legacy unbounded)",
    )
    p.add_argument(
        "--autoscale", action="store_true",
        help="broker-hosting peer only: supervise an elastic worker fleet — "
        "poll the workers' telemetry snapshots and grow/shrink the cohort "
        "between --autoscale_min and --autoscale_max supervised workers "
        "(moolib_tpu.autoscaler; this peer itself is not counted)",
    )
    p.add_argument("--autoscale_min", type=int, default=1,
                   help="minimum supervised workers under --autoscale")
    p.add_argument("--autoscale_max", type=int, default=4,
                   help="maximum supervised workers under --autoscale")
    p.add_argument("--autoscale_interval", type=float, default=2.0,
                   help="supervision poll cadence seconds under --autoscale")
    p.add_argument("--watchdog", type=float, default=0.0,
                   help="deadman seconds per loop section (0 = off); expiry "
                   "dumps telemetry + thread stacks and raises "
                   "WatchdogTimeout, so the finally-block leader checkpoint "
                   "still lands (docs/RESILIENCE.md)")
    return common.finalize_flags(p, argv)


def _bool_flag(v) -> bool:
    """argparse-friendly bool: ``--device_rollout false`` works (store_true
    can't express an =false override)."""
    return str(v).strip().lower() not in ("0", "false", "no", "off", "")


# Sebulba control-plane traffic: how many bytes of params the actor submesh
# pulls per learner version bump (docs/TELEMETRY.md).
_M_PARAM_SYNC = telemetry.get_registry().counter(
    "actor_param_sync_bytes_total",
    "Sebulba actor-submesh param refreshes (learner -> actor devices)",
)


def _actor_rep_sharding(actor_mesh):
    from jax.sharding import NamedSharding, PartitionSpec as P

    return NamedSharding(actor_mesh, P())


def make_env_factory(flags):
    # Envs use OS-entropy seeding (seed=None): a fixed seed here would make
    # every env in every worker replay identical trajectories, silently
    # correlating the whole actor batch. flags.seed still seeds the model.
    if flags.env == "catch":
        return CatchEnv, CatchEnv().num_actions, (10, 5, 1)
    if flags.env == "catch_flat":
        # Board flattened to a (50,) uint8 vector -> ActorCriticNet MLP:
        # per-frame model compute is negligible, so whole-agent SPS measures
        # the actor data plane itself (agent_bench --scale small).
        from ...envs import FlatCatchEnv

        return FlatCatchEnv, FlatCatchEnv.num_actions, (50,)
    if flags.env == "pixel_catch":
        # Catch rendered as a frame: the optimal policy requires *reading the
        # pixels* (ball position only exists in the image), so this is the
        # learnable-from-pixels bar for the ImpalaNet ResNet encoder
        # (VERDICT round-1 ask #7; intent of the reference's Atari flagship).
        factory = partial(CatchEnv, frame_shape=(42, 42))
        return factory, CatchEnv.num_actions, (42, 42, 1)
    if flags.env == "pixel_catch84":
        # The reference's full observation scale: (84, 84, 4) stacked frames
        # (examples/atari/environment.py) through the complete 16/32/32
        # ImpalaNet — the pixel bar at Atari geometry, without ALE.
        factory = _pixel_catch84_factory
        return factory, CatchEnv.num_actions, (84, 84, 4)
    if flags.env == "cartpole":
        return CartPoleEnv, 2, (4,)
    if flags.env.startswith("atari:"):
        # Real ALE (reference examples/atari/environment.py), e.g.
        # --env atari:Pong.  Probe once in the parent for a clear error and
        # for the action count; workers build their own instances.
        from ...envs.atari import create_env

        game = flags.env.split(":", 1)[1]
        probe = create_env(game)
        n, shape = probe.num_actions, probe.observation_shape
        probe.close()
        return partial(create_env, game), n, shape
    if flags.env.startswith("gym:"):
        # Any gymnasium env id with a Discrete action space, e.g.
        # --env gym:CartPole-v1, through the GymEnv protocol adapter.
        from ...envs.atari import GymEnv

        env_id = flags.env.split(":", 1)[1]
        probe = GymEnv(env_id)
        n, shape = probe.num_actions, probe.reset().shape
        probe.close()
        return partial(GymEnv, env_id), n, tuple(shape)
    if flags.env != "synthetic":
        raise ValueError(
            f"unknown --env {flags.env!r} (catch | catch_flat | pixel_catch "
            "| pixel_catch84 | cartpole | synthetic | atari:<Game> | gym:<id>)"
        )
    return SyntheticAtariEnv, 6, (84, 84, 4)


def _pixel_catch84_factory():
    # Module-level (picklable) for EnvPool's forkserver path.
    from ...envs import FrameStack

    return FrameStack(CatchEnv(frame_shape=(84, 84)), num_stack=4)


def make_model(flags, num_actions, obs_shape):
    if len(obs_shape) == 3:
        channels = (16, 32, 32) if obs_shape[0] >= 32 else (16, 32)
        return ImpalaNet(
            num_actions=num_actions, channels=channels, use_lstm=flags.use_lstm
        )
    return ActorCriticNet(num_actions=num_actions, use_lstm=flags.use_lstm)


def compute_loss(params, batch, initial_core_state, model, flags):
    """V-trace actor-critic loss over a [T+1, B] learner batch (reference
    ``experiment.py:103-155``)."""
    learner_outputs, _ = model.apply(params, batch, initial_core_state)
    target_logits = learner_outputs["policy_logits"][:-1]
    baseline = learner_outputs["baseline"]
    bootstrap_value = baseline[-1]

    behavior_logits = batch["policy_logits"][:-1]
    actions = batch["action"][:-1]
    rewards = jnp.clip(batch["reward"][1:], -1, 1)
    done = batch["done"][1:]
    discounts = (~done).astype(jnp.float32) * flags.discounting

    vt = vtrace.from_logits(
        behavior_logits,
        target_logits,
        actions,
        discounts,
        rewards,
        baseline[:-1],
        jax.lax.stop_gradient(bootstrap_value),
    )
    pg_loss = jnp.mean(
        softmax_cross_entropy(target_logits, actions) * vt.pg_advantages
    )
    baseline_loss = 0.5 * jnp.mean((vt.vs - baseline[:-1]) ** 2)
    ent_loss = entropy_loss(target_logits)
    total = (
        pg_loss
        + flags.baseline_cost * baseline_loss
        + flags.entropy_cost * ent_loss
    )
    return total, {
        "pg_loss": pg_loss,
        "baseline_loss": baseline_loss,
        "entropy_loss": ent_loss,
    }


def _use_checkpointer(path: str) -> bool:
    """A ``.pkl`` path keeps the reference-style single-file pickle; any
    other path is treated as a Checkpointer directory (orbax when
    available: sharding-aware, retains history)."""
    return not path.endswith(".pkl")


def save_checkpoint(path, params, opt_state, steps, model_version):
    state = {
        "params": jax.device_get(params),
        "opt_state": jax.device_get(opt_state),
        "steps": steps,
        "model_version": model_version,
    }
    if _use_checkpointer(path):
        from ...checkpoint import Checkpointer

        Checkpointer(path).save(int(steps), state)
        return
    tmp = path + ".tmp"
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    with open(tmp, "wb") as f:
        pickle.dump(state, f)
    os.replace(tmp, path)  # atomic tmp+rename like the reference (:186-204)


def load_checkpoint(path, target=None):
    """``target`` is a template pytree (same treedef as what was saved) so
    orbax restores container types — optax states are NamedTuples —
    faithfully; the pickle path preserves types on its own."""
    if _use_checkpointer(path):
        from ...checkpoint import Checkpointer

        ck = Checkpointer(path)
        return ck.restore(target=target)
    with open(path, "rb") as f:
        return pickle.load(f)


def train(flags, on_stats=None) -> dict:
    # Before the first jit: restarts must hit the persistent compile cache
    # (utils/compile_cache.py).
    utils.init_compile_cache()
    # Opt-in exporters (MOOLIB_TELEMETRY_* env knobs, docs/TELEMETRY.md):
    # Prometheus /metrics endpoint, JSONL snapshots, SIGUSR1 dumps.
    tele = telemetry.init_from_env()
    # kill -USR2 toggles an on-demand jax.profiler device-trace window.
    telemetry.profiling.install_signal_toggle()
    if tele["http_port"]:
        print(f"telemetry: http://127.0.0.1:{tele['http_port']}/metrics", flush=True)
    from ...testing import faults as _faults

    _faults.install_from_env()  # opt-in chaos (MOOLIB_FAULTS; no-op unset)
    if flags.coordinator:
        # Multi-host: join the jax.distributed world before any device use.
        from ... import parallel as _parallel

        _parallel.initialize_distributed(
            flags.coordinator,
            num_processes=flags.num_processes,
            process_id=flags.process_id,
        )
    if flags.actor_mesh and (not flags.mesh or flags.env_backend != "jax"):
        raise ValueError(
            "--actor_mesh is the Sebulba split: it needs --mesh (devices to "
            "split) and --env_backend jax (the actor submesh runs on-device "
            "envs)"
        )
    jax_env = None
    if flags.env_backend == "jax":
        # Anakin: the env lives on the device; no worker processes at all.
        from ...envs import make_jax_env

        jax_env = make_jax_env(flags.env)
        num_actions = jax_env.num_actions
        obs_shape = tuple(jax_env.obs_spec[0])
        envs = []
    else:
        env_factory, num_actions, obs_shape = make_env_factory(flags)
        # Fork env workers before jax device state exists in this process.
        envs = [
            EnvPool(
                env_factory,
                num_processes=flags.num_env_processes,
                batch_size=flags.actor_batch_size,
                num_batches=1,
            )
            for _ in range(flags.num_actor_batches)
        ]

    model = make_model(flags, num_actions, obs_shape)
    B = flags.actor_batch_size
    T = flags.unroll_length
    rng = jax.random.key(flags.seed)
    device = None
    if flags.device:
        matches = [d for d in jax.devices() if flags.device in str(d).lower()]
        if not matches:
            raise ValueError(
                f"--device {flags.device!r} matches none of {jax.devices()}"
            )
        device = matches[0]

    def dummy_batch(t, b):
        return {
            "state": jnp.zeros((t, b, *obs_shape), jnp.float32),
            "reward": jnp.zeros((t, b), jnp.float32),
            "done": jnp.zeros((t, b), bool),
            "prev_action": jnp.zeros((t, b), jnp.int32),
            "action": jnp.zeros((t, b), jnp.int32),
            "policy_logits": jnp.zeros((t, b, num_actions), jnp.float32),
        }

    rng, init_rng = jax.random.split(rng)
    params = model.init(init_rng, dummy_batch(1, B), model.initial_state(B))
    opt = optax.chain(
        optax.clip_by_global_norm(flags.grad_norm_clipping),
        optax.rmsprop(flags.learning_rate, decay=0.99, eps=0.01),
    )
    opt_state = opt.init(params)
    steps_done = 0
    model_version = 0

    if flags.checkpoint and os.path.exists(flags.checkpoint):
        template = {
            "params": params,
            "opt_state": opt_state,
            "steps": 0,
            "model_version": 0,
        }
        ck = load_checkpoint(flags.checkpoint, target=template)
        if ck is not None:
            params, opt_state = ck["params"], ck["opt_state"]
            steps_done, model_version = ck["steps"], ck["model_version"]

    dckpt = None
    if flags.checkpoint_dir:
        if not flags.shard_grads:
            raise ValueError(
                "--checkpoint_dir is the distributed checkpoint plane and "
                "requires --shard_grads (use --checkpoint for single-host "
                "snapshots)"
            )
        from ...checkpoint import DistributedCheckpointer

        dckpt = DistributedCheckpointer(flags.checkpoint_dir)
        r = dckpt.restore()
        if r is not None:
            # The committed step IS the model version the cohort agreed on
            # at capture; election then prefers this restored peer.
            model_version, (params, _buffers, st) = r
            opt_state = st["opt_state"]
            steps_done = int(st.get("steps", 0))
            print(f"resumed from checkpoint step {model_version}", flush=True)

    @jax.jit
    def act_step(params, inputs, core_state, rng_key):
        out, new_core = model.apply(params, inputs, core_state, sample_rng=rng_key)
        return out, new_core

    # Device performance plane: signature-tracked jits (recompile flight
    # events) + XLA step cost for the MFU/roofline log fields below.
    act_step = telemetry.devmon.instrument_jit(act_step, "vtrace.act_step")

    # Learner step: plain jit, or sharded over a dp×tp mesh (one mesh, one
    # jit — VERDICT round-1 ask #5; same shardings as dryrun_multichip).
    raw_grad = jax.value_and_grad(
        partial(compute_loss, model=model, flags=flags), has_aux=True
    )
    mesh = None
    batch_sharding = None
    core_sharding = None

    def _opt_apply(p, o, g):
        updates, o = opt.update(g, o, p)
        return optax.apply_updates(p, updates), o

    actor_mesh = None
    if flags.mesh and getattr(flags, "overlap_grads", False):
        raise ValueError(
            "--overlap_grads is the unmeshed learner's overlap plane; with "
            "--mesh the jitted step already psums gradients over ICI inside "
            "the jit (drop one of the two flags)"
        )
    if flags.mesh:
        from ... import parallel
        from jax.sharding import NamedSharding, PartitionSpec as P

        mesh = parallel.parse_mesh_spec(flags.mesh)
        if flags.actor_mesh:
            # Sebulba: the actor submesh runs the fused rollout, the rest of
            # the devices (below, as `mesh`) form the learner; trajectories
            # hop between them through the Batcher's device_put.
            actor_mesh, mesh = parallel.split_mesh(mesh, flags.actor_mesh)
            # split_mesh partitions by construction; the explicit check
            # keeps a future hand-rolled spec from wedging the cohort at
            # the first cross-program collective (a clear error instead).
            parallel.check_disjoint(mesh, actor_mesh,
                                    what_a="--mesh (learner remainder)",
                                    what_b="--actor_mesh")
        if flags.batch_size % mesh.shape.get("dp", 1):
            raise ValueError("the dp mesh axis size must divide --batch_size")
        sp = mesh.shape.get("sp", 1)
        if (flags.unroll_length + 1) % sp:
            raise ValueError("the sp mesh axis size must divide unroll_length+1")
        param_sh = parallel.auto_shardings(params, mesh)
        rep = parallel.replicated(mesh)
        # [T+1, B, ...]: batch over dp, and the unroll (time) axis over sp
        # when present — sequence parallelism on the learner batch.
        batch_sharding = NamedSharding(mesh, P("sp" if sp > 1 else None, "dp"))
        core_sharding = NamedSharding(mesh, P("dp"))  # [B, ...]
        params = jax.device_put(params, param_sh)
        # Optimizer moments follow the same TP/FSDP layout as the params
        # (auto_shardings is shape-driven, so same-shaped leaves get the
        # same specs) — without this they'd sit whole on one device and
        # defeat the FSDP memory win.
        opt_sh = parallel.auto_shardings(opt_state, mesh)
        opt_state = jax.device_put(opt_state, opt_sh)
        grad_fn = jax.jit(
            raw_grad,
            in_shardings=(param_sh, batch_sharding, core_sharding),
            out_shardings=((rep, rep), param_sh),
        )
        # No donation: the Accumulator retains references to the previous
        # params tree for model sync; donating would invalidate them.
        opt_apply = jax.jit(
            _opt_apply,
            in_shardings=(param_sh, opt_sh, param_sh),
            out_shardings=(param_sh, opt_sh),
        )
    elif getattr(flags, "overlap_grads", False):
        # Two-jit overlap schedule (DESIGN.md §6e): the step returns loss,
        # aux, and a GradientStream delivering the tail of the flatten
        # order first; reduce_gradients() consumes it and launches each
        # bucket's inter-host reduce while the head jit is still running.
        # Bit-identical to the single-jit step (same primal/backward
        # graphs, cut on a leaf boundary).
        from ... import parallel

        _ostep = parallel.make_train_step(
            lambda p, b, r: compute_loss(
                p, b["batch"], b["core"], model=model, flags=flags
            ),
            overlap_grads=True,
        )
        _ov_rng = jax.random.key(0)  # compute_loss ignores it; fixed key

        def grad_fn(p, batch, initial_core):
            loss, aux, stream = _ostep(
                p, {"batch": batch, "core": initial_core}, _ov_rng
            )
            return (loss, aux), stream

        opt_apply = jax.jit(_opt_apply)
    else:
        grad_fn = jax.jit(raw_grad)
        # Jitted even unmeshed: the eager optax chain re-dispatches ~100 ops
        # per apply (~30 ms on a 1-core box vs ~1 ms compiled) and
        # host-numpy cohort gradients cross in one fused transfer.  Same
        # no-donation rule as the mesh path.
        opt_apply = jax.jit(_opt_apply)
    grad_fn = telemetry.devmon.instrument_jit(grad_fn, "vtrace.grad")
    opt_apply = telemetry.devmon.instrument_jit(opt_apply, "vtrace.opt_apply")

    # --- cohort wiring ---------------------------------------------------
    broker: Optional[Broker] = None
    broker_list = [a.strip() for a in (flags.broker_addrs or "").split(",")
                   if a.strip()]
    # Host when no external broker was named: --connect, or a --broker_addrs
    # list that does NOT include our own --address, means join-only.
    hosting = flags.connect is None and (
        not broker_list or flags.address in broker_list)
    if hosting:
        broker = Broker()
        broker.set_name("broker")
        broker.listen(flags.address)
        standbys = [a for a in broker_list if a != flags.address]
        if standbys:
            broker.set_peer_brokers(standbys)
    connect_addrs = broker_list or [flags.connect or flags.address]
    # Comma-joined for the autoscaler: example_spawn re-emits a multi-address
    # plane as --broker_addrs so supervised workers inherit the failover list.
    broker_addr = ",".join(connect_addrs)

    # Elastic fleet supervision (ROADMAP item 4): the broker-hosting peer can
    # run the telemetry-driven autoscaler, spawning/decommissioning worker
    # subprocesses that join this same cohort.
    scaler = None
    if flags.autoscale:
        if broker is None:
            raise ValueError("--autoscale requires hosting the broker "
                             "(omit --connect)")
        from ... import autoscaler as autoscaler_mod

        fleet_dir = os.path.join(flags.localdir or ".", "fleet")
        worker_args = [
            "--env", flags.env,
            "--total_steps", str(flags.total_steps),
            "--batch_size", str(flags.batch_size),
            "--virtual_batch_size", str(flags.virtual_batch_size),
            "--actor_batch_size", str(flags.actor_batch_size),
            "--unroll_length", str(flags.unroll_length),
            "--num_env_processes", str(flags.num_env_processes),
            "--train_id", flags.train_id,
            "--quiet",
        ]
        scaler = autoscaler_mod.Autoscaler(
            autoscaler_mod.AutoscalePolicy(
                flags.autoscale_min, flags.autoscale_max
            ),
            autoscaler_mod.SubprocessFleet(
                autoscaler_mod.example_spawn(
                    broker_addr, fleet_dir,
                    "moolib_tpu.examples.vtrace.experiment", worker_args,
                ),
                fleet_dir,
            ),
            poll_interval=flags.autoscale_interval,
        )

    rpc = Rpc()
    rpc.set_name(flags.local_name or f"impala-{os.getpid()}")
    rpc.listen("127.0.0.1:0")
    for a in connect_addrs:
        rpc.connect(a)
    rpc_group = Group(rpc, name=flags.train_id)
    if len(connect_addrs) > 1:
        rpc_group.set_brokers(connect_addrs)
    accumulator = Accumulator(
        "model", params, buffers=None, group=rpc_group
    )
    accumulator.set_virtual_batch_size(flags.virtual_batch_size)
    accumulator.set_model_version(model_version)
    if flags.shard_grads:
        # Hierarchical inter-host rounds (DESIGN.md §6d).  Wire protocol:
        # identical on every cohort peer.  Grads arrive already sharded when
        # --mesh is set (grad_fn's out_shardings), so the flat layout pins
        # bucket cuts to the shard boundaries; without a mesh the rounds
        # still shard by flat range.
        accumulator.set_sharded_allreduce(True)
    if flags.ici:
        accumulator.set_ici_backend(True)
    if flags.wire_dtype == "bf16":
        accumulator.set_wire_dtype(jnp.bfloat16)
    elif flags.wire_dtype == "int8":
        accumulator.set_wire_dtype("int8")
    if flags.chunked:
        accumulator.set_chunked_allreduce(True)
    if flags.trace_dir:
        # Trace the first seconds of training (compile + early steps); the
        # tracer's host spans appear in it (telemetry/tracing.py).
        jax.profiler.start_trace(flags.trace_dir)
        trace_stop_at = time.monotonic() + 30.0
    else:
        trace_stop_at = None

    stats = {
        "mean_episode_return": common.StatMean(),
        "mean_episode_step": common.StatMean(),
        "episodes_done": common.StatSum(),
        "steps_done": common.StatSum(),
        "sgd_steps": common.StatSum(),
        "loss": common.StatMean(),
        "pg_loss": common.StatMean(),
        "entropy_loss": common.StatMean(),
    }
    # Resume: continue the step count from the checkpoint.
    stats["steps_done"] += steps_done
    # Registry counter deltas piggyback on the same periodic stats reduce:
    # leader logs can show fleet-wide env/wire rates with no extra protocol.
    stats["telemetry"] = telemetry.CohortCounters()
    global_stats = common.GlobalStatsAccumulator(rpc_group, stats)
    timer = StepTimer()  # registry-backed loop-phase breakdown
    # Device performance plane: XLA-counted cost of the jitted grad step
    # (flops + bytes accessed), captured once after the first learn call and
    # combined with the StepTimer "learn" EMA into step_mfu at each log tick.
    devmon_cost: dict = {}
    # Per-section deadman (--watchdog seconds; disabled at 0): a wedged
    # section raises through the loop so the finally block below still
    # writes the leader checkpoint — a preempted-but-hung run stays
    # resumable (docs/RESILIENCE.md).
    wd = Watchdog(timeout=flags.watchdog, name="impala")
    if dckpt is not None:
        # Distributed snapshots ride the accumulator's model-version
        # lockstep; a hung shard write fires the watchdog (and shows in the
        # flight recorder) instead of wedging the writer thread silently.
        dckpt.set_watchdog(wd)
        # The env-step total is host-local (each peer's reduced stats lag
        # differently), so it rides the leader-broadcast aux dict; state_fn
        # may only return lockstep-replicated values — the blob digests
        # must agree across every member.
        accumulator.enable_distributed_checkpoint(
            dckpt, interval=flags.checkpoint_interval,
            aux_fn=lambda: {"steps": int(stats["steps_done"].value)},
        )

    def dckpt_state_fn():
        return {"opt_state": jax.device_get(opt_state)}

    tsv = None
    if flags.localdir:
        tsv = common.TsvLogger(
            os.path.join(flags.localdir, "logs.tsv"),
            metadata={"train_id": flags.train_id, "env": flags.env},
        )
    # One-shot per incarnation: the per-phase recovery breakdown
    # (reconnect/re_elect/model_sync/first_compile/first_contribution) lands
    # in <localdir>/recovery.json once the chain completes — the soak
    # harness aggregates these into its summary (docs/RESILIENCE.md).
    recovery_written = False
    wandb_run = None
    if flags.wandb:
        try:
            import wandb

            wandb_run = wandb.init(project=flags.train_id, config=flags.to_dict())
        except Exception as e:  # noqa: BLE001 — gated: package absent or offline
            utils.log_error("wandb requested but unavailable: %s", e)

    anakin = None
    actor_params = None
    actor_params_version = -1
    anakin_frames_seen = 0
    anakin_prev = {"episodes": 0, "return_sum": 0.0, "len_sum": 0.0}
    if jax_env is not None:
        # Anakin: ONE fused rollout over all the envs the envpool config
        # would have spread across actor batches — double buffering exists
        # to hide host env latency, and there is none to hide.
        roll_B = B * flags.num_actor_batches
        rng, env_rng, act_key = jax.random.split(rng, 3)
        anakin = rollout.AnakinRollout(
            model, jax_env, roll_B, T,
            env_key=env_rng, act_rng=act_key, mesh=actor_mesh,
        )
        env_states = []
    else:
        env_states = [
            common.EnvBatchState(B, T, model) for _ in range(flags.num_actor_batches)
        ]
        if flags.device_rollout:
            # Device-resident rollout buffers (docs/DESIGN.md "Actor data
            # plane"): sized from the pool's discovered spec so the env's
            # native dtype — uint8 for frames — is what crosses the boundary.
            env_obs_shape, env_obs_dtype = envs[0].obs_spec["state"]
            for st in env_states:
                st.rollout = rollout.DeviceRollout(
                    model, B, T, env_obs_shape, env_obs_dtype, num_actions
                )

    def _sync_anakin_stats() -> None:
        """Fold the device-side episode aggregates into the stats dict (the
        deltas since the last snapshot).  This is the Anakin plane's only
        D2H, and it runs per stats/log tick, not per frame."""
        if anakin is None:
            return
        snap = anakin.stats()
        de = snap["episodes"] - anakin_prev["episodes"]
        stats["mean_episode_return"] += common.StatMean(
            snap["return_sum"] - anakin_prev["return_sum"], de
        )
        stats["mean_episode_step"] += common.StatMean(
            snap["len_sum"] - anakin_prev["len_sum"], de
        )
        stats["episodes_done"] += de
        anakin_prev.update(
            episodes=snap["episodes"],
            return_sum=snap["return_sum"],
            len_sum=snap["len_sum"],
        )
    # With a mesh, the Batcher lands batches pre-sharded (device_put accepts
    # a NamedSharding target): [T+1, B] over (∅, dp).
    learn_batcher = Batcher(
        flags.batch_size, device=batch_sharding if mesh is not None else device, dim=1,
        max_outstanding=flags.batcher_max_outstanding, name="learn",
    )
    # Initial LSTM states ride a parallel batcher (batch axis 0) so they
    # split/merge across learner batches exactly like the unrolls do.
    core_batcher = (
        Batcher(
            flags.batch_size,
            device=core_sharding if mesh is not None else device,
            dim=0,
        )
        if flags.use_lstm
        else None
    )

    # Learner scalars accumulate as device arrays and are fetched in ONE
    # device_get per stats/log tick — the per-SGD-step float(loss) sync they
    # replace stalled the learner stream on every step.
    pending_learn_stats: list = []
    # For the returned summary: the newest learner loss, and where the
    # learner's first batch was found (on the device path the batch IS the
    # rollout buffer).
    learn_seen = {"loss": None, "batch_placement": None}

    def _flush_learn_stats() -> None:
        if not pending_learn_stats:
            return
        for loss_v, pg_v, ent_v in jax.device_get(pending_learn_stats):
            learn_seen["loss"] = float(loss_v)
            stats["loss"] += float(loss_v)
            stats["pg_loss"] += float(pg_v)
            stats["entropy_loss"] += float(ent_v)
        pending_learn_stats.clear()

    last_stats = time.monotonic()
    last_log = time.monotonic()
    last_checkpoint = time.monotonic()
    final_return = None
    start = time.time()
    # (wall time, steps) samples at each log tick: lets callers separate
    # steady-state throughput from the compile/startup transient (the
    # whole-run mean buries ~90 s of jit warmup in short benchmark runs).
    sps_samples = [(start, 0.0)]
    cur = 0
    # Graceful shutdown: SIGTERM (scheduler preemption) stops the loop so
    # the finally block runs — leader checkpoints on the way out, exactly
    # like SIGINT (reference signal handling, examples/vtrace/
    # experiment.py:331-348). Restored on exit so nested runs are clean.
    stop_requested = False
    # Graceful scale-down: the autoscaler drops this flag file; the loop
    # drains + __broker_leave's instead of waiting to be ping-evicted.
    from ... import autoscaler as autoscaler_flagmod

    decommission_flag = (
        os.path.join(flags.localdir, autoscaler_flagmod.DECOMMISSION_FLAG)
        if flags.localdir else None
    )
    decommissioning = False

    def _on_sigterm(signum, frame):
        nonlocal stop_requested
        stop_requested = True

    import signal as _signal

    prev_sigterm = _signal.signal(_signal.SIGTERM, _on_sigterm)

    # Kick off the first step of every actor batch (double buffering).
    for i, st in enumerate(env_states):
        st.future = envs[i].step(0, np.zeros(B, np.int64))

    try:
        while stats["steps_done"].value < flags.total_steps and not stop_requested:
            if broker is not None:
                broker.update()
            rpc_group.update()
            accumulator.update()
            if dckpt is not None:
                accumulator.checkpoint_tick(state_fn=dckpt_state_fn)
            if scaler is not None:
                scaler.step()  # self-rate-limited supervision tick
            if decommission_flag is not None and not decommissioning:
                if os.path.exists(decommission_flag):
                    # Supervisor asked this peer to scale out: drain and
                    # leave gracefully, then exit through the normal
                    # checkpoint/teardown path.
                    decommissioning = True
                    stop_requested = True

            if accumulator.wants_state():
                accumulator.set_state(
                    {
                        "opt_state": jax.device_get(opt_state),
                        "steps": stats["steps_done"].value,
                    }
                )
            if accumulator.has_new_state():
                st = accumulator.state()
                if st is not None:
                    opt_state = st["opt_state"]
                    params = accumulator.parameters()

            if not accumulator.connected():
                time.sleep(0.05)
                continue

            now = time.monotonic()
            if trace_stop_at is not None and now > trace_stop_at:
                trace_stop_at = None
                jax.profiler.stop_trace()
                print(f"profiler trace written to {flags.trace_dir}")
            if now - last_stats > flags.stats_interval:
                last_stats = now
                _flush_learn_stats()  # one fetch; cohort sees fresh loss
                _sync_anakin_stats()
                global_stats.reduce(stats)
            if (
                flags.checkpoint
                and accumulator.is_leader()
                and now - last_checkpoint > flags.checkpoint_interval
            ):
                last_checkpoint = now
                save_checkpoint(
                    flags.checkpoint, params, opt_state,
                    stats["steps_done"].value, accumulator.model_version(),
                )

            if accumulator.has_gradients():
                with timer.section("apply"), wd.section("apply"):
                    grads = accumulator.gradients()
                    params, opt_state = opt_apply(params, opt_state, grads)
                    accumulator.set_parameters(params)
                    accumulator.zero_gradients()
                stats["sgd_steps"] += 1
            elif not learn_batcher.empty() and accumulator.wants_gradients():
                with timer.section("learn"), wd.section("learn"):
                    batch = learn_batcher.get()
                    initial_core = core_batcher.get() if core_batcher is not None else ()
                    if not flags.device_rollout:
                        # Legacy host batches cross implicitly at this jit
                        # call — the third float32 crossing of every frame.
                        rollout.count_h2d(
                            sum(
                                x.nbytes
                                for x in utils.nest.flatten(batch)
                                if isinstance(x, np.ndarray)
                            )
                        )
                    (loss, aux), grads = grad_fn(params, batch, initial_core)
                    if learn_seen["batch_placement"] is None:
                        learn_seen["batch_placement"] = common.placement_of(batch)
                    if "cost" not in devmon_cost:
                        # One lower() per geometry; cached per-signature in
                        # devmon so shape churn doesn't re-lower every step.
                        devmon_cost["cost"] = telemetry.devmon.step_cost(
                            "vtrace.grad", grad_fn, params, batch, initial_core
                        )
                    # Device scalars only: the float() fetch that used to
                    # live here synced the learner stream every SGD step.
                    # They accumulate on device and are fetched in one batch
                    # per stats/log tick (_flush_learn_stats).
                    pending_learn_stats.append(
                        (loss, aux["pg_loss"], aux["entropy_loss"])
                    )
                    # Device grads go straight in: Accumulator staging
                    # issues per-leaf copy_to_host_async so D2H overlaps
                    # the flat fill (PR 4) — a device_get here would
                    # serialize the whole tree first.
                    accumulator.reduce_gradients(flags.batch_size, grads)
            elif anakin is not None:
                # --- act: Anakin/Sebulba ---------------------------------
                # One lax.scan dispatch = one completed [T+1, B] unroll.
                # Env, model, auto-reset, and episode accounting all run on
                # device; zero host-boundary bytes per frame.
                if actor_mesh is not None:
                    if actor_params_version != accumulator.model_version():
                        # Refresh the actor submesh's param replica only when
                        # the learner actually stepped (device-to-device).
                        with timer.section("param_sync"), wd.section("param_sync"):
                            actor_params = jax.device_put(
                                params, _actor_rep_sharding(actor_mesh)
                            )
                        _M_PARAM_SYNC.inc(
                            sum(
                                x.nbytes
                                for x in jax.tree_util.tree_leaves(actor_params)
                            )
                        )
                        actor_params_version = accumulator.model_version()
                    act_params = actor_params
                else:
                    act_params = params
                with timer.section("act"), wd.section("act"):
                    unroll = anakin.unroll(act_params)
                learn_batcher.cat(unroll)  # Sebulba: the inter-mesh handoff
                if core_batcher is not None:
                    core_batcher.cat(anakin.completed_initial_core)
                stats["steps_done"] += anakin.frames_done - anakin_frames_seen
                anakin_frames_seen = anakin.frames_done
            else:
                # --- act ------------------------------------------------
                st = env_states[cur]
                with timer.section("env_wait"), wd.section("env_wait"):
                    obs = st.future.result()
                st.update(obs, stats)
                if flags.device_rollout:
                    # Device-resident path: obs crosses once (native dtype),
                    # the fused jitted step writes the on-chip [T+1, B]
                    # buffer, and the action comes back asynchronously.
                    with timer.section("act"), wd.section("act"):
                        pending, rng = st.rollout.step(params, obs, rng)
                    unroll = st.rollout.take_unroll()  # device pytree or None
                    if unroll is not None:
                        learn_batcher.cat(unroll)  # on-device cat/split
                        if core_batcher is not None:
                            core_batcher.cat(st.rollout.completed_initial_core)
                    # Realize as late as possible: the D2H issued at
                    # dispatch overlapped the unroll hand-off above.  A
                    # separate timer/watchdog section keeps `act` honest —
                    # it now measures dispatch, this measures the fetch.
                    with timer.section("act_fetch"), wd.section("act_fetch"):
                        action_np = pending.realize()
                    st.future = envs[cur].step(0, action_np)
                else:
                    # Legacy host-batcher path (--device_rollout=false):
                    # float32 staging on the host, three boundary crossings
                    # per frame — kept bit-exact as the equivalence baseline
                    # (tests/test_rollout.py), with its crossings counted on
                    # the same telemetry the device path reports.
                    # np.array (copy=True): obs are zero-copy shm views the
                    # env workers overwrite on the next step — the unroll
                    # rows must own their memory.
                    state_f32 = np.array(obs["state"], np.float32)
                    reward_np = np.array(obs["reward"], np.float32)
                    done_np = np.array(obs["done"], bool)
                    inputs = {
                        "state": jnp.asarray(state_f32)[None],
                        "reward": jnp.asarray(reward_np)[None],
                        "done": jnp.asarray(done_np)[None],
                        "prev_action": st.prev_action[None],
                    }
                    rollout.count_h2d(
                        state_f32.nbytes + reward_np.nbytes + done_np.nbytes
                    )
                    rollout.count_frames(B)
                    rng, act_rng = jax.random.split(rng)
                    core_before = st.core_state  # LSTM state entering this step
                    with timer.section("act"), wd.section("act"):
                        out, new_core = act_step(params, inputs, st.core_state, act_rng)
                    action = out["action"][0]
                    logits = out["policy_logits"][0]
                    # Start both D2H transfers before the first blocking
                    # fetch: two serialized np.asarray round trips would
                    # otherwise cost this path a second full dispatch RTT
                    # per frame.
                    for _x in (action, logits):
                        if hasattr(_x, "copy_to_host_async"):
                            _x.copy_to_host_async()
                    action_np = np.asarray(action)
                    logits_np = np.asarray(logits)
                    rollout.count_d2h(action_np.nbytes + logits_np.nbytes)
                    # Queue the next env step immediately (overlaps with learning).
                    st.future = envs[cur].step(0, action_np)
                    st.time_batcher.stack(
                        {
                            "state": state_f32,
                            "reward": reward_np,
                            "done": done_np,
                            "prev_action": st.prev_action_host,
                            "action": action_np,
                            "policy_logits": logits_np,
                        }
                    )
                    st.prev_action = action
                    st.prev_action_host = action_np
                    st.core_state = new_core
                    if not st.time_batcher.empty():
                        unroll = st.time_batcher.get()  # [T+1, B, ...] host
                        learn_batcher.cat(unroll)
                        if core_batcher is not None:
                            core_batcher.cat(st.initial_core_state)
                        # Carry the last timestep into the next unroll; its
                        # initial LSTM state is the state *before* that step.
                        st.initial_core_state = core_before
                        st.time_batcher.stack(
                            {k: v[-1] for k, v in unroll.items()}
                        )
                cur = (cur + 1) % flags.num_actor_batches

            if not recovery_written and flags.localdir:
                rec = accumulator.recovery_info()
                if rec["complete"]:
                    recovery_written = True
                    import json as _json

                    with open(os.path.join(flags.localdir, "recovery.json"), "w") as f:
                        _json.dump(rec, f, indent=1)
                    if not flags.quiet:
                        print(f"recovered: {_json.dumps(rec)}", flush=True)

            if now - last_log > flags.log_interval:
                last_log = now
                _flush_learn_stats()
                _sync_anakin_stats()
                sps = stats["steps_done"].value / max(time.time() - start, 1e-6)
                sps_samples.append((time.time(), stats["steps_done"].value))
                ret = stats["mean_episode_return"].result()
                # Device performance plane: HBM watermarks each tick, and
                # MFU/roofline from the XLA-counted grad-step cost over the
                # StepTimer "learn" EMA (None until both exist).
                telemetry.devmon.sample_memory()
                mfu_info = None
                learn_s = timer.summary().get("learn")
                if devmon_cost.get("cost") is not None and learn_s:
                    mfu_info = telemetry.devmon.publish_step(
                        "vtrace.grad", devmon_cost["cost"], learn_s
                    )
                if mfu_info is not None:
                    devmon_cost["mfu"] = mfu_info["mfu"]
                if not flags.quiet:
                    # Fleet-wide env step total: this peer's counter plus
                    # every remote delta learned through the stats reduce.
                    fleet_env = stats["telemetry"].value("envpool_steps_total")
                    mfu_s = (
                        f" mfu={mfu_info['mfu']:.3%} bound={mfu_info['bound']}"
                        if mfu_info is not None
                        else ""
                    )
                    print(
                        f"steps={int(stats['steps_done'].value)} sps={sps:.0f} "
                        f"return={ret if ret is None else round(ret, 2)} "
                        f"sgd={int(stats['sgd_steps'].value)} "
                        f"loss={stats['loss'].result()} "
                        f"fleet_env_steps={int(fleet_env)}{mfu_s} "
                        f"[{timer.report()}]",
                        flush=True,
                    )
                if on_stats is not None or tsv is not None or wandb_run is not None:
                    row = {
                        k: v.result() if hasattr(v, "result") else v
                        for k, v in stats.items()
                        if not isinstance(v, telemetry.CohortCounters)
                    }
                    if on_stats is not None:
                        on_stats(row)
                    # Reduction-plane observability (which plane gradient
                    # sync rode: ICI psum vs the elastic RPC tree).
                    adbg = accumulator.debug_info()
                    row = dict(
                        row,
                        sps=round(sps, 1),
                        reduce_plane=adbg["last_plane"],
                        ici_reduces=adbg["ici_reduces"],
                        rpc_reduces=adbg["rpc_reduces"],
                        model_version=accumulator.model_version(),
                    )
                    if tsv is not None:
                        tsv.log(**row)
                    if wandb_run is not None:
                        wandb_run.log(row)
                last_return = stats["mean_episode_return"].result()
                if last_return is not None:
                    final_return = last_return
                # Windowed stats reset through the accumulator so the delta
                # allreduce stays in sync (a bare .reset() would broadcast a
                # huge negative delta to the cohort).
                global_stats.local_reset(
                    "loss", "pg_loss", "entropy_loss",
                    "mean_episode_return", "mean_episode_step",
                )
        # Loop exit: stamp the end sample here, not after teardown — the
        # finally block below (checkpoint save, env/rpc close) can take
        # tens of seconds with zero step progress and would deflate the
        # steady-state window it exists to measure.
        _flush_learn_stats()
        _sync_anakin_stats()
        sps_samples.append((time.time(), stats["steps_done"].value))
    finally:
        wd.close()
        if trace_stop_at is not None:
            try:
                jax.profiler.stop_trace()
            except Exception:
                pass
        _signal.signal(_signal.SIGTERM, prev_sigterm)
        if dckpt is not None:
            s = dckpt.stats()
            print(
                "ckpt_async: captures=%d commits=%d stall_s=%.4f "
                "write_s=%.4f" % (
                    s["captures"], s["commits"], s["stall_s"], s["write_s"],
                ),
                flush=True,
            )
            dckpt.close()
        if flags.checkpoint and accumulator.is_leader():
            save_checkpoint(
                flags.checkpoint, params, opt_state,
                stats["steps_done"].value, accumulator.model_version(),
            )
        if decommissioning:
            # Drain in-flight contributions, then tell the broker we're gone
            # so the cohort's epoch bumps now (not after the ping timeout).
            accumulator.decommission(timeout=15.0)
        for e in envs:
            e.close()
        if scaler is not None:
            scaler.fleet.terminate_all()
        accumulator.close()
        rpc.close()
        if broker is not None:
            broker.close()
        if wandb_run is not None:
            try:
                wandb_run.finish()
            except Exception:  # noqa: BLE001
                pass
        telemetry.flush()  # final JSONL snapshot + host trace, if enabled

    # Short runs (bench captures, CI smoke) can finish inside one log
    # interval — publish the final MFU reading here so out["mfu"] is
    # populated whenever the learn section ran at all.
    if "mfu" not in devmon_cost and devmon_cost.get("cost") is not None:
        learn_s = timer.summary().get("learn")
        if learn_s:
            fin = telemetry.devmon.publish_step(
                "vtrace.grad", devmon_cost["cost"], learn_s
            )
            if fin is not None:
                devmon_cost["mfu"] = fin["mfu"]

    recent = stats["mean_episode_return"].result()
    final_steps = stats["steps_done"].value
    if sps_samples[-1][1] < final_steps:  # loop left via an exception path
        sps_samples.append((time.time(), final_steps))
    # Steady-state window: from the first sample at or past half the final
    # step count (compile transients live in the first half of short runs).
    mid = next(
        (s for s in sps_samples if s[1] >= final_steps / 2), sps_samples[0]
    )
    end = sps_samples[-1]
    steady = (
        (end[1] - mid[1]) / (end[0] - mid[0])
        if end[0] > mid[0] and end[1] > mid[1]
        else None
    )
    return {
        "steps": final_steps,
        "episodes": stats["episodes_done"].value,
        "sgd_steps": stats["sgd_steps"].value,
        "mean_episode_return": recent if recent is not None else final_return,
        "sps": final_steps / max(time.time() - start, 1e-6),
        "steady_sps": None if steady is None else round(steady, 1),
        "mfu": devmon_cost.get("mfu"),
        "loss": learn_seen["loss"],
        "param_placement": common.placement_of(params),
        "batch_placement": learn_seen["batch_placement"],
    }


def main(argv=None):
    started = time.monotonic()
    common.print_report(train(make_flags(argv)), started)


if __name__ == "__main__":
    main()
