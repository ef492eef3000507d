"""Device-resident actor rollout buffers (the Podracer/Sebulba data plane).

The legacy actor path (``examples/vtrace/experiment.py`` host-batcher branch)
moves every observation across the host↔device boundary three times, in
float32: a host ``astype(np.float32)`` before upload (4x the H2D bytes of the
uint8 frame the env produced), a D2H when the host time-batcher stacks the
step back into an unroll, and a second H2D when the assembled learner batch
reaches the device.  On a colocated chip those are wasted DMAs, and each is
a dispatch the actor loop waits on.

This module keeps the rollout on the device instead (arXiv:2104.06272 §
Sebulba: "rollouts are built in device memory"):

- one ``[T+1, B, ...]`` buffer pytree lives in device memory; the fused,
  jitted act step writes timestep ``t`` into it with
  ``jax.lax.dynamic_update_slice_in_dim`` and the buffer is **donated**, so
  XLA updates it in place instead of reallocating 6 arrays per step;
- the observation crosses the boundary **once, in its native dtype** (uint8
  frames stay uint8 — normalization is the model's on-chip ``astype/255``);
- the PRNG key is carried on-device through the fused step (the per-step
  ``jax.random.split`` host dispatch disappears; the split happens inside
  the same executable, producing bit-identical keys);
- the action comes back as a device array whose D2H transfer is started
  with ``copy_to_host_async()`` at dispatch time; :class:`PendingAction`
  realizes it as late as possible so ``EnvPool.step`` submission stops
  serializing behind a blocking ``np.asarray`` (dispatch is decoupled from
  fetch — the ``actor_act_dispatch_depth`` gauge counts in-flight actions,
  and realize time is accounted separately from dispatch time so the
  ``act`` timer stays honest under async dispatch);
- a completed unroll is handed over as a device pytree (consumed by the
  :class:`~moolib_tpu.batcher.Batcher` device-side path, which assembles
  learner batches by on-device cat/split — no further crossing), and the
  carried last timestep seeds the next buffer through a small **non**-donated
  jit, so the completed unroll stays valid while the fresh buffer is
  donated onwards (the donation-safety contract ``tests/test_rollout.py``
  locks down).

Bit-exactness: the fused step computes ``model.apply`` on the same float32
values the legacy path uploads (uint8 -> f32 is exact) and splits the key
with the same function, so device-rollout trajectories are bit-identical to
the legacy host-batcher path — ``tests/test_rollout.py`` compares
obs/actions/logits/core state with ``array_equal``.

Telemetry (docs/TELEMETRY.md): ``actor_h2d_bytes_total`` /
``actor_d2h_bytes_total`` / ``actor_frames_total`` make the one-crossing
contract a measured artifact (``benchmarks/agent_bench.py`` reports
``host_boundary_bytes_per_frame`` from them); ``actor_act_dispatch_seconds``
vs ``actor_act_realize_seconds`` split the old ``act`` wall time into its
dispatch and fetch halves.

:class:`AnakinRollout` goes one step further (arXiv:2104.06272 § Anakin):
when the env itself is a pure-JAX function (``envs.jax_envs``), ``env.step``
fuses INTO the jitted act step — observation, action, and reward never exist
on the host, auto-reset happens on device, and a ``lax.scan`` fast path
produces a completed ``[T+1, B]`` unroll in ONE dispatch.  The rollout loop
moves **zero host-boundary bytes per frame**: ``actor_h2d/d2h_bytes_total``
stay untouched; only the occasional episode-stats snapshot crosses, on its
own counter (``actor_stats_d2h_bytes_total``) so the per-frame contract
stays a measured zero.
"""

from __future__ import annotations

import time
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import telemetry
from .telemetry import devmon

_REG = telemetry.get_registry()
# Host-boundary accounting: every byte the actor path moves between host and
# device, by direction.  The legacy path increments these too (via
# count_h2d/count_d2h at its conversion sites), so the two rollout modes are
# comparable on one metric family.
_M_H2D = _REG.counter(
    "actor_h2d_bytes_total", "actor-path bytes uploaded host -> device"
)
_M_D2H = _REG.counter(
    "actor_d2h_bytes_total", "actor-path bytes fetched device -> host"
)
_M_FRAMES = _REG.counter(
    "actor_frames_total", "env frames through the actor path (for bytes/frame)"
)
_M_DISPATCH = _REG.histogram(
    "actor_act_dispatch_seconds", "act step dispatch (enqueue, not compute)"
)
_M_REALIZE = _REG.histogram(
    "actor_act_realize_seconds", "pending action realize (D2H completion wait)"
)
_M_DEPTH = _REG.gauge(
    "actor_act_dispatch_depth", "act steps dispatched but not yet realized"
)
_M_UNROLLS = _REG.counter("actor_unrolls_total", "completed [T+1, B] unrolls")
_M_STATS_D2H = _REG.counter(
    "actor_stats_d2h_bytes_total",
    "episode-stats snapshot fetches (Anakin; outside the per-frame loop)",
)


def count_h2d(nbytes: int) -> None:
    """Record an actor-path host->device crossing (legacy path call sites)."""
    _M_H2D.inc(nbytes)


def count_d2h(nbytes: int) -> None:
    """Record an actor-path device->host crossing (legacy path call sites)."""
    _M_D2H.inc(nbytes)


def count_frames(n: int) -> None:
    _M_FRAMES.inc(n)


class PendingAction:
    """A dispatched-but-not-realized action batch.

    Holds the device array with its ``copy_to_host_async()`` already issued;
    :meth:`realize` blocks only on whatever is still outstanding (ideally
    nothing — the transfer overlapped the host work since dispatch) and
    returns host numpy.  ``EnvPool.step`` also accepts the device array (or
    this object) directly; realizing explicitly keeps the fetch wait visible
    to the ``act_fetch`` timer/watchdog section instead of hiding it inside
    the env seam.
    """

    __slots__ = ("_dev", "_host")

    def __init__(self, action_dev):
        self._dev = action_dev
        self._host: Optional[np.ndarray] = None
        if hasattr(action_dev, "copy_to_host_async"):
            action_dev.copy_to_host_async()
        _M_DEPTH.inc()

    def realize(self) -> np.ndarray:
        if self._host is None:
            t0 = time.monotonic()
            # The actor's wait for its actions to reach the host.
            with telemetry.span("rollout.act_fetch"):
                # mtlint: allow-host-sync(the realize seam IS the intentional D2H, counted on actor_d2h_bytes_total)
                self._host = np.asarray(self._dev)
            _M_REALIZE.observe(time.monotonic() - t0)
            _M_D2H.inc(self._host.nbytes)
            _M_DEPTH.dec()
        return self._host

    def __array__(self, dtype=None):
        out = self.realize()
        return out if dtype is None else out.astype(dtype, copy=False)

    @property
    def device_array(self):
        return self._dev


# One compiled (step, carry) pair per distinct rollout geometry: several
# actor batches of the same experiment share executables instead of
# compiling per DeviceRollout instance.  Keyed on the flax module (a frozen
# dataclass, hashable by config) + shapes/dtypes.
_JIT_CACHE: Dict[Tuple, Tuple[Any, Any]] = {}


def _build_jits(model, unroll_length: int):
    def _step(params, buf, t, state, reward, done, prev_action, core_state, rng):
        # Same split the legacy host loop performs per step — inside the
        # executable, so the key never leaves the device.
        rng, act_rng = jax.random.split(rng)
        inputs = {
            # On-chip normalization: uint8 -> f32 is exact, so the model sees
            # bit-identical values to the legacy host astype(np.float32).
            "state": state.astype(jnp.float32)[None],
            "reward": reward[None],
            "done": done[None],
            "prev_action": prev_action[None],
        }
        out, new_core = model.apply(params, inputs, core_state, sample_rng=act_rng)
        action = out["action"][0]
        logits = out["policy_logits"][0]
        row = {
            "state": state,  # native dtype: the buffer stores what the env sent
            "reward": reward,
            "done": done,
            "prev_action": prev_action,
            "action": action,
            "policy_logits": logits,
        }
        buf = {
            k: jax.lax.dynamic_update_slice_in_dim(buf[k], row[k][None], t, axis=0)
            for k in buf
        }
        return buf, action, new_core, rng

    def _carry(buf):
        # Seed the next unroll with the completed one's last timestep
        # (reference carry-over).  NOT donated: the completed buffer is the
        # learner's unroll and must outlive this copy.
        return {k: jnp.zeros_like(v).at[0].set(v[unroll_length]) for k, v in buf.items()}

    # Recompile detector (telemetry.devmon): a geometry change slipping
    # through the cache key would silently recompile per call here.
    return (
        devmon.instrument_jit(jax.jit(_step, donate_argnums=(1,)), "rollout.step"),
        devmon.instrument_jit(jax.jit(_carry), "rollout.carry"),
    )


class DeviceRollout:
    """Per-actor-batch device-resident rollout state.

    Drop-in replacement for the host-batcher bookkeeping in
    ``examples.common.EnvBatchState``: owns the ``[T+1, B, ...]`` device
    buffer, the carried LSTM core, the on-device previous action, and the
    unroll boundary logic (carry last step into the next buffer, track the
    initial core state entering each unroll).

    Usage per act step::

        pending, rng = roll.step(params, obs, rng)   # obs: EnvPool views
        ...                                          # overlap host work here
        env.step(batch, pending.realize())
        unroll = roll.take_unroll()                  # device pytree or None
        if unroll is not None:
            learn_batcher.cat(unroll)                # on-device assembly
            core_batcher.cat(roll.completed_initial_core)
    """

    def __init__(self, model, batch_size: int, unroll_length: int,
                 obs_shape: Tuple[int, ...], obs_dtype, num_actions: int):
        self.batch_size = batch_size
        self.unroll_length = unroll_length
        self._obs_dtype = np.dtype(obs_dtype)
        if self._obs_dtype == np.float64:
            # x64 is disabled on the device: stage f64 env vectors as f32 on
            # the host (same cast the legacy path makes) instead of letting
            # jit canonicalize a 2x-wide upload.
            self._obs_dtype = np.dtype(np.float32)
        key = (model, batch_size, unroll_length, tuple(obs_shape),
               self._obs_dtype.str, int(num_actions))
        jits = _JIT_CACHE.get(key)
        if jits is None:
            jits = _JIT_CACHE.setdefault(key, _build_jits(model, unroll_length))
        self._step_jit, self._carry_jit = jits
        T1 = unroll_length + 1
        B = batch_size
        self._buf = {
            "state": jnp.zeros((T1, B, *obs_shape), self._obs_dtype),
            "reward": jnp.zeros((T1, B), jnp.float32),
            "done": jnp.zeros((T1, B), bool),
            "prev_action": jnp.zeros((T1, B), jnp.int32),
            "action": jnp.zeros((T1, B), jnp.int32),
            "policy_logits": jnp.zeros((T1, B, num_actions), jnp.float32),
        }
        self._t = 0
        self.core_state = model.initial_state(batch_size)
        self.prev_action = jnp.zeros((B,), jnp.int32)
        # Initial LSTM state entering the unroll currently being filled.
        self._initial_core = self.core_state
        self._completed: Optional[dict] = None
        self.completed_initial_core = None

    def step(self, params, obs: Dict[str, np.ndarray], rng):
        """Upload one env observation batch (single crossing, native dtype),
        run the fused act step, and return ``(PendingAction, rng)``.

        ``rng`` is the carried device key; the split happens inside the
        executable.  The returned pending action's D2H is already issued.
        """
        t0 = time.monotonic()
        # mtlint: allow-host-sync(obs leaves are EnvPool shm views, already host memory — asarray is a view)
        state = np.asarray(obs["state"])
        if state.dtype != self._obs_dtype:
            # Non-uint8 envs (e.g. float64 gym vectors): cast on host once to
            # the buffer dtype — still a single crossing.
            state = state.astype(self._obs_dtype)
        reward = np.asarray(obs["reward"], np.float32)  # mtlint: allow-host-sync(host shm view, see above)
        done = np.asarray(obs["done"], bool)  # mtlint: allow-host-sync(host shm view, see above)
        # THE crossing: the host arrays go straight into the fused call —
        # the jit C++ fastpath uploads them inline (native dtype, one DMA
        # per leaf), an order of magnitude cheaper per step than an
        # explicit python-side device_put.
        _M_H2D.inc(state.nbytes + reward.nbytes + done.nbytes)
        _M_FRAMES.inc(self.batch_size)
        core_before = self.core_state
        self._buf, action, self.core_state, rng = self._step_jit(
            params, self._buf, self._t, state, reward, done,
            self.prev_action, self.core_state, rng,
        )
        self.prev_action = action
        if self._t == self.unroll_length:
            # Index T written: the unroll is complete.  Hand it over and
            # seed the next buffer from its last step via the non-donated
            # carry (the completed pytree stays valid for the learner).
            self._completed = self._buf
            self.completed_initial_core = self._initial_core
            self._initial_core = core_before
            self._buf = self._carry_jit(self._completed)
            self._t = 1
            _M_UNROLLS.inc()
        else:
            self._t += 1
        _M_DISPATCH.observe(time.monotonic() - t0)
        return PendingAction(action), rng

    def take_unroll(self) -> Optional[dict]:
        """The completed ``[T+1, B, ...]`` device unroll, or None.  Reading
        clears it; ``completed_initial_core`` stays valid until the next
        unroll completes."""
        out, self._completed = self._completed, None
        return out


# --------------------------------------------------------------------------
# Anakin: env fused into the rollout (zero host-boundary bytes per frame)
# --------------------------------------------------------------------------

_ANAKIN_JIT_CACHE: Dict[Tuple, Tuple[Any, ...]] = {}


def _env_cache_key(env) -> Tuple:
    """JaxEnv instances are plain-attribute config objects; their identity
    for executable sharing is (class, config)."""
    return (
        type(env).__module__,
        type(env).__qualname__,
        tuple(sorted(vars(env).items())),
    )


def _build_anakin_jits(model, env, unroll_length: int):
    from .envs import jax_envs

    T = unroll_length

    def _body(params, carry):
        """One fused timestep: act on the carried observation, then step the
        batched env ON DEVICE (vmap), auto-reset included.  Identical math to
        ``DeviceRollout``'s ``_step`` — same split order, same f32 staging —
        so a JaxEnv rollout is bit-comparable between per-step and scan modes.
        """
        obs = carry["obs"]
        rng, act_rng = jax.random.split(carry["rng"])
        inputs = {
            "state": obs.astype(jnp.float32)[None],
            "reward": carry["reward"][None],
            "done": carry["done"][None],
            "prev_action": carry["prev_action"][None],
        }
        out, new_core = model.apply(
            params, inputs, carry["core"], sample_rng=act_rng
        )
        action = out["action"][0]
        row = {
            "state": obs,
            "reward": carry["reward"],
            "done": carry["done"],
            "prev_action": carry["prev_action"],
            "action": action,
            "policy_logits": out["policy_logits"][0],
        }
        env_state, ts = jax_envs.batch_step(env, carry["env"], action)
        # Device-side episode accounting: aggregates only ever leave the chip
        # through the explicit stats() snapshot, never per frame.
        st = carry["stats"]
        ep_return = st["ep_return"] + ts["reward"]
        ep_len = st["ep_len"] + 1
        d = ts["done"]
        stats = {
            "ep_return": jnp.where(d, 0.0, ep_return),
            "ep_len": jnp.where(d, 0, ep_len),
            "return_sum": st["return_sum"] + jnp.sum(jnp.where(d, ep_return, 0.0)),
            "len_sum": st["len_sum"] + jnp.sum(jnp.where(d, ep_len, 0)),
            "episodes": st["episodes"] + jnp.sum(d.astype(jnp.int32)),
        }
        new_carry = {
            "env": env_state,
            "obs": ts["state"],
            "reward": ts["reward"],
            "done": ts["done"],
            "prev_action": action,
            "core": new_core,
            "rng": rng,
            "stats": stats,
        }
        return new_carry, row

    def _step(params, buf, t, carry):
        carry, row = _body(params, carry)
        buf = {
            k: jax.lax.dynamic_update_slice_in_dim(buf[k], row[k][None], t, axis=0)
            for k in buf
        }
        return buf, carry

    def _carry_buf(buf):
        return {k: jnp.zeros_like(v).at[0].set(v[T]) for k, v in buf.items()}

    def _scan(params, carry, length):
        return jax.lax.scan(
            lambda c, _: _body(params, c), carry, None, length=length
        )

    def _finish(params, carry, rows_head):
        """Shared tail of both unroll entrypoints: run the last body step
        outside the scan so the core state ENTERING row T (= row 0 of the
        next unroll) is available as ``completed_initial_core`` for the
        learner without stacking cores across time."""
        core_into_last = carry["core"]
        carry, last = _body(params, carry)
        buf = jax.tree_util.tree_map(
            lambda *parts: jnp.concatenate(
                [p if p.ndim > parts[-1].ndim else p[None] for p in parts], axis=0
            ),
            *rows_head,
            last,
        )
        last_row = {k: buf[k][T] for k in buf}
        return buf, last_row, carry, core_into_last

    def _unroll_first(params, carry):
        # Bootstrap: no carried row yet, so rows 0..T-1 come from the scan
        # and row T from the explicit tail step — T+1 env steps, ONE dispatch.
        carry, rows = _scan(params, carry, T)
        return _finish(params, carry, (rows,))

    def _unroll_next(params, last_row, carry):
        # Steady state: row 0 is the carried last row of the previous unroll
        # (the reference carry-over), rows 1..T-1 from the scan, row T from
        # the tail step — T env steps, ONE dispatch.
        carry, rows = _scan(params, carry, T - 1)
        return _finish(params, carry, (last_row, rows))

    return (
        devmon.instrument_jit(jax.jit(_step, donate_argnums=(1,)), "anakin.step"),
        devmon.instrument_jit(jax.jit(_carry_buf), "anakin.carry"),
        devmon.instrument_jit(jax.jit(_unroll_first), "anakin.unroll_first"),
        devmon.instrument_jit(jax.jit(_unroll_next), "anakin.unroll_next"),
    )


class AnakinRollout:
    """Fully on-device rollout: jitted env + model, zero crossings per frame.

    Two modes over the same fused body (``tests/test_jax_envs.py`` proves
    them equivalent):

    - **per-step** (:meth:`step`): the fused env+act step writes timestep
      ``t`` into the donated ``[T+1, B]`` buffer — ``DeviceRollout``'s
      exact bookkeeping (carry row ``T`` to row 0, non-donated carry copy),
      with the env now inside the executable;
    - **scan** (:meth:`unroll`): one ``lax.scan`` dispatch produces the
      whole completed unroll.  This is the throughput path: per-frame
      dispatch cost disappears entirely, the host only enqueues one call
      per T steps.

    Neither mode touches ``actor_h2d/d2h_bytes_total``: observations,
    actions, and rewards are born and consumed on device.  Episode stats
    accumulate on device and leave only through :meth:`stats`
    (``actor_stats_d2h_bytes_total``).

    One instance is one mode: mixing :meth:`step` and :meth:`unroll` on the
    same instance would interleave two bookkeeping schemes over one env
    state and raises.
    """

    def __init__(self, model, env, batch_size: int, unroll_length: int, *,
                 env_key, act_rng, mesh=None, max_inflight: int = 2):
        from .envs import jax_envs

        self.batch_size = batch_size
        self.unroll_length = unroll_length
        self.env = env
        self.frames_done = 0
        # Scan-mode backpressure: unroll() is pure async dispatch, so an
        # unpaced caller (a host loop with nothing else to wait on — the
        # whole point of Anakin) would race arbitrarily far ahead of the
        # device, inflating dispatch-side step counts and ballooning the
        # execution queue.  Cap the dispatched-but-unfinished unrolls at
        # ``max_inflight`` (2 = classic double buffering: one computing,
        # one queued) by blocking on the oldest before dispatching past it.
        self._max_inflight = max(1, int(max_inflight))
        self._inflight: list = []
        obs_shape, obs_dtype = env.obs_spec
        cache_key = (model, _env_cache_key(env), batch_size, unroll_length)
        jits = _ANAKIN_JIT_CACHE.get(cache_key)
        if jits is None:
            jits = _ANAKIN_JIT_CACHE.setdefault(
                cache_key, _build_anakin_jits(model, env, unroll_length)
            )
        (self._step_jit, self._carry_jit,
         self._unroll_first_jit, self._unroll_next_jit) = jits

        B = batch_size
        env_state = jax_envs.batch_init(env, env_key, B)
        self._carry = {
            "env": env_state,
            "obs": jax_envs.batch_observe(env, env_state),
            # First reset: reward 0, done False — EnvPool's first-obs
            # convention, so backends line up from step 0.
            "reward": jnp.zeros((B,), jnp.float32),
            "done": jnp.zeros((B,), bool),
            "prev_action": jnp.zeros((B,), jnp.int32),
            "core": model.initial_state(B),
            "rng": act_rng,
            "stats": {
                "ep_return": jnp.zeros((B,), jnp.float32),
                "ep_len": jnp.zeros((B,), jnp.int32),
                "return_sum": jnp.zeros((), jnp.float32),
                "len_sum": jnp.zeros((), jnp.int32),
                "episodes": jnp.zeros((), jnp.int32),
            },
        }
        T1 = unroll_length + 1
        self._buf = {
            "state": jnp.zeros((T1, B, *obs_shape), obs_dtype),
            "reward": jnp.zeros((T1, B), jnp.float32),
            "done": jnp.zeros((T1, B), bool),
            "prev_action": jnp.zeros((T1, B), jnp.int32),
            "action": jnp.zeros((T1, B), jnp.int32),
            "policy_logits": jnp.zeros((T1, B, env.num_actions), jnp.float32),
        }
        if mesh is not None:
            # Sebulba: pin the whole rollout working set to the ACTOR submesh
            # (batch leaves sharded over its dp axis, scalars replicated on
            # it) — the jits then compile as SPMD programs over the actor
            # devices only, leaving the learner submesh free to overlap.
            from jax.sharding import NamedSharding, PartitionSpec as P

            dp = mesh.shape.get("dp", 1)
            if B % dp:
                raise ValueError(
                    f"actor-mesh dp={dp} must divide batch_size={B}"
                )
            batch_sh = NamedSharding(mesh, P("dp"))
            rep = NamedSharding(mesh, P())

            def _place(x):
                batched = getattr(x, "ndim", 0) >= 1 and x.shape[0] == B
                return jax.device_put(x, batch_sh if batched else rep)

            self._carry = jax.tree_util.tree_map(_place, self._carry)
            self._buf = jax.device_put(
                self._buf, NamedSharding(mesh, P(None, "dp"))
            )
        self._t = 0
        self._mode: Optional[str] = None
        self._last_row: Optional[dict] = None
        self._initial_core = self._carry["core"]
        self._completed: Optional[dict] = None
        self.completed_initial_core = None

    def _claim_mode(self, mode: str) -> None:
        if self._mode is None:
            self._mode = mode
        elif self._mode != mode:
            raise RuntimeError(
                f"AnakinRollout is in {self._mode!r} mode; one instance is "
                "one mode (per-step and scan bookkeeping share the env state)"
            )

    def step(self, params) -> None:
        """One fused env+act step into the donated buffer.  No arguments
        besides params and no return: there is nothing to upload and no
        action to fetch — the env that consumes the action is inside the
        same executable."""
        self._claim_mode("step")
        t0 = time.monotonic()
        core_before = self._carry["core"]
        self._buf, self._carry = self._step_jit(
            params, self._buf, self._t, self._carry
        )
        _M_FRAMES.inc(self.batch_size)
        self.frames_done += self.batch_size
        if self._t == self.unroll_length:
            self._completed = self._buf
            self.completed_initial_core = self._initial_core
            self._initial_core = core_before
            self._buf = self._carry_jit(self._completed)
            self._t = 1
            _M_UNROLLS.inc()
        else:
            self._t += 1
        _M_DISPATCH.observe(time.monotonic() - t0)

    def take_unroll(self) -> Optional[dict]:
        """Per-step mode hand-over: the completed device unroll, or None."""
        out, self._completed = self._completed, None
        return out

    def unroll(self, params) -> dict:
        """The scan fast path: ONE dispatch -> a completed ``[T+1, B]``
        device pytree.  Sets ``completed_initial_core`` to the core state
        entering the unroll's row 0, exactly as per-step mode does."""
        self._claim_mode("scan")
        t0 = time.monotonic()
        if self._last_row is None:
            buf, self._last_row, self._carry, next_initial = (
                self._unroll_first_jit(params, self._carry)
            )
            steps = self.unroll_length + 1
        else:
            buf, self._last_row, self._carry, next_initial = (
                self._unroll_next_jit(params, self._last_row, self._carry)
            )
            steps = self.unroll_length
        self.completed_initial_core = self._initial_core
        self._initial_core = next_initial
        # All leaves of one dispatch come from the same XLA execution, so
        # blocking on any one of them waits for the whole unroll.  Retire the
        # oldest dispatch once the window is full -- keeps dispatch-side
        # frame accounting within max_inflight unrolls of computed reality.
        self._inflight.append(buf["done"])
        while len(self._inflight) > self._max_inflight:
            # mtlint: allow-host-sync(max_inflight backpressure: deliberately retire the oldest dispatch so frame accounting cannot race the device)
            jax.block_until_ready(self._inflight.pop(0))
        _M_FRAMES.inc(self.batch_size * steps)
        self.frames_done += self.batch_size * steps
        _M_UNROLLS.inc()
        _M_DISPATCH.observe(time.monotonic() - t0)
        return buf

    def stats(self) -> Dict[str, Any]:
        """Snapshot the device-side episode aggregates (cumulative).  The
        ONLY D2H in the Anakin plane — counted on its own counter so the
        per-frame boundary reads a measured zero."""
        # mtlint: allow-host-sync(the documented sole D2H of the Anakin plane, counted on actor_stats_d2h_bytes_total)
        host = jax.device_get(self._carry["stats"])
        _M_STATS_D2H.inc(
            # mtlint: allow-host-sync(byte accounting over the already-fetched host snapshot)
            int(sum(np.asarray(v).nbytes for v in jax.tree_util.tree_leaves(host)))
        )
        return {
            "episodes": int(host["episodes"]),
            "return_sum": float(host["return_sum"]),
            "len_sum": int(host["len_sum"]),
            "ep_return": np.asarray(host["ep_return"]),  # mtlint: allow-host-sync(already-fetched host snapshot)
            "ep_len": np.asarray(host["ep_len"]),  # mtlint: allow-host-sync(already-fetched host snapshot)
        }
