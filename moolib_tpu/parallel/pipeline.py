"""Pipeline parallelism: GPipe and circular (interleaved) schedules over a
``pp`` mesh axis.

New capability beyond the reference (SURVEY.md §2.3: pipeline parallelism
absent).  SPMD formulation: every device runs the same program inside
``shard_map``; device ``d`` holds its stages' parameters (stage-stacked
arrays sharded over ``pp``), activations march around the ring with
``ppermute`` once per tick.

Schedules (S = pipeline devices, M = microbatches, v = circular_repeats):

- ``circular_repeats=1`` (GPipe): one stage per device, ``M + S - 1`` ticks,
  bubble fraction ``(S-1)/(M+S-1)``.
- ``circular_repeats=v`` (circular / interleaved, the Megatron-interleaved
  idea in ring form): ``L = v*S`` virtual stages laid round-robin over the
  ring — layer ``j`` lives on device ``j % S`` — so each microbatch laps the
  ring ``v`` times.  Total ``v*M + S - 1`` ticks of ONE virtual-stage compute
  each, versus GPipe's ``(M + S - 1)`` ticks of ``v`` stages each: the same
  compute, but the bubble shrinks from ``(S-1)*v`` ticks to ``S - 1``.

The tick loop is a ``lax.scan``, so both schedules are
reverse-differentiable: ``jax.grad`` through ``pipeline_apply`` trains the
pipeline (scan stashes per-tick activations for the backward pass; pass
``remat=True`` to recompute the stage forward in the backward instead —
activation memory drops from O(ticks) full traces to O(ticks) boundaries).
"""

from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def pipeline_apply(
    stage_fn: Callable,
    stage_params: Any,
    microbatches: jax.Array,
    mesh: Mesh,
    axis_name: str = "pp",
    data_axis: str = None,
    circular_repeats: int = 1,
    remat: bool = False,
    remat_policy=None,
):
    """Run ``y_m = stage_{L-1}(... stage_0(x_m))`` for every microbatch.

    Args:
      stage_fn: ``stage_fn(params_for_one_stage, x) -> y`` with x/y of the
        same shape (activation shape is uniform across stages).
      stage_params: pytree whose leaves have a leading *virtual stage* axis
        of size ``L = circular_repeats * S`` in execution order (leaf ``j``
        is the ``j``-th layer the activation meets; it runs on device
        ``j % S`` during lap ``j // S``).
      microbatches: [M, B, ...] array of microbatch inputs.  With
        ``circular_repeats > 1``, M must be a multiple of S (microbatches
        stream through the ring in groups of S).
      mesh: mesh with an ``axis_name`` axis of size S.  The mesh may carry
        other axes (dp/tp): pass ``data_axis="dp"`` to also shard the
        microbatch batch dim (axis 1) over it — a data-parallel pipeline in
        ONE mesh, each dp slice streaming its own microbatches.
      data_axis: optional mesh axis for the batch dim of ``microbatches``.
      circular_repeats: virtual stages per device (``v``); 1 = GPipe.
      remat: rematerialize stage_fn in the backward pass (jax.checkpoint).
      remat_policy: optional jax.checkpoint policy callable selecting what
        the checkpoint saves (e.g. ``jax.checkpoint_policies.checkpoint_dots``);
        None saves nothing.  Ignored unless ``remat=True``.

    Returns: [M, B, ...] outputs from the final virtual stage.
    """
    S = mesh.shape[axis_name]
    V = circular_repeats
    M = microbatches.shape[0]
    if V > 1 and M % S:
        raise ValueError(
            f"circular schedule needs microbatches % pp == 0, got {M} % {S}"
        )
    L = jax.tree_util.tree_leaves(stage_params)[0].shape[0]
    if L != V * S:
        raise ValueError(
            f"stage_params leading axis is {L}, need circular_repeats*pp = {V * S}"
        )
    fn = jax.checkpoint(stage_fn, policy=remat_policy) if remat else stage_fn
    n_ticks = V * M + S - 1

    # [L, ...] execution-order leaves -> [V, S, ...]: lap r of device d is
    # layer r*S + d, i.e. reshaped[r, d].
    grouped = jax.tree_util.tree_map(
        lambda p: p.reshape(V, S, *p.shape[1:]), stage_params
    )

    def body(params_local, xs):
        # params_local: leaves [V, 1, ...] (this device's V laps); xs: all
        # microbatches (replicated — only stage 0 consumes them).
        params_me = jax.tree_util.tree_map(lambda p: p[:, 0], params_local)
        stage = jax.lax.axis_index(axis_name)
        act_shape = xs.shape[1:]
        # Mark the loop buffers as varying over the pipeline axis (their
        # updates depend on axis_index, so the carry type must match) — and
        # over the data axis too when microbatches are sharded across it.
        carry_axes = (axis_name,) if data_axis is None else (axis_name, data_axis)
        carry = jax.lax.pcast(jnp.zeros(act_shape, xs.dtype), carry_axes, to="varying")
        outs = jax.lax.pcast(jnp.zeros_like(xs), axis_name, to="varying")

        def tick(state, i):
            carry, outs = state
            # The activation this device touches at tick i started tick
            # t = i - stage; its lap r and microbatch m are static functions
            # of t (groups of S microbatches lap the ring V times each).
            t = i - stage
            u = t % (S * V)  # position within the group's V*S-tick window
            r = u // S  # lap (virtual-stage repeat) index
            m = jnp.clip((t // (S * V)) * S + u % S, 0, M - 1)
            valid = jnp.logical_and(t >= 0, t < V * M)
            # Device 0 ingests microbatch m on its first lap; everything
            # else takes the activation handed over the ring.
            x_in = jnp.where(
                jnp.logical_and(stage == 0, r == 0), xs[m], carry
            )
            # V is static: GPipe (V=1) keeps the old static slice instead of
            # a traced gather of the whole parameter shard every tick.
            p_r = (
                jax.tree_util.tree_map(lambda p: p[0], params_me)
                if V == 1
                else jax.tree_util.tree_map(lambda p: p[r], params_me)
            )
            y = fn(p_r, x_in)
            # Final device banks microbatch m after its last lap.
            bank = jnp.logical_and(
                valid, jnp.logical_and(stage == S - 1, r == V - 1)
            )
            outs = outs.at[m].set(jnp.where(bank, y, outs[m]))
            # Hand activations to the next device (ring step).
            perm = [(j, (j + 1) % S) for j in range(S)]
            carry = jax.lax.ppermute(y, axis_name, perm)
            return (carry, outs), None

        (_, outs), _ = jax.lax.scan(tick, (carry, outs), jnp.arange(n_ticks))
        # Results live on the last device; share them with everyone.
        outs = jax.lax.psum(
            jnp.where(stage == S - 1, outs, jnp.zeros_like(outs)), axis_name
        )
        return outs

    param_specs = jax.tree_util.tree_map(lambda _: P(None, axis_name), grouped)
    xs_spec = P(None, data_axis) if data_axis is not None else P()
    fn_mapped = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(param_specs, xs_spec),
        out_specs=xs_spec,
    )
    sharded_params = jax.tree_util.tree_map(
        lambda p: jax.device_put(p, NamedSharding(mesh, P(None, axis_name))), grouped
    )
    return fn_mapped(sharded_params, microbatches)
