"""Sharded train-step construction: DP/FSDP/TP on a mesh, one jit.

The reference's data-parallel heartbeat is the Accumulator's RPC-tree
allreduce (``src/accumulator.cc:880-1078``).  On a static mesh the same math
is a *sharding annotation*: batch sharded over ``dp``, params replicated (DP)
or sharded (FSDP/TP), and XLA inserts the gradient all-reduce/reduce-scatter
over ICI during compilation — no hand-written collective, and it fuses with
the backward pass.
"""

from __future__ import annotations

import itertools
from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .. import buckets, telemetry
from ..telemetry import devmon
from ..utils import init_compile_cache
from .mesh import replicated

# Host-side view of the jitted step: dispatch wall time (async — the device
# may still be executing) and a step counter.  The device-side truth lives
# in jax.profiler traces; this is the cheap always-on signal.
_REG = telemetry.get_registry()
_M_STEPS = _REG.counter("train_steps_total", "train-step invocations")
_M_DISPATCH = _REG.histogram(
    "train_step_dispatch_seconds",
    "host time in the jitted train step call (dispatch, not device time)",
)

# Each built step gets its own devmon name: two different train steps in
# one process (tests, A/B runs) must not read as each other's recompiles.
_STEP_SEQ = itertools.count()


def _instrument_step(fn, name: Optional[str] = None):
    if name is None:
        n = next(_STEP_SEQ)
        name = "parallel.train_step" + (f"#{n}" if n else "")

    # Recompile detector (telemetry.devmon): a shape/dtype signature change
    # here means XLA is retracing the train step mid-run.  Where ``fn`` is
    # the jit itself the detector asks its cache (O(1) a call); a closure
    # that builds its jit lazily has no cache to ask and pays for the
    # signature at every call.
    step = devmon.instrument_jit(fn, name)

    def timed_step(*args, **kwargs):
        with _M_DISPATCH.time():
            out = step(*args, **kwargs)
        _M_STEPS.inc()
        return out

    return timed_step


def fsdp_spec(
    x, axis: str = "dp", min_size: int = 2**16, size: int = 1, held: P = P()
) -> P:
    """ZeRO-style spec of one leaf: a leaf of at least ``min_size`` elements
    is cut over ``axis`` on its largest dimension that ``size`` (the mesh
    axis's size) divides and ``held`` (the spec other mesh axes already give
    the leaf) leaves free.  Any other leaf keeps ``held``: a small one, or
    one with no such dimension (a vocabulary of 50,257 is cut on d_model)."""
    shape = np.shape(x)
    spec = list(held) + [None] * (len(shape) - len(held))
    free = [i for i, d in enumerate(shape) if spec[i] is None and d % size == 0]
    if not free or np.prod(shape) < min_size:
        return held
    spec[max(free, key=lambda i: shape[i])] = axis
    return P(*spec)


def param_shardings(
    params, mesh: Mesh, mode: str = "replicated", axis: str = "dp", base=None
):
    """Pytree of NamedShardings for the model params: "replicated" (pure DP)
    or "fsdp" (:func:`fsdp_spec` over the mesh's ``axis`` for every leaf).
    ``base``, a pytree of NamedShardings like ``params`` (``moe_shardings``'
    ep cut), is what "fsdp" cuts further; without it, and on a mesh whose
    ``axis`` is 1 or missing, a leaf it does not cut is replicated."""
    whole = jax.tree_util.tree_map(lambda _: replicated(mesh), params)
    if mode == "replicated":
        return whole
    if mode == "fsdp":
        base = whole if base is None else base
        size = mesh.shape.get(axis, 1)
        if size == 1:
            return base
        return jax.tree_util.tree_map(
            lambda x, b: NamedSharding(
                mesh, fsdp_spec(x, axis, size=size, held=b.spec)
            ),
            params, base,
        )
    raise ValueError(f"unknown mode {mode!r}")


def mirror_shardings(state, params, shardings, mesh: Mesh):
    """Shardings for an optimizer ``state`` whose moments mirror ``params``:
    every subtree of ``state`` with the structure of ``params`` (AdamW's mu
    and nu) takes ``shardings``, the params' own; any other leaf (a step
    count) is replicated."""
    like = jax.tree_util.tree_structure(params)

    def mirrors(x):
        return jax.tree_util.tree_structure(x) == like

    return jax.tree_util.tree_map(
        lambda x: shardings if mirrors(x) else replicated(mesh), state,
        is_leaf=mirrors,
    )


def auto_shardings(
    params,
    mesh: Mesh,
    tp_axis: str = "tp",
    dp_axis: str = "dp",
    tp_min: int = 16,
    fsdp_min: int = 2**12,
):
    """Pytree of NamedShardings composing TP and FSDP on ONE mesh: tensor
    parallelism on the last axis of ≥2-D kernels (output features — Dense and
    conv kernels alike) when it divides the ``tp`` size, then FSDP over
    ``dp`` on the largest remaining divisible axis of big leaves.  Used by
    both the flagship agent (``--mesh dp=N,tp=M``) and ``dryrun_multichip``
    so the dry run exercises the exact sharding the agent trains with."""
    has_tp = tp_axis in mesh.axis_names and mesh.shape[tp_axis] > 1
    has_dp = dp_axis in mesh.axis_names and mesh.shape[dp_axis] > 1

    def spec_of(x):
        shape = np.shape(x)
        spec = [None] * len(shape)
        if (
            has_tp
            and len(shape) >= 2
            and shape[-1] >= tp_min
            and shape[-1] % mesh.shape[tp_axis] == 0
        ):
            spec[-1] = tp_axis
        if has_dp and np.prod(shape) >= fsdp_min:
            cand = max(
                (d for d in range(len(shape)) if spec[d] is None),
                key=lambda d: shape[d],
                default=None,
            )
            if cand is not None and shape[cand] % mesh.shape[dp_axis] == 0:
                spec[cand] = dp_axis
        return NamedSharding(mesh, P(*spec))

    return jax.tree_util.tree_map(spec_of, params)


def _overlap_cut_index(leaves) -> int:
    """Default two-jit cut for ``overlap_grads=True``: the param-leaf
    boundary nearest the flat-bucket grid boundary nearest the payload
    midpoint.  Cutting on (near) a bucket boundary means the tail jit's
    gradients complete whole buckets of the accumulator's ``BucketLayout``,
    so their wire ops launch while the head jit is still running backward.
    """
    sizes = [max(1, int(np.prod(np.shape(l)))) for l in leaves]
    if len(sizes) < 2:
        return 0
    total = sum(sizes)
    itemsize = np.dtype(getattr(leaves[0], "dtype", np.float32)).itemsize
    grid = max(1, buckets.bucket_bytes() // itemsize)
    # Bucket-grid boundary nearest the midpoint of the flat payload.
    target = round((total / 2) / grid) * grid
    off, best, best_d = 0, 1, None
    for i in range(1, len(sizes)):
        off += sizes[i - 1]
        d = abs(off - target)
        if best_d is None or d < best_d:
            best, best_d = i, d
    return best


def make_train_step(
    loss_fn: Callable,
    optimizer: Optional[optax.GradientTransformation] = None,
    mesh: Optional[Mesh] = None,
    params_sharding=None,
    batch_spec: Optional[P] = None,
    donate: bool = True,
    grad_spec=None,
    overlap_grads: bool = False,
    overlap_cut: Optional[int] = None,
):
    """Build ``step(params, opt_state, batch, rng) -> (params, opt_state,
    loss, aux)``.

    ``loss_fn(params, batch, rng) -> (loss, aux)`` must return the *local
    mean* loss; with the batch sharded over ``dp`` XLA turns the global mean
    gradient into an all-reduce over ICI automatically.

    With ``grad_spec=`` (requires ``mesh=``) the optimizer apply is elided
    and the step instead returns ``(loss, aux, grads)`` — the hierarchical
    learner's in-mesh half (DESIGN.md §6d): the psum over the mesh's ``dp``
    axis happens INSIDE the jitted step (pinned by the grads' out_shardings,
    so "replicated" compiles to an all-reduce and "fsdp"/"params" to a
    reduce-scatter over ICI), and the caller hands the already-reduced
    sharded grads to ``Accumulator.reduce_gradients`` for the inter-host
    round.  ``grad_spec`` is a mode string ("replicated" / "fsdp" /
    "params" to mirror ``params_sharding``) or a sharding pytree.

    With ``overlap_grads=True`` (DESIGN.md §6e) the step is split into TWO
    jits cut on a param-leaf boundary near a flat-bucket grid boundary
    (``overlap_cut=`` overrides the leaf index): the first computes the loss
    and the gradients of the *tail* leaves (shortest backprop chains, ready
    first), the second the gradients of the *head* leaves.  The step then
    returns ``(loss, aux, stream)`` where ``stream`` is a
    ``buckets.GradientStream`` that delivers the tail gradients while the
    head jit is still executing backward — handing it to
    ``Accumulator.reduce_gradients`` launches each bucket's inter-host wire
    op as soon as that bucket is staged, hiding comm under the backward
    tail.  Composes with ``grad_spec=`` (the stream carries the grad
    shardings for the sharded inter-host round); does not compose with
    ``optimizer=`` (apply updates after the reduce completes).
    """

    def step(params, opt_state, batch, rng):
        (loss, aux), grads = jax.value_and_grad(loss_fn, has_aux=True)(params, batch, rng)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        return params, opt_state, loss, aux

    def grad_step(params, batch, rng):
        (loss, aux), grads = jax.value_and_grad(loss_fn, has_aux=True)(params, batch, rng)
        return loss, aux, grads

    if grad_spec is not None and mesh is None:
        raise ValueError("grad_spec= requires mesh=")
    if overlap_grads and optimizer is not None:
        raise ValueError(
            "overlap_grads=True streams raw gradients to the caller; it does "
            "not compose with optimizer= (apply updates after the reduce)"
        )
    if overlap_grads and mesh is not None and grad_spec is None:
        raise ValueError("overlap_grads=True with mesh= requires grad_spec=")
    if grad_spec is None and optimizer is None and not overlap_grads:
        raise ValueError("make_train_step needs an optimizer unless grad_spec= is given")

    def _build_overlap(shard):
        # Two-jit schedule: tail grads first (short backprop chains), head
        # grads second; the GradientStream hands each chunk to the caller the
        # moment its jit's outputs exist as (async) device arrays, so the
        # consumer's per-bucket D2H + wire launches run under the head jit's
        # device time.  Compiled lazily on first call (needs real pytrees).
        state: dict = {}

        def overlap_step(params, batch, rng):
            leaves, treedef = jax.tree_util.tree_flatten(params)
            if "fns" not in state:
                if len(leaves) < 2:
                    cut = 0
                else:
                    cut = overlap_cut if overlap_cut is not None else _overlap_cut_index(leaves)
                    cut = int(max(1, min(len(leaves) - 1, cut)))

                def tail_loss(tail, head, b, r):
                    p = jax.tree_util.tree_unflatten(treedef, list(head) + list(tail))
                    return loss_fn(p, b, r)

                def tail_step(tail, head, b, r):
                    (loss, aux), g = jax.value_and_grad(tail_loss, has_aux=True)(tail, head, b, r)
                    return loss, aux, g

                def head_loss(head, tail, b, r):
                    p = jax.tree_util.tree_unflatten(treedef, list(head) + list(tail))
                    return loss_fn(p, b, r)

                def head_step(head, tail, b, r):
                    g, _ = jax.grad(head_loss, has_aux=True)(head, tail, b, r)
                    return g

                if shard is None:
                    gsh = None
                    tail_fn = jax.jit(tail_step)
                    head_fn = jax.jit(head_step) if cut else None
                else:
                    init_compile_cache()
                    psh = jax.tree_util.tree_leaves(shard["get_ps"](params))
                    gsh = jax.tree_util.tree_leaves(shard["get_gs"](params))
                    bsh = jax.tree_util.tree_map(lambda _: shard["bsharding"], batch)
                    rep_ = shard["rep"]
                    tail_fn = jax.jit(
                        tail_step,
                        in_shardings=(psh[cut:], psh[:cut], bsh, rep_),
                        out_shardings=(rep_, None, gsh[cut:]),
                    )
                    head_fn = (
                        jax.jit(
                            head_step,
                            in_shardings=(psh[:cut], psh[cut:], bsh, rep_),
                            out_shardings=gsh[:cut],
                        )
                        if cut
                        else None
                    )
                state.update(fns=(tail_fn, head_fn), cut=cut, gsh=gsh)
            tail_fn, head_fn = state["fns"]
            cut = state["cut"]
            head_p, tail_p = leaves[:cut], leaves[cut:]
            loss, aux, gtail = tail_fn(tail_p, head_p, batch, rng)
            ghead = list(head_fn(head_p, tail_p, batch, rng)) if head_fn is not None else []
            glist = ghead + list(gtail)
            stream = buckets.GradientStream(
                treedef,
                [tuple(np.shape(g)) for g in glist],
                [np.dtype(g.dtype) for g in glist],
                shardings=state["gsh"],
            )
            # Tail first: its jit was dispatched first and its grads need
            # only the shallow end of the backward graph, so they land while
            # the head jit is still executing.
            stream.deliver(cut, list(gtail))
            if ghead:
                stream.deliver(0, ghead)
            return loss, aux, stream

        return _instrument_step(overlap_step)

    if mesh is None:
        if overlap_grads:
            return _build_overlap(None)
        return _instrument_step(jax.jit(step, donate_argnums=(0, 1) if donate else ()))

    if params_sharding is None:
        params_sharding = "replicated"
    ps = params_sharding  # may be a mode string or a sharding pytree
    if isinstance(ps, str):
        # Resolved lazily at first call (needs a params pytree).
        resolved = {}

        def get_ps(params):
            if "v" not in resolved:
                resolved["v"] = param_shardings(params, mesh, ps)
            return resolved["v"]

    else:

        def get_ps(params):
            return ps

    bspec = batch_spec if batch_spec is not None else P(None, "dp")
    bsharding = NamedSharding(mesh, bspec)
    rep = replicated(mesh)

    compiled = {}

    if grad_spec is not None:
        gs = grad_spec
        if isinstance(gs, str):
            if gs not in ("replicated", "fsdp", "params"):
                raise ValueError(
                    f"unknown grad_spec {gs!r} (expected 'replicated', 'fsdp', "
                    "'params', or a sharding pytree)"
                )
            g_resolved = {}

            def get_gs(params):
                if "v" not in g_resolved:
                    if gs == "params":
                        g_resolved["v"] = get_ps(params)
                    else:
                        g_resolved["v"] = param_shardings(params, mesh, gs)
                return g_resolved["v"]

        else:

            def get_gs(params):
                return gs

        if overlap_grads:
            return _build_overlap(
                {"get_ps": get_ps, "get_gs": get_gs, "bsharding": bsharding, "rep": rep}
            )

        def sharded_grad_step(params, batch, rng):
            if "fn" not in compiled:
                # Persistent compile cache so a multi-host restart replays
                # the pjit'd step from disk instead of recompiling
                # (utils/compile_cache.py).
                init_compile_cache()
                compiled["fn"] = jax.jit(
                    grad_step,
                    in_shardings=(
                        get_ps(params),
                        jax.tree_util.tree_map(lambda _: bsharding, batch),
                        rep,
                    ),
                    out_shardings=(rep, None, get_gs(params)),
                )
            return compiled["fn"](params, batch, rng)

        return _instrument_step(sharded_grad_step)

    def sharded_step(params, opt_state, batch, rng):
        if "fn" not in compiled:
            init_compile_cache()
            p_sh = get_ps(params)
            o_sh = jax.tree_util.tree_map(
                lambda _: rep, opt_state,
                is_leaf=lambda x: isinstance(x, jnp.ndarray),
            )
            # Optimizer state mirrors the param sharding where shapes match.
            compiled["fn"] = jax.jit(
                step,
                in_shardings=(
                    p_sh,
                    None,
                    jax.tree_util.tree_map(lambda _: bsharding, batch),
                    rep,
                ),
                out_shardings=(p_sh, None, rep, None),
                donate_argnums=(0, 1) if donate else (),
            )
        return compiled["fn"](params, opt_state, batch, rng)

    return _instrument_step(sharded_step)
