"""Device mesh construction and sharding helpers.

This is the TPU-native data plane the reference lacked (SURVEY.md §2.4): the
reference synchronizes gradients with a hand-rolled RPC tree over TCP
(``src/group.h:553-654``); here a static cohort forms a
``jax.sharding.Mesh`` and gradient/model math runs *inside* jit with XLA
collectives riding ICI.  Axis convention (used throughout the framework):

- ``dp``: data parallel (batch sharded, grads all-reduced)
- ``tp``: tensor parallel (weight matrices sharded)
- ``sp``: sequence/context parallel (time axis sharded; ring attention)
- ``ep``: expert parallel (MoE experts sharded)

Multi-host: call :func:`initialize_distributed` first (wraps
``jax.distributed.initialize``); ``jax.devices()`` then spans all hosts and
meshes lay out so that dp crosses DCN while tp/sp stay inside the ICI domain.
"""

from __future__ import annotations

import math
from typing import Dict, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

AXES = ("dp", "tp", "sp", "ep")


def initialize_distributed(
    coordinator_address: Optional[str] = None,
    num_processes: Optional[int] = None,
    process_id: Optional[int] = None,
    local_device_ids: Optional[Sequence[int]] = None,
) -> None:
    """Multi-host bring-up (control plane: DCN; data plane: ICI).

    On the CPU backend, cross-process collectives silently hang unless a
    collectives implementation is selected — pin gloo before the backend
    initializes (this was the round-1 "cross-process CPU collectives hang":
    XLA:CPU defaults to no cross-process implementation at all).
    """
    platforms = jax.config.jax_platforms or ""
    if "cpu" in platforms or platforms == "":
        jax.config.update("jax_cpu_collectives_implementation", "gloo")
    jax.distributed.initialize(
        coordinator_address=coordinator_address,
        num_processes=num_processes,
        process_id=process_id,
        local_device_ids=local_device_ids,
    )
    # Force backend creation NOW, while every process is at the same program
    # point. Backend init under jax.distributed is a cross-process rendezvous
    # (global device exchange): left lazy, the first stray jax call — e.g.
    # process_count() on the Accumulator's reduce path — blocks that process
    # for as long as its peers take to touch jax themselves, which stalls its
    # broker pings and can deadlock an elastic cohort (peer A blocked in the
    # rendezvous waiting for peer B, peer B waiting on A's RPC responses).
    jax.devices()


def make_mesh(
    axes: Optional[Dict[str, int]] = None, devices: Optional[Sequence] = None
) -> Mesh:
    """Build a Mesh from an axis-size dict, e.g. ``{"dp": 4, "tp": 2}``.

    Missing sizes are inferred: at most one axis may be -1 (absorbs the rest);
    with no dict at all, every device goes to ``dp``.
    """
    devices = list(devices if devices is not None else jax.devices())
    n = len(devices)
    if not axes:
        axes = {"dp": n}
    axes = dict(axes)
    unknown = [k for k, v in axes.items() if v == -1]
    if len(unknown) > 1:
        raise ValueError("at most one axis may be -1")
    known = math.prod(v for v in axes.values() if v != -1)
    if unknown:
        if n % known:
            raise ValueError(f"{n} devices not divisible by {known}")
        axes[unknown[0]] = n // known
    total = math.prod(axes.values())
    if total != n:
        raise ValueError(f"mesh {axes} needs {total} devices, have {n}")
    arr = np.array(devices).reshape(tuple(axes.values()))
    return Mesh(arr, tuple(axes.keys()))


def parse_mesh_spec(spec: str) -> Optional[Mesh]:
    """Build a mesh from a CLI string like ``"dp=2,tp=4"`` over the first
    prod(sizes) devices ('' → None).  The shared parser behind the example
    agents' ``--mesh`` flags."""
    if not spec:
        return None
    axes: Dict[str, int] = {}
    for part in spec.split(","):
        if not part.strip():
            continue
        k, _, v = part.partition("=")
        axes[k.strip()] = int(v)
    if any(v == -1 for v in axes.values()):
        return make_mesh(axes)  # -1 absorbs the remaining devices
    need = math.prod(axes.values())
    return make_mesh(axes, devices=jax.devices()[:need])


def split_mesh(mesh: Mesh, actor_devices: int) -> Tuple[Mesh, Mesh]:
    """Carve a Podracer "Sebulba" split out of one device cohort: the first
    ``actor_devices`` devices become a pure-dp **actor mesh** (inference +
    on-device envs), the remainder keep the original axis layout as the
    **learner mesh** (arXiv:2104.06272 § Sebulba — actors and learner on
    disjoint device subsets, trajectories handed over device-to-device).

    Returns ``(actor_mesh, learner_mesh)``.  The learner keeps every axis of
    the input mesh whose size still divides the remaining device count; axes
    that no longer fit collapse into dp (the common case is a pure-dp input
    mesh, where the learner is simply the dp remainder).
    """
    devices = list(mesh.devices.flat)
    n = len(devices)
    if not (0 < actor_devices < n):
        raise ValueError(
            f"actor_devices must be in (0, {n}) to leave the learner at "
            f"least one device; got {actor_devices}"
        )
    actor = make_mesh({"dp": actor_devices}, devices[:actor_devices])
    remaining = devices[actor_devices:]
    axes = dict(zip(mesh.axis_names, mesh.devices.shape))
    non_dp = {k: v for k, v in axes.items() if k != "dp" and v > 1}
    tail = math.prod(non_dp.values()) if non_dp else 1
    if non_dp and len(remaining) % tail == 0:
        learner_axes = {"dp": len(remaining) // tail, **non_dp}
    else:
        learner_axes = {"dp": len(remaining)}
    learner = make_mesh(learner_axes, remaining)
    return actor, learner


def check_disjoint(
    mesh_a: Mesh, mesh_b: Mesh, what_a: str = "--mesh", what_b: str = "--actor_mesh"
) -> None:
    """Raise a clear ValueError when two meshes share devices.

    Overlapping actor/learner meshes don't fail fast on their own — the two
    jit'd programs contend for the same chips and the cohort *wedges* at the
    first cross-program collective instead of erroring.  The example agents
    call this at flag-parse time so the operator sees which device ids
    collide and which flags produced them.
    """
    ids_a = {d.id for d in mesh_a.devices.flat}
    ids_b = {d.id for d in mesh_b.devices.flat}
    shared = sorted(ids_a & ids_b)
    if shared:
        raise ValueError(
            f"{what_a} and {what_b} overlap on device ids {shared}: the two "
            f"meshes must be disjoint ({what_a} spans {sorted(ids_a)}, "
            f"{what_b} spans {sorted(ids_b)}). Use split_mesh() or shift one "
            "spec onto different devices."
        )


def named(mesh: Mesh, *spec) -> NamedSharding:
    """Shorthand: ``named(mesh, "dp", None)`` → NamedSharding over P(dp, ∅)."""
    return NamedSharding(mesh, P(*spec))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def shard_batch_spec(mesh: Mesh, time_major: bool = True) -> P:
    """PartitionSpec for an RL batch: batch axis over dp (and time over sp if
    the mesh has one). Time-major [T, B, ...] per the framework convention."""
    has_sp = "sp" in mesh.axis_names and mesh.shape["sp"] > 1
    if time_major:
        return P("sp" if has_sp else None, "dp")
    return P("dp", "sp" if has_sp else None)


def local_batch_size(mesh: Mesh, global_batch: int, axis: str = "dp") -> int:
    size = mesh.shape[axis]
    if global_batch % size:
        raise ValueError(f"batch {global_batch} not divisible by {axis}={size}")
    return global_batch // size
