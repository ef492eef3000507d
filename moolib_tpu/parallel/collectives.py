"""Collective helpers for use inside jit/shard_map.

XLA inserts most collectives automatically from sharding propagation; these
wrappers are for explicit ``shard_map`` regions (ring attention, hand-written
reductions) and for pytree-level convenience.
"""

from __future__ import annotations

from typing import Any

import jax

from .. import telemetry

# Shared with the accumulator's sharded rounds (registration is idempotent):
# one histogram covers every in-mesh share-down / resharding hop so the
# hierarchical plane's device-redistribution cost reads off a single series.
_M_PSUM = telemetry.get_registry().histogram(
    "accum_psum_seconds",
    "host wall time in the in-mesh share-down / resharding of reduced "
    "tensors (parallel.redistribute and the sharded-round share-down)",
)


def tree_psum(tree: Any, axis_name: str) -> Any:
    return jax.tree_util.tree_map(lambda x: jax.lax.psum(x, axis_name), tree)


def tree_pmean(tree: Any, axis_name: str) -> Any:
    return jax.tree_util.tree_map(lambda x: jax.lax.pmean(x, axis_name), tree)


def ring_permute(x: jax.Array, axis_name: str, shift: int = 1) -> jax.Array:
    """Send ``x`` to the next device on the ring (ICI neighbour)."""
    n = jax.lax.axis_size(axis_name)
    perm = [(i, (i + shift) % n) for i in range(n)]
    return jax.lax.ppermute(x, axis_name, perm)


def all_gather_axis(x: jax.Array, axis_name: str, axis: int = 0) -> jax.Array:
    return jax.lax.all_gather(x, axis_name, axis=axis, tiled=True)


def reduce_scatter_axis(x: jax.Array, axis_name: str, axis: int = 0) -> jax.Array:
    return jax.lax.psum_scatter(x, axis_name, scatter_dimension=axis, tiled=True)


def redistribute(tree: Any, shardings: Any, block: bool = False) -> Any:
    """Reshard a pytree onto target shardings (mesh-to-mesh redistribution).

    The all-gather-by-multicast half of the hierarchical reduce plane
    (DESIGN.md §6d), following the portable-collective redistribution recipe
    of arxiv 2112.01075: each leaf is ``device_put`` to its target
    ``NamedSharding``/``Sharding``, which XLA lowers to the minimal transfer
    between the source and target layouts (all-gather when un-sharding a
    ZeRO-applied update, plain layout change otherwise).  ``shardings`` is a
    pytree of shardings matching ``tree`` or a single sharding broadcast to
    every leaf.  With ``block=True`` the call waits for the transfers so the
    recorded wall time covers the copies, not just their dispatch.  Host
    time lands in ``accum_psum_seconds``.
    """
    is_single = not isinstance(shardings, (dict, list, tuple)) and not hasattr(
        shardings, "keys"
    )
    # The share-down's host wall time (the copies too, with ``block``).
    with _M_PSUM.time(), telemetry.span("parallel.redistribute"):
        if is_single:
            out = jax.tree_util.tree_map(lambda x: jax.device_put(x, shardings), tree)
        else:
            out = jax.tree_util.tree_map(
                lambda x, s: jax.device_put(x, s), tree, shardings
            )
        if block:
            for leaf in jax.tree_util.tree_leaves(out):
                if hasattr(leaf, "block_until_ready"):
                    leaf.block_until_ready()
        return out
