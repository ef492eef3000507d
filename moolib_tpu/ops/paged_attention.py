"""Paged KV-cache decode attention (block-table gather).

The serving engine's KV layout: instead of one dense ``[B, max_len, Hk, hd]``
cache per sequence, K/V live in a shared device-resident pool of fixed-size
token blocks ``[num_blocks, block_size, Hk, hd]`` and each decode *slot* owns
an int32 row of block ids (its block table).  Attention gathers the slot's
blocks back into a contiguous context and runs the exact same grouped-query
math as the dense ``decode=True`` path in ``models.transformer.Block`` — the
shared function :func:`gathered_decode_attention` is called by BOTH paths, so
paged decode is bit-identical to the dense cache whenever the gathered context
length equals the dense ``max_len`` (tests/test_paged_attention.py pins this).

Why a gather kernel and not a fused pallas kernel: decode attention at serve
batch sizes is bandwidth-bound on the KV pool read either way; the XLA gather
lowers to the same HBM traffic on TPU and runs unmodified on CPU, which is
where tier-1 CI executes.  The layout (pool + block tables + per-slot
lengths) is exactly what a fused kernel would take, so one can slot in later
without touching the engine.

Block id 0 is the *null block*: never handed out by the allocator, and the
write path redirects inactive slots' scatters at it, so a fixed-shape jitted
step over all S slots never branches on occupancy.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

_NEG_INF = -1e30


class PagedState(NamedTuple):
    """Per-slot decode state threaded through a paged decode step.

    block_tables: int32 [S, max_blocks_per_seq] — pool block ids per slot
        (unused tail entries hold 0, the null block).
    lengths: int32 [S] — tokens already in the cache for each slot; the
        current step writes at position ``lengths`` and attends over
        ``<= lengths`` (the just-written token included).
    active: bool [S] — occupied slots.  Inactive slots still execute the
        step (fixed shape); their writes land in the null block and their
        outputs are ignored by the engine.
    """

    block_tables: jax.Array
    lengths: jax.Array
    active: jax.Array


def gathered_decode_attention(q, k_ctx, v_ctx, t):
    """Single-position grouped-query attention over a gathered context.

    q: [B, 1, H, hd]; k_ctx/v_ctx: [B, T_ctx, Hk, hd] (any dtype — cast to
    f32 here, like the dense path); t: scalar or [B] int — attend over
    positions ``<= t`` (everything past t contributes exactly 0: the -1e30
    masked scores underflow to 0 in the f32 softmax).  This is the one
    definition of the decode-attention math; the dense ``decode=True`` branch
    and the paged gather path both call it, which is what makes the two
    cache layouts bit-exact against each other.
    """
    B, T, H, hd = q.shape
    Hk = k_ctx.shape[2]
    group = H // Hk
    T_ctx = k_ctx.shape[1]
    scale = hd**-0.5
    qg = q.reshape(B, T, Hk, group, hd)
    scores = (
        jnp.einsum(
            "bqhgd,bkhd->bhgqk",
            qg.astype(jnp.float32),
            k_ctx.astype(jnp.float32),
        )
        * scale
    )
    t = jnp.asarray(t)
    pos = jnp.arange(T_ctx)
    if t.ndim == 0:
        mask = pos[None, None, None, None, :] <= t
    else:
        mask = pos[None, None, None, None, :] <= t[:, None, None, None, None]
    scores = jnp.where(mask, scores, _NEG_INF)
    p_att = jax.nn.softmax(scores, axis=-1)
    att = jnp.einsum("bhgqk,bkhd->bqhgd", p_att, v_ctx.astype(jnp.float32))
    return att.reshape(B, T, H, hd).astype(q.dtype)


@jax.named_scope("paged_attention")
def paged_kv_write(pool, x, block_tables, lengths, active):
    """Scatter one new K (or V) row per slot into the block pool, in place.

    pool: [num_blocks, block_size, Hk, hd]; x: [S, Hk, hd] (this step's K or
    V at position ``lengths``); block_tables/lengths/active as in
    :class:`PagedState`.  Inactive slots write to the null block 0 — the
    allocator never hands it out, so the garbage is harmless and the op
    keeps a fixed shape.  Used under donation: ``pool.at[...].set`` on a
    donated buffer updates HBM in place (no copy at join/retire).
    """
    bs = pool.shape[1]
    blk = jnp.take_along_axis(block_tables, (lengths // bs)[:, None], axis=1)[:, 0]
    blk = jnp.where(active, blk, 0)
    off = lengths % bs
    return pool.at[blk, off].set(x.astype(pool.dtype))


@jax.named_scope("paged_attention")
def paged_gather(pool, block_tables):
    """Gather each slot's blocks into a contiguous [S, T_ctx, Hk, hd] context
    (T_ctx = max_blocks_per_seq * block_size).  Positions past a slot's
    length are stale pool contents; the attention mask zeroes them."""
    S, nb = block_tables.shape
    ctx = pool[block_tables]  # [S, nb, bs, Hk, hd]
    return ctx.reshape(S, nb * pool.shape[1], *pool.shape[2:])


@jax.named_scope("paged_attention")
def paged_attention(q, pool_k, pool_v, block_tables, lengths):
    """Decode attention against a paged KV pool: gather, then the shared
    grouped-query math.  q: [S, 1, H, hd]; returns [S, 1, H, hd]."""
    k_ctx = paged_gather(pool_k, block_tables)
    v_ctx = paged_gather(pool_v, block_tables)
    return gathered_decode_attention(q, k_ctx, v_ctx, lengths)
